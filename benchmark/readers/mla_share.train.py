"""Share of the step program's mean device time in latent attention's
projections and rotary (``nn/attention_layers.py`` ``LatentAttention``: the
scopes ``mla_qkv``, ``rope`` and ``mla_out``), forward and backward, in the
trunk's blocks and in the prediction layer's, from the trace's ``scopes``.
The T x T part (``flash``) is ``attention_share.train``'s. Nothing to read
where no such scope ran."""

from benchmark.trace_reduce import scope_share


def read(run, trace, cell, peak):
    return scope_share(trace.get("scopes"), ("mla_qkv", "rope", "mla_out"))
