"""Share of the step program's mean device time in grouped-query attention's
projections (``nn/attention_layers.py`` ``GroupedQueryAttention``: the scopes
``qkv``, ``qk_norm``, ``rope`` with the join into (b, h, t, d), and
``out_proj``), forward and backward, from the trace's ``scopes``. The T x T
part (``flash``) is ``attention_share.train``'s. Nothing to read where no
such scope ran."""

from benchmark.trace_reduce import scope_share


def read(run, trace, cell, peak):
    return scope_share(trace.get("scopes"), ("qkv", "qk_norm", "rope", "out_proj"))
