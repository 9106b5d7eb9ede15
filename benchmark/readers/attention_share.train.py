"""Share of the step program's mean device time spent under attention's
scopes (``nn/attention_layers.py``: the fused kernel pair, or the XLA
form's ``scores`` + ``softmax`` + ``context``, or the flash kernel), forward
and backward, from the trace's ``scopes``. The projections (``qkv``,
``out_proj``) are not attention's T x T part and are left out. Nothing to
read where no such scope ran."""

from benchmark.trace_reduce import scope_share


def read(run, trace, cell, peak):
    return scope_share(trace.get("scopes"), ("fused_attention", "scores", "softmax", "context", "flash"))
