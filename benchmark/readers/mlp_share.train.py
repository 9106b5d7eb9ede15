"""Share of the step program's mean device time under the ``mlp`` scope
(``nn/attention_layers.py`` ``GatedMLP``: the dense SwiGLU's three matmuls;
Adam's update of their weights is fused into the backward matmuls and counts
here), forward and backward, from the trace's ``scopes``. Nothing to read
where no such scope ran."""

from benchmark.trace_reduce import scope_share


def read(run, trace, cell, peak):
    return scope_share(trace.get("scopes"), ("mlp",))
