"""Share of the step program's mean device time in the block-diffusion head
(``nn/recurrent_layers.py`` ``BlockDiffusionLoss``): the head's matmul over
the noisy half (``loss/lm_head``) and the weighted loss around it (``loss``),
forward and backward, from the trace's ``scopes``. Nothing to read where no
such scope ran."""

from benchmark.trace_reduce import scope_share


def read(run, trace, cell, peak):
    return scope_share(trace.get("scopes"), ("lm_head", "loss"))
