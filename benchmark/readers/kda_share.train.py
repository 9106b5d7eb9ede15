"""Share of the step program's mean device time under the delta-rule
mixer's scopes (``nn/linear_attention_layers.py``): ``kda_in`` (projections,
convolutions, gates), ``kda_scan`` with ``while`` (the scan's body, see
``kda_scan_share.train.py``) and ``kda_out`` (gated norm, output projection),
forward and backward, from the trace's ``scopes``. Nothing to read where no
such scope ran."""

from benchmark.trace_reduce import scope_share


def read(run, trace, cell, peak):
    return scope_share(trace.get("scopes"), ("kda_in", "kda_scan", "while", "kda_out"))
