"""Share of the step program's mean device time in the chunked gated delta
rule (``nn/linear_attention_layers.py`` ``chunk_kda``), forward and backward
with what the backward recomputes, from the trace's ``scopes``: what a kernel
for the recurrence would replace. Two scopes: ``kda_scan`` (the re-layouts
around the scan) and ``while`` (the ops of the scan's body; the program opens
the scan under no scope of its own so that the ``while`` op itself, whose
span covers its body's ops a second time, is booked under the layer alone
and is not read here). Nothing to read where no such scope ran."""

from benchmark.trace_reduce import scope_share


def read(run, trace, cell, peak):
    return scope_share(trace.get("scopes"), ("kda_scan", "while"))
