"""1 - (union of device-op intervals) / traced seconds, averaged over chips."""


def read(run, trace, cell, peak):
    return 100.0 * (1.0 - trace["busy_s"] / run["traced_s"])
