"""Seconds of tracing and lowering (Pallas / Mosaic kernels included) the
process spent before the window's fit call began: the self time of jax's
trace and lower spans as ``runtime.compile_cache`` booked them, from
``TrainingProfiler.report()["setup"]``. None where the program keeps no such
record."""


def read(run, trace, cell, peak):
    setup = (run.get("profiler") or {}).get("setup")
    if setup is None:
        return None
    return round(setup["trace_seconds"] + setup["lower_seconds"], 4)
