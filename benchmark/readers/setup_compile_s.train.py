"""Seconds of backend compiles before the window's fit call began: the self
time of the compiles the persistent cache could not serve, plus the
backend-compile spans in which it served one (the retrieval and what jax did
around it), from ``TrainingProfiler.report()["setup"]``. None where the
program keeps no such record."""


def read(run, trace, cell, peak):
    setup = (run.get("profiler") or {}).get("setup")
    if setup is None:
        return None
    return round(setup["compile_seconds"] + setup["retrieval_seconds"], 4)
