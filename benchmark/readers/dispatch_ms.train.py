"""Mean host seconds per dispatch, from the program's ``TrainingProfiler``."""


def read(run, trace, cell, peak):
    report = run["profiler"]
    return report["dispatch_mean_ms"] if report and report["iterations"] else None
