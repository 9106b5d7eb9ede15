"""Share of the window's wall time the fit loop waited for its next batch,
from the program's ``TrainingProfiler``."""


def read(run, trace, cell, peak):
    report = run["profiler"]
    return 100.0 * report["data_wait_fraction"] if report and report["iterations"] else None
