"""Share of the fit call's wall time that no fit stage covers, from the
program's ``TrainingProfiler`` (``unattributed_fraction``: the root minus
the stages on the fit thread). A program without the stages has no such
key: nothing to read."""


def read(run, trace, cell, peak):
    report = run["profiler"]
    if not report or not report["iterations"] or "unattributed_fraction" not in report:
        return None
    return 100.0 * report["unattributed_fraction"]
