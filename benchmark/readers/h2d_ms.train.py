"""Mean host milliseconds a step spent turning the host batch into device
arrays (the ``h2d`` stage of the program's ``TrainingProfiler``). A program
without that stage has no such key: nothing to read."""


def read(run, trace, cell, peak):
    report = run["profiler"]
    if not report or not report["iterations"] or "h2d_mean_ms" not in report:
        return None
    return report["h2d_mean_ms"]
