"""Assignments routed to the experts this chip holds in the window's last
step, added up over the expert layers: the ``assigned`` counters of the
model state as ``TrainingProfiler.report()["model_state"]`` gives them
(traced run only). The grouped matmuls' rows, and so their time, follow this
number from seed to seed. Nothing to read where the program's profiler
reports no model state."""


def read(run, trace, cell, peak):
    state = (run.get("profiler") or {}).get("model_state")
    counts = [sum(values) for path, values in (state or {}).items() if path.rsplit("/", 1)[-1] == "assigned"]
    return float(sum(counts)) if counts else None
