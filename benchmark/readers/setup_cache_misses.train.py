"""Executables compiled before the window's fit call began because the
persistent cache did not hold them (``compile_cache.stats()["misses"]`` as
``TrainingProfiler.report()["setup"]`` has it): the twin, for set-up, of
``compiles_in_window.train``. None where the program keeps no such record."""


def read(run, trace, cell, peak):
    setup = (run.get("profiler") or {}).get("setup")
    if setup is None:
        return None
    return setup["misses"]
