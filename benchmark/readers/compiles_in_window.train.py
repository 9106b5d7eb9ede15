"""Executables the compile cache was asked for between the window's start
and its end (hits + misses + corrupt); 0 when warm-up covered every shape."""


def read(run, trace, cell, peak):
    return run["compiles_in_window"]
