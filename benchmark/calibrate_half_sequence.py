"""``calibrate.py`` for cells of one row a step (a language model at batch 1 x T): the planted fault
leaves out the second half of the positions, where ``calibrate.py``'s leaves out half of the rows (of
one row, none: its readings are NaN there). Same arguments, same output lines; the reading keeps the
name ``fault_half_batch``."""

import sys

from benchmark import calibrate  # the caller puts the repository's root on the path, as for calibrate.py


def half_sequence(step):
    """Fault: the step sees only the first half of every row's positions."""
    def faulty(params, state, moments, batch, t):
        return step(params, state, moments, tuple(None if a is None else a[:, :a.shape[1] // 2] for a in batch), t)
    return faulty


if __name__ == "__main__":
    calibrate.half_batch = half_sequence
    sys.exit(calibrate.main())
