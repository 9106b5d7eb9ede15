"""``calibrate.py`` for cells whose batch is a row of two halves and a shorter row of labels (a
block-diffusion model: features ``[xt ; x0]`` of 2 T positions, labels of T): leaving out half of the
positions, as ``calibrate_half_sequence.py`` does, would cut features and labels at different places.
The planted fault leaves out half of the *trained* positions instead: every second label that is not
negative is set to -1. Same arguments, same output lines; the reading keeps the name ``fault_half_batch``."""

import sys

import jax.numpy as jnp

from benchmark import calibrate  # the caller puts the repository's root on the path, as for calibrate.py


def half_labels(step):
    """Fault: the step sees only every second of each row's trained positions."""
    def faulty(params, state, moments, batch, t):
        ids, labels, mask = batch
        trained = labels >= 0
        kept = trained & (jnp.cumsum(trained, axis=1) % 2 == 1)
        return step(params, state, moments, (ids, jnp.where(kept, labels, -1), mask), t)
    return faulty


if __name__ == "__main__":
    calibrate.half_batch = half_labels
    sys.exit(calibrate.main())
