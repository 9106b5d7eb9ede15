"""Shared knobs for the Pallas kernel modules."""

from __future__ import annotations

import os

import jax
from jax.experimental.pallas import tpu as pltpu

# Scoped VMEM the routed kernels ask Mosaic for, explicitly: the compiler's
# own default differs by chip generation (16 MiB on a v5e, whose core has
# 128 MiB), and a kernel should not start or stop compiling with it.
VMEM_LIMIT_BYTES = 32 * 1024 * 1024
# Ceiling of the kernels' eligibility estimates (``_vmem_bytes``). They
# count a pinned block (W_rec) once while the grid pipeline holds two
# copies of every block, so the ceiling is under half the limit, with room
# left for Mosaic's own stack.
VMEM_BUDGET = 15 * 1024 * 1024


COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


def interpret_mode() -> bool:
    """Whether kernels run under the Pallas interpreter: the CPU test path,
    selected by ``DL4J_TPU_PALLAS_INTERPRET=1`` in the test environment. On
    a TPU the variable is an error — a compiled run never enters the
    interpreter, and a run that would have is told so."""
    if os.environ.get("DL4J_TPU_PALLAS_INTERPRET", "") != "1":
        return False
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            "DL4J_TPU_PALLAS_INTERPRET=1 on a tpu backend: the interpreter "
            "is the CPU test path; unset it to run the compiled kernels")
    return True


def kernels_available() -> bool:
    """The one platform gate of kernel eligibility: compiled on a TPU,
    interpreted in the CPU test environment, otherwise the XLA path."""
    return interpret_mode() or jax.default_backend() == "tpu"
