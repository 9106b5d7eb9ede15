"""Fused inverted dropout (+ optional residual add) with IN-KERNEL PRNG.

Why a kernel: profiled on v5e, XLA materialises every dropout site three
times over — the ``rng-bit-generator`` writes a u32[batch, T, d] bits tensor
(25 MB at BERT-base shape), a layout ``copy`` of it follows (the rbg output
tiling never matches the consumer), and the bool keep-mask is saved for the
backward pass.  At 25 dropout sites per BERT-base train step that is
gigabytes of pure mask traffic per step (the round-3 profile showed
~1500 copy ops/step, the largest being exactly these u32 bits tensors).

Here the mask NEVER exists in HBM, in either pass:

- forward:  seed the per-core PRNG (``pltpu.prng_seed``) from a scalar
  folded with the grid position, draw the bits straight into VMEM, apply
  ``x + where(bits < keep_threshold, h/keep, 0)`` and write only the output.
- backward: re-seed identically, regenerate the SAME bits, and scale the
  incoming cotangent — recompute-in-backward at the kernel level, so the
  residual set is empty (the custom_vjp saves only the scalar seed).

This is the cuDNN-style fused-dropout role from the reference's helper layer
(SURVEY.md §7.2, upstream ``org.deeplearning4j.cuda`` dropout helpers),
designed TPU-first: the VPU generates bits faster than HBM could store them.

The mask distribution matches ``nn.base.dropout_mask`` statistically
(Bernoulli(keep) per element) but uses the Mosaic PRNG stream, not the jax
rbg stream — seeds produce different (equally valid) masks than the jnp
path. Tests assert statistics + determinism-given-seed + fwd/bwd mask
consistency, not specific bits.

CPU/test path: ``DL4J_TPU_PALLAS_INTERPRET=1`` runs the same kernels under
the Pallas interpreter.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops.pallas.common import interpret_mode as _interpret
from deeplearning4j_tpu.ops.pallas.common import kernels_available

# Rows per grid step over the flattened (rows, features) view. 512 rows of
# bf16[*, 768] = 0.77 MB in + out + 1.5 MB of u32 bits — far under VMEM.
BLOCK_ROWS = 512


def _fwd_kernel(seed_ref, h_ref, x_ref, o_ref, *, thresh, inv_keep):
    pltpu.prng_seed(seed_ref[0], pl.program_id(0))
    bits = pltpu.prng_random_bits(h_ref.shape).astype(jnp.uint32)
    kept = bits < jnp.uint32(thresh)
    y = jnp.where(kept, h_ref[...] * jnp.asarray(inv_keep, h_ref.dtype),
                  jnp.zeros((), h_ref.dtype))
    if x_ref is not None:
        y = x_ref[...] + y
    o_ref[...] = y


def _bwd_kernel(seed_ref, g_ref, o_ref, *, thresh, inv_keep):
    pltpu.prng_seed(seed_ref[0], pl.program_id(0))
    bits = pltpu.prng_random_bits(g_ref.shape).astype(jnp.uint32)
    kept = bits < jnp.uint32(thresh)
    o_ref[...] = jnp.where(kept, g_ref[...] * jnp.asarray(inv_keep, g_ref.dtype),
                           jnp.zeros((), g_ref.dtype))


def _flatten(h):
    d = h.shape[-1]
    return h.reshape(-1, d)


def fused_dropout_compatible(h, rate: float) -> bool:
    """Kernel eligibility: TPU (or interpret mode), 0<rate<1, flattenable to
    (rows, d) with rows % BLOCK_ROWS == 0 and d % 128 == 0."""
    if not (0.0 < float(rate) < 1.0):
        return False
    if not kernels_available():
        return False
    if h.ndim < 2:
        return False
    d = h.shape[-1]
    rows = int(np.prod(h.shape[:-1]))
    return rows % BLOCK_ROWS == 0 and d % 128 == 0


def _ref_bits(seed, rows, d):
    """Interpreter/CPU emulation of the in-kernel draw: the Mosaic PRNG
    primitives have no interpreter lowering in this jax version, so tests
    use a jax-rbg stream keyed by the same scalar seed. Statistically
    identical, deterministic given the seed, consistent between fwd and bwd
    (both call this) — but a DIFFERENT stream than the TPU kernel's."""
    key = jax.random.wrap_key_data(
        jnp.stack([seed.astype(jnp.uint32)] * 4).reshape(4), impl="rbg")
    return jax.random.bits(key, (rows, d), jnp.uint32)


def _call(kernel, seed, args, out_dtype, rows, d, thresh, inv_keep):
    seed = jnp.reshape(seed, (1,)).astype(jnp.int32)
    if _interpret():
        bits = _ref_bits(seed[0], rows, d)
        kept = bits < jnp.uint32(thresh)
        h = args[0]
        y = jnp.where(kept, h * jnp.asarray(inv_keep, h.dtype),
                      jnp.zeros((), h.dtype))
        if len(args) > 1:
            y = args[1] + y
        return y
    grid = (rows // BLOCK_ROWS,)
    # index_map receives the scalar-prefetch ref after the grid indices
    spec = pl.BlockSpec((BLOCK_ROWS, d), lambda i, *_: (i, 0))
    return pl.pallas_call(
        functools.partial(kernel, thresh=thresh, inv_keep=inv_keep),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[spec] * len(args),
            out_specs=spec,
        ),
        out_shape=jax.ShapeDtypeStruct((rows, d), out_dtype),
        interpret=_interpret(),
    )(seed, *args)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def fused_dropout_add(x, h, seed, rate: float):
    """``x + inverted_dropout(h, rate)`` (x may be None for plain dropout).

    ``seed``: int32 scalar array — fold the training step's PRNG key down
    with ``seed_from_key``. Same seed -> same mask, forward and backward.
    """
    y, _ = _fwd_res(x, h, seed, rate)
    return y


def _thresh(rate: float) -> int:
    keep = 1.0 - float(rate)
    return min(int(keep * 4294967296.0), 4294967295)


def _fwd_res(x, h, seed, rate):
    d = h.shape[-1]
    rows = int(np.prod(h.shape[:-1]))
    keep = 1.0 - float(rate)
    hf = _flatten(h)
    args = (hf,) if x is None else (hf, _flatten(x))
    # kernel positional order is (seed, h, x, o); adapt when x is None
    if x is None:
        def kern(seed_ref, h_ref, o_ref, *, thresh, inv_keep):
            return _fwd_kernel(seed_ref, h_ref, None, o_ref,
                               thresh=thresh, inv_keep=inv_keep)
    else:
        kern = _fwd_kernel
    y = _call(kern, seed, args, h.dtype, rows, d, _thresh(rate), 1.0 / keep)
    return y.reshape(h.shape), (seed,)


def _fwd_vjp(x, h, seed, rate):
    y, res = _fwd_res(x, h, seed, rate)
    return y, (res, x is None)


def _bwd_vjp(rate, packed, gy):
    (seed,), x_was_none = packed
    d = gy.shape[-1]
    rows = int(np.prod(gy.shape[:-1]))
    keep = 1.0 - float(rate)
    dh = _call(_bwd_kernel, seed, (_flatten(gy),), gy.dtype, rows, d,
               _thresh(rate), 1.0 / keep).reshape(gy.shape)
    dx = None if x_was_none else gy
    return (dx, dh, jnp.zeros_like(seed))


fused_dropout_add.defvjp(_fwd_vjp, _bwd_vjp)


def fused_dropout(h, seed, rate: float):
    """Plain fused inverted dropout (no residual)."""
    return fused_dropout_add(None, h, seed, rate)


def seed_from_key(key) -> jax.Array:
    """Fold a jax PRNG key to the kernel's int32 scalar seed (one tiny
    threefry draw; fuses into the surrounding program)."""
    return jax.random.bits(key, (), jnp.uint32).astype(jnp.int32)
