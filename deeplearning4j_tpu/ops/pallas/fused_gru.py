"""Persistent fused GRU as Pallas TPU kernels (forward AND backward).

Companion to :mod:`fused_lstm` (SURVEY.md §7.2's hand-written-kernel layer):
the input projection ``x @ W + b`` for the whole sequence is hoisted to one
MXU matmul outside the kernel; the sequential recurrence runs with ``W_rec``
pinned in VMEM and ``h`` carried in VMEM scratch across the grid.

Gate order matches the layer convention [r, u, n] (reset, update, new):

    zh  = h @ W_rec                       (one (B,H)@(H,3H) matmul per step)
    r   = sigmoid(zx_r + zh_r)
    u   = sigmoid(zx_u + zh_u)
    n   = tanh(zx_n + r * zh_n)
    h'  = (1 - u) * n + u * h

Backward (reverse-time kernel): with dh' arriving from t+1 and dys_t,

    du    = dh' * (h - n) * u * (1-u)
    da    = dh' * (1-u) * (1-n^2)          (pre-tanh grad of n)
    dr    = da * zh_n;  ds_r = dr * r * (1-r)
    dzx   = [ds_r, ds_u, da]               (input-projection grad, streamed)
    ds_rec= [ds_r, ds_u, da * r]           (recurrent-projection grad)
    dh    = dh' * u + ds_rec @ W_rec^T

The weight gradients are large matmuls OUTSIDE the kernel:
``dW_rec = h_prev^T @ ds_rec`` where ``ds_rec`` is rebuilt from the streamed
``dzx`` and the saved reset gate (only the n-third differs by the factor r).

Residuals saved by the forward for backward: activated gates [r, u, n]
(T, B, 3H) and the pre-activation recurrent n-slice ``zh_n`` (T, B, H).

Applicability mirrors the LSTM kernel: default activations, no mask,
tile-aligned shapes within the VMEM budget, T >= 32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops.pallas.common import VMEM_BUDGET as _VMEM_BUDGET
from deeplearning4j_tpu.ops.pallas.common import COMPILER_PARAMS
from deeplearning4j_tpu.ops.pallas.common import interpret_mode as _interpret
from deeplearning4j_tpu.ops.pallas.common import kernels_available


def _vmem_bytes(b: int, h: int, itemsize: int) -> int:
    """Worst-case (backward) footprint: pinned W_rec^T + double-buffered
    streams (dys, gates, zh_n, h_prev, dzx) + boundary blocks + f32
    scratch."""
    w_rec = h * 3 * h * itemsize
    streams = 2 * (b * h + b * 3 * h + b * h + b * h + b * 3 * h) * itemsize
    boundary = 2 * b * h * itemsize
    scratch = b * h * 4
    return w_rec + streams + boundary + scratch


def fused_gru_compatible(zx, h0) -> bool:
    if zx.ndim != 3 or h0.ndim != 2:
        return False
    t, b, h3 = zx.shape
    h = h0.shape[1]
    if h3 != 3 * h:
        return False
    if b % 8 or h % 128:
        return False
    if t < 32 and not _interpret():
        return False
    if zx.dtype not in (jnp.float32, jnp.bfloat16):
        return False
    if _vmem_bytes(b, h, jnp.dtype(zx.dtype).itemsize) > _VMEM_BUDGET:
        return False
    return kernels_available()


# ---------------------------------------------------------------- forward


def _fwd_kernel(zx_ref, wrec_ref, h0_ref,
                ys_ref, hT_ref, gates_ref, zhn_ref,
                h_scr, *, hidden: int):
    t = pl.program_id(0)
    n_t = pl.num_programs(0)
    H = hidden

    @pl.when(t == 0)
    def _():
        h_scr[:] = h0_ref[:].astype(jnp.float32)

    h = h_scr[:]
    in_dtype = zx_ref.dtype
    zh = jax.lax.dot(h.astype(in_dtype), wrec_ref[:],
                     preferred_element_type=jnp.float32)
    zx = zx_ref[0].astype(jnp.float32)
    r = jax.nn.sigmoid(zx[:, :H] + zh[:, :H])
    u = jax.nn.sigmoid(zx[:, H:2 * H] + zh[:, H:2 * H])
    zh_n = zh[:, 2 * H:]
    n = jnp.tanh(zx[:, 2 * H:] + r * zh_n)
    h_new = (1.0 - u) * n + u * h

    ys_ref[0] = h_new.astype(ys_ref.dtype)
    if gates_ref is not None:
        gates_ref[0, :, :H] = r.astype(gates_ref.dtype)
        gates_ref[0, :, H:2 * H] = u.astype(gates_ref.dtype)
        gates_ref[0, :, 2 * H:] = n.astype(gates_ref.dtype)
        zhn_ref[0] = zh_n.astype(zhn_ref.dtype)
    h_scr[:] = h_new

    @pl.when(t == n_t - 1)
    def _():
        hT_ref[:] = h_new.astype(hT_ref.dtype)


def _gru_fwd(zx, w_rec, h0, save_residuals):
    t, b, h3 = zx.shape
    h = h3 // 3
    dtype = zx.dtype
    out_shape = [
        jax.ShapeDtypeStruct((t, b, h), dtype),   # ys
        jax.ShapeDtypeStruct((b, h), dtype),      # hT
    ]
    out_specs = [
        pl.BlockSpec((1, b, h), lambda i: (i, 0, 0)),
        pl.BlockSpec((b, h), lambda i: (0, 0)),
    ]
    if save_residuals:
        out_shape += [
            jax.ShapeDtypeStruct((t, b, h3), dtype),  # gates [r,u,n]
            jax.ShapeDtypeStruct((t, b, h), dtype),   # zh_n
        ]
        out_specs += [
            pl.BlockSpec((1, b, h3), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, b, h), lambda i: (i, 0, 0)),
        ]
    kernel = functools.partial(_fwd_kernel, hidden=h)
    if not save_residuals:
        kernel = functools.partial(
            lambda *refs, hidden: _fwd_kernel(
                *refs[:5], None, None, *refs[5:], hidden=hidden),
            hidden=h)
    res = pl.pallas_call(
        kernel,
        name="fused_gru_fwd",
        out_shape=out_shape,
        grid=(t,),
        in_specs=[
            pl.BlockSpec((1, b, h3), lambda i: (i, 0, 0)),   # zx_t
            pl.BlockSpec((h, h3), lambda i: (0, 0)),         # W_rec (pinned)
            pl.BlockSpec((b, h), lambda i: (0, 0)),          # h0
        ],
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((b, h), jnp.float32)],
        compiler_params=COMPILER_PARAMS,
        interpret=_interpret(),
    )(zx, w_rec, h0)
    if save_residuals:
        ys, hT, gates, zhn = res
        return ys, hT, (gates, zhn)
    ys, hT = res
    return ys, hT, None


# ---------------------------------------------------------------- backward


def _bwd_kernel(dys_ref, dhT_ref, gates_ref, zhn_ref, hprev_ref, wrecT_ref,
                dzx_ref, dh0_ref,
                dh_scr, *, hidden: int):
    """Reverse-time step (grid index i counts BACKWARD: t = T-1-i)."""
    i_step = pl.program_id(0)
    n_t = pl.num_programs(0)
    H = hidden

    @pl.when(i_step == 0)
    def _():
        dh_scr[:] = dhT_ref[:].astype(jnp.float32)

    gates = gates_ref[0].astype(jnp.float32)
    r = gates[:, :H]
    u = gates[:, H:2 * H]
    n = gates[:, 2 * H:]
    zh_n = zhn_ref[0].astype(jnp.float32)
    h_prev = hprev_ref[0].astype(jnp.float32)

    dh = dh_scr[:] + dys_ref[0].astype(jnp.float32)
    du = dh * (h_prev - n) * u * (1.0 - u)
    da = dh * (1.0 - u) * (1.0 - n * n)
    dr = da * zh_n
    ds_r = dr * r * (1.0 - r)

    in_dtype = dzx_ref.dtype
    dzx_ref[0, :, :H] = ds_r.astype(in_dtype)
    dzx_ref[0, :, H:2 * H] = du.astype(in_dtype)
    dzx_ref[0, :, 2 * H:] = da.astype(in_dtype)

    # ds_rec differs from dzx only in the n-third: da * r
    ds_rec_n = (da * r).astype(in_dtype)
    # dh_prev = dh*u + ds_rec @ W_rec^T, assembled from the three thirds
    wT = wrecT_ref[:]  # (3H, H)
    dh_prev = (dh * u
               + jax.lax.dot(ds_r.astype(in_dtype), wT[:H],
                             preferred_element_type=jnp.float32)
               + jax.lax.dot(du.astype(in_dtype), wT[H:2 * H],
                             preferred_element_type=jnp.float32)
               + jax.lax.dot(ds_rec_n, wT[2 * H:],
                             preferred_element_type=jnp.float32))
    dh_scr[:] = dh_prev

    @pl.when(i_step == n_t - 1)
    def _():
        dh0_ref[:] = dh_scr[:].astype(dh0_ref.dtype)


def _gru_bwd_kernel_call(dys, dhT, gates, zhn, h_prev_seq, w_rec):
    t, b, h3 = gates.shape
    h = h3 // 3
    dtype = gates.dtype
    w_rec_t = w_rec.T  # (3H, H)
    rev = lambda i: (t - 1 - i, 0, 0)  # noqa: E731 — reverse-time index map
    dzx, dh0 = pl.pallas_call(
        functools.partial(_bwd_kernel, hidden=h),
        name="fused_gru_bwd",
        out_shape=[
            jax.ShapeDtypeStruct((t, b, h3), dtype),
            jax.ShapeDtypeStruct((b, h), dtype),
        ],
        grid=(t,),
        in_specs=[
            pl.BlockSpec((1, b, h), rev),                    # dys_t
            pl.BlockSpec((b, h), lambda i: (0, 0)),          # dhT
            pl.BlockSpec((1, b, h3), rev),                   # gates_t
            pl.BlockSpec((1, b, h), rev),                    # zh_n
            pl.BlockSpec((1, b, h), rev),                    # h_{t-1}
            pl.BlockSpec((h3, h), lambda i: (0, 0)),         # W_rec^T (pinned)
        ],
        out_specs=[
            pl.BlockSpec((1, b, h3), rev),
            pl.BlockSpec((b, h), lambda i: (0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((b, h), jnp.float32)],
        compiler_params=COMPILER_PARAMS,
        interpret=_interpret(),
    )(dys, dhT, gates, zhn, h_prev_seq, w_rec_t)
    return dzx, dh0


# ------------------------------------------------------------- public VJP


@jax.custom_vjp
def fused_gru(zx, w_rec, h0):
    """Run the fused GRU recurrence. ``zx`` is the hoisted input projection
    ``x @ W + b`` laid out (T, B, 3H); returns ``(ys, hT)``. Check
    :func:`fused_gru_compatible` first."""
    ys, hT, _ = _gru_fwd(zx, w_rec, h0, save_residuals=False)
    return ys, hT


def _fused_gru_vjp_fwd(zx, w_rec, h0):
    ys, hT, (gates, zhn) = _gru_fwd(zx, w_rec, h0, save_residuals=True)
    return (ys, hT), (ys, gates, zhn, w_rec, h0)


def _fused_gru_vjp_bwd(res, cotangents):
    dys, dhT = cotangents
    ys, gates, zhn, w_rec, h0 = res
    h_prev = jnp.concatenate([h0[None], ys[:-1]], axis=0)
    dzx, dh0 = _gru_bwd_kernel_call(dys, dhT, gates, zhn, h_prev, w_rec)
    # ds_rec rebuilt from dzx: only the n-third is scaled by the reset gate
    h = h0.shape[1]
    r = gates[..., :h]
    ds_rec = jnp.concatenate(
        [dzx[..., :2 * h],
         (dzx[..., 2 * h:].astype(jnp.float32)
          * r.astype(jnp.float32)).astype(dzx.dtype)], axis=-1)
    hp = h_prev.reshape(-1, h)
    dsf = ds_rec.reshape(-1, 3 * h)
    dw_rec = jax.lax.dot_general(
        hp, dsf, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(w_rec.dtype)
    return dzx, dw_rec, dh0


fused_gru.defvjp(_fused_gru_vjp_fwd, _fused_gru_vjp_bwd)
