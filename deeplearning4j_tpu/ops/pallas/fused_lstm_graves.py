"""Persistent fused LSTM with peepholes and sequence masks (Pallas TPU).

Generalisation of :mod:`fused_lstm` covering the reference's ``GravesLSTM``
cell (peephole connections, ``org.deeplearning4j.nn.layers.recurrent.
GravesLSTM`` / cuDNN-helper role, SURVEY.md §2.1) and DL4J's masked-sequence
semantics (masked steps hold h/c and emit the held h). With zero peepholes
this is exactly the plain cell, so it also serves as the fast path for
masked ``LSTM`` layers — the two cases round 1 left on the scan path
(BASELINE config #3 benches GravesLSTM!).

Same structure as fused_lstm: whole-sequence input projection hoisted
outside; ``W_rec`` (and the tiny peephole row) pinned in VMEM; h/c carried
in f32 scratch across the sequential grid; per-step tensors streamed.
Backward runs the reverse-time recurrence in-kernel producing pre-activation
grads ``ds``; weight/peephole grads are large fused contractions outside.

Cell (gate order [i, f, g, o], peephole rows [p_i, p_f, p_o]):

    z   = zx_t + h @ W_rec
    i   = sigmoid(z_i + c * p_i)
    f   = sigmoid(z_f + c * p_f)
    g   = tanh(z_g)
    c~  = f * c + i * g
    o   = sigmoid(z_o + c~ * p_o)
    h~  = o * tanh(c~)
    h'  = m * h~ + (1-m) * h          (m: per-step mask, 1.0 when unmasked)
    c'  = m * c~ + (1-m) * c
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
    w_rec = h * 4 * h * itemsize
    streams = 2 * (b * h + 2 * b * 4 * h + b * h + b + b * h) * itemsize
    boundary = 4 * b * h * itemsize
    scratch = 2 * b * h * 4
    peep = b * 3 * h * itemsize
    return w_rec + streams + boundary + scratch + peep


def fused_graves_lstm_compatible(zx, h0) -> bool:
    """Same applicability rules as the plain kernel (tile-aligned B/H,
    T>=32, dtype, VMEM budget)."""
    if zx.ndim != 3 or h0.ndim != 2:
        return False
    t, b, h4 = zx.shape
    h = h0.shape[1]
    if h4 != 4 * h or b % 8 or h % 128:
        return False
    if t < 32 and not _interpret():
        return False
    if zx.dtype not in (jnp.float32, jnp.bfloat16):
        return False
    if _vmem_bytes(b, h, jnp.dtype(zx.dtype).itemsize) > _VMEM_BUDGET:
        return False
    return kernels_available()


# ---------------------------------------------------------------- forward
def _fwd_kernel(zx_ref, wrec_ref, peep_ref, h0_ref, c0_ref, mask_ref,
                ys_ref, hT_ref, cT_ref, gates_ref, cseq_ref,
                h_scr, c_scr, *, hidden: int):
    t = pl.program_id(0)
    n_t = pl.num_programs(0)
    H = hidden

    @pl.when(t == 0)
    def _():
        h_scr[:] = h0_ref[:].astype(jnp.float32)
        c_scr[:] = c0_ref[:].astype(jnp.float32)

    h = h_scr[:]
    c = c_scr[:]
    in_dtype = zx_ref.dtype
    z = zx_ref[0].astype(jnp.float32) + jax.lax.dot(
        h.astype(in_dtype), wrec_ref[:], preferred_element_type=jnp.float32)
    p = peep_ref[:].astype(jnp.float32)  # (B, 3H) pre-broadcast
    i = jax.nn.sigmoid(z[:, :H] + c * p[:, :H])
    f = jax.nn.sigmoid(z[:, H:2 * H] + c * p[:, H:2 * H])
    g = jnp.tanh(z[:, 2 * H:3 * H])
    c_til = f * c + i * g
    o = jax.nn.sigmoid(z[:, 3 * H:] + c_til * p[:, 2 * H:])
    h_til = o * jnp.tanh(c_til)
    m = mask_ref[0, 0].astype(jnp.float32)[:, None]  # (B, 1)
    h_new = m * h_til + (1.0 - m) * h
    c_new = m * c_til + (1.0 - m) * c

    ys_ref[0] = h_new.astype(ys_ref.dtype)
    if gates_ref is not None:
        gates_ref[0, :, :H] = i.astype(gates_ref.dtype)
        gates_ref[0, :, H:2 * H] = f.astype(gates_ref.dtype)
        gates_ref[0, :, 2 * H:3 * H] = g.astype(gates_ref.dtype)
        gates_ref[0, :, 3 * H:] = o.astype(gates_ref.dtype)
        cseq_ref[0] = c_new.astype(cseq_ref.dtype)  # CARRIED cell (masked)
    h_scr[:] = h_new
    c_scr[:] = c_new

    @pl.when(t == n_t - 1)
    def _():
        hT_ref[:] = h_new.astype(hT_ref.dtype)
        cT_ref[:] = c_new.astype(cT_ref.dtype)


def _graves_fwd(zx, w_rec, peep, h0, c0, mask, save_residuals):
    t, b, h4 = zx.shape
    h = h4 // 4
    dtype = zx.dtype
    out_shape = [
        jax.ShapeDtypeStruct((t, b, h), dtype),
        jax.ShapeDtypeStruct((b, h), dtype),
        jax.ShapeDtypeStruct((b, h), dtype),
    ]
    out_specs = [
        pl.BlockSpec((1, b, h), lambda i: (i, 0, 0)),
        pl.BlockSpec((b, h), lambda i: (0, 0)),
        pl.BlockSpec((b, h), lambda i: (0, 0)),
    ]
    if save_residuals:
        out_shape += [
            jax.ShapeDtypeStruct((t, b, h4), dtype),
            jax.ShapeDtypeStruct((t, b, h), dtype),
        ]
        out_specs += [
            pl.BlockSpec((1, b, h4), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, b, h), lambda i: (i, 0, 0)),
        ]
    kernel = functools.partial(_fwd_kernel, hidden=h)
    if not save_residuals:
        kernel = functools.partial(
            lambda *refs, hidden: _fwd_kernel(
                *refs[:9], None, None, *refs[9:], hidden=hidden),
            hidden=h)
    res = pl.pallas_call(
        kernel,
        name="fused_lstm_graves_fwd",
        out_shape=out_shape,
        grid=(t,),
        in_specs=[
            pl.BlockSpec((1, b, h4), lambda i: (i, 0, 0)),   # zx_t
            pl.BlockSpec((h, h4), lambda i: (0, 0)),         # W_rec (pinned)
            # peepholes pre-broadcast to (B, 3H) outside: Mosaic cannot
            # broadcast a lane-offset slice of a (1, 3H) vreg to (B, H)
            pl.BlockSpec((b, 3 * h), lambda i: (0, 0)),      # peepholes (pinned)
            pl.BlockSpec((b, h), lambda i: (0, 0)),          # h0
            pl.BlockSpec((b, h), lambda i: (0, 0)),          # c0
            # (T, 1, B) layout: Mosaic requires the last two block dims
            # to tile (8, 128) or equal the array dims — (1, B) of a (T, B)
            # array does neither, (1, 1, B) of (T, 1, B) does
            pl.BlockSpec((1, 1, b), lambda i: (i, 0, 0)),    # mask_t
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((b, h), jnp.float32),
            pltpu.VMEM((b, h), jnp.float32),
        ],
        compiler_params=COMPILER_PARAMS,
        interpret=_interpret(),
    )(zx, w_rec, jnp.broadcast_to(peep.reshape(1, 3 * h), (b, 3 * h)),
      h0, c0, mask.reshape(t, 1, b))
    if save_residuals:
        ys, hT, cT, gates, cseq = res
        return ys, hT, cT, (gates, cseq)
    ys, hT, cT = res
    return ys, hT, cT, None


# ---------------------------------------------------------------- backward
def _bwd_kernel(dys_ref, dhT_ref, dcT_ref, gates_ref, cprev_ref, mask_ref,
                wrecT_ref, peep_ref,
                ds_ref, dh0_ref, dc0_ref,
                dh_scr, dc_scr, *, hidden: int):
    """Reverse-time step (grid index counts backward)."""
    i_step = pl.program_id(0)
    n_t = pl.num_programs(0)
    H = hidden

    @pl.when(i_step == 0)
    def _():
        dh_scr[:] = dhT_ref[:].astype(jnp.float32)
        dc_scr[:] = dcT_ref[:].astype(jnp.float32)

    gates = gates_ref[0].astype(jnp.float32)
    i_g = gates[:, :H]
    f_g = gates[:, H:2 * H]
    g_g = gates[:, 2 * H:3 * H]
    o_g = gates[:, 3 * H:]
    c_prev = cprev_ref[0].astype(jnp.float32)
    c_til = f_g * c_prev + i_g * g_g
    tanh_c = jnp.tanh(c_til)
    p = peep_ref[:].astype(jnp.float32)
    m = mask_ref[0, 0].astype(jnp.float32)[:, None]

    dh_tot = dh_scr[:] + dys_ref[0].astype(jnp.float32)
    dc_tot = dc_scr[:]
    dh_til = m * dh_tot
    dc_til = m * dc_tot

    do = dh_til * tanh_c * o_g * (1.0 - o_g)
    dc_til = dc_til + dh_til * o_g * (1.0 - tanh_c * tanh_c) \
        + do * p[:, 2 * H:]
    di = dc_til * g_g * i_g * (1.0 - i_g)
    df = dc_til * c_prev * f_g * (1.0 - f_g)
    dg = dc_til * i_g * (1.0 - g_g * g_g)

    in_dtype = ds_ref.dtype
    ds_ref[0, :, :H] = di.astype(in_dtype)
    ds_ref[0, :, H:2 * H] = df.astype(in_dtype)
    ds_ref[0, :, 2 * H:3 * H] = dg.astype(in_dtype)
    ds_ref[0, :, 3 * H:] = do.astype(in_dtype)
    ds = ds_ref[0]
    dh_scr[:] = jax.lax.dot(ds, wrecT_ref[:],
                            preferred_element_type=jnp.float32) \
        + (1.0 - m) * dh_tot
    dc_scr[:] = dc_til * f_g + di * p[:, :H] + df * p[:, H:2 * H] \
        + (1.0 - m) * dc_tot

    @pl.when(i_step == n_t - 1)
    def _():
        dh0_ref[:] = dh_scr[:].astype(dh0_ref.dtype)
        dc0_ref[:] = dc_scr[:].astype(dc0_ref.dtype)


def _graves_bwd_kernel_call(dys, dhT, dcT, gates, c_prev_seq, mask, w_rec,
                            peep):
    t, b, h4 = gates.shape
    h = h4 // 4
    dtype = gates.dtype
    w_rec_t = w_rec.T
    rev3 = lambda i: (t - 1 - i, 0, 0)  # noqa: E731
    ds, dh0, dc0 = pl.pallas_call(
        functools.partial(_bwd_kernel, hidden=h),
        name="fused_lstm_graves_bwd",
        out_shape=[
            jax.ShapeDtypeStruct((t, b, h4), dtype),
            jax.ShapeDtypeStruct((b, h), dtype),
            jax.ShapeDtypeStruct((b, h), dtype),
        ],
        grid=(t,),
        in_specs=[
            pl.BlockSpec((1, b, h), rev3),                   # dys_t
            pl.BlockSpec((b, h), lambda i: (0, 0)),          # dhT
            pl.BlockSpec((b, h), lambda i: (0, 0)),          # dcT
            pl.BlockSpec((1, b, h4), rev3),                  # gates_t
            pl.BlockSpec((1, b, h), rev3),                   # c_{t-1}
            pl.BlockSpec((1, 1, b), lambda i: (t - 1 - i, 0, 0)),  # mask_t
            pl.BlockSpec((h4, h), lambda i: (0, 0)),         # W_rec^T
            pl.BlockSpec((b, 3 * h), lambda i: (0, 0)),      # peepholes
        ],
        out_specs=[
            pl.BlockSpec((1, b, h4), rev3),
            pl.BlockSpec((b, h), lambda i: (0, 0)),
            pl.BlockSpec((b, h), lambda i: (0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((b, h), jnp.float32),
            pltpu.VMEM((b, h), jnp.float32),
        ],
        compiler_params=COMPILER_PARAMS,
        interpret=_interpret(),
    )(dys, dhT, dcT, gates, c_prev_seq, mask.reshape(t, 1, b), w_rec_t,
      jnp.broadcast_to(peep.reshape(1, 3 * h), (b, 3 * h)))
    return ds, dh0, dc0


# ------------------------------------------------------------- public VJP
@jax.custom_vjp
def fused_graves_lstm(zx, w_rec, peep, h0, c0, mask):
    """Peephole+masked fused recurrence. ``zx`` (T, B, 4H) hoisted input
    projection, ``peep`` (3H,), ``mask`` (T, B) with 1.0 = real step.
    Returns ``(ys, hT, cT)``; check :func:`fused_graves_lstm_compatible`."""
    ys, hT, cT, _ = _graves_fwd(zx, w_rec, peep, h0, c0, mask,
                                save_residuals=False)
    return ys, hT, cT


def _vjp_fwd(zx, w_rec, peep, h0, c0, mask):
    ys, hT, cT, (gates, cseq) = _graves_fwd(zx, w_rec, peep, h0, c0, mask,
                                            save_residuals=True)
    return (ys, hT, cT), (ys, gates, cseq, w_rec, peep, h0, c0, mask)


def _vjp_bwd(res, cotangents):
    dys, dhT, dcT = cotangents
    ys, gates, cseq, w_rec, peep, h0, c0, mask = res
    h = h0.shape[-1]
    c_prev = jnp.concatenate([c0[None].astype(cseq.dtype), cseq[:-1]], axis=0)
    ds, dh0, dc0 = _graves_bwd_kernel_call(dys, dhT, dcT, gates, c_prev,
                                           mask, w_rec, peep)
    h_prev = jnp.concatenate([h0[None].astype(ys.dtype), ys[:-1]], axis=0)
    hp = h_prev.reshape(-1, h)
    dsf = ds.reshape(-1, 4 * h)
    dw_rec = jax.lax.dot_general(
        hp, dsf, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(w_rec.dtype)
    # Peephole grads: three fused (T,B,H) reductions outside the kernel.
    dsf32 = ds.astype(jnp.float32)
    cpf = c_prev.astype(jnp.float32)
    i_g = gates[..., :h].astype(jnp.float32)
    f_g = gates[..., h:2 * h].astype(jnp.float32)
    g_g = gates[..., 2 * h:3 * h].astype(jnp.float32)
    c_til = f_g * cpf + i_g * g_g
    dp_i = jnp.sum(dsf32[..., :h] * cpf, axis=(0, 1))
    dp_f = jnp.sum(dsf32[..., h:2 * h] * cpf, axis=(0, 1))
    dp_o = jnp.sum(dsf32[..., 3 * h:] * c_til, axis=(0, 1))
    dpeep = jnp.concatenate([dp_i, dp_f, dp_o]).astype(peep.dtype)
    return ds, dw_rec, dpeep, dh0, dc0, jnp.zeros_like(mask)


fused_graves_lstm.defvjp(_vjp_fwd, _vjp_bwd)
