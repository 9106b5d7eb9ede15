"""Persistent fused LSTM as Pallas TPU kernels (forward AND backward).

The role cuDNN's fused LSTM (``CudnnLSTMHelper`` in the reference,
SURVEY.md §2.1/§7.2) plays on GPU, done the TPU way: the input projection
``x @ W + b`` for the WHOLE sequence is one big MXU matmul outside the
kernel (hoisted, as the scan path already does); the kernel then runs the
sequential recurrence with

- ``W_rec`` pinned in VMEM for the entire sequence (the scan path re-reads
  it from HBM every timestep — at H=512 that is 2 MB x T of pure HBM
  traffic this kernel eliminates),
- h/c carried in VMEM scratch across grid steps (TPU grids execute
  sequentially, so scratch persists from t to t+1),
- per-timestep inputs/outputs streamed through the grid pipeline
  (Pallas double-buffers the DMAs automatically).

The backward kernel runs the reverse-time recurrence producing the
per-step pre-activation gradients ``ds`` (and dh0/dc0); the weight/input
gradients are then three large MXU matmuls OUTSIDE the kernel:

    dzx    = ds                      (input-projection grad, streamed out)
    dW_rec = h_prev^T @ ds           (one (H, B*T) @ (B*T, 4H) matmul)
    dh0    = ds_0 @ W_rec^T          (computed in-kernel as the dh carry)

Gate order matches the layer convention [i, f, g, o]. Residuals saved for
backward: activated gates (T, B, 4H) and the cell sequence (T, B, H).

Applicability: default activations (sigmoid gates, tanh cell), no
per-timestep mask (masked sequences fall back to the scan path), shapes
aligned to TPU tiles. Set ``DL4J_TPU_PALLAS_INTERPRET=1`` to run in
interpreter mode on CPU (test path).
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
    """Worst-case kernel VMEM footprint — the BACKWARD kernel is the larger
    one: pinned W_rec^T plus double-buffered per-step streams (dys, gates,
    c_prev, ds) plus the boundary blocks (dhT/dcT/dh0/dc0) and f32 dh/dc
    scratch."""
    w_rec = h * 4 * h * itemsize
    streams = 2 * (b * h + b * 4 * h + b * h + b * 4 * h) * itemsize
    boundary = 4 * b * h * itemsize
    scratch = 2 * b * h * 4
    return w_rec + streams + boundary + scratch


def fused_lstm_compatible(zx, h0) -> bool:
    """Kernel applicability for ``(T, B, 4H)`` projected inputs and ``(B, H)``
    initial state: tile-aligned B/H, supported dtype, pinned weights within
    the VMEM budget, TPU (or interpreter)."""
    if zx.ndim != 3 or h0.ndim != 2:
        return False
    t, b, h4 = zx.shape
    h = h0.shape[1]
    if h4 != 4 * h:
        return False
    if b % 8 or h % 128:
        return False
    # Below ~T=32 the fixed kernel launch/DMA cost loses to the plain scan
    # (measured on v5e: 0.80x @T=4, 0.88x @16, 1.17x @64) — and T=1 is the
    # latency-critical rnnTimeStep path.
    if t < 32 and not _interpret():
        return False
    if zx.dtype not in (jnp.float32, jnp.bfloat16):
        return False
    if _vmem_bytes(b, h, jnp.dtype(zx.dtype).itemsize) > _VMEM_BUDGET:
        return False
    return kernels_available()


# ---------------------------------------------------------------- forward


def _fwd_kernel(zx_ref, wrec_ref, h0_ref, c0_ref,
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
        h.astype(in_dtype), wrec_ref[:],
        preferred_element_type=jnp.float32)
    i = jax.nn.sigmoid(z[:, :H])
    f = jax.nn.sigmoid(z[:, H:2 * H])
    g = jnp.tanh(z[:, 2 * H:3 * H])
    o = jax.nn.sigmoid(z[:, 3 * H:])
    c_new = f * c + i * g
    h_new = o * jnp.tanh(c_new)

    ys_ref[0] = h_new.astype(ys_ref.dtype)
    if gates_ref is not None:
        # sliced writes (no in-kernel concatenate — that is a VPU copy)
        gates_ref[0, :, :H] = i.astype(gates_ref.dtype)
        gates_ref[0, :, H:2 * H] = f.astype(gates_ref.dtype)
        gates_ref[0, :, 2 * H:3 * H] = g.astype(gates_ref.dtype)
        gates_ref[0, :, 3 * H:] = o.astype(gates_ref.dtype)
        cseq_ref[0] = c_new.astype(cseq_ref.dtype)
    h_scr[:] = h_new
    c_scr[:] = c_new

    @pl.when(t == n_t - 1)
    def _():
        hT_ref[:] = h_new.astype(hT_ref.dtype)
        cT_ref[:] = c_new.astype(cT_ref.dtype)


def _lstm_fwd(zx, w_rec, h0, c0, save_residuals):
    t, b, h4 = zx.shape
    h = h4 // 4
    dtype = zx.dtype
    out_shape = [
        jax.ShapeDtypeStruct((t, b, h), dtype),      # ys
        jax.ShapeDtypeStruct((b, h), dtype),         # hT
        jax.ShapeDtypeStruct((b, h), dtype),         # cT
    ]
    out_specs = [
        pl.BlockSpec((1, b, h), lambda i: (i, 0, 0)),
        pl.BlockSpec((b, h), lambda i: (0, 0)),
        pl.BlockSpec((b, h), lambda i: (0, 0)),
    ]
    if save_residuals:
        out_shape += [
            jax.ShapeDtypeStruct((t, b, h4), dtype),  # activated gates
            jax.ShapeDtypeStruct((t, b, h), dtype),   # cell sequence
        ]
        out_specs += [
            pl.BlockSpec((1, b, h4), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, b, h), lambda i: (i, 0, 0)),
        ]
    kernel = functools.partial(_fwd_kernel, hidden=h)
    if not save_residuals:
        kernel = functools.partial(
            lambda *refs, hidden: _fwd_kernel(
                *refs[:7], None, None, *refs[7:], hidden=hidden),
            hidden=h)
    res = pl.pallas_call(
        kernel,
        name="fused_lstm_fwd",
        out_shape=out_shape,
        grid=(t,),
        in_specs=[
            pl.BlockSpec((1, b, h4), lambda i: (i, 0, 0)),   # zx_t
            pl.BlockSpec((h, h4), lambda i: (0, 0)),         # W_rec (pinned)
            pl.BlockSpec((b, h), lambda i: (0, 0)),          # h0
            pl.BlockSpec((b, h), lambda i: (0, 0)),          # c0
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((b, h), jnp.float32),
            pltpu.VMEM((b, h), jnp.float32),
        ],
        compiler_params=COMPILER_PARAMS,
        interpret=_interpret(),
    )(zx, w_rec, h0, c0)
    if save_residuals:
        ys, hT, cT, gates, cseq = res
        return ys, hT, cT, (gates, cseq)
    ys, hT, cT = res
    return ys, hT, cT, None


# ---------------------------------------------------------------- backward


def _bwd_kernel(dys_ref, dhT_ref, dcT_ref, gates_ref, cprev_ref, wrecT_ref,
                ds_ref, dh0_ref, dc0_ref,
                dh_scr, dc_scr, *, hidden: int):
    """Reverse-time step (grid index i counts BACKWARD: t = T-1-i)."""
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
    # c_t rebuilt from the saved residuals instead of re-streaming cseq:
    c_t = f_g * c_prev + i_g * g_g
    tanh_c = jnp.tanh(c_t)

    dh = dh_scr[:] + dys_ref[0].astype(jnp.float32)
    dc = dc_scr[:] + dh * o_g * (1.0 - tanh_c * tanh_c)

    di = dc * g_g * i_g * (1.0 - i_g)
    df = dc * c_prev * f_g * (1.0 - f_g)
    dg = dc * i_g * (1.0 - g_g * g_g)
    do = dh * tanh_c * o_g * (1.0 - o_g)

    in_dtype = ds_ref.dtype
    ds_ref[0, :, :H] = di.astype(in_dtype)
    ds_ref[0, :, H:2 * H] = df.astype(in_dtype)
    ds_ref[0, :, 2 * H:3 * H] = dg.astype(in_dtype)
    ds_ref[0, :, 3 * H:] = do.astype(in_dtype)
    ds = ds_ref[0]
    dh_scr[:] = jax.lax.dot(ds, wrecT_ref[:],
                            preferred_element_type=jnp.float32)
    dc_scr[:] = dc * f_g

    @pl.when(i_step == n_t - 1)
    def _():
        dh0_ref[:] = dh_scr[:].astype(dh0_ref.dtype)
        dc0_ref[:] = dc_scr[:].astype(dc0_ref.dtype)


def _lstm_bwd_kernel_call(dys, dhT, dcT, gates, c_prev_seq, w_rec):
    t, b, h4 = gates.shape
    h = h4 // 4
    dtype = gates.dtype
    w_rec_t = w_rec.T  # (4H, H); one transpose outside the loop
    rev = lambda i: (t - 1 - i, 0, 0)  # noqa: E731 — reverse-time index map
    ds, dh0, dc0 = pl.pallas_call(
        functools.partial(_bwd_kernel, hidden=h),
        name="fused_lstm_bwd",
        out_shape=[
            jax.ShapeDtypeStruct((t, b, h4), dtype),
            jax.ShapeDtypeStruct((b, h), dtype),
            jax.ShapeDtypeStruct((b, h), dtype),
        ],
        grid=(t,),
        in_specs=[
            pl.BlockSpec((1, b, h), rev),                    # dys_t
            pl.BlockSpec((b, h), lambda i: (0, 0)),          # dhT
            pl.BlockSpec((b, h), lambda i: (0, 0)),          # dcT
            pl.BlockSpec((1, b, h4), rev),                   # gates_t
            pl.BlockSpec((1, b, h), rev),                    # c_{t-1}
            pl.BlockSpec((h4, h), lambda i: (0, 0)),         # W_rec^T (pinned)
        ],
        out_specs=[
            pl.BlockSpec((1, b, h4), rev),
            pl.BlockSpec((b, h), lambda i: (0, 0)),
            pl.BlockSpec((b, h), lambda i: (0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((b, h), jnp.float32),
            pltpu.VMEM((b, h), jnp.float32),
        ],
        compiler_params=COMPILER_PARAMS,
        interpret=_interpret(),
    )(dys, dhT, dcT, gates, c_prev_seq, w_rec_t)
    return ds, dh0, dc0


# ------------------------------------------------------------- public VJP


@jax.custom_vjp
def fused_lstm(zx, w_rec, h0, c0):
    """Run the fused recurrence. ``zx`` is the hoisted input projection
    ``x @ W + b`` laid out (T, B, 4H); returns ``(ys, hT, cT)`` with ys
    (T, B, H). Check :func:`fused_lstm_compatible` first."""
    ys, hT, cT, _ = _lstm_fwd(zx, w_rec, h0, c0, save_residuals=False)
    return ys, hT, cT


def _fused_lstm_vjp_fwd(zx, w_rec, h0, c0):
    ys, hT, cT, (gates, cseq) = _lstm_fwd(zx, w_rec, h0, c0,
                                          save_residuals=True)
    return (ys, hT, cT), (ys, gates, cseq, w_rec, h0, c0)


def _fused_lstm_vjp_bwd(res, cotangents):
    dys, dhT, dcT = cotangents
    ys, gates, cseq, w_rec, h0, c0 = res
    t = gates.shape[0]
    # c_{t-1} sequence: c0 then cseq[:-1]
    c_prev = jnp.concatenate([c0[None], cseq[:-1]], axis=0)
    ds, dh0, dc0 = _lstm_bwd_kernel_call(dys, dhT, dcT, gates, c_prev, w_rec)
    # Weight gradient as ONE large MXU matmul: h_{t-1} sequence is h0 ++ ys[:-1].
    h_prev = jnp.concatenate([h0[None], ys[:-1]], axis=0)
    hp = h_prev.reshape(-1, h_prev.shape[-1])
    dsf = ds.reshape(-1, ds.shape[-1])
    dw_rec = jax.lax.dot_general(
        hp, dsf, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(w_rec.dtype)
    return ds, dw_rec, dh0, dc0


fused_lstm.defvjp(_fused_lstm_vjp_fwd, _fused_lstm_vjp_bwd)
