"""Fused multi-head attention for SHORT sequences (BERT-class T <= 512).

.. deprecated:: round 6
   This kernel has no winning regime and is kept only as a measured
   negative result. The round-4 "4x vs XLA in isolation" figure was a
   single-shot per-call wall timing, which charges the multi-op XLA
   reference one dispatch per op but the single-kernel Pallas path one
   total — the bench-of-record chain-amortised A/B
   (``verify_kernels``, ``short_attn_isolated_speedup_vs_xla``) reads
   **parity** (0.98-1.01 across rounds), and auto-routing it in-model was
   a measured LOSS (51-55 ms/step vs 37 for BERT-base: each pallas_call
   boundary in the big traced step costs ~0.5-0.7 ms of lost fusion/async
   overlap, x24 sites). Nothing routes to it; correctness tests and the
   bench row remain so the record stays auditable. Use the XLA softmax
   path (``nn.attention_layers.dot_product_attention``) at short T and the
   flash kernel beyond ``MIN_SEQ_FOR_KERNEL``.

The flash kernel (``flash_attention.py``) exists for long sequences where
the (T, T) score matrix cannot live on chip; below ``MIN_SEQ_FOR_KERNEL``
it loses to XLA and bows out. But the XLA path it bows out TO is itself
slow at short T: profiled on v5e at BERT-base fine-tune shape
(64x128, 12 heads, d=64), the six per-layer batched attention matmuls run
as 72 standalone ``convolution`` ops at ~5% MXU utilisation (5.6 ms of a
32 ms step) plus layout copies for the (b,h,t,d) transposes and the saved
softmax tensor.

This kernel owns the whole short-T case: one grid step per BATCH ROW
processes ALL heads of that row — q/k/v blocks (H, T, d) live entirely in
VMEM, scores are computed per-head with a batched ``dot_general``, the
softmax never touches HBM, and the backward saves NOTHING: it re-reads
q/k/v, recomputes scores and probabilities, and emits dq/dk/dv in a single
kernel (the per-row correction ``ds = p * (dp - rowsum(dp*p))`` needs no
forward output, so there is no lse/delta residual either — T fits, so the
softmax is exact, not streaming).

Reference role: the cuDNN fused-attention helper layer
(``org.deeplearning4j.cuda`` attention helpers; SURVEY.md §7.2), built
TPU-first for the MXU + VMEM regime instead of translated.

Numerics: scores/softmax in f32 (same as the XLA path's effective
accumulation), output in the input dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from deeplearning4j_tpu.ops.pallas.common import VMEM_BUDGET
from deeplearning4j_tpu.ops.pallas.common import interpret_mode as _interpret
from deeplearning4j_tpu.ops.pallas.common import kernels_available

MASK_VALUE = -1e30
MAX_SEQ = 512  # beyond this the streaming flash kernel takes over


def _scores(q, k, scale):
    # (H, Tq, d) x (H, Tk, d) -> (H, Tq, Tk), f32 accumulation on the MXU
    return jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale


def _fwd_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, *, scale):
    s = _scores(q_ref[0], k_ref[0], scale)
    if bias_ref is not None:
        s = s + bias_ref[0][None]
    p = jax.nn.softmax(s, axis=-1)
    o = jax.lax.dot_general(p.astype(v_ref.dtype), v_ref[0],
                            (((2,), (1,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32)
    o_ref[0] = o.astype(o_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, bias_ref, do_ref,
                dq_ref, dk_ref, dv_ref, *, scale):
    q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
    s = _scores(q, k, scale)
    if bias_ref is not None:
        s = s + bias_ref[0][None]
    p = jax.nn.softmax(s, axis=-1)                      # (H, Tq, Tk) f32
    pc = p.astype(do.dtype)
    # dv = p^T @ do   -> (H, Tk, d)
    dv = jax.lax.dot_general(pc, do, (((1,), (1,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
    # dp = do @ v^T   -> (H, Tq, Tk)
    dp = jax.lax.dot_general(do, v, (((2,), (2,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - jnp.sum(dp * p, axis=-1, keepdims=True)) * scale
    dsc = ds.astype(q.dtype)
    # dq = ds @ k     -> (H, Tq, d)
    dq = jax.lax.dot_general(dsc, k, (((2,), (1,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
    # dk = ds^T @ q   -> (H, Tk, d)
    dk = jax.lax.dot_general(dsc, q, (((1,), (1,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
    dq_ref[0] = dq.astype(dq_ref.dtype)
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _bias_from_mask(mask, b, t):
    """(b, t_k) key-padding mask -> additive f32 bias, or None."""
    if mask is None:
        return None
    m = mask
    if m.ndim == 4:  # (b, 1, 1, t) broadcast form
        m = m[:, 0, 0, :]
    m = m.astype(bool)
    # (b, 1, t): Mosaic wants the last two block dims 8/128-divisible or
    # full; a (1, 1, t) block over (b, 1, t) satisfies that exactly
    return jnp.where(m, 0.0, MASK_VALUE).astype(jnp.float32)[:, None, :]


def short_attention_compatible(q, k, v, mask=None, causal: bool = False) -> bool:
    """(b, h, t, d) self-attention, t_q == t_k <= MAX_SEQ, d a multiple of
    64, whole (h, t, t) score block fitting in VMEM."""
    if causal:
        return False  # short-T causal stays on XLA (decode shapes vary)
    if q.ndim != 4 or q.shape != k.shape or k.shape != v.shape:
        return False
    b, h, t, d = q.shape
    if t > MAX_SEQ or t % 128 != 0 or d % 64 != 0:
        return False
    if mask is not None:
        m = mask
        if m.ndim == 4:
            if m.shape != (b, 1, 1, t):
                return False
        elif m.shape != (b, t):
            return False
    if not kernels_available():
        return False
    # VMEM: q/k/v/o + do/dq/dk/dv plus ~4 f32 (h,t,t) temporaries
    need = 8 * h * t * d * q.dtype.itemsize + 4 * h * t * t * 4
    return need < VMEM_BUDGET


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def short_attention(q, k, v, mask=None, scale: float | None = None):
    """softmax(q k^T * scale + mask) v for (b, h, t, d), t <= MAX_SEQ."""
    y, _ = _short_fwd(q, k, v, mask, scale)
    return y


def _specs(b, h, t, d, with_bias):
    qspec = pl.BlockSpec((1, h, t, d), lambda i: (i, 0, 0, 0))
    bspec = pl.BlockSpec((1, 1, t), lambda i: (i, 0, 0)) if with_bias else None
    return qspec, bspec


def _short_fwd(q, k, v, mask, scale):
    b, h, t, d = q.shape
    scale = float(scale) if scale is not None else float(d) ** -0.5
    bias = _bias_from_mask(mask, b, t)
    qspec, bspec = _specs(b, h, t, d, bias is not None)
    in_specs = [qspec, qspec, qspec] + ([bspec] if bias is not None else [])
    args = (q, k, v) + ((bias,) if bias is not None else ())
    kern = _fwd_kernel if bias is not None else \
        (lambda q_ref, k_ref, v_ref, o_ref, *, scale:
         _fwd_kernel(q_ref, k_ref, v_ref, None, o_ref, scale=scale))
    y = pl.pallas_call(
        functools.partial(kern, scale=scale),
        grid=(b,),
        in_specs=in_specs,
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=_interpret(),
    )(*args)
    return y, (q, k, v, mask)


def _short_fwd_vjp(q, k, v, mask, scale):
    return _short_fwd(q, k, v, mask, scale)


def _short_bwd_vjp(scale, res, gy):
    q, k, v, mask = res
    b, h, t, d = q.shape
    sc = float(scale) if scale is not None else float(d) ** -0.5
    bias = _bias_from_mask(mask, b, t)
    qspec, bspec = _specs(b, h, t, d, bias is not None)
    in_specs = [qspec, qspec, qspec] + \
        ([bspec] if bias is not None else []) + [qspec]
    args = (q, k, v) + ((bias,) if bias is not None else ()) + (gy,)
    kern = _bwd_kernel if bias is not None else \
        (lambda q_ref, k_ref, v_ref, do_ref, dq_ref, dk_ref, dv_ref, *, scale:
         _bwd_kernel(q_ref, k_ref, v_ref, None, do_ref,
                     dq_ref, dk_ref, dv_ref, scale=scale))
    dq, dk, dv = pl.pallas_call(
        functools.partial(kern, scale=sc),
        grid=(b,),
        in_specs=in_specs,
        out_specs=(qspec, qspec, qspec),
        out_shape=(jax.ShapeDtypeStruct(q.shape, q.dtype),) * 3,
        interpret=_interpret(),
    )(*args)
    return dq, dk, dv, None


short_attention.defvjp(_short_fwd_vjp, _short_bwd_vjp)


# ---------------------------------------------------------------------------
# Native-layout variant: q/k/v in (B, T, H*Dh) exactly as the QKV projections
# produce them. The (b,h,t,d) form above needs a transpose before the call
# and a 64-lane last dim (half-filled lane tiles, strided DMAs) — measured
# 13 ms/step SLOWER in-model despite the kernel itself being 4x faster than
# XLA in isolation. Here the block is (T, H*Dh) = lane-perfect, the head
# split happens in VMEM via static lane slices, and the output feeds the
# O-projection without any transpose either.
# ---------------------------------------------------------------------------


def _fwd_btd_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, *, scale, heads):
    d = q_ref.shape[-1] // heads
    q, k, v = q_ref[0], k_ref[0], v_ref[0]
    for h in range(heads):
        sl = slice(h * d, (h + 1) * d)
        s = jax.lax.dot_general(q[:, sl], k[:, sl], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if bias_ref is not None:
            s = s + bias_ref[0]
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        o_ref[0, :, sl] = jnp.dot(
            p, v[:, sl], preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _bwd_btd_kernel(q_ref, k_ref, v_ref, bias_ref, do_ref,
                    dq_ref, dk_ref, dv_ref, *, scale, heads):
    d = q_ref.shape[-1] // heads
    q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
    for h in range(heads):
        sl = slice(h * d, (h + 1) * d)
        qh, kh, vh, doh = q[:, sl], k[:, sl], v[:, sl], do[:, sl]
        s = jax.lax.dot_general(qh, kh, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if bias_ref is not None:
            s = s + bias_ref[0]
        p = jax.nn.softmax(s, axis=-1)
        pc = p.astype(doh.dtype)
        dv = jax.lax.dot_general(pc, doh, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(doh, vh, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - jnp.sum(dp * p, axis=-1, keepdims=True)) * scale
              ).astype(qh.dtype)
        dq_ref[0, :, sl] = jnp.dot(
            ds, kh, preferred_element_type=jnp.float32).astype(dq_ref.dtype)
        dk_ref[0, :, sl] = jax.lax.dot_general(
            ds, qh, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(dk_ref.dtype)
        dv_ref[0, :, sl] = dv.astype(dv_ref.dtype)


def short_attention_btd_compatible(q, mask=None, heads: int = 0,
                                   causal: bool = False) -> bool:
    """(b, t, h*dh) layout eligibility."""
    if causal or q.ndim != 3 or heads <= 0:
        return False
    b, t, hd = q.shape
    if hd % heads or t > MAX_SEQ or t % 128 != 0:
        return False
    d = hd // heads
    if d % 64 != 0 or hd % 128 != 0:
        return False
    if mask is not None:
        m = mask
        if m.ndim == 4:
            if m.shape != (b, 1, 1, t):
                return False
        elif m.shape != (b, t):
            return False
    if not kernels_available():
        return False
    need = 8 * t * hd * q.dtype.itemsize + 6 * t * t * 4
    return need < VMEM_BUDGET


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def short_attention_btd(q, k, v, mask=None, heads: int = 12,
                        scale: float | None = None):
    """Multi-head attention on (b, t, h*dh) without ever forming the
    (b, h, t, d) transposed view."""
    y, _ = _btd_fwd(q, k, v, mask, heads, scale)
    return y


def _btd_specs(b, t, hd, with_bias):
    qspec = pl.BlockSpec((1, t, hd), lambda i: (i, 0, 0))
    bspec = pl.BlockSpec((1, 1, t), lambda i: (i, 0, 0)) if with_bias else None
    return qspec, bspec


def _btd_fwd(q, k, v, mask, heads, scale):
    b, t, hd = q.shape
    d = hd // heads
    sc = float(scale) if scale is not None else float(d) ** -0.5
    bias = _bias_from_mask(mask, b, t)
    qspec, bspec = _btd_specs(b, t, hd, bias is not None)
    in_specs = [qspec, qspec, qspec] + ([bspec] if bias is not None else [])
    args = (q, k, v) + ((bias,) if bias is not None else ())
    if bias is not None:
        kern = _fwd_btd_kernel
    else:
        def kern(q_ref, k_ref, v_ref, o_ref, *, scale, heads):
            return _fwd_btd_kernel(q_ref, k_ref, v_ref, None, o_ref,
                                   scale=scale, heads=heads)
    y = pl.pallas_call(
        functools.partial(kern, scale=sc, heads=heads),
        grid=(b,),
        in_specs=in_specs,
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=_interpret(),
    )(*args)
    return y, (q, k, v, mask)


def _btd_fwd_vjp(q, k, v, mask, heads, scale):
    return _btd_fwd(q, k, v, mask, heads, scale)


def _btd_bwd_vjp(heads, scale, res, gy):
    q, k, v, mask = res
    b, t, hd = q.shape
    d = hd // heads
    sc = float(scale) if scale is not None else float(d) ** -0.5
    bias = _bias_from_mask(mask, b, t)
    qspec, bspec = _btd_specs(b, t, hd, bias is not None)
    in_specs = [qspec, qspec, qspec] + \
        ([bspec] if bias is not None else []) + [qspec]
    args = (q, k, v) + ((bias,) if bias is not None else ()) + (gy,)
    if bias is not None:
        kern = _bwd_btd_kernel
    else:
        def kern(q_ref, k_ref, v_ref, do_ref, dq_ref, dk_ref, dv_ref, *,
                 scale, heads):
            return _bwd_btd_kernel(q_ref, k_ref, v_ref, None, do_ref,
                                   dq_ref, dk_ref, dv_ref,
                                   scale=scale, heads=heads)
    dq, dk, dv = pl.pallas_call(
        functools.partial(kern, scale=sc, heads=heads),
        grid=(b,),
        in_specs=in_specs,
        out_specs=(qspec, qspec, qspec),
        out_shape=(jax.ShapeDtypeStruct(q.shape, q.dtype),) * 3,
        interpret=_interpret(),
    )(*args)
    return dq, dk, dv, None


short_attention_btd.defvjp(_btd_fwd_vjp, _btd_bwd_vjp)
