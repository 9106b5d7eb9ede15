"""Resident fused multi-head self-attention for sequences that fit one block
(T <= 512): scores, softmax and context of a head in VMEM, one kernel
forward and one backward.

The flash kernel (``flash_attention.py``) streams K/V because at long T the
(T, T) scores cannot live on the chip. At T <= 512 they can, so the (T, T)
tensors never reach HBM: a block's HBM traffic is the eight (b, t, h*d)
operands and results, where the XLA softmax form also writes and re-reads
scores, weights and their gradients (201 MB each at 32 x 12 x 512 x 512).

Operand layout. ``fused_attention`` takes q / k / v as the projections
write them, (b, t, h*d), and hands the kernel their (b, h*d, t) transposes:
features on sublanes, time on lanes. That is not a copy. XLA on the TPU
keeps this model's activations time-minor anyway (its weight-gradient
matmuls contract over (b, t)) and emits the projection straight into the
layout the custom call asks for; a kernel on (b, t, h*d) blocks forced nine
25 MB layout copies a block and slower matmul fusions around it, which gave
back 17 of the 22 ms a step the kernel had won (PERF.md section 6, PR 27).
In this layout a head is an aligned slice of 64 or more sublanes - no lane
masks, no half-used lane tiles - and every matmul of the backward but two
small (d, T) ones has its operands as the MXU wants them:

    S^T = K Q^T (keys on sublanes, queries on lanes), softmax down the columns
    O^T = V^T E^T / l                     dV^T = dO^T P
    dP^T = V dO^T                         dK^T = Q^T dS
    dS^T = P^T * (dP^T - sum_d(dO^T * O^T))   dQ^T = K^T dS^T

The backward is ONE kernel and the forward saves nothing for it: it re-reads
q / k / v, recomputes scores and weights (T fits, so the softmax is exact,
not streaming), and emits dq, dk, dv with five matmuls a head
(``flash_attention``'s resident backward is two kernels and seven). The row
term of the softmax gradient is ``sum(dO * O)`` over the head's features of
the forward's output, which the output projection's own gradient keeps
anyway; no (T, T) tensor is ever transposed.

Same mathematics as the XLA form of ``dot_product_attention`` under
``set_compute_dtype("bfloat16")``: scores and softmax in f32, weights and
dScores rounded to the input dtype before their matmuls. The scale is
applied to q in f32 before it is rounded for the MXU (exact for a
power-of-two scale such as 64 ** -0.5). A masked key gets exactly zero
weight; a batch row with no valid key gets uniform weights, as the XLA
form's ``where(mask, scores, -1e9)`` gives it, and zero dq / dk (that
``where`` passes no gradient to a masked score). No key block is skipped
for being padded.

Measured on a v5e (my chip runs, PR 27), 32 x 512, 12 heads x 64, bf16,
ragged key-padding mask. The kernel pair alone, forward + backward: 1.06 ms
against 3.18 for the XLA form with its transposes. In BERT-base's step
(``examples/scope_table.py``): 0.38 + 0.66 ms a block under
``fused_attention`` where ``scores`` + ``softmax`` + ``context`` took 0.89 +
2.02, the step program 90.7 -> 68.0 ms, nothing else grew.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from deeplearning4j_tpu.ops.pallas.common import (COMPILER_PARAMS,
                                                  VMEM_BUDGET,
                                                  kernels_available)
from deeplearning4j_tpu.ops.pallas.common import interpret_mode as _interpret
from deeplearning4j_tpu.ops.pallas.flash_attention import (MASK_VALUE,
                                                           _padding_mask_2d)

MAX_SEQ = 512  # beyond this a head's (T, T) block leaves VMEM to the flash kernel
# Below this length the XLA softmax form is faster: its (T, T) tensors are
# small and the kernel's matmuls at d = 64 fill half of the MXU whatever T
# is. v5e, SelfAttentionLayer forward + backward (projections included) at
# 16,384 tokens a step, 12 heads x 64, bf16, XLA form -> this kernel:
# 128 x 128 1.81 -> 2.26 ms (loses), 64 x 256 2.50 -> 2.16 (1.15x),
# 32 x 512 4.25 -> 2.31 (1.84x) (my chip run, PR 27).
MIN_SEQ_FOR_KERNEL = 256
# Features (sublanes of the (h*d, t) operand) one grid step takes when h*d
# divides by it, else 128. v5e, 32 x 512 x 768 bf16, forward + backward of
# the kernel pair alone: 128 -> 1.134 ms, 256 -> 1.063, 384 -> 1.050, 768
# (a whole row) -> 1.028; a Mosaic compile of the pair 1.1 / 1.5 / 2.7 /
# 4.9 s, and a model compiles one pair a block (my chip run, PR 27).
BLOCK_FEATURES = 256

_NN = (((1,), (0,)), ((), ()))  # a @ b
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def _weights(qt, kt, bias):
    """(Tk, Tq) unnormalised softmax weights of one head, f32, and their
    column sums (1, Tq); ``bias`` is the key mask as a (Tk, 1) column."""
    st = jax.lax.dot_general(kt, qt, _TN, preferred_element_type=jnp.float32)
    if bias is not None:
        st = st + bias
    e = jnp.exp(st - jnp.max(st, axis=0, keepdims=True))
    return e, jnp.sum(e, axis=0, keepdims=True)


def _scaled(q_ref, rows, scale, dtype):
    return (q_ref[0, rows, :].astype(jnp.float32) * scale).astype(dtype)


def _fwd_kernel(*refs, scale: float, d: int, has_bias: bool):
    q_ref, k_ref, v_ref = refs[:3]
    bias = refs[3][0] if has_bias else None
    o_ref = refs[-1]
    for h in range(q_ref.shape[1] // d):
        rows = slice(h * d, (h + 1) * d)
        vt = v_ref[0, rows, :]
        e, l = _weights(_scaled(q_ref, rows, scale, vt.dtype),
                        k_ref[0, rows, :], bias)
        # the division goes to the (d, T) output, not the (T, T) weights
        ot = jax.lax.dot_general(vt, e.astype(vt.dtype), _NN,
                                 preferred_element_type=jnp.float32)
        o_ref[0, rows, :] = (ot * (1.0 / l)).astype(o_ref.dtype)


def _bwd_kernel(*refs, scale: float, d: int, has_bias: bool):
    q_ref, k_ref, v_ref = refs[:3]
    bias = refs[3][0] if has_bias else None
    o_ref, do_ref, dq_ref, dk_ref, dv_ref = refs[3 + has_bias:]
    # a batch row with no valid key: see the module docstring
    alive = 1.0 if bias is None else (
        jnp.max(bias, axis=0, keepdims=True) > 0.5 * MASK_VALUE
    ).astype(jnp.float32)
    for h in range(q_ref.shape[1] // d):
        rows = slice(h * d, (h + 1) * d)
        kt, vt, dot = k_ref[0, rows, :], v_ref[0, rows, :], do_ref[0, rows, :]
        qt = _scaled(q_ref, rows, scale, kt.dtype)
        e, l = _weights(qt, kt, bias)
        pt = e * (1.0 / l)
        dpt = jax.lax.dot_general(vt, dot, _TN,
                                  preferred_element_type=jnp.float32)
        delta = jnp.sum(dot.astype(jnp.float32)
                        * o_ref[0, rows, :].astype(jnp.float32),
                        axis=0, keepdims=True)
        dst = (pt * (dpt - delta)).astype(kt.dtype)
        dq = jax.lax.dot_general(kt, dst, _NN,
                                 preferred_element_type=jnp.float32)
        dk = jax.lax.dot_general(qt, dst, _NT,
                                 preferred_element_type=jnp.float32)
        dv = jax.lax.dot_general(dot, pt.astype(dot.dtype), _NT,
                                 preferred_element_type=jnp.float32)
        dq_ref[0, rows, :] = (dq * (scale * alive)).astype(dq_ref.dtype)
        dk_ref[0, rows, :] = (dk * alive).astype(dk_ref.dtype)
        dv_ref[0, rows, :] = dv.astype(dv_ref.dtype)


def _block_features(hd: int, heads: int) -> int:
    """Features one grid step takes: whole heads, a multiple of 128 (the
    block's sublanes and the lane tile of the untransposed operand); 0
    when the heads do not tile that way."""
    if heads <= 0 or hd % heads or hd % 128:
        return 0
    d = hd // heads
    if d % 64:
        return 0
    if d % 128 == 0 and d >= BLOCK_FEATURES:
        return d  # one wide head a step
    features = BLOCK_FEATURES if hd % BLOCK_FEATURES == 0 else 128
    return features if features % d == 0 else 0


def _vmem_bytes(t: int, features: int, itemsize: int) -> int:
    # the backward's nine (F, T) blocks, counted once as VMEM_BUDGET wants
    # them, and five f32 (T, T) temporaries of a head (scores / weights,
    # P, dP, dScores and the rounded copies), for two heads at once: the
    # unrolled heads overlap
    return 9 * t * features * itemsize + 2 * 5 * t * t * 4


def fused_attention_compatible(q, mask=None, heads: int = 0,
                               causal: bool = False) -> bool:
    """Whether :func:`fused_attention` takes this self-attention call: q
    (and k, v of the same shape and dtype) in (b, t, heads * d), the whole
    sequence one block, no causal triangle, mask absent or key-padding.
    Under the interpreter (the CPU test path) the size crossover is left
    out, as ``flash_attention_compatible`` leaves its own out."""
    if causal or q.ndim != 3:
        return False
    b, t, hd = q.shape
    features = _block_features(hd, heads)
    if not features or t % 128 or t > MAX_SEQ:
        return False
    if q.dtype not in (jnp.float32, jnp.bfloat16):
        return False
    if mask is not None and _padding_mask_2d(mask, b, t) is None:
        return False
    if not kernels_available():
        return False
    if _vmem_bytes(t, features, q.dtype.itemsize) > VMEM_BUDGET:
        return False
    return _interpret() or t >= MIN_SEQ_FOR_KERNEL


def _call(kernel, n_out, operands, mask, heads, name):
    b, hd, t = operands[0].shape
    features = _block_features(hd, heads)
    block = pl.BlockSpec((1, features, t), lambda i, j: (i, j, 0))
    in_specs, args = [block] * 3, list(operands[:3])
    if mask is not None:
        # keys run down the sublanes of S^T: the mask is a (t, 1) column
        bias = jnp.where(_padding_mask_2d(mask, b, t).astype(bool), 0.0,
                         MASK_VALUE).astype(jnp.float32)[:, :, None]
        in_specs.append(pl.BlockSpec((1, t, 1), lambda i, j: (i, 0, 0)))
        args.append(bias)
    in_specs += [block] * (len(operands) - 3)
    args += list(operands[3:])
    d = hd // heads
    return pl.pallas_call(
        functools.partial(kernel, scale=float(d) ** -0.5, d=d,
                          has_bias=mask is not None),
        name=name,
        grid=(b, hd // features),
        in_specs=in_specs,
        out_specs=[block] * n_out,
        out_shape=[jax.ShapeDtypeStruct(operands[0].shape,
                                        operands[0].dtype)] * n_out,
        compiler_params=COMPILER_PARAMS,
        interpret=_interpret(),
    )(*args)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _attention_t(qt, kt, vt, mask, heads: int):
    return _call(_fwd_kernel, 1, (qt, kt, vt), mask, heads,
                 "fused_attention_fwd")[0]


def _vjp_fwd(qt, kt, vt, mask, heads):
    ot = _attention_t(qt, kt, vt, mask, heads)
    return ot, (qt, kt, vt, mask, ot)


def _vjp_bwd(heads, res, dot):
    qt, kt, vt, mask, ot = res
    dq, dk, dv = _call(_bwd_kernel, 3, (qt, kt, vt, ot, dot), mask, heads,
                       "fused_attention_bwd")
    return dq, dk, dv, None


_attention_t.defvjp(_vjp_fwd, _vjp_bwd)


def fused_attention(q, k, v, mask, heads: int):
    """softmax(q k^T / sqrt(d) + key-padding mask) v per head, on
    (b, t, heads * d) operands, for calls that
    :func:`fused_attention_compatible` accepts. The transposes here are
    layout requests, not copies: see the module docstring."""
    qt, kt, vt = (x.transpose(0, 2, 1) for x in (q, k, v))
    return _attention_t(qt, kt, vt, mask, heads).transpose(0, 2, 1)
