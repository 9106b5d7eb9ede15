"""Flash attention as Pallas TPU kernels — forward AND fused backward, with
key-padding-mask and causal support.

The hand-written-kernel layer of the framework (the role cuDNN's fused
attention / libnd4j's CUDA helpers play in the reference — SURVEY.md §7.2):
blockwise softmax with running max/denominator so the (T, T) score matrix is
never materialised in HBM. Q is tiled over the grid; K/V stream through VMEM
in BLOCK_K chunks with the classic flash update:

    m' = max(m, rowmax(S_blk))
    l' = l * e^{m-m'} + rowsum(e^{S_blk - m'})
    acc' = acc * e^{m-m'} + e^{S_blk - m'} @ V_blk

The forward additionally emits the per-row logsumexp L = m + log(l), which
the backward uses to recompute P = exp(S - L) blockwise (never storing the
(T, T) matrix):

    D   = rowsum(dO * O)                  (precomputed, fused by XLA)
    dV += P^T @ dO
    dP  = dO @ V^T
    dS  = P * (dP - D) * scale
    dQ += dS @ K        (dq kernel: grid over query blocks)
    dK += dS^T @ Q      (dkv kernel: grid over key blocks)

Masking: a key-padding mask becomes an additive bias (0 / -1e30) of shape
(batch, T_k, 1) streamed per batch row (the grid runs over batch*heads; the
index map divides by heads so the bias is NOT materialised per head).
Sequence lengths: up to T=8192 the BACKWARD kernels keep the full K/V (dq
pass) and Q/dO (dkv pass) VMEM-resident per grid step; past that
(`BWD_CHUNK_THRESHOLD`) the round-5 CHUNKED backward kernels stream those
operands through VMEM in `BWD_CHUNK`-row chunks over a third grid
dimension, accumulating in f32 scratch that persists across the
sequential minor grid steps — single-chip fwd+bwd verified at T=16384,
D=64 on v5e. Longer contexts still shard across chips via ring attention
(parallel/ring_attention).

``causal=True`` masks the upper triangle AND skips fully-masked key blocks:
the forward/dq loops stop at the diagonal, the dk/dv loop starts there —
roughly halving the FLOPs, which XLA's dense softmax cannot do.

Used automatically by ``nn.attention_layers.dot_product_attention`` when
:func:`flash_attention_compatible` says the shapes and the platform allow;
otherwise the XLA softmax form runs. ``DL4J_TPU_PALLAS_INTERPRET=1`` runs
the kernels in interpreter mode on the CPU backend (test path only).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_Q = 512
BLOCK_K = 512
# Mosaic requires the last block dim to be 128-divisible or equal to the full
# array dim, so per-row residuals (logsumexp, delta) are stored lane-broadcast
# with a narrow trailing axis rather than as 1-D vectors.
RES_LANES = 8
# Large-but-finite mask value (the standard flash choice): -inf would poison
# the running max for fully-masked rows.
MASK_VALUE = -1e30

# Below this key length XLA's unfused softmax attention measures faster on
# v5e (the (T, T) scores still fit cache-friendly HBM tiles and the kernel's
# fixed overhead dominates): fwd+bwd speedup was 0.86x @T=128, 0.94x @512,
# 1.26x @2048, 1.40x @4096.
MIN_SEQ_FOR_KERNEL = 1024


from deeplearning4j_tpu.ops.pallas.common import COMPILER_PARAMS
from deeplearning4j_tpu.ops.pallas.common import interpret_mode as _interpret
from deeplearning4j_tpu.ops.pallas.common import kernels_available


def _pick_block(t: int, limit: int) -> int:
    """Largest 128-multiple <= limit that divides t (measured on v5e: 512
    beats 128 by ~2x — bigger tiles keep the MXU busy and amortise loop
    overhead; past 512 returns diminish and VMEM pressure grows)."""
    b = min(limit, t)
    while b > 128 and t % b:
        b -= 128
    return b


def _padding_mask_2d(mask, b: int, t_k: int):
    """Reduce a broadcastable attention mask to a (batch, t_k) key-padding
    mask, or None if it is not that shape family."""
    if mask is None:
        return None
    if mask.ndim == 2 and mask.shape == (b, t_k):
        return mask
    if mask.ndim == 4 and mask.shape[1] == 1 and mask.shape[2] == 1 \
            and mask.shape[0] == b and mask.shape[3] == t_k:
        return mask[:, 0, 0, :]
    return None


def flash_attention_compatible(q, k, v, mask=None, causal: bool = False) -> bool:
    """Kernel applicability: key-padding masks only (other mask shapes fall
    back to XLA), block-divisible sequence, head dim that tiles onto the MXU
    lanes, and a key length long enough that the kernel beats XLA."""
    if q.ndim != 4:
        return False
    t_q, d = q.shape[2], q.shape[3]
    t_k = k.shape[2]
    if mask is not None and _padding_mask_2d(mask, q.shape[0], t_k) is None:
        return False
    if causal and t_q != t_k:
        return False
    if t_q % 128 or t_k % 128:  # adaptive blocks bottom out at 128
        return False
    if d > 256:
        return False
    if q.dtype not in (jnp.float32, jnp.bfloat16):
        return False
    if k.dtype != q.dtype or v.dtype != q.dtype:
        return False
    if _interpret():
        return True  # CPU test path exercises the kernel at any size
    return t_k >= MIN_SEQ_FOR_KERNEL and kernels_available()


def _causal_hi(qi, block_q: int, block_k: int):
    """Number of key blocks needed for query block qi under causal masking."""
    return (qi * block_q + block_q + block_k - 1) // block_k


def _diag_mask(s, qi, i, block_q: int, block_k: int):
    """Apply the causal triangle inside a (block_q, block_k) score tile."""
    rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = i * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(cols <= rows, s, MASK_VALUE)


# ---------------------------------------------------------------- forward


def _fwd_kernel(*refs, scale: float, block_k: int, has_bias: bool,
                causal: bool, save_residuals: bool):
    if has_bias:
        q_ref, k_ref, v_ref, bias_ref = refs[:4]
        rest = refs[4:]
    else:
        q_ref, k_ref, v_ref = refs[:3]
        bias_ref = None
        rest = refs[3:]
    o_ref = rest[0]
    lse_ref = rest[1] if save_residuals else None

    # Matmul operands stay in the input dtype (bf16 on the fast path) so the
    # MXU runs at full rate; accumulation and softmax stats are f32.
    q = q_ref[0]  # (BLOCK_Q, D)
    in_dtype = q.dtype
    qi = pl.program_id(1)
    t_k = k_ref.shape[1]
    n_blocks = t_k // block_k
    block_q = q.shape[0]

    def body(i, carry):
        acc, m, l = carry
        k_blk = k_ref[0, pl.ds(i * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(i * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if bias_ref is not None:
            s = s + bias_ref[0, pl.ds(i * block_k, block_k), 0][None, :]
        if causal:
            s = _diag_mask(s, qi, i, block_q, block_k)
        m_blk = jnp.max(s, axis=1)
        m_new = jnp.maximum(m, m_blk)
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=1)
        acc_new = acc * corr[:, None] + jax.lax.dot(
            p.astype(in_dtype), v_blk, preferred_element_type=jnp.float32)
        return acc_new, m_new, l_new

    bq, d_v = q.shape[0], v_ref.shape[2]
    acc = jnp.zeros((bq, d_v), jnp.float32)
    m = jnp.full((bq,), -jnp.inf, jnp.float32)
    l = jnp.zeros((bq,), jnp.float32)
    hi = _causal_hi(qi, block_q, block_k) if causal else n_blocks
    acc, m, l = jax.lax.fori_loop(0, hi, body, (acc, m, l))
    l_safe = jnp.maximum(l, 1e-20)
    o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    if lse_ref is not None:  # residuals only requested under differentiation
        lse = m + jnp.log(l_safe)
        lse_ref[0] = jax.lax.broadcast_in_dim(lse, (bq, RES_LANES), (0,))


def _flash_fwd(q, k, v, bias, scale, causal, has_bias, save_residuals=True):
    b, h, t_q, d = q.shape
    t_k = k.shape[2]
    d_v = v.shape[-1]
    qf = q.reshape(b * h, t_q, d)
    kf = k.reshape(b * h, t_k, d)
    vf = v.reshape(b * h, t_k, d_v)
    block_q = _pick_block(t_q, BLOCK_Q)
    block_k = _pick_block(t_k, BLOCK_K)
    grid = (b * h, t_q // block_q)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0)),
        pl.BlockSpec((1, t_k, d), lambda bh, qi: (bh, 0, 0)),
        pl.BlockSpec((1, t_k, d_v), lambda bh, qi: (bh, 0, 0)),
    ]
    args = [qf, kf, vf]
    if has_bias:
        # bias is (b, t_k, 1); the index map divides the grid's batch*heads
        # row by heads, so all heads of one batch share the same block.
        in_specs.append(
            pl.BlockSpec((1, t_k, 1), lambda bh, qi: (bh // h, 0, 0)))
        args.append(bias)
    out_shape = [jax.ShapeDtypeStruct((b * h, t_q, d_v), q.dtype)]
    out_specs = [pl.BlockSpec((1, block_q, d_v), lambda bh, qi: (bh, qi, 0))]
    if save_residuals:
        out_shape.append(
            jax.ShapeDtypeStruct((b * h, t_q, RES_LANES), jnp.float32))
        out_specs.append(
            pl.BlockSpec((1, block_q, RES_LANES), lambda bh, qi: (bh, qi, 0)))
    res = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, block_k=block_k,
                          has_bias=has_bias, causal=causal,
                          save_residuals=save_residuals),
        name="flash_attention_fwd",
        out_shape=out_shape,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        compiler_params=COMPILER_PARAMS,
        interpret=_interpret(),
    )(*args)
    out = res[0].reshape(b, h, t_q, d_v)
    return (out, res[1]) if save_residuals else (out, None)


# ---------------------------------------------------------------- backward


def _bwd_dq_kernel(*refs, scale: float, block_k: int, has_bias: bool,
                   causal: bool):
    if has_bias:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, bias_ref = refs[:7]
        dq_ref = refs[7]
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:6]
        bias_ref = None
        dq_ref = refs[6]
    q = q_ref[0]                              # (BQ, D)
    do = do_ref[0]                            # (BQ, Dv)
    in_dtype = q.dtype
    lse = lse_ref[0][:, 0]                    # (BQ,)
    delta = delta_ref[0][:, 0]                # (BQ,)
    qi = pl.program_id(1)
    t_k = k_ref.shape[1]
    n_blocks = t_k // block_k
    block_q = q.shape[0]

    def body(i, dq_acc):
        k_blk = k_ref[0, pl.ds(i * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(i * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if bias_ref is not None:
            s = s + bias_ref[0, pl.ds(i * block_k, block_k), 0][None, :]
        if causal:
            s = _diag_mask(s, qi, i, block_q, block_k)
        p = jnp.exp(s - lse[:, None])                       # (BQ, BK)
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - delta[:, None]) * scale).astype(in_dtype)
        return dq_acc + jax.lax.dot(ds, k_blk,
                                    preferred_element_type=jnp.float32)

    hi = _causal_hi(qi, block_q, block_k) if causal else n_blocks
    dq = jax.lax.fori_loop(0, hi,
                           body, jnp.zeros(q.shape, jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, scale: float, block_q: int, has_bias: bool,
                    causal: bool):
    if has_bias:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, bias_ref = refs[:7]
        dk_ref, dv_ref = refs[7:9]
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:6]
        bias_ref = None
        dk_ref, dv_ref = refs[6:8]
    k = k_ref[0]                              # (BK, D)
    v = v_ref[0]                              # (BK, Dv)
    in_dtype = k.dtype
    ki = pl.program_id(1)
    t_q = q_ref.shape[1]
    n_blocks = t_q // block_q
    block_k = k.shape[0]
    # this key block's bias column (shared across q blocks)
    bias_col = (bias_ref[0, pl.ds(ki * block_k, block_k), 0]
                if bias_ref is not None else None)

    def body(i, carry):
        dk_acc, dv_acc = carry
        q_blk = q_ref[0, pl.ds(i * block_q, block_q), :]
        do_blk = do_ref[0, pl.ds(i * block_q, block_q), :]
        lse_blk = lse_ref[0, pl.ds(i * block_q, block_q), :][:, 0]
        delta_blk = delta_ref[0, pl.ds(i * block_q, block_q), :][:, 0]
        s = jax.lax.dot_general(
            q_blk, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if bias_col is not None:
            s = s + bias_col[None, :]
        if causal:
            s = _diag_mask(s, i, ki, block_q, block_k)
        p = jnp.exp(s - lse_blk[:, None])                   # (BQ, BK)
        p_cast = p.astype(in_dtype)
        dv_acc = dv_acc + jax.lax.dot_general(
            p_cast, do_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # (BK, Dv)
        dp = jax.lax.dot_general(
            do_blk, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_blk[:, None]) * scale).astype(in_dtype)
        dk_acc = dk_acc + jax.lax.dot_general(
            ds, q_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # (BK, D)
        return dk_acc, dv_acc

    # under causal masking, query blocks strictly above the diagonal
    # contribute nothing to this key block
    lo = (ki * block_k) // block_q if causal else 0
    dk, dv = jax.lax.fori_loop(
        lo, n_blocks, body,
        (jnp.zeros(k.shape, jnp.float32), jnp.zeros(v.shape, jnp.float32)))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


# Above this sequence length the backward switches to the CHUNKED kernels:
# the single-chunk forms keep full K/V (dq pass) and full Q/dO (dkv pass)
# VMEM-resident per grid step, which blows the ~16 MB VMEM budget past
# T=8192; the chunked forms stream those operands through VMEM in
# BWD_CHUNK-row chunks via a third grid dimension, accumulating in f32
# scratch that persists across the (sequential) minor grid steps. The two
# kernel families are NOT unified into always-chunked (measured on v5e:
# chunked == resident at T=8192, 17.9 ms both, but causal T=2048 runs
# 6.3 vs 4.8 ms chunked — the 3-D grid + scratch structure costs ~30% at
# short causal lengths, so the resident forms stay for T <= threshold).
BWD_CHUNK_THRESHOLD = 8192
BWD_CHUNK = 4096
# ... and, whatever the length, where the resident forms' full-sequence
# operands would not fit the kernels' scoped VMEM (32 MiB asked for):
# q/k and v/dO rows in the input dtype plus the two float32 row statistics,
# which Mosaic pads from RES_LANES to 128 lanes, all double-buffered. At
# T=8192 a head of 64 needs 21 MB and stays resident; a q.k head of 192
# against a v head of 128 needs 27 MB plus its blocks, and Mosaic refused
# it by 0.8 MB (compiled for a v5e, PR 30).
RESIDENT_BWD_VMEM = 24 * 1024 * 1024


def _bwd_dq_kernel_chunked(*refs, scale: float, block_k: int,
                           has_bias: bool, causal: bool, n_chunks: int):
    """dq pass with K/V streamed in chunks: grid (bh, qi, ci); K/V blocks
    are the ci-th chunk; dq accumulates in scratch, flushed at the last
    chunk."""
    if has_bias:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, bias_ref = refs[:7]
        dq_ref, acc_ref = refs[7], refs[8]
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:6]
        bias_ref = None
        dq_ref, acc_ref = refs[6], refs[7]
    q = q_ref[0]
    do = do_ref[0]
    in_dtype = q.dtype
    lse = lse_ref[0][:, 0]
    delta = delta_ref[0][:, 0]
    qi = pl.program_id(1)
    ci = pl.program_id(2)
    chunk_k = k_ref.shape[1]
    nb = chunk_k // block_k
    block_q = q.shape[0]

    @pl.when(ci == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def body(i, dq_acc):
        kb = ci * nb + i  # global key-block index (for the causal mask)
        k_blk = k_ref[0, pl.ds(i * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(i * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if bias_ref is not None:
            s = s + bias_ref[0, pl.ds(i * block_k, block_k), 0][None, :]
        if causal:
            s = _diag_mask(s, qi, kb, block_q, block_k)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - delta[:, None]) * scale).astype(in_dtype)
        return dq_acc + jax.lax.dot(ds, k_blk,
                                    preferred_element_type=jnp.float32)

    if causal:
        hi_global = _causal_hi(qi, block_q, block_k)
        nblk = jnp.clip(hi_global - ci * nb, 0, nb)
    else:
        nblk = nb
    acc_ref[...] = jax.lax.fori_loop(0, nblk, body, acc_ref[...])

    @pl.when(ci == n_chunks - 1)
    def _flush():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel_chunked(*refs, scale: float, block_q: int,
                            has_bias: bool, causal: bool, n_chunks: int):
    """dk/dv pass with Q/dO/lse/delta streamed in chunks: grid
    (bh, ki, ci); scratch accumulators flushed at the last chunk."""
    if has_bias:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, bias_ref = refs[:7]
        dk_ref, dv_ref, dk_acc_ref, dv_acc_ref = refs[7:11]
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:6]
        bias_ref = None
        dk_ref, dv_ref, dk_acc_ref, dv_acc_ref = refs[6:10]
    k = k_ref[0]
    v = v_ref[0]
    in_dtype = k.dtype
    ki = pl.program_id(1)
    ci = pl.program_id(2)
    chunk_q = q_ref.shape[1]
    nb = chunk_q // block_q
    block_k = k.shape[0]
    bias_col = (bias_ref[0, :, 0] if bias_ref is not None else None)

    @pl.when(ci == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    def body(i, carry):
        dk_acc, dv_acc = carry
        qb = ci * nb + i  # global query-block index
        q_blk = q_ref[0, pl.ds(i * block_q, block_q), :]
        do_blk = do_ref[0, pl.ds(i * block_q, block_q), :]
        lse_blk = lse_ref[0, pl.ds(i * block_q, block_q), :][:, 0]
        delta_blk = delta_ref[0, pl.ds(i * block_q, block_q), :][:, 0]
        s = jax.lax.dot_general(
            q_blk, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if bias_col is not None:
            s = s + bias_col[None, :]
        if causal:
            s = _diag_mask(s, qb, ki, block_q, block_k)
        p = jnp.exp(s - lse_blk[:, None])
        p_cast = p.astype(in_dtype)
        dv_acc = dv_acc + jax.lax.dot_general(
            p_cast, do_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do_blk, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_blk[:, None]) * scale).astype(in_dtype)
        dk_acc = dk_acc + jax.lax.dot_general(
            ds, q_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk_acc, dv_acc

    if causal:
        lo_global = (ki * block_k) // block_q
        lo = jnp.clip(lo_global - ci * nb, 0, nb)
    else:
        lo = 0
    dk, dv = jax.lax.fori_loop(lo, nb, body,
                               (dk_acc_ref[...], dv_acc_ref[...]))
    dk_acc_ref[...] = dk
    dv_acc_ref[...] = dv

    @pl.when(ci == n_chunks - 1)
    def _flush():
        dk_ref[0] = dk_acc_ref[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[...].astype(dv_ref.dtype)


def _flash_bwd_chunked(q, k, v, bias, out, lse, g, scale, causal, has_bias):
    """Backward for T > BWD_CHUNK_THRESHOLD: same math as ``_flash_bwd``,
    with the full-sequence operands streamed chunkwise (third grid dim)."""
    b, h, t_q, d = q.shape
    t_k = k.shape[2]
    d_v = v.shape[-1]
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)

    qf = q.reshape(b * h, t_q, d)
    kf = k.reshape(b * h, t_k, d)
    vf = v.reshape(b * h, t_k, d_v)
    dof = g.reshape(b * h, t_q, d_v)
    lsef = lse
    deltaf = jnp.broadcast_to(delta.reshape(b * h, t_q, 1),
                              (b * h, t_q, RES_LANES))
    block_q = _pick_block(t_q, BLOCK_Q)
    block_k = _pick_block(t_k, BLOCK_K)

    def _pick_chunk(t, block):
        # largest multiple of `block` <= BWD_CHUNK that divides t (the
        # kernels index sub-blocks inside the chunk, so block | chunk)
        c = (BWD_CHUNK // block) * block
        while c > block and t % c:
            c -= block
        return c

    chunk_k = _pick_chunk(t_k, block_k)
    chunk_q = _pick_chunk(t_q, block_q)
    n_chunks_k = t_k // chunk_k
    n_chunks_q = t_q // chunk_q

    if causal:
        # Steps whose whole K/V chunk lies above the causal diagonal are
        # compute-skipped in the kernel (nblk clips to 0) — ALSO skip
        # their DMA by re-mapping the chunk index to the last needed
        # chunk: consecutive grid steps with the same block index reuse
        # the resident block, so dead chunks are never fetched.
        def _k_chunk(bh, qi, ci):
            return (bh, jnp.minimum(ci, ((qi + 1) * block_q - 1) // chunk_k),
                    0)
    else:
        def _k_chunk(bh, qi, ci):
            return (bh, ci, 0)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda bh, qi, ci: (bh, qi, 0)),
        pl.BlockSpec((1, chunk_k, d), _k_chunk),
        pl.BlockSpec((1, chunk_k, d_v), _k_chunk),
        pl.BlockSpec((1, block_q, d_v), lambda bh, qi, ci: (bh, qi, 0)),
        pl.BlockSpec((1, block_q, RES_LANES), lambda bh, qi, ci: (bh, qi, 0)),
        pl.BlockSpec((1, block_q, RES_LANES), lambda bh, qi, ci: (bh, qi, 0)),
    ]
    args = [qf, kf, vf, dof, lsef, deltaf]
    if has_bias:
        in_specs.append(
            pl.BlockSpec((1, chunk_k, 1),
                         lambda bh, qi, ci: (bh // h,) + _k_chunk(bh, qi, ci)[1:]))
        args.append(bias)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel_chunked, scale=scale,
                          block_k=block_k, has_bias=has_bias, causal=causal,
                          n_chunks=n_chunks_k),
        name="flash_attention_bwd_dq_chunked",
        out_shape=jax.ShapeDtypeStruct((b * h, t_q, d), q.dtype),
        grid=(b * h, t_q // block_q, n_chunks_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, qi, ci: (bh, qi, 0)),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=COMPILER_PARAMS,
        interpret=_interpret(),
    )(*args)

    if causal:
        # mirror of the dq-pass DMA skip: query chunks strictly above the
        # diagonal for this key block re-map to the first needed chunk
        def _q_chunk(bh, ki, ci):
            return (bh, jnp.maximum(ci, (ki * block_k) // chunk_q), 0)
    else:
        def _q_chunk(bh, ki, ci):
            return (bh, ci, 0)
    in_specs_kv = [
        pl.BlockSpec((1, chunk_q, d), _q_chunk),
        pl.BlockSpec((1, block_k, d), lambda bh, ki, ci: (bh, ki, 0)),
        pl.BlockSpec((1, block_k, d_v), lambda bh, ki, ci: (bh, ki, 0)),
        pl.BlockSpec((1, chunk_q, d_v), _q_chunk),
        pl.BlockSpec((1, chunk_q, RES_LANES), _q_chunk),
        pl.BlockSpec((1, chunk_q, RES_LANES), _q_chunk),
    ]
    args_kv = [qf, kf, vf, dof, lsef, deltaf]
    if has_bias:
        in_specs_kv.append(
            pl.BlockSpec((1, block_k, 1), lambda bh, ki, ci: (bh // h, ki, 0)))
        args_kv.append(bias)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel_chunked, scale=scale,
                          block_q=block_q, has_bias=has_bias, causal=causal,
                          n_chunks=n_chunks_q),
        name="flash_attention_bwd_dkv_chunked",
        out_shape=[
            jax.ShapeDtypeStruct((b * h, t_k, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, t_k, d_v), v.dtype),
        ],
        grid=(b * h, t_k // block_k, n_chunks_q),
        in_specs=in_specs_kv,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda bh, ki, ci: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, d_v), lambda bh, ki, ci: (bh, ki, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d_v), jnp.float32)],
        compiler_params=COMPILER_PARAMS,
        interpret=_interpret(),
    )(*args_kv)

    return (dq.reshape(b, h, t_q, d), dk.reshape(b, h, t_k, d),
            dv.reshape(b, h, t_k, d_v))


def _resident_bwd_bytes(t: int, d: int, d_v: int, itemsize: int) -> int:
    return 2 * t * ((d + d_v) * itemsize + 2 * 128 * 4)


def _flash_bwd(q, k, v, bias, out, lse, g, scale, causal, has_bias):
    t = max(q.shape[2], k.shape[2])
    if (t > BWD_CHUNK_THRESHOLD
            or _resident_bwd_bytes(t, q.shape[-1], v.shape[-1], q.dtype.itemsize) > RESIDENT_BWD_VMEM):
        return _flash_bwd_chunked(q, k, v, bias, out, lse, g, scale,
                                  causal, has_bias)
    b, h, t_q, d = q.shape
    t_k = k.shape[2]
    d_v = v.shape[-1]
    # D = rowsum(dO * O): cheap elementwise-reduce, fused by XLA, stored
    # lane-broadcast like lse (Mosaic block layout requirement).
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)

    qf = q.reshape(b * h, t_q, d)
    kf = k.reshape(b * h, t_k, d)
    vf = v.reshape(b * h, t_k, d_v)
    dof = g.reshape(b * h, t_q, d_v)
    lsef = lse  # already (b*h, t_q, RES_LANES) from the forward
    deltaf = jnp.broadcast_to(delta.reshape(b * h, t_q, 1),
                              (b * h, t_q, RES_LANES))
    block_q = _pick_block(t_q, BLOCK_Q)
    block_k = _pick_block(t_k, BLOCK_K)
    bias_spec_q = pl.BlockSpec((1, t_k, 1), lambda bh, qi: (bh // h, 0, 0))
    bias_spec_k = pl.BlockSpec((1, t_k, 1), lambda bh, ki: (bh // h, 0, 0))

    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0)),
        pl.BlockSpec((1, t_k, d), lambda bh, qi: (bh, 0, 0)),
        pl.BlockSpec((1, t_k, d_v), lambda bh, qi: (bh, 0, 0)),
        pl.BlockSpec((1, block_q, d_v), lambda bh, qi: (bh, qi, 0)),
        pl.BlockSpec((1, block_q, RES_LANES), lambda bh, qi: (bh, qi, 0)),
        pl.BlockSpec((1, block_q, RES_LANES), lambda bh, qi: (bh, qi, 0)),
    ]
    args = [qf, kf, vf, dof, lsef, deltaf]
    if has_bias:
        in_specs.append(bias_spec_q)
        args.append(bias)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, block_k=block_k,
                          has_bias=has_bias, causal=causal),
        name="flash_attention_bwd_dq",
        out_shape=jax.ShapeDtypeStruct((b * h, t_q, d), q.dtype),
        grid=(b * h, t_q // block_q),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0)),
        compiler_params=COMPILER_PARAMS,
        interpret=_interpret(),
    )(*args)

    in_specs_kv = [
        pl.BlockSpec((1, t_q, d), lambda bh, ki: (bh, 0, 0)),
        pl.BlockSpec((1, block_k, d), lambda bh, ki: (bh, ki, 0)),
        pl.BlockSpec((1, block_k, d_v), lambda bh, ki: (bh, ki, 0)),
        pl.BlockSpec((1, t_q, d_v), lambda bh, ki: (bh, 0, 0)),
        pl.BlockSpec((1, t_q, RES_LANES), lambda bh, ki: (bh, 0, 0)),
        pl.BlockSpec((1, t_q, RES_LANES), lambda bh, ki: (bh, 0, 0)),
    ]
    args_kv = [qf, kf, vf, dof, lsef, deltaf]
    if has_bias:
        in_specs_kv.append(bias_spec_k)
        args_kv.append(bias)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, block_q=block_q,
                          has_bias=has_bias, causal=causal),
        name="flash_attention_bwd_dkv",
        out_shape=[
            jax.ShapeDtypeStruct((b * h, t_k, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, t_k, d_v), v.dtype),
        ],
        grid=(b * h, t_k // block_k),
        in_specs=in_specs_kv,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda bh, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, d_v), lambda bh, ki: (bh, ki, 0)),
        ],
        compiler_params=COMPILER_PARAMS,
        interpret=_interpret(),
    )(*args_kv)

    return (dq.reshape(b, h, t_q, d), dk.reshape(b, h, t_k, d),
            dv.reshape(b, h, t_k, d_v))


# ------------------------------------------------------------- public VJP


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash(q, k, v, bias, scale, causal, has_bias):
    out, _ = _flash_fwd(q, k, v, bias, scale, causal, has_bias,
                        save_residuals=False)
    return out


def _flash_vjp_fwd(q, k, v, bias, scale, causal, has_bias):
    out, lse = _flash_fwd(q, k, v, bias, scale, causal, has_bias)
    return out, (q, k, v, bias, out, lse)


def _flash_vjp_bwd(scale, causal, has_bias, res, g):
    q, k, v, bias, out, lse = res
    dq, dk, dv = _flash_bwd(q, k, v, bias, out, lse, g, scale, causal,
                            has_bias)
    return dq, dk, dv, jnp.zeros_like(bias)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q, k, v, mask=None, causal: bool = False):
    """(batch, heads, time, d) flash attention. ``mask`` may be a key-padding
    mask of shape (batch, t_k) or (batch, 1, 1, t_k) — 1/True = attend (check
    :func:`flash_attention_compatible` first). ``causal=True`` applies the
    autoregressive triangle with diagonal block skipping."""
    b, t_k = q.shape[0], k.shape[2]
    kmask = _padding_mask_2d(mask, b, t_k)
    if mask is not None and kmask is None:
        raise ValueError("flash_attention supports key-padding masks only; "
                         "use the XLA fallback for other mask shapes")
    scale = 1.0 / float(q.shape[-1]) ** 0.5
    has_bias = kmask is not None
    if has_bias:
        bias = jnp.where(kmask.astype(bool), 0.0, MASK_VALUE)
        bias = bias.astype(jnp.float32)[:, :, None]  # (b, t_k, 1)
    else:
        bias = jnp.zeros((b, t_k, 1), jnp.float32)  # unused dummy
    return _flash(q, k, v, bias, scale, bool(causal), has_bias)
