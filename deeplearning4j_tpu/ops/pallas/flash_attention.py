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
    dS  = P * (dP - D)
    dQ  = scale * sum dS @ K      (dq kernel: grid over query blocks)
    dK  = scale * sum dS^T @ Q    (dkv kernel: grid over key blocks)

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
roughly halving the FLOPs, which XLA's dense softmax cannot do. Of the
blocks that are left only those the diagonal crosses are masked (one a grid
step where ``block_q == block_k``): each kernel's loop is split in two, and
the blocks that lie wholly under the diagonal run a body with no iota, no
compare and no select (0.04 us a tile in the forward, nothing in the
backward passes, whose mask hides behind their matmuls).

What a kernel does between a tile's matmuls decides how long the MXU waits
(PR 36, measured on v5e at T=8192; us a (512, 512) tile at heads of 256:
forward 1.96 -> 1.63, dq pass 2.60 -> 2.43, dk/dv pass 3.51 -> 3.04, against
1.36 / 2.04 / 2.73 for the matmuls alone at the MXU's peak):

- The accumulators live in VMEM scratch and are updated in place: the output
  with its running max and denominator in the forward (the two statistics
  lane-replicated, (block_q, 128)), dq, and dk with dv. A loop carries
  nothing: what a ``fori_loop`` carries is copied once an iteration (most
  of what PR 36 won), and two tiles share an iteration (``_loop``).
- ``scale`` is not multiplied into the tile. Where it is a power of two (a
  head of 64 or 256) the (block, d) operand a grid step holds (q; k in the
  dk/dv pass) is scaled once, which is exact; otherwise the scores keep
  their float32 multiply. dS is accumulated unscaled and the float32 dq / dk
  accumulator is multiplied once where it is written out.
- The dk/dv pass computes the tile transposed, ``S^T = K @ Q_blk^T`` and
  ``dP^T = V @ dO_blk^T``, so that ``dV += P^T @ dO_blk`` and
  ``dK += dS^T @ Q_blk`` are plain matmuls and no (block_q, block_k) tile goes
  through a transpose (0.07 us a tile at heads of 256, 0.37 at 192 / 128). It
  takes the row statistics along lanes, (b*h, 1, T): the forward stores the
  logsumexp that way too, D is made by XLA in either shape.
- Carrying the next tile's scores through the loop, so as to start them
  early, costs more than it hides (the copy again: +0.3 to +0.9 us a tile).

All three kernels are entered through one ``jax.jit`` each, so that the
call sites of a model, which call at one shape, share one traced kernel.

``block_diffusion=(t, block)`` is a third mask family, for training a model
that denoises blocks of tokens (BD3-LM, arXiv:2503.09573): the sequence is
``[noisy ; clean]``, ``2 t`` positions, cut into blocks of ``block`` within
each half. A noisy query sees the noisy keys of its own block and the clean
keys of earlier blocks; a clean query sees the clean keys of its own and
earlier blocks and no noisy key (:func:`block_diffusion_allowed`):
``t^2 + t block`` allowed entries of ``(2 t)^2``. The kernels work the mask
out per tile from two iotas and the tile's place (no ``2t x 2t`` array
anywhere) and visit only the tiles that hold an allowed entry: 80 of 256
at ``t`` = 4096, ``block`` = 4, tiles of 512, 24 of them masked, the others
whole. Under this family the query heads may outnumber the key/value heads
(grouped-query attention): a key/value head's group of query heads is laid
head after head along the kernels' row axis, ``(b * h_kv, group * 2t, d)``,
a reshape of what the caller holds, so that K and V are fetched once a
group in the forward and the dq pass. The dk/dv pass runs its grid over
(key/value head, query head, key tile): a query head's Q and dO (4 MB at
2t = 8192, heads of 128) are fetched once a query head, not once a key tile
(32 fetches a run where there were 512 at the SDAR cell's shape), and dk and
dv of the whole key/value head add up over the group in float32 scratch,
each key tile zeroed at the first query head and written out at the last.
K and V are never repeated in HBM.
These calls are named ``bd_flash_attention_fwd`` / ``_bwd_dq`` /
``_bwd_dkv``; what a masked tile costs over a whole one is measured, not
yet cut (PERF.md).

Under ``causal``, without a key-padding mask, the query heads may be a
multiple of the key/value heads too, in the same row layout: the forward and
the dq pass fetch K and V once a group. The resident dk/dv pass runs its
grid over (key/value head, query head, key tile) as above, the key tiles
from last to first (the first, which every query tile sees, ends a head);
its chunked form takes the group's query heads one after another along its
chunk axis. These runs keep the causal kernels' names. With one query head
a key/value head the kernels are the ones they were.

Used automatically by ``nn.attention_layers.dot_product_attention`` when
:func:`flash_attention_compatible` says the shapes and the platform allow;
otherwise the XLA softmax form runs. ``DL4J_TPU_PALLAS_INTERPRET=1`` runs
the kernels in interpreter mode on the CPU backend (test path only).
"""

from __future__ import annotations

import functools
import math

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


def flash_attention_compatible(q, k, v, mask=None, causal: bool = False, block_diffusion=None) -> bool:
    """Kernel applicability: key-padding masks only (other mask shapes fall
    back to XLA), block-divisible sequence, head dim that tiles onto the MXU
    lanes, and a key length long enough that the kernel beats XLA. Under
    ``block_diffusion=(t, block)``: no other mask, ``2 t`` positions on both
    sides, whole tiles in each half (``t`` a multiple of 128) and whole
    blocks, and what a head keeps in VMEM within bounds: K and V of a head
    (forward, dq pass) or Q and dO of one (dk/dv pass), double-buffered,
    and in the dk/dv pass dk and dv of a whole key/value head in float32
    scratch (:func:`_by_query_head_bytes`). The query heads may be a
    multiple of the key/value heads under ``block_diffusion`` and under
    ``causal`` without a key-padding mask (past the VMEM bound the causal
    backward takes its chunked forms)."""
    if q.ndim != 4:
        return False
    t_q, d = q.shape[2], q.shape[3]
    t_k = k.shape[2]
    if block_diffusion is not None:
        t, block = block_diffusion
        if (mask is not None or causal or t_q != 2 * t or t_k != 2 * t or t % 128 or t % block
                or q.shape[1] % k.shape[1] or k.shape[1] != v.shape[1]
                or _by_query_head_bytes(t_k, d, v.shape[3], q.dtype.itemsize) > RESIDENT_BWD_VMEM):
            return False
    elif k.shape[1] != q.shape[1] and (not causal or mask is not None or q.shape[1] % k.shape[1]
                                       or k.shape[1] != v.shape[1]):
        return False
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


_NT = (((1,), (1,)), ((), ()))  # a @ b^T: both operands contracted over their last axis


def _causal_hi(qi, block_q: int, block_k: int):
    """Number of key blocks needed for query block qi under causal masking."""
    return pl.cdiv((qi + 1) * block_q, block_k)


def _diag_mask(s, q0, k0, q_axis: int = 0):
    """Apply the causal triangle inside a score tile whose first query is
    ``q0`` and whose first key is ``k0``; queries run along ``q_axis``."""
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
    return jnp.where(k_pos <= q_pos, s, MASK_VALUE)


def block_diffusion_allowed(q_pos, k_pos, t: int, block: int):
    """Whether the query at ``q_pos`` may see the key at ``k_pos`` under the
    block-diffusion mask: positions in ``[0, 2t)``, the noisy half first,
    blocks of ``block`` within each half. The mask's definition, on arrays
    that broadcast against each other (the XLA path builds ``2t x 2t`` from
    it; the kernels never do)."""
    q_clean, k_clean = q_pos >= t, k_pos >= t
    q_blk = (q_pos - jnp.where(q_clean, t, 0)) // block
    k_blk = (k_pos - jnp.where(k_clean, t, 0)) // block
    return jnp.where(k_clean, jnp.where(q_clean, k_blk <= q_blk, k_blk < q_blk), ~q_clean & (k_blk == q_blk))


def _bd_mask(s, q0, k0, q_axis: int = 0, *, t: int, block: int):
    """:func:`block_diffusion_allowed` inside a score tile whose first query
    is ``q0`` and whose first key is ``k0`` (scalars; a tile lies in one half
    on either axis, so which halves is a scalar matter): how many blocks the
    query lies ahead of the key, held between two scalars."""
    q_clean, k_clean = (q0 >= t).astype(jnp.int32), (k0 >= t).astype(jnp.int32)

    def block_of(first, axis):
        pos = first + jax.lax.broadcasted_iota(jnp.int32, s.shape, axis)
        # positions are not negative: a shift is the floor division
        return pos >> (block.bit_length() - 1) if block & (block - 1) == 0 else jax.lax.div(pos, block)

    ahead = block_of(q0 - q_clean * t, q_axis) - block_of(k0 - k_clean * t, 1 - q_axis)
    lo = k_clean - q_clean                  # a noisy query sees clean keys of earlier blocks only
    hi = (k_clean - q_clean * (1 - k_clean)) * t  # noisy keys: the same block (a clean query: none)
    return jnp.where((ahead >= lo) & (ahead <= hi), s, MASK_VALUE)


def _tile_mask(bd):
    """What a kernel's ``tile`` applies where a range is masked."""
    return functools.partial(_bd_mask, t=bd[0], block=bd[1]) if bd else _diag_mask


def _bd_key_tiles(qi, block_q: int, block_k: int, t: int, block: int):
    """The key tiles that query tile ``qi`` (of ``2t / block_q``) visits under
    block diffusion, as ``(lo, hi, masked)`` ranges of key-tile indices: the
    noisy keys of its own blocks (a noisy tile only), the clean keys that
    all of its queries see, the clean keys that some of them see."""
    clean = qi >= t // block_q
    r0 = (qi - jnp.where(clean, t // block_q, 0)) * block_q  # its first row within its half
    b_lo, b_hi = r0 // block, (r0 + block_q - 1) // block    # its first and last block
    own_lo = (b_lo * block) // block_k
    own_hi = jnp.where(clean, own_lo, pl.cdiv((b_hi + 1) * block, block_k))
    strict = jnp.where(clean, 0, 1)  # a noisy query does not see its own block's clean keys
    first = t // block_k
    whole = first + ((b_lo - strict + 1) * block) // block_k
    some = first + pl.cdiv((b_hi - strict + 1) * block, block_k)
    return (own_lo, own_hi, True), (first, whole, False), (whole, some, True)


def _bd_query_tiles(ki, block_q: int, block_k: int, t: int, block: int):
    """The mirror of :func:`_bd_key_tiles` for the dk/dv pass: the query
    tiles (of one head, ``2t / block_q``) that key tile ``ki`` is seen by.
    Noisy keys: the noisy queries of their own blocks. Clean keys: the noisy
    queries of later blocks and the clean queries of their own and later
    blocks, each as a masked range and a whole one."""
    clean = ki >= t // block_k
    c0 = (ki - jnp.where(clean, t // block_k, 0)) * block_k
    b_lo, b_hi = c0 // block, (c0 + block_k - 1) // block
    n_q = t // block_q
    own_lo = (b_lo * block) // block_q
    ranges = [(own_lo, jnp.where(clean, own_lo, pl.cdiv((b_hi + 1) * block, block_q)), True)]
    for strict, first in ((1, 0), (0, n_q)):  # the noisy queries, then the clean ones
        lo = first + ((b_lo + strict) * block) // block_q
        mid = first + pl.cdiv((b_hi + strict) * block, block_q)
        ranges += [(lo, jnp.where(clean, mid, lo), True), (mid, jnp.where(clean, first + n_q, mid), False)]
    return ranges


def _folds(scale: float) -> bool:
    """Whether ``scale`` is a power of two: an operand times it is exact in
    every float type, so the (block, d) operand takes it once a grid step
    in place of every score tile."""
    return math.frexp(scale)[0] == 0.5


def _pre_scaled(x, scale: float):
    return (x.astype(jnp.float32) * scale).astype(x.dtype) if _folds(scale) else x


def _scores(a, b, scale: float):
    """``scale * a @ b^T`` in float32, for ``a`` through :func:`_pre_scaled`."""
    s = jax.lax.dot_general(a, b, _NT, preferred_element_type=jnp.float32)
    return s if _folds(scale) else s * scale


# Tiles a loop iteration: a loop's back edge is a wall to Mosaic's scheduler,
# so an iteration of one tile starts its first matmul only when the last
# accumulate of the tile before is through; with two in a body the second's
# scores run on the MXU while the VPU is on the first's softmax (measured on
# v5e at T=8192, heads of 256, us a (512, 512) tile: forward 1.74 -> 1.63,
# the backward passes 0.03 each; four copies of the tile in the traced body).
TILES_PER_ITERATION = 2


def _loop(tile, lo, hi, masked: bool):
    """``tile(i, masked)`` for ``lo <= i < hi``. The tiles accumulate into
    VMEM scratch in place and the loop carries nothing: what a ``fori_loop``
    carries is copied once an iteration, ~2.7 cycles a vreg (measured on
    v5e, PR 36: a carried (512, 256) float32 accumulator with its (512,) row
    statistics cost 0.22 us a tile, a carried (512, 512) score tile 0.3).
    The masked blocks are the few the diagonal crosses: one an iteration."""
    def one(i, _):
        tile(i, masked)

    if not masked:
        def several(j, _):
            for u in range(TILES_PER_ITERATION):
                tile(lo + TILES_PER_ITERATION * j + u, masked)

        whole = (hi - lo) // TILES_PER_ITERATION
        jax.lax.fori_loop(0, whole, several, None)
        lo = lo + TILES_PER_ITERATION * whole
    jax.lax.fori_loop(lo, hi, one, None)


def _heads_flat(x, h_kv: int = None):
    """(b, h, t, d) as the kernels' (b * h, t, d); with fewer key/value
    heads, ``h_kv``, a group's query heads head after head along the rows:
    (b * h_kv, h / h_kv * t, d)."""
    return x.reshape(x.shape[0] * (h_kv or x.shape[1]), -1, x.shape[3])


def _q_tiles(q, k, block_q: int, bd):
    """Query tiles a query head has, where the kernels' rows run over a
    group's query heads (always under ``bd``, else where the key/value heads
    are fewer): a grid step's tile within its head is its row block modulo
    this. ``None`` where a row is one query head."""
    return q.shape[2] // block_q if bd or k.shape[1] != q.shape[1] else None


def _lanes(x, n: int):
    """A lane-replicated (rows, 128) row statistic as (rows, n)."""
    return jnp.tile(x, (1, n // 128)) if n % 128 == 0 else jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _stepwise(n_chunks, init, work, flush, axis: int = 2):
    """A grid step of a kernel that accumulates in scratch: resident
    (``n_chunks`` None) it does all three, chunked the first chunk's step
    initialises and the last one's flushes; the chunks run along the grid's
    ``axis``."""
    if n_chunks is None:
        init()
        work(0)
        flush()
        return
    ci = pl.program_id(axis)
    pl.when(ci == 0)(init)
    work(ci)
    pl.when(ci == n_chunks - 1)(flush)


# ---------------------------------------------------------------- forward


def _fwd_kernel(*refs, scale: float, block_k: int, has_bias: bool,
                causal: bool, save_residuals: bool, bd=None, q_tiles=None):
    q_ref, k_ref, v_ref = refs[:3]
    bias_ref = refs[3] if has_bias else None
    o_ref = refs[3 + has_bias]
    acc_ref, m_ref, l_ref = refs[-3:]  # (BQ, Dv), and lane-replicated (BQ, 128) row statistics

    # Matmul operands stay in the input dtype (bf16 on the fast path) so the
    # MXU runs at full rate; accumulation and softmax stats are f32.
    q = _pre_scaled(q_ref[0], scale)  # (BLOCK_Q, D)
    in_dtype = q.dtype
    qi = pl.program_id(1)
    block_q = q.shape[0]
    mask = _tile_mask(bd)
    if q_tiles:  # the rows run over a group's query heads, head after head
        qi = qi % q_tiles
    d_v = v_ref.shape[2]
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
    l_ref[...] = jnp.zeros_like(l_ref)

    def tile(i, masked):
        k_blk = k_ref[0, pl.ds(i * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(i * block_k, block_k), :]
        s = _scores(q, k_blk, scale)
        if bias_ref is not None:
            s = s + bias_ref[0, pl.ds(i * block_k, block_k), 0][None, :]
        if masked:
            s = mask(s, qi * block_q, i * block_k)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, block_k))
        corr = jnp.exp(m - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * _lanes(corr, d_v) + jax.lax.dot(
            p.astype(in_dtype), v_blk, preferred_element_type=jnp.float32)

    if bd:
        for lo, hi, masked in _bd_key_tiles(qi, block_q, block_k, *bd):
            _loop(tile, lo, hi, masked)
    elif causal:
        # the key blocks wholly under the diagonal, then those it crosses
        below = (qi * block_q) // block_k
        _loop(tile, 0, below, False)
        _loop(tile, below, _causal_hi(qi, block_q, block_k), True)
    else:
        _loop(tile, 0, k_ref.shape[1] // block_k, False)
    l_safe = jnp.maximum(l_ref[...], 1e-20)
    o_ref[0] = (acc_ref[...] / _lanes(l_safe, d_v)).astype(o_ref.dtype)
    if save_residuals:  # only requested under differentiation
        # logsumexp twice: a row a sublane for the dq pass, along lanes for
        # the dk/dv pass (a re-layout in XLA would read the padded array)
        lse_ref, lse_lanes_ref = refs[4 + has_bias:6 + has_bias]
        lse = m_ref[...] + jnp.log(l_safe)
        lse_ref[0] = lse[:, :RES_LANES]
        lse_lanes_ref[0] = lse[:, 0][None, :]


_STATIC = ("scale", "causal", "has_bias", "block_q", "block_k", "interpret", "bd")


@functools.partial(jax.jit, static_argnames=_STATIC + ("save_residuals",))
def _flash_fwd(q, k, v, bias, *, scale, causal, has_bias, block_q, block_k,
               interpret, save_residuals, bd=None):
    b, h, t_q, d = q.shape
    t_k = k.shape[2]
    d_v = v.shape[-1]
    # the grid's rows are the key/value heads; a head's group of query heads
    # lies head after head along the query axis
    bh, rows_q = b * k.shape[1], h // k.shape[1] * t_q
    grid = (bh, rows_q // block_q)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0)),
        pl.BlockSpec((1, t_k, d), lambda bh, qi: (bh, 0, 0)),
        pl.BlockSpec((1, t_k, d_v), lambda bh, qi: (bh, 0, 0)),
    ]
    args = [_heads_flat(q, k.shape[1]), _heads_flat(k), _heads_flat(v)]
    if has_bias:
        # bias is (b, t_k, 1); the index map divides the grid's batch*heads
        # row by heads, so all heads of one batch share the same block.
        in_specs.append(
            pl.BlockSpec((1, t_k, 1), lambda bh, qi: (bh // h, 0, 0)))
        args.append(bias)
    out_shape = [jax.ShapeDtypeStruct((bh, rows_q, d_v), q.dtype)]
    out_specs = [pl.BlockSpec((1, block_q, d_v), lambda bh, qi: (bh, qi, 0))]
    if save_residuals:
        out_shape.append(
            jax.ShapeDtypeStruct((bh, rows_q, RES_LANES), jnp.float32))
        out_specs.append(
            pl.BlockSpec((1, block_q, RES_LANES), lambda bh, qi: (bh, qi, 0)))
        out_shape.append(jax.ShapeDtypeStruct((bh, 1, rows_q), jnp.float32))
        out_specs.append(pl.BlockSpec((1, 1, block_q), lambda bh, qi: (bh, 0, qi)))
    res = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, block_k=block_k,
                          has_bias=has_bias, causal=causal,
                          save_residuals=save_residuals, bd=bd, q_tiles=_q_tiles(q, k, block_q, bd)),
        name=_name("fwd", bd),
        out_shape=out_shape,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((block_q, d_v), jnp.float32),
                        pltpu.VMEM((block_q, 128), jnp.float32),
                        pltpu.VMEM((block_q, 128), jnp.float32)],
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(*args)
    return (res[0].reshape(b, h, t_q, d_v), *res[1:])


# ---------------------------------------------------------------- backward
#
# Each pass has a RESIDENT form (grid (bh, block): the full K/V, or Q/dO,
# of a head is one VMEM block a grid step) and a CHUNKED form (grid
# (bh, block, chunk): those operands stream through VMEM a chunk a step and
# the float32 scratch accumulators persist across the sequential minor grid
# steps, flushed at the last).


def _bwd_dq_kernel(*refs, scale: float, block_k: int, has_bias: bool,
                   causal: bool, n_chunks, bd=None, q_tiles=None):
    """dq pass. Resident (``n_chunks`` None): grid (bh, qi). Chunked: grid
    (bh, qi, ci), K/V blocks are the ci-th chunk. dq accumulates in scratch,
    unscaled, and takes ``scale`` where it is written out."""
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:6]
    bias_ref = refs[6] if has_bias else None
    dq_ref, acc_ref = refs[-2:]
    q = _pre_scaled(q_ref[0], scale)          # (BQ, D)
    do = do_ref[0]                            # (BQ, Dv)
    in_dtype = q.dtype
    lse = lse_ref[0][:, 0]                    # (BQ,)
    delta = delta_ref[0][:, 0]                # (BQ,)
    qi = pl.program_id(1)
    block_q = q.shape[0]
    nb = k_ref.shape[1] // block_k
    mask = _tile_mask(bd)
    if q_tiles:  # the rows run over a group's query heads
        qi = qi % q_tiles

    def init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def work(ci):
        first = ci * nb  # global index of this chunk's first key block

        def tile(i, masked):
            k_blk = k_ref[0, pl.ds(i * block_k, block_k), :]
            v_blk = v_ref[0, pl.ds(i * block_k, block_k), :]
            s = _scores(q, k_blk, scale)
            if bias_ref is not None:
                s = s + bias_ref[0, pl.ds(i * block_k, block_k), 0][None, :]
            if masked:
                s = mask(s, qi * block_q, (first + i) * block_k)
            p = jnp.exp(s - lse[:, None])                       # (BQ, BK)
            dp = jax.lax.dot_general(do, v_blk, _NT,
                                     preferred_element_type=jnp.float32)
            ds = (p * (dp - delta[:, None])).astype(in_dtype)
            acc_ref[...] += jax.lax.dot(ds, k_blk,
                                        preferred_element_type=jnp.float32)

        if bd:
            for lo, hi, masked in _bd_key_tiles(qi, block_q, block_k, *bd):
                _loop(tile, lo, hi, masked)
            return
        if not causal:
            _loop(tile, 0, nb, False)
            return
        # of this chunk: the key blocks wholly under the diagonal, then
        # those it crosses; the blocks above it are skipped
        below = jnp.clip((qi * block_q) // block_k - first, 0, nb)
        hi = jnp.clip(_causal_hi(qi, block_q, block_k) - first, 0, nb)
        _loop(tile, 0, below, False)
        _loop(tile, below, hi, True)

    def flush():
        dq_ref[0] = (acc_ref[...] * scale).astype(dq_ref.dtype)

    _stepwise(n_chunks, init, work, flush)


def _bwd_dkv_kernel(*refs, scale: float, block_q: int, has_bias: bool,
                    causal: bool, n_chunks, bd=None, by_query_head=False, head_chunks=None):
    """dk/dv pass. Resident (``n_chunks`` None): grid (bh, ki). Chunked:
    grid (bh, ki, ci), Q/dO/lse/delta blocks are the ci-th chunk; with
    grouped query heads a head has ``head_chunks`` of them and the group's
    heads follow one another along the chunk axis. The score tile is
    computed transposed, (BK, BQ): keys on sublanes, queries on lanes, so
    that both results are plain matmuls of it; ``lse`` and ``delta`` come
    along lanes. dk accumulates unscaled. With ``by_query_head`` (under
    ``bd``, and for grouped heads in the resident form) the grid is (bh,
    query head of the group, key tile in :func:`_key_order`) and
    ``n_chunks`` the group: the scratch holds every key tile of the
    key/value head and adds up over the group, a key tile's rows zeroed at
    the first query head and written out at the last."""
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:6]
    # this key block's bias (shared across q blocks), one a row of the tile
    bias = refs[6][0] if has_bias else None   # (BK, 1)
    dk_ref, dv_ref, dk_acc_ref, dv_acc_ref = refs[-4:]
    k = _pre_scaled(k_ref[0], scale)          # (BK, D): for the scores alone
    v = v_ref[0]                              # (BK, Dv)
    in_dtype = k.dtype
    block_k = k.shape[0]
    if by_query_head:
        ki = _key_order(pl.program_id(2), dk_acc_ref.shape[0] // block_k, bd)
        own = pl.ds(pl.multiple_of(ki * block_k, block_k), block_k)
        dk_acc_ref, dv_acc_ref = dk_acc_ref.at[own], dv_acc_ref.at[own]
    else:
        ki = pl.program_id(1)
    nb = q_ref.shape[1] // block_q
    mask = _tile_mask(bd)

    def init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    def work(ci):
        # index of this chunk's first query block within its head
        first = 0 if by_query_head else (ci % head_chunks if head_chunks else ci) * nb

        def tile(i, masked):
            rows = pl.ds(i * block_q, block_q)
            q_blk = q_ref[0, rows, :]
            do_blk = do_ref[0, rows, :]
            s = _scores(k, q_blk, scale)                        # (BK, BQ)
            if bias is not None:
                s = s + bias
            if masked:
                s = mask(s, (first + i) * block_q, ki * block_k, q_axis=1)
            p = jnp.exp(s - lse_ref[0, :, rows])
            dv_acc_ref[...] += jax.lax.dot(p.astype(in_dtype), do_blk,
                                           preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(v, do_blk, _NT,
                                     preferred_element_type=jnp.float32)
            ds = (p * (dp - delta_ref[0, :, rows])).astype(in_dtype)
            dk_acc_ref[...] += jax.lax.dot(ds, q_blk,
                                           preferred_element_type=jnp.float32)

        if bd:
            for lo, hi, masked in _bd_query_tiles(ki, block_q, block_k, *bd):
                _loop(tile, lo, hi, masked)
            return
        if not causal:
            _loop(tile, 0, nb, False)
            return
        # of this chunk: query blocks strictly above the diagonal contribute
        # nothing to this key block; then those the diagonal crosses, then
        # those wholly under it
        lo = jnp.clip((ki * block_k) // block_q - first, 0, nb)
        clear = jnp.clip(pl.cdiv((ki + 1) * block_k, block_q) - first, 0, nb)
        _loop(tile, lo, clear, True)
        _loop(tile, clear, nb, False)

    def flush():
        dk_ref[0] = (dk_acc_ref[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[...].astype(dv_ref.dtype)

    _stepwise(n_chunks, init, work, flush, axis=1 if by_query_head else 2)


# Above this sequence length the backward switches to the CHUNKED forms:
# the resident forms keep full K/V (dq pass) and full Q/dO (dkv pass)
# VMEM-resident per grid step, which blows the ~16 MB VMEM budget past
# T=8192; the chunked forms stream those operands through VMEM in
# BWD_CHUNK-row chunks via a third grid dimension. Both forms are one kernel
# a pass (`_bwd_dq_kernel`, `_bwd_dkv_kernel`): the same loops over a grid
# step's blocks (under `causal` the blocks the diagonal crosses masked, the
# blocks under it plain, the blocks above it skipped, each range clipped to
# the chunk), accumulating in place in f32 scratch, which in the chunked
# form persists across the (sequential) minor grid steps and is scaled and
# flushed at the last. The resident grid is NOT dropped for always-chunked
# (measured on v5e: chunked == resident at T=8192, 17.9 ms both, but causal
# T=2048 runs 6.3 vs 4.8 ms chunked — the 3-D grid costs ~30% at short
# causal lengths, so the resident forms stay for T <= threshold).
BWD_CHUNK_THRESHOLD = 8192
BWD_CHUNK = 4096
# ... and, whatever the length, where `_resident_bwd_bytes` passes this: q/k
# and v/dO rows in the input dtype plus two float32 row statistics padded
# from RES_LANES to 128 lanes (as the dk/dv pass took them until PR 36; it
# now takes them along lanes, (1, T), and the rule was left as it was: at
# T=8192 the forms cost the same), all double-buffered, against the
# kernels' scoped VMEM (32 MiB asked for). At T=8192 a head of 64 counts
# 21 MB and stays resident; a q.k head of 192 against a v head of 128
# counted 27 MB plus its blocks, and Mosaic refused it by 0.8 MB (compiled
# for a v5e, PR 30).
RESIDENT_BWD_VMEM = 24 * 1024 * 1024


def _name(kernel: str, bd, chunk=None) -> str:
    """The ``pallas_call``'s name, which the device trace shows: the
    block-diffusion family's carry ``bd_``, so that their work is never
    counted with the causal kernels' (their third grid axis is the group,
    not a chunk of the sequence: no ``_chunked``)."""
    return ("bd_" if bd else "") + "flash_attention_" + kernel + ("_chunked" if chunk and not bd else "")


def _resident_bwd_bytes(t: int, d: int, d_v: int, itemsize: int) -> int:
    return 2 * t * ((d + d_v) * itemsize + 2 * 128 * 4)


def _by_query_head_bytes(t: int, d: int, d_v: int, itemsize: int) -> int:
    """What the dk/dv pass that runs over query heads keeps in VMEM: a query
    head's Q and dO, double-buffered, and dk and dv of the whole key/value
    head in float32 scratch (the dq pass's K and V, double-buffered, are
    less). The forms whose grid runs over query heads go by this rule."""
    return t * (d + d_v) * (2 * itemsize + 4)


def _pick_chunk(t: int, block: int) -> int:
    """Largest multiple of ``block`` <= BWD_CHUNK that divides t (the
    kernels index sub-blocks inside the chunk, so block | chunk)."""
    c = (BWD_CHUNK // block) * block
    while c > block and t % c:
        c -= block
    return c


@functools.partial(jax.jit, static_argnames=_STATIC + ("chunk",))
def _flash_bwd_dq(q, k, v, do, lse, delta, bias, *, chunk, scale, causal,
                  has_bias, block_q, block_k, interpret, bd=None):
    """dq from (b, h, t, d) operands and (b*h, t_q, RES_LANES) row
    statistics; K/V whole a grid step (``chunk`` None) or ``chunk`` rows."""
    b, h, t_q, d = q.shape
    t_k = k.shape[2]
    d_v = v.shape[-1]
    chunk_k = chunk or t_k
    bh, rows_q = b * k.shape[1], h // k.shape[1] * t_q  # as in the forward
    q_tiles = _q_tiles(q, k, block_q, bd)

    def own(bh, qi, *ci):
        return (bh, qi, 0)

    def streamed(bh, qi, *ci):
        if not ci:
            return (bh, 0, 0)
        if q_tiles:  # the query tile within its head
            qi = qi % q_tiles
        # Steps whose whole K/V chunk lies above the causal diagonal are
        # compute-skipped in the kernel (its ranges clip to nothing) — ALSO
        # skip their DMA by re-mapping the chunk index to the last needed
        # chunk: consecutive grid steps with the same block index reuse
        # the resident block, so dead chunks are never fetched.
        last = ((qi + 1) * block_q - 1) // chunk_k
        return (bh, jnp.minimum(ci[0], last) if causal else ci[0], 0)

    in_specs = [
        pl.BlockSpec((1, block_q, d), own),
        pl.BlockSpec((1, chunk_k, d), streamed),
        pl.BlockSpec((1, chunk_k, d_v), streamed),
        pl.BlockSpec((1, block_q, d_v), own),
        pl.BlockSpec((1, block_q, RES_LANES), own),
        pl.BlockSpec((1, block_q, RES_LANES), own),
    ]
    args = [_heads_flat(q, k.shape[1]), _heads_flat(k), _heads_flat(v), _heads_flat(do, k.shape[1]), lse, delta]
    if has_bias:
        in_specs.append(pl.BlockSpec(
            (1, chunk_k, 1), lambda bh, *at: (bh // h,) + streamed(bh, *at)[1:]))
        args.append(bias)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, block_k=block_k,
                          has_bias=has_bias, causal=causal,
                          n_chunks=t_k // chunk if chunk else None, bd=bd, q_tiles=q_tiles),
        name=_name("bwd_dq", bd, chunk),
        out_shape=jax.ShapeDtypeStruct((bh, rows_q, d), q.dtype),
        grid=(bh, rows_q // block_q) + ((t_k // chunk,) if chunk else ()),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_q, d), own),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(*args)
    return dq.reshape(b, h, t_q, d)


def _bd_key_order(j, n_k: int):
    """The key tile that the ``j``-th step of a query head visits in the
    block-diffusion dk/dv pass: the first clean key tile, seen by every query
    tile of the head (16 tiles at 2t = 8192 in tiles of 512), comes last, so
    that the next query head's Q and dO are fetched behind the heaviest
    step of the head."""
    return jax.lax.rem(j + n_k // 2 + 1, n_k)


def _key_order(j, n_k: int, bd):
    """The key tile of the ``j``-th step of a query head in a dk/dv pass
    whose grid runs over query heads: :func:`_bd_key_order` under ``bd``;
    else the last key tile first and the first, which every query tile of
    the head sees under ``causal``, last (the same reason)."""
    return _bd_key_order(j, n_k) if bd else n_k - 1 - j


@functools.partial(jax.jit, static_argnames=_STATIC + ("chunk",))
def _flash_bwd_dkv(q, k, v, do, lse, delta, bias, *, chunk, scale, causal,
                   has_bias, block_q, block_k, interpret, bd=None):
    """dk, dv from (b, h, t, d) operands and (b*h, 1, t_q) row statistics;
    Q/dO whole a grid step (``chunk`` None) or ``chunk`` rows. Under ``bd``,
    and with grouped query heads in the resident form, the grid is
    (key/value head, query head of its group, key tile): a query head's Q,
    dO and row statistics keep one block for all the key tiles, so they are
    fetched once a (key/value head, query head); dk and dv keep the block of
    the key tile the last query head starts with until that head, so that
    each tile is written back once, when it is whole. Chunked, the group's
    query heads follow one another along the chunk axis."""
    b, h, t_q, d = q.shape
    h_kv, t_k = k.shape[1], k.shape[2]
    d_v = v.shape[-1]
    group = h // h_kv
    by_query_head, head_chunks = bool(bd) or (group > 1 and not chunk), None
    if by_query_head:
        n_k = t_k // block_k
        grid, n_chunks, chunk_q, acc_rows = (b * h_kv, group, n_k), group, t_q, t_k

        def own(bh, g, kj):
            return (bh, _key_order(kj, n_k, bd), 0)

        def rows(bh, g, kj):
            return (bh, g, 0)

        def lanes(bh, g, kj):
            return (bh, 0, g)

        def out(bh, g, kj):
            return (bh, _key_order(jnp.where(g == group - 1, kj, 0), n_k, bd), 0)
    else:
        chunk_q = chunk or t_q
        n_chunks = group * t_q // chunk if chunk else None
        head_chunks = t_q // chunk if chunk and group > 1 else None
        grid, acc_rows = (b * h_kv, t_k // block_k) + ((n_chunks,) if chunk else ()), block_k

        def own(bh, ki, *ci):
            return (bh, ki, 0)

        def streamed(bh, ki, *ci):
            if not ci:
                return 0
            # mirror of the dq-pass DMA skip: query chunks strictly above the
            # diagonal for this key block re-map to the first needed chunk
            first = (ki * block_k) // chunk_q
            if head_chunks:  # within the query head whose chunks these are
                c = ci[0] % head_chunks
                return ci[0] - c + (jnp.maximum(c, first) if causal else c)
            return jnp.maximum(ci[0], first) if causal else ci[0]

        def rows(bh, *at):
            return (bh, streamed(bh, *at), 0)

        def lanes(bh, *at):
            return (bh, 0, streamed(bh, *at))

        out = own

    in_specs = [
        pl.BlockSpec((1, chunk_q, d), rows),
        pl.BlockSpec((1, block_k, d), own),
        pl.BlockSpec((1, block_k, d_v), own),
        pl.BlockSpec((1, chunk_q, d_v), rows),
        pl.BlockSpec((1, 1, chunk_q), lanes),
        pl.BlockSpec((1, 1, chunk_q), lanes),
    ]
    args = [_heads_flat(q, h_kv), _heads_flat(k), _heads_flat(v), _heads_flat(do, h_kv), lse, delta]
    if has_bias:
        in_specs.append(pl.BlockSpec(
            (1, block_k, 1), lambda bh, ki, *ci: (bh // h, ki, 0)))
        args.append(bias)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, block_q=block_q,
                          has_bias=has_bias, causal=causal, n_chunks=n_chunks, bd=bd,
                          by_query_head=by_query_head, head_chunks=head_chunks),
        name=_name("bwd_dkv", bd, chunk),
        out_shape=[
            jax.ShapeDtypeStruct((b * h_kv, t_k, d), k.dtype),
            jax.ShapeDtypeStruct((b * h_kv, t_k, d_v), v.dtype),
        ],
        grid=grid,
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((1, block_k, d), out),
                   pl.BlockSpec((1, block_k, d_v), out)],
        scratch_shapes=[pltpu.VMEM((acc_rows, d), jnp.float32),
                        pltpu.VMEM((acc_rows, d_v), jnp.float32)],
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(*args)
    return dk.reshape(b, h_kv, t_k, d), dv.reshape(b, h_kv, t_k, d_v)


def _blocks(q, k, bd=None) -> dict:
    """Under ``bd`` a tile lies in one half: the blocks divide ``t``."""
    return dict(block_q=_pick_block(bd[0] if bd else q.shape[2], BLOCK_Q),
                block_k=_pick_block(bd[0] if bd else k.shape[2], BLOCK_K), interpret=_interpret())


def _flash_bwd(q, k, v, bias, out, lse, lse_lanes, g, scale, causal, has_bias, bd=None):
    b, h, t_q, d = q.shape
    t_k = k.shape[2]
    kw = dict(scale=scale, causal=causal, has_bias=has_bias, bd=bd, **_blocks(q, k, bd))
    rule = _by_query_head_bytes if k.shape[1] != h else _resident_bwd_bytes
    chunked = (max(t_q, t_k) > BWD_CHUNK_THRESHOLD
               or rule(max(t_q, t_k), d, v.shape[-1], q.dtype.itemsize) > RESIDENT_BWD_VMEM)
    # D = rowsum(dO * O): cheap elementwise-reduce, fused by XLA. The dq pass
    # takes it and lse a row a sublane, lane-broadcast over a narrow trailing
    # axis as the forward stores lse (Mosaic block layout requirement); the
    # dk/dv pass along lanes.
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).reshape(lse.shape[:2])
    if bd:  # K and V whole in the dq pass, a query head's Q and dO in the dk/dv pass (``flash_attention_compatible``)
        chunk_k = chunk_q = None
    else:
        chunk_k = _pick_chunk(t_k, kw["block_k"]) if chunked else None
        chunk_q = _pick_chunk(t_q, kw["block_q"]) if chunked else None
    dq = _flash_bwd_dq(
        q, k, v, g, lse, jnp.broadcast_to(delta[:, :, None], lse.shape), bias,
        chunk=chunk_k, **kw)
    dk, dv = _flash_bwd_dkv(
        q, k, v, g, lse_lanes, delta[:, None, :], bias,
        chunk=chunk_q, **kw)
    return dq, dk, dv


# ------------------------------------------------------------- public VJP


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash(q, k, v, bias, scale, causal, has_bias, bd=None):
    return _flash_fwd(q, k, v, bias, scale=scale, causal=causal,
                      has_bias=has_bias, save_residuals=False, bd=bd,
                      **_blocks(q, k, bd))[0]


def _flash_vjp_fwd(q, k, v, bias, scale, causal, has_bias, bd):
    out, lse, lse_lanes = _flash_fwd(q, k, v, bias, scale=scale, causal=causal,
                                     has_bias=has_bias, save_residuals=True, bd=bd,
                                     **_blocks(q, k, bd))
    return out, (q, k, v, bias, out, lse, lse_lanes)


def _flash_vjp_bwd(scale, causal, has_bias, bd, res, g):
    q, k, v, bias, out, lse, lse_lanes = res
    dq, dk, dv = _flash_bwd(q, k, v, bias, out, lse, lse_lanes, g, scale,
                            causal, has_bias, bd)
    return dq, dk, dv, jnp.zeros_like(bias)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q, k, v, mask=None, causal: bool = False, block_diffusion=None):
    """(batch, heads, time, d) flash attention. ``mask`` may be a key-padding
    mask of shape (batch, t_k) or (batch, 1, 1, t_k) — 1/True = attend (check
    :func:`flash_attention_compatible` first). ``causal=True`` applies the
    autoregressive triangle with diagonal block skipping;
    ``block_diffusion=(t, block)`` the block-diffusion mask over ``[noisy ;
    clean]`` of ``2 t`` positions. Under either, without a key-padding mask,
    ``k`` and ``v`` may have fewer heads than ``q`` (query head ``i`` reads
    key/value head ``i // group``)."""
    b, t_k = q.shape[0], k.shape[2]
    if block_diffusion is not None:
        if mask is not None or causal:
            raise ValueError("block_diffusion is a mask family of its own: no key-padding mask, not causal")
        return _flash(q, k, v, jnp.zeros((b, t_k, 1), jnp.float32), 1.0 / float(q.shape[-1]) ** 0.5, False, False,
                      tuple(int(n) for n in block_diffusion))
    kmask = _padding_mask_2d(mask, b, t_k)
    if mask is not None and kmask is None:
        raise ValueError("flash_attention supports key-padding masks only; "
                         "use the XLA fallback for other mask shapes")
    if k.shape[1] != q.shape[1] and (mask is not None or not causal):
        raise ValueError("grouped query heads: causal without a key-padding mask, or block_diffusion")
    scale = 1.0 / float(q.shape[-1]) ** 0.5
    has_bias = kmask is not None
    if has_bias:
        bias = jnp.where(kmask.astype(bool), 0.0, MASK_VALUE)
        bias = bias.astype(jnp.float32)[:, :, None]  # (b, t_k, 1)
    else:
        bias = jnp.zeros((b, t_k, 1), jnp.float32)  # unused dummy
    return _flash(q, k, v, bias, scale, bool(causal), has_bias)
