"""The chunked gated delta rule (``nn/linear_attention_layers.py``
``chunk_kda``) as a Pallas kernel pair that carries the (d_k, d_v) state in
VMEM from chunk to chunk: ``chunk_kda_fwd`` and ``chunk_kda_bwd``.

Same equations, constants and precision as the XLA form (that module's
docstring): matmul operands in q's dtype accumulating in float32; the state,
the cumulative log-decay, every decay factor and the unit lower-triangular
solve in float32; no exponent ever positive (``A`` and ``B`` from sub-blocks
of ``SUB`` rows: off-diagonal ones through the row block's first token,
diagonal ones from the differences themselves).

**The chunk loop is the grid, never Python.** Grid (batch, blocks of chunks,
heads). A grid step takes the ``BLOCK_CHUNKS`` chunks of one block and head
*together*: everything that does not need the state is computed for all of
them as one batch (that batch is what lets the compiler overlap the chain of
float32 matmuls: one chunk at a time took 13.0 + 19.0 ms a layer forward +
backward at 8192 x 32 x 128, four together 8.3 + 11.9, my chip runs, PR 32),
then the state goes through them in turn, two products a chunk. The traced
body is one block's work whatever T is. The state of every head, float32 and
kept transposed (d_v, d_k) so that a chunk's decay scales its lanes, lives
in one VMEM scratch across the grid's block axis (zeroed at the first
block), so blocks and heads are sequential axes and heads the inner one:
``beta`` (b, t, h) then comes in, and its gradient goes out, as one
(rows, h) block a block of chunks, each head taking and writing its own
lane.

**Operand layout.** q, k, v, g, o and their gradients live in HBM as
(b, h * d, t): features on sublanes, time on lanes, a head an aligned slice
of 128 sublanes, a block of chunks (1, 128, rows) with 256 tokens on two
lane tiles. A ``pallas_call`` pins row-major operands, and XLA on the TPU
keeps this model's (1, t, h * d) activations head by head and, beside its
matmuls (the weight gradients contract over t), time-minor: a pair on
(b, t, h * d) blocks was paid for in 36 re-layout copies of 67 and 134 MB a
step at 8192 x 32 x 128 and more around them, 5.2 GB moved again (PERF.md
section 6, PR 34; PR 27 found the same around ``fused_attention``).
``chunk_kda`` asks for the transposes, and the step compiled for a v5e
holds no copy for them: the fusions on either side write and read this
layout themselves. Inside, a block is widened to float32 and transposed in
VMEM as it is loaded, and a result transposed back before it is rounded and
stored (0.4 + 0.6 ms a layer, forward + backward, of 7.4 + 11.6; 16-bit
transposes return 0.14 of it: not kept): the body works on (rows, 128) as
it always did; g stays float32.

**The solve** ``(I + A) [W | U0] = beta [k exp(G) | v]`` is float32 on the
MXU (``Precision.HIGHEST``): the inverse of the 16-row diagonal blocks as
the finite Neumann product ``(I + N)(I + N^2)(I + N^4)(I + N^8)``, ``N = -A``
within a block (``N^16 = 0``), then two exact block merges
``T <- T - T A_off T`` (blocks of 32, of 64), then ``T @ rhs``. The backward
needs only ``T``: ``d rhs = T^T d[W | U0]`` and ``dA = -d rhs [W | U0]^T``.

**Backward.** ``jax.custom_vjp``. The forward saves its inputs, the state at
every block's start (float32, d_v x d_k) and every chunk's ``T`` (float32,
two chunks' side by side to a lane tile): 67 + 67 MB a layer at 8192 x 32 x
128. The backward kernel walks the blocks in reverse and carries dS the same
way; in a block it recomputes the local quantities but for the solve,
follows the state from the block's first, and emits dq, dk, dv (q's dtype),
dg and dbeta (float32). ``E_ij = exp(G_i - G_j)`` is differentiated as such:
the row block's first token, through which the off-diagonal blocks are
computed, cancels in the derivative.

``chunk_kda_pallas`` enters both kernels through one ``jax.jit`` each, so that
the layers of a model, which call at one shape, share one traced kernel and
one lowered function in the step's module.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops.pallas.common import (VMEM_BUDGET,
                                                  VMEM_LIMIT_BYTES,
                                                  kernels_available)
from deeplearning4j_tpu.ops.pallas.common import interpret_mode as _interpret
from deeplearning4j_tpu.ops.pallas.fused_attention import _NN, _NT, _TN

CHUNK = 64          # tokens a step of the carried state (the published kernel's)
SUB = 16            # rows of a sub-block inside a chunk
BLOCK_CHUNKS = 4    # chunks a grid step takes together, when the sequence has that many

_B_NT = (((2,), (2,)), ((0,), (0,)))  # sik,sjk->sij
_B_NN = (((2,), (1,)), ((0,), (0,)))  # sij,sjk->sik
_B_TN = (((1,), (1,)), ((0,), (0,)))  # sij,sik->sjk
_F32 = jnp.float32


def _mm(a, b, dims=_NN):
    """Operands in the matmuls' dtype, accumulated in float32."""
    return jax.lax.dot_general(a, b, dims, preferred_element_type=_F32)


def _mm32(a, b, dims=_NN):
    """Float32 through and through (the cumulative sums and the solve)."""
    return jax.lax.dot_general(a, b, dims, precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=_F32)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _running_sum(x, reverse=False):
    """Within every chunk of ``x`` (rows of whole chunks, K), float32: the
    sum over the rows up to each row, or from it on (``reverse``), in six
    doubling steps of a sublane roll and an add."""
    n = x.shape[0]
    at = _iota(x.shape, 0) % CHUNK
    step = 1
    while step < CHUNK:
        if reverse:
            x = x + jnp.where(at < CHUNK - step, pltpu.roll(x, n - step, 0), 0.0)
        else:
            x = x + jnp.where(at >= step, pltpu.roll(x, step, 0), 0.0)
        step *= 2
    return x


def _sub(a):
    """(chunks, C, x) -> (chunks * sub-blocks, SUB, x)."""
    return a.reshape(-1, SUB, a.shape[-1])


def _local(qf, kf, vf, g, beta, mm, T=None):
    """What chunks compute without the state, for the ``nb`` chunks of a
    grid step at once (the batch is what lets the compiler overlap the
    chain of float32 matmuls). ``qf``, ``kf``, ``g``: (nb, C, K) and ``vf``:
    (nb, C, V), ``beta``: (nb, C, 1), all float32 (q, k, v widened from
    ``mm``, the matmuls' dtype, without loss). Returns a dict: ``W`` ``qd``
    ``kout`` ``B`` (matmul dtype), ``U0`` (float32), ``eGend`` (nb, 1, K),
    ``T`` = (I + A)^-1 (nb, C, C) float32; given ``T`` (the backward pass:
    the forward saved it) the solve is not repeated and what the gradients
    re-use is returned too."""
    nb, c, kdim = qf.shape
    n_sub = c // SUB
    m = nb * n_sub
    G = _running_sum(g.reshape(nb * c, kdim)).reshape(nb, c, kdim)       # from each chunk's start
    G4, q4, k4 = _sub(G), _sub(qf), _sub(kf)
    # G just before each sub-block's first row (0 for a chunk's first)
    before = jnp.concatenate([jnp.zeros((1, 1, kdim), _F32), G4[:-1, SUB - 1:SUB, :]], 0)
    start = jnp.where(_iota(before.shape, 0) % n_sub == 0, 0.0, before)
    row_decay = jnp.exp(G4 - start)                                      # rows, from their block's start
    every = lambda a: jnp.broadcast_to(a[:, None], (nb, n_sub, c, kdim)).reshape(m, c, kdim)
    col_decay = jnp.exp(jnp.minimum(start - every(G), 0.0))              # (m, C, K)
    k_cols = (every(kf) * col_decay).astype(mm)
    # within a sub-block: the differences themselves, masked before the exponential
    low = _iota((m, SUB, SUB, kdim), 1) >= _iota((m, SUB, SUB, kdim), 2)
    E = jnp.where(low, jnp.exp(jnp.where(low, G4[:, :, None, :] - G4[:, None, :, :], 0.0)), 0.0)
    kE = k4[:, None, :, :] * E                                           # (m, SUB i, SUB j, K)
    row, col = _iota((nb, c, c), 1), _iota((nb, c, c), 2)
    row_block, col_block = row // SUB, col // SUB
    earlier, in_block = col_block < row_block, col_block == row_block
    lhs = (jnp.concatenate([k4, q4], 1) * jnp.concatenate([row_decay, row_decay], 1)).astype(mm)
    off = _mm(lhs, k_cols, _B_NT)                                        # k's rows and q's against one weight

    def pairs(off, a4):
        diag = jnp.tile(jnp.sum(a4[:, :, None, :] * kE, -1).reshape(nb * c, SUB), (1, n_sub)).reshape(nb, c, c)
        return jnp.where(earlier, off.reshape(nb, c, c), 0.0) + jnp.where(in_block, diag, 0.0)

    A_raw = jnp.where(row > col, pairs(off[:, :SUB], k4), 0.0)
    B = jnp.where(row >= col, pairs(off[:, SUB:], q4), 0.0)
    A = A_raw * beta
    with_grad_parts = T is not None
    if T is None:
        # (I + A)^-1: the diagonal blocks' inverse as a finite Neumann product, two exact merges
        eye = (row == col).astype(_F32)
        N = jnp.where(in_block, -A, 0.0)
        N2 = _mm32(N, N, _B_NN)
        N4 = _mm32(N2, N2, _B_NN)
        T = _mm32(_mm32(eye + N, eye + N2, _B_NN), _mm32(eye + N4, eye + _mm32(N4, N4, _B_NN), _B_NN), _B_NN)
        same_pair = row // (2 * SUB) == col // (2 * SUB)
        for a_off in (jnp.where(same_pair & ~in_block, A, 0.0), jnp.where(~same_pair, A, 0.0)):
            T = T - _mm32(T, _mm32(a_off, T, _B_NN), _B_NN)
    eG = jnp.exp(G)
    kd = kf * eG
    X = _mm32(T, jnp.concatenate([kd, vf], 2) * beta, _B_NN)             # [W | U0]
    Gend = G[:, c - 1:c, :]
    eOut = jnp.exp(Gend - G)
    out = {"W": X[:, :, :kdim].astype(mm), "U0": X[:, :, kdim:], "B": B.astype(mm), "qd": (qf * eG).astype(mm),
           "kout": (kf * eOut).astype(mm), "eGend": jnp.exp(Gend), "T": T}
    if with_grad_parts:
        out.update(qf=qf, kf=kf, vf=vf, q4=q4, k4=k4, earlier=earlier, row_decay=row_decay, col_decay=col_decay,
                   k_cols=k_cols, lhs=lhs, E=E, kE=kE, A_raw=A_raw, X=X, eG=eG, kd=kd,
                   eOut=eOut)
    return out


def _chunks(ref, nb):
    """A (1, x, nb * C) block, features on sublanes and time on lanes as HBM
    holds it, as float32 (nb, C, x): widened first, so that the transpose in
    VMEM is a 32-bit one whatever the block's dtype."""
    return ref[0].astype(_F32).T.reshape(nb, CHUNK, ref.shape[1])


def _store(ref, a):
    """Float32 (..., x) rows into a (1, x, rows) block: transposed back in
    VMEM, then rounded to the block's dtype."""
    ref[0] = a.reshape(-1, a.shape[-1]).T.astype(ref.dtype)


def _head_lane(ref, nb, head):
    """(nb, C, 1): the lane ``head`` of a (1, rows, h) block."""
    block = ref[0]
    lane = jnp.sum(jnp.where(_iota(block.shape, 1) == head, block, 0.0), -1, keepdims=True)
    return lane.reshape(nb, CHUNK, 1)


def _pack(T):
    """(nb, C, C) -> (pairs, C, 2 C): two chunks' matrices side by side fill
    the lane tile that HBM is written and read in."""
    nb, c, _ = T.shape
    if nb % 2:
        T = jnp.concatenate([T, jnp.zeros((1, c, c), T.dtype)], 0)
    T = T.reshape(-1, 2, c, c)
    return jnp.concatenate([T[:, 0], T[:, 1]], 2)


def _unpack(packed, nb):
    c = packed.shape[1]
    return jnp.stack([packed[:, :, :c], packed[:, :, c:]], 1).reshape(-1, c, c)[:nb]


def _through_chunk(L, j, St):
    """Chunk ``j``'s ``U`` and q S from the transposed state (d_v, d_k) at
    its start, and the state after it: two products."""
    c, mm = L["U0"].shape[1], L["W"].dtype
    both = _mm(jnp.concatenate([L["W"][j], L["qd"][j]], 0), St.astype(mm), _NT)  # W S and q S in one product
    U = (L["U0"][j] - both[:c]).astype(mm)
    return U, both[c:], St * L["eGend"][j] + _mm(U, L["kout"][j], _TN)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, s_ref, t_ref, st_ref):
    block, head = pl.program_id(1), pl.program_id(2)
    nb = q_ref.shape[2] // CHUNK

    @pl.when(block == 0)
    def _():
        st_ref[head] = jnp.zeros(st_ref.shape[1:], _F32)

    L = _local(_chunks(q_ref, nb), _chunks(k_ref, nb), _chunks(v_ref, nb), _chunks(g_ref, nb),
               _head_lane(beta_ref, nb, head), q_ref.dtype)
    t_ref[0, 0] = _pack(L["T"])
    St, U, qS = st_ref[head], [None] * nb, [None] * nb
    s_ref[0, 0, 0] = St
    for j in range(nb):  # the state through the chunks in turn
        U[j], qS[j], St = _through_chunk(L, j, St)
    st_ref[head] = St
    _store(o_ref, jnp.stack(qS) + _mm(L["B"], jnp.stack(U), _B_NN))


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, s_ref, t_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dst_ref):
    block, head = pl.program_id(1), pl.program_id(2)
    nb, c, kdim, mm = q_ref.shape[2] // CHUNK, CHUNK, q_ref.shape[1], q_ref.dtype
    n_sub = c // SUB

    @pl.when(block == 0)  # the sequence's last block: nothing follows it
    def _():
        dst_ref[head] = jnp.zeros(dst_ref.shape[1:], _F32)

    beta = _head_lane(beta_ref, nb, head)
    L = _local(_chunks(q_ref, nb), _chunks(k_ref, nb), _chunks(v_ref, nb), _chunks(g_ref, nb), beta, mm,
               _unpack(t_ref[0, 0], nb))
    St, U = [s_ref[0, 0, 0]] * (nb + 1), [None] * nb  # the block's first state was saved; the others follow
    for j in range(nb):
        U[j], _, St[j + 1] = _through_chunk(L, j, St[j])
    St, U, dO = jnp.stack(St[:nb]), jnp.stack(U), _chunks(do_ref, nb).astype(mm)
    Stb = St.astype(mm)
    # through the state: U = U0 - W S, O = qd S + B U, S' = eGend S + kout^T U; dS from the last chunk back
    dU_of_O = _mm(L["B"], dO, _B_TN)
    qd_W = jnp.concatenate([L["qd"], -L["W"]], 1)
    dStn, dStns, dU = dst_ref[head], [None] * nb, [None] * nb
    for j in reversed(range(nb)):
        dStns[j] = dStn
        dU[j] = dU_of_O[j] + _mm(L["kout"][j], dStn.astype(mm), _NT)
        dStn = dStn * L["eGend"][j] + _mm(jnp.concatenate([dO[j], dU[j].astype(mm)], 0), qd_W[j], _TN)
    dst_ref[head] = dStn
    dStn, dU = jnp.stack(dStns), jnp.stack(dU)
    both = _mm(jnp.concatenate([dO, dU.astype(mm)], 1), Stb, _B_NN)      # d qd and -dW in one product
    dqd, dW = both[:, :c], -both[:, c:]
    dkout = _mm(U, dStn.astype(mm), _B_NN)
    dGend = jnp.sum(St * dStn, 1, keepdims=True) * L["eGend"]
    dB = _mm(dO, U, _B_NT)
    # the solve: d rhs = T^T d[W | U0], dA = -d rhs [W | U0]^T
    dR = _mm32(L["T"], jnp.concatenate([dW, dU], 2), _B_TN)
    dRW, dRU = dR[:, :, :kdim], dR[:, :, kdim:]
    dA = -_mm32(dR, L["X"], _B_NT)
    dA_in = -_mm32(_sub(dR), _sub(L["X"]), _B_NT)                         # the diagonal blocks again, block by block
    dB_in = _mm(_sub(dO), _sub(U), _B_NT)
    dbeta = (jnp.sum(dRW * L["kd"], -1, keepdims=True) + jnp.sum(dRU * L["vf"], -1, keepdims=True)
             + jnp.sum(dA * L["A_raw"], -1, keepdims=True))
    dkd = dRW * beta
    # A and B: off-diagonal blocks through the row block's first token
    PQ = jnp.concatenate([jnp.where(L["earlier"], dA * beta, 0.0).reshape(nb * n_sub, SUB, c),
                          jnp.where(L["earlier"], dB, 0.0).reshape(nb * n_sub, SUB, c)], 1).astype(mm)
    r = _mm(PQ, L["k_cols"], _B_NN)
    rk, rq = L["row_decay"] * r[:, :SUB], L["row_decay"] * r[:, SUB:]
    ck = jnp.sum((L["col_decay"] * _mm(PQ, L["lhs"], _B_TN)).reshape(nb, n_sub, c, kdim), 1)
    # ... diagonal ones from the differences themselves
    i, j = _iota(dA_in.shape, 1), _iota(dA_in.shape, 2)
    Pd = jnp.where(i > j, dA_in * _sub(beta), 0.0)[..., None]
    Qd = jnp.where(i >= j, dB_in, 0.0)[..., None]
    rk = (rk + jnp.sum(Pd * L["kE"], 2)).reshape(nb, c, kdim)
    rq = (rq + jnp.sum(Qd * L["kE"], 2)).reshape(nb, c, kdim)
    ck = ck + jnp.sum((Pd * L["k4"][:, :, None, :] + Qd * L["q4"][:, :, None, :]) * L["E"], 1).reshape(nb, c, kdim)
    kf, qf, eG = L["kf"], L["qf"], L["eG"]
    leaving = dkout * kf * L["eOut"]                                     # d(G_end - G) of kout
    dG = dqd * qf * eG + dkd * L["kd"] - leaving + kf * (rk - ck) + qf * rq
    dG = dG + jnp.where(_iota(dG.shape, 1) == c - 1, jnp.sum(leaving, 1, keepdims=True) + dGend, 0.0)
    _store(dq_ref, dqd * eG + rq)
    _store(dk_ref, dkout * L["eOut"] + dkd * eG + rk + ck)
    _store(dv_ref, dRU * beta)
    _store(dg_ref, _running_sum(dG.reshape(nb * c, kdim), reverse=True))
    lanes = dbeta_ref[0]
    dbeta_ref[0] = jnp.where(_iota(lanes.shape, 1) == head, dbeta.reshape(nb * c, 1), lanes)


def _block_chunks(n: int) -> int:
    return next(m for m in (BLOCK_CHUNKS, 2, 1) if n % m == 0)


def _vmem_bytes(rows: int, d_k: int, d_v: int, heads: int, itemsize: int) -> int:
    """The backward kernel's blocks counted once, as ``VMEM_BUDGET`` wants
    them (q, k, dq, dk and v, dO, dv in the matmuls' dtype, g and dg, each
    (d, rows) with the rows on whole lane tiles, so no byte of padding;
    beta and dbeta padded to a lane tile, the block's first state and its
    chunks' ``T``), the carried dS of every head, and the float32
    temporaries of the chunks taken together that are live at once (four
    (SUB, SUB, d_k) arrays a sub-block, three (CHUNK, d_k) a sub-block, the
    transposed blocks among them; Mosaic compiles twice the block under
    ``VMEM_LIMIT_BYTES``)."""
    chunks = rows // CHUNK
    blocks = (4 * d_k + 3 * d_v) * rows * itemsize + 2 * rows * d_k * 4 + 2 * rows * max(heads, 128) * 4
    saved = d_k * d_v * 4 + (chunks + 1) // 2 * CHUNK * 2 * CHUNK * 4
    carried = heads * d_k * d_v * 4
    together = chunks * (CHUNK // SUB) * (4 * SUB * SUB + 3 * CHUNK) * d_k * 4
    return blocks + saved + carried + together


def chunk_kda_compatible(q, v, chunk: int = CHUNK) -> bool:
    """Whether the kernel pair takes this call: q (and k) of (b, t, h, d_k)
    and v of (b, t, h, d_v) with both head sizes lane tiles, t whole chunks
    of the kernel's size that make up blocks of whole lane tiles (time is on
    lanes: an even number of chunks, or one), a platform with kernels, and a
    block that fits."""
    if chunk != CHUNK or q.ndim != 4 or q.dtype not in (jnp.float32, jnp.bfloat16):
        return False
    _, t, h, d_k = q.shape
    d_v = v.shape[-1]
    if d_k % 128 or d_v % 128 or t % CHUNK or not kernels_available():
        return False
    rows = _block_chunks(t // CHUNK) * CHUNK
    if rows % 128 and rows != t:
        return False
    return _vmem_bytes(rows, d_k, d_v, h, q.dtype.itemsize) <= VMEM_BUDGET


def _specs(b, t, heads, d_k, d_v, reverse):
    """Grid and block specs shared by the two kernels: blocks of chunks in
    order (forward) or from the last (backward), heads innermost."""
    n = _block_chunks(t // CHUNK)
    n_blocks = t // (n * CHUNK)
    at = (lambda j: n_blocks - 1 - j) if reverse else (lambda j: j)
    of_head = lambda d: pl.BlockSpec((1, d, n * CHUNK), lambda i, j, hd: (i, hd, at(j)))
    per_block = pl.BlockSpec((1, n * CHUNK, heads), lambda i, j, hd: (i, at(j), 0))
    state = pl.BlockSpec((1, 1, 1, d_v, d_k), lambda i, j, hd: (i, hd, at(j), 0, 0))
    solved = pl.BlockSpec((1, 1, (n + 1) // 2, CHUNK, 2 * CHUNK), lambda i, j, hd: (i, hd, at(j), 0, 0))
    return (b, n_blocks, heads), of_head, per_block, state, solved


_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary", "arbitrary"),
                               vmem_limit_bytes=VMEM_LIMIT_BYTES)


@functools.partial(jax.jit, static_argnames=("heads", "interpret"))
def _forward(q, k, v, g, beta, *, heads, interpret):
    b, hk, t = q.shape
    d_k, d_v = hk // heads, v.shape[1] // heads
    grid, of_head, per_block, state, solved = _specs(b, t, heads, d_k, d_v, False)
    return pl.pallas_call(
        _fwd_kernel, name="chunk_kda_fwd", grid=grid,
        in_specs=[of_head(d_k), of_head(d_k), of_head(d_v), of_head(d_k), per_block],
        out_specs=[of_head(d_v), state, solved],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((b, heads, grid[1], d_v, d_k), _F32),
                   jax.ShapeDtypeStruct((b, heads, grid[1] * solved.block_shape[2], CHUNK, 2 * CHUNK), _F32)],
        scratch_shapes=[pltpu.VMEM((heads, d_v, d_k), _F32)],
        compiler_params=_PARAMS, interpret=interpret)(q, k, v, g, beta)


@functools.partial(jax.jit, static_argnames=("heads", "interpret"))
def _backward(q, k, v, g, beta, states, solved, do, *, heads, interpret):
    b, hk, t = q.shape
    d_k, d_v = hk // heads, v.shape[1] // heads
    grid, of_head, per_block, state, solved_spec = _specs(b, t, heads, d_k, d_v, True)
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
    return pl.pallas_call(
        _bwd_kernel, name="chunk_kda_bwd", grid=grid,
        in_specs=[of_head(d_k), of_head(d_k), of_head(d_v), of_head(d_k), per_block, state, solved_spec,
                  of_head(d_v)],
        out_specs=[of_head(d_k), of_head(d_k), of_head(d_v), of_head(d_k), per_block],
        out_shape=[like(q), like(k), like(v), like(g), like(beta)],
        scratch_shapes=[pltpu.VMEM((heads, d_v, d_k), _F32)],
        compiler_params=_PARAMS, interpret=interpret)(q, k, v, g, beta, states, solved, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def chunk_kda_pallas(q, k, v, g, beta, heads: int):
    """The gated delta rule on time-minor (b, heads * d, t) ``q``, ``k``,
    ``v``, float32 ``g`` in the same layout and float32 ``beta``
    (b, t, heads), for calls that :func:`chunk_kda_compatible` accepts.
    Returns (b, heads * d_v, t); the gradients come in and go out likewise."""
    return _forward(q, k, v, g, beta, heads=heads, interpret=_interpret())[0]


def _vjp_fwd(q, k, v, g, beta, heads):
    o, states, solved = _forward(q, k, v, g, beta, heads=heads, interpret=_interpret())
    return o, (q, k, v, g, beta, states, solved)


def _vjp_bwd(heads, res, do):
    return tuple(_backward(*res, do, heads=heads, interpret=_interpret()))


chunk_kda_pallas.defvjp(_vjp_fwd, _vjp_bwd)
