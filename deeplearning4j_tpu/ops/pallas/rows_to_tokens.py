"""Sum a mixture-of-experts buffer's rows back into their tokens: one
Pallas kernel where XLA would scatter-add.

``MixtureOfExperts`` sorts its (token, expert) assignments by expert into a
buffer of R rows. Two maps join the (N, D) tokens and the (R, D) rows, and
each is the other's transpose:

    tokens_to_rows:  rows[r]   = tokens[ids[r]]           (a gather)
    rows_to_tokens:  tokens[n] = sum of rows[r] with ids[r] == n

``combine`` is the second in the forward pass; the gather of ``_dispatch``
has the second as its gradient. XLA lowers that sum to a scatter-add with
repeated indices: a sort of the R indices, then the sorted scatter.

The kernel reads the rows in the order the buffer already has them. The
stable sort by expert leaves each expert's rows (a *run*) ascending by
token, so the rows of one run that belong to one tile of tokens are
contiguous: a *segment*. A grid step is a tile. It lists the windows of
``WINDOW`` rows, at aligned starts, that its segments touch (scalar work
from the segments' bounds, which come in by scalar prefetch), fetches them
by DMA ``BATCH`` windows at a time, the next batch in flight under this
one, and adds a batch into a float32 accumulator as one matmul on the MXU:
a (tile, BATCH * WINDOW) one-hot of each staged row's token within the
tile times the rows. The sum is the same sum, accumulated in float32 and
rounded once. Rows whose id is N (the buffer's unfilled rows) lie in no
segment and are never read, so the cost follows the rows filled, not R.

Where the segments begin is worked out once from the ids and the runs'
ends (``plan``: one compare-and-sum in XLA) and shared by both maps. A
window's rows outside its segment are selected away before the matmul:
they may hold anything (``ragged_dot`` leaves the rows past its last group
as they are), and 0 x NaN is NaN on the MXU.

The route (``kernel_route``): compiled on a TPU or interpreted in the CPU
test environment, D a multiple of 128, N a whole number of tiles, R of
lane tiles, and every operand on one device. A ``pallas_call`` is not
partitioned by the compiler (PERF.md section 7 row 1), so a call traced
over several devices keeps XLA's scatter.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops.pallas.common import (COMPILER_PARAMS,
                                                  kernels_available)
from deeplearning4j_tpu.ops.pallas.common import interpret_mode as _interpret

TILE = 512  # tokens a grid step, when N divides by it (else the largest of 256 .. 16 that does)
WINDOW = 16  # rows a DMA: one sublane tile of packed bf16, so every start is aligned
LANES = 128
BATCH = LANES // WINDOW  # windows a matmul: a contraction of 128 rows, the MXU's width, one lane tile of ids
_OFF_BITS = 5  # a window's code: its index, then its segment's first and end row in it (0..WINDOW)


def _tile(n_tokens: int) -> int:
    for t in (TILE, 256, 128, 64, 32, 16):
        if n_tokens % t == 0:
            return t
    return 0


def _one_device(*xs) -> bool:
    """Whether every operand, traced or not, lies on one device."""
    if jax.sharding.get_abstract_mesh().size > 1:
        return False
    for x in xs:
        mesh = getattr(getattr(jax.typeof(x), "sharding", None), "mesh", None)
        if mesh is not None and mesh.size > 1:
            return False
    return True


def kernel_route(n_tokens: int, n_rows: int, widths, *operands) -> bool:
    """Whether the kernel sums rows into tokens for a layer of these
    sizes; otherwise the caller keeps XLA's scatter-add."""
    return (kernels_available() and all(d % LANES == 0 for d in widths)
            and _tile(n_tokens) > 0 and n_rows % LANES == 0
            and _one_device(*operands))


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=("ids", "bounds"), meta_fields=("n_tokens",))
@dataclasses.dataclass(frozen=True)
class RowsPlan:
    """``ids`` (R,) int32: each row's token, N for a row that holds none;
    ``bounds`` (G * (N / tile + 1),) int32: for each run, the row where its
    segment of each tile begins, then where its last one ends."""

    ids: jax.Array
    bounds: jax.Array
    n_tokens: int


def plan(ids, run_ends, n_tokens: int) -> RowsPlan:
    """The segments of the buffer. ``ids`` (R,): each row's token, or
    ``n_tokens`` for a row that holds none; the rows form runs ending at
    ``run_ends`` (G,), ascending, and within a run the ids ascend (its
    unfilled rows last). Rows past the last run are read by no tile."""
    ids = ids.astype(jnp.int32)
    n_rows = ids.shape[0]
    ends = run_ends.astype(jnp.int32)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends[:-1]])
    n_edges = n_tokens // _tile(n_tokens) + 1
    run = jnp.arange(ends.size * n_edges, dtype=jnp.int32) // n_edges  # per bound: its run, its tile's first token
    edge = jnp.arange(ends.size * n_edges, dtype=jnp.int32) % n_edges * _tile(n_tokens)
    row = jnp.arange(n_rows, dtype=jnp.int32)
    # a run's rows below each tile's first token, counted over the buffer in one pass
    below = (row >= starts[run][:, None]) & (row < ends[run][:, None]) & (ids < edge[:, None])
    bounds = starts[run] + jnp.sum(below, axis=1, dtype=jnp.int32)
    return RowsPlan(ids=ids, bounds=bounds, n_tokens=n_tokens)


def _kernel(bounds_ref, ids_ref, rows_hbm, out_ref, stage, acc, windows, sem, *, tile, n_runs):
    t = pl.program_id(0)
    per_run = pl.num_programs(0) + 1
    mask = (1 << _OFF_BITS) - 1

    def segment(g, n):  # list the windows of run g's segment of this tile
        lo, hi = bounds_ref[g * per_run + t], bounds_ref[g * per_run + t + 1]

        def put(w, n):
            start = w * WINDOW
            windows[n] = ((w << (2 * _OFF_BITS)) | (jnp.clip(lo - start, 0, WINDOW) << _OFF_BITS)
                          | jnp.clip(hi - start, 0, WINDOW))
            return n + 1

        return jax.lax.fori_loop(lo // WINDOW, jnp.where(hi > lo, (hi + WINDOW - 1) // WINDOW, 0), put, n)

    n_windows = jax.lax.fori_loop(0, n_runs, segment, 0)
    for p in range(BATCH):  # the last batch's spare windows hold no rows
        windows[n_windows + p] = 0
    n_batches = (n_windows + BATCH - 1) // BATCH
    acc[...] = jnp.zeros_like(acc)

    def fields(b, p):
        code = windows[b * BATCH + p]
        return (code >> (2 * _OFF_BITS)) * WINDOW, (code >> _OFF_BITS) & mask, code & mask

    def each_copy(b, slot, act):  # the batch's windows that hold rows of the segment
        def one(p, carry):
            start, lo, hi = fields(b, p)

            @pl.when(hi > lo)
            def _():
                act(pltpu.make_async_copy(rows_hbm.at[pl.ds(pl.multiple_of(start, WINDOW), WINDOW)],
                                          stage.at[slot, pl.ds(pl.multiple_of(p * WINDOW, WINDOW), WINDOW)],
                                          sem.at[slot]))
            return carry

        jax.lax.fori_loop(0, BATCH, one, 0)

    @pl.when(n_batches > 0)
    def _():
        each_copy(0, 0, lambda c: c.start())

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def body(b, carry):
        slot = b % 2
        each_copy(b, slot, lambda c: c.wait())

        @pl.when(b + 1 < n_batches)
        def _():
            each_copy(b + 1, 1 - slot, lambda c: c.start())

        def place(p, local):  # window p's tokens within the tile on its lanes, -1 outside its segment
            start, lo, hi = fields(b, p)
            line = pltpu.roll(ids_ref[pl.ds(start // LANES, 1), :], (p * WINDOW - start % LANES) % LANES, 1)
            i = lane - p * WINDOW
            return jnp.where((i >= lo) & (i < hi), line - t * tile, local)

        local = jax.lax.fori_loop(0, BATCH, place, jnp.full((1, LANES), -1, jnp.int32))
        live = jnp.transpose(jnp.broadcast_to(local, (8, LANES)))[:, :1] >= 0
        rows = jnp.where(live, stage[slot], jnp.zeros((), stage.dtype))
        onehot = (jax.lax.broadcasted_iota(jnp.int32, (tile, LANES), 0) == local).astype(rows.dtype)
        acc[...] += jnp.dot(onehot, rows, preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.HIGHEST if rows.dtype == jnp.float32 else None)
        return carry

    jax.lax.fori_loop(0, n_batches, body, 0)
    out_ref[...] = acc[...].astype(out_ref.dtype)


@jax.jit
def _sum_rows(rows, p: RowsPlan):
    """Entered through one ``jax.jit``: the layers of a model, which call at
    one shape, share one traced and lowered kernel in the step's module."""
    n_rows, d = rows.shape
    tile = _tile(p.n_tokens)
    n_tiles = p.n_tokens // tile
    n_runs = p.bounds.shape[0] // (n_tiles + 1)
    most = -(-n_rows // WINDOW) + 2 * n_runs + BATCH  # windows of a tile, and a batch's spares
    return pl.pallas_call(
        functools.partial(_kernel, tile=tile, n_runs=n_runs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_tiles,),
            in_specs=[pl.BlockSpec((n_rows // LANES, LANES), lambda t, *_: (0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tile, d), lambda t, *_: (t, 0)),
            scratch_shapes=[pltpu.VMEM((2, LANES, d), rows.dtype),
                            pltpu.VMEM((tile, d), jnp.float32),
                            pltpu.SMEM((most,), jnp.int32),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=jax.ShapeDtypeStruct((p.n_tokens, d), rows.dtype),
        compiler_params=COMPILER_PARAMS,
        name="rows_to_tokens",
        interpret=_interpret(),
    )(p.bounds, p.ids.reshape(n_rows // LANES, LANES), rows)


def _gather(tokens, p: RowsPlan):
    return tokens.at[p.ids].get(mode="fill", fill_value=0)


@jax.custom_vjp
def rows_to_tokens(rows, p: RowsPlan):
    """(N, D): the sum of the rows of each token, by the kernel. Its
    gradient is ``tokens_to_rows``'s gather."""
    return _sum_rows(rows, p)


def _rows_to_tokens_fwd(rows, p):
    return _sum_rows(rows, p), p


def _rows_to_tokens_bwd(p, g):
    return _gather(g, p), None


rows_to_tokens.defvjp(_rows_to_tokens_fwd, _rows_to_tokens_bwd)


@jax.custom_vjp
def tokens_to_rows(tokens, p: RowsPlan):
    """(R, D): each row's token, zero for a row that holds none. Its
    gradient is ``rows_to_tokens``'s kernel."""
    return _gather(tokens, p)


def _tokens_to_rows_fwd(tokens, p):
    return _gather(tokens, p), p


def _tokens_to_rows_bwd(p, g):
    return _sum_rows(g, p), None


tokens_to_rows.defvjp(_tokens_to_rows_fwd, _tokens_to_rows_bwd)
