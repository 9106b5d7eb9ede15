"""Small-leaf train-state packing: flat-buffer storage for the step boundary.

TPU-native analog of the reference's flat-parameter design (upstream
``MultiLayerNetwork.init()`` flattens every layer's parameters into ONE
``INDArray`` and hands layers views — ``org.deeplearning4j.nn.multilayer.
MultiLayerNetwork``, ``ParamInitializer``; SURVEY.md §3.1). There the flat
buffer made updater application and parameter averaging cheap; here it cuts
the *dispatch* cost of a jitted train step.

What it does: a ResNet-50 ``TrainState`` is 429 leaves, of which 371 are
tiny per-channel vectors (BN gamma/beta/mean/var + their momenta — 13 MB
total). Every step dispatch marshals one buffer handle per leaf, and
on-device XLA stages each tiny buffer into scratch memory with its own
async copy pair. Packing every sub-threshold leaf into one flat buffer per
dtype cuts both counts (~4x fewer handles); values are bit-identical
(pack/unpack is pure reshape/slice plumbing inside the same jitted
program). Its benefit on this machine is not measured (ROADMAP queue 1
item 6).

Sharded training keeps per-leaf state (packing would force one common
sharding across leaves); this is the single-device/replicated fast path.
"""

from __future__ import annotations

import contextlib
import dataclasses
import warnings
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
from jax import lax


@contextlib.contextmanager
def _quiet_donation():
    """The one-off pack/unpack donations intentionally donate many tiny
    leaves that XLA cannot alias into the concatenated buffer (it copies
    them instead — exactly the desired semantics); silence jax's
    per-compile warning about it."""
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        yield

# One packed segment: leaf index in tree_flatten order, original shape,
# dtype name, offset (elements) into that dtype's flat buffer, element count.
_Spec = Tuple[int, Tuple[int, ...], str, int, int]

#: Leaves at or below this byte size are packed (conv kernels / embedding
#: tables stay standalone so their tiled layouts are preserved).
DEFAULT_MAX_LEAF_BYTES = 1 << 20

#: Segment alignment in elements — keeps every slice on a lane-tile boundary
#: so unpacked views never need a layout conversion.
DEFAULT_ALIGN = 1024


class LeafPacker:
    """Packs all small leaves of a pytree into one flat buffer per dtype.

    ``pack``/``unpack`` are pure, jittable, and exact inverses; use them
    INSIDE a jitted step so the step's boundary carries the flat buffers::

        packer = LeafPacker(train_state)
        def packed_step(pts, *args):
            ts = packer.unpack(pts)
            new_ts, loss = step(ts, *args)
            return packer.pack(new_ts), loss

    The packed representation is ``(buffers, kept)`` where ``buffers`` maps
    dtype name -> 1-D array and ``kept`` is the list of above-threshold
    leaves in tree order — a plain pytree, so donation works unchanged.
    """

    def __init__(self, template: Any, max_leaf_bytes: int = DEFAULT_MAX_LEAF_BYTES,
                 align: int = DEFAULT_ALIGN):
        leaves, treedef = jax.tree_util.tree_flatten(template)
        self._treedef = treedef
        self._n_leaves = len(leaves)
        self._specs: List[_Spec] = []
        self._kept_idx: List[int] = []
        self._sizes: Dict[str, int] = {}
        for i, leaf in enumerate(leaves):
            if not hasattr(leaf, "dtype") or not hasattr(leaf, "size"):
                self._kept_idx.append(i)  # non-array leaf (plain Python value)
                continue
            nbytes = leaf.size * jnp.dtype(leaf.dtype).itemsize
            if nbytes <= max_leaf_bytes and leaf.ndim <= 2:
                dt = jnp.dtype(leaf.dtype).name
                off = self._sizes.get(dt, 0)
                n = int(leaf.size)
                self._specs.append((i, tuple(leaf.shape), dt, off, n))
                self._sizes[dt] = off + ((n + align - 1) // align) * align
            else:
                self._kept_idx.append(i)

    @property
    def n_packed(self) -> int:
        return len(self._specs)

    @property
    def n_kept(self) -> int:
        return len(self._kept_idx)

    def stats(self) -> Dict[str, Any]:
        return {
            "leaves": self._n_leaves,
            "packed": self.n_packed,
            "kept": self.n_kept,
            "buffer_bytes": {dt: n * jnp.dtype(dt).itemsize
                             for dt, n in self._sizes.items()},
        }

    # ------------------------------------------------------------------ pack
    def pack(self, tree: Any) -> Tuple[Dict[str, jax.Array], List[jax.Array]]:
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        if treedef != self._treedef:
            raise ValueError(
                "LeafPacker.pack: tree structure differs from the template "
                f"this packer was built for ({treedef} vs {self._treedef})")
        segments: Dict[str, List[jax.Array]] = {dt: [] for dt in self._sizes}
        cursor: Dict[str, int] = {dt: 0 for dt in self._sizes}
        for i, shape, dt, off, n in self._specs:
            if jnp.dtype(leaves[i].dtype).name != dt:
                # a silent astype here would mask a stale packer (e.g. an
                # f32 checkpoint restored into a bf16-template packer) as
                # precision loss; raising routes callers to rebuild
                raise ValueError(
                    f"LeafPacker.pack: leaf {i} is {leaves[i].dtype}, the "
                    f"packer template recorded {dt} — rebuild the packer "
                    "for the current state")
            pad_to = off - cursor[dt]
            if pad_to:  # alignment gap from the PREVIOUS segment
                segments[dt].append(jnp.zeros((pad_to,), dtype=dt))
            segments[dt].append(leaves[i].reshape((n,)))
            cursor[dt] = off + n
        buffers = {}
        for dt, total in self._sizes.items():
            if total - cursor[dt]:
                segments[dt].append(jnp.zeros((total - cursor[dt],), dtype=dt))
            buffers[dt] = (jnp.concatenate(segments[dt]) if len(segments[dt]) > 1
                           else segments[dt][0])
        kept = [leaves[i] for i in self._kept_idx]
        return buffers, kept

    # ---------------------------------------------------------------- unpack
    def unpack(self, packed: Tuple[Dict[str, jax.Array], List[jax.Array]]) -> Any:
        buffers, kept = packed
        leaves: List[Any] = [None] * self._n_leaves
        for i, shape, dt, off, n in self._specs:
            leaves[i] = lax.slice(buffers[dt], (off,), (off + n,)).reshape(shape)
        for j, i in enumerate(self._kept_idx):
            leaves[i] = kept[j]
        return jax.tree_util.tree_unflatten(self._treedef, leaves)

    @staticmethod
    def is_dead(packed) -> bool:
        """True if a donated step consumed these buffers (it dispatched,
        then raised): no post-step state exists anywhere."""
        buffers, kept = packed
        return (any(a.is_deleted() for a in buffers.values())
                or any(a.is_deleted() for a in kept
                       if hasattr(a, "is_deleted")))

    # ------------------------------------------------------------ round trip
    def pack_device(self, tree: Any):
        """Jitted pack (fit-loop entry). DONATES the input tree: kept big
        leaves alias through (no copy), and the caller's original per-leaf
        state is consumed — so only ONE full copy of the state exists while
        a packed loop runs. Wrapper cached so repeat packs don't retrace."""
        if not hasattr(self, "_pack_jit"):
            self._pack_jit = jax.jit(self.pack, donate_argnums=(0,))
        with _quiet_donation():
            return self._pack_jit(tree)

    def unpack_device(self, packed, donate: bool = False):
        """Jitted unpack (fit-loop exit / listener access); cached wrappers.
        ``donate=True`` consumes the packed buffers (kept leaves alias
        through) — use when the packed copy is being released."""
        if donate:
            if not hasattr(self, "_unpack_jit_donate"):
                self._unpack_jit_donate = jax.jit(self.unpack, donate_argnums=(0,))
            with _quiet_donation():
                return self._unpack_jit_donate(packed)
        if not hasattr(self, "_unpack_jit"):
            self._unpack_jit = jax.jit(self.unpack)
        return self._unpack_jit(packed)


def make_unrolled_packed_step(raw_step, packer, k: int):
    """One jitted program running ``k`` sequential train steps
    (env.dispatch_unroll). The per-step argument tuples arrive as a LIST
    pytree — never pre-stacked on device, which would cost one tiny
    dispatch per array per group (the very overhead grouping removes).
    ``raw_step`` takes ``(train_state, *step_args)`` and returns
    ``(new_state, loss)``."""
    def unrolled_packed_train_steps(pts, args_list):
        ts = packer.unpack(pts)
        losses = []
        for i in range(k):
            ts, loss = raw_step(ts, *args_list[i])
            losses.append(loss)
        return packer.pack(ts), jnp.stack(losses)

    return jax.jit(unrolled_packed_train_steps, donate_argnums=(0,))


def make_unrolled_step(raw_step, k: int):
    """One jitted program running ``k`` sequential train steps over
    PER-LEAF state — the sharded-training counterpart of
    :func:`make_unrolled_packed_step` (sharded training cannot pack: one
    flat buffer would force a common sharding across leaves, see module
    docstring): how ``env.dispatch_unroll`` is honored on a mesh; state
    donated, losses stacked."""
    def unrolled_train_steps(ts, args_list):
        losses = []
        for i in range(k):
            ts, loss = raw_step(ts, *args_list[i])
            losses.append(loss)
        return ts, jnp.stack(losses)

    return jax.jit(unrolled_train_steps, donate_argnums=(0,))


class GroupedDispatch:
    """Buffer-and-flush protocol for grouped dispatch, shared by the fit
    loops (a raising listener or iterator must never leave an executed
    group buffered — the exceptional-exit flush would train it twice, a
    bug reproduced in review before this class existed).

    - ``run_single(args) -> loss`` and ``run_group([args, ...]) -> [loss]``
      perform the dispatches;
    - ``compatible(a, b)`` says whether two buffered tuples may share one
      unrolled program (same shapes / mask presence);
    - ``deliver(args, loss)`` does the caller's per-step bookkeeping
      (score, iteration counters, listeners) in submission order.
    """

    def __init__(self, unroll: int, compatible, run_single, run_group,
                 deliver):
        self._unroll = max(1, int(unroll))
        self._compatible = compatible
        self._run_single = run_single
        self._run_group = run_group
        self._deliver = deliver
        self._pending: list = []

    def submit(self, args) -> None:
        if self._unroll <= 1:
            self._deliver(args, self._run_single(args))
            return
        if self._pending and not self._compatible(self._pending[0], args):
            self.flush()
        self._pending.append(args)
        if len(self._pending) >= self._unroll:
            self.flush()

    def flush(self) -> None:
        if not self._pending:
            return
        # snapshot-and-clear BEFORE dispatch/listeners (see class docstring)
        todo = list(self._pending)
        self._pending.clear()
        if len(todo) == self._unroll and self._unroll > 1:
            losses = self._run_group(todo)
        else:  # partial tail group: single steps avoid a fresh compile
            losses = [self._run_single(a) for a in todo]
        for args, loss in zip(todo, losses):
            self._deliver(args, loss)

    def drain_on_error(self) -> None:
        """Best-effort flush for exceptional exits: deliver batches that
        were buffered but never dispatched; if the state itself is dead (a
        raising donated step), drop them without masking the original
        exception."""
        try:
            self.flush()
        except Exception:
            self._pending.clear()


def step_args_signature(args) -> tuple:
    """Cheap structural signature of a step's per-batch argument tuple
    (shapes/dtypes of arrays, None-ness of masks, dict/list structure) —
    the :class:`~deeplearning4j_tpu.runtime.compile_cache.AotCache` key for
    the fit loops. Dtypes are canonicalized (an np.float64 batch lands on
    the float32 program when x64 is off, for jit and compiled executables
    alike). Collisions are safe (the executable's argument check falls
    back to jit); misses only cost one extra lower+compile."""
    def leaf(a):
        if a is None:
            return None
        if isinstance(a, dict):
            return tuple(sorted((k, leaf(v)) for k, v in a.items()))
        if isinstance(a, (list, tuple)):
            return tuple(leaf(v) for v in a)
        shape = getattr(a, "shape", None)
        if shape is None:
            return type(a).__name__
        try:
            dt = str(jax.dtypes.canonicalize_dtype(a.dtype))
        except (TypeError, ValueError):  # extended dtypes (typed PRNG keys)
            dt = str(a.dtype)
        return tuple(shape), dt

    return tuple(leaf(a) for a in args)
