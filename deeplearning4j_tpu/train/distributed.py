"""Multi-process data-parallel trainer with threshold-encoded gradient
exchange (ISSUE 6 — the reference's ``SharedTrainingMaster`` + Aeron
encoded-update path, SURVEY §L6).

The reference's signature scaling feature: Spark workers compute local
gradients, threshold-encode them (Strom 2015 — sparse 1-bit updates, the
un-sent remainder accumulating in a local *residual*), and exchange the
sparse encodings over Aeron; every worker decodes every peer's contribution
and applies the combined update. Here the same wire format
(:mod:`deeplearning4j_tpu.native` ``ThresholdCodec``) rides jax's gloo CPU
collectives (``runtime.mesh.initialize_multihost``) instead of Aeron, and
the combined update goes through the net's own optax updater chain —
the existing updater/solver machinery, not a side-channel SGD.

Layers:

- :class:`GradientExchange` — codec + transport. Each step the worker's
  local gradient contribution (scaled by ``1/world``) is threshold-encoded
  (sparse sign-index or 2-bit bitmap, whichever is *predicted* smaller —
  the choice must precede encoding because the residual is stateful),
  framed with a CRC32 header, allgathered in two phases (sizes, then
  payloads padded to the round's max), CRC-verified and decode-accumulated
  in rank order. ``threshold == 0`` selects the dense f32 transport (the
  encoded format degenerates to ±0 contributions there, so dense is the
  correctness fallback, exactly as the issue specifies). A corrupted or
  failed exchange raises :class:`ExchangeError` — never a silent
  divergence.
- :class:`DistributedTrainer` — the per-process step loop: local gradients
  via the AOT step path (PR 5's :class:`~deeplearning4j_tpu.runtime
  .compile_cache.AotCache`), exchange, combined update through
  ``net._tx``, periodic parameter re-broadcast from rank 0 to bound
  drift, crash-safe checkpoints with per-rank residual state and exact
  batch-level resume.
- :class:`DistributedSupervisor` — the multihost analog of
  :class:`~deeplearning4j_tpu.train.fault_tolerance.FaultTolerantTrainer`.
  An SPMD step is all-or-nothing: one lost worker stalls every peer in the
  collective, so supervision must sit ABOVE the process group — the
  supervisor watches per-worker heartbeat files with the same
  :class:`~deeplearning4j_tpu.train.fault_tolerance.HeartbeatMonitor`,
  and on a worker death *or* a stalled straggler kills the whole group,
  re-forms the mesh on a fresh coordinator port and relaunches within the
  same restart budget semantics; workers restore the newest valid
  checkpoint and resume at the exact batch.

Determinism contract (the correctness anchor): every worker iterates the
SAME deterministic global-batch iterator and slices its rank's shard, so
the single-process oracle is this very class in *loopback* mode
(``rank=None``): one process simulates all ranks' gradient computations
with the same jitted functions, per-rank codecs and the same rank-order
combine — the N-process trajectory must (and is tested to) match it
bit-for-bit, at threshold 0 and above.

Chaos points: ``train.distributed.exchange`` fires once per step at the
top of the exchange (fail → the worker dies → supervised restart);
``train.distributed.exchange.bytes`` passes the encoded payload through
byte corruption — the CRC check turns injected wire corruption into an
:class:`ExchangeError`, proving the no-silent-divergence property.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import struct
import subprocess
import threading
import time
import zlib
from collections import deque
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from deeplearning4j_tpu.native import TreeCodec
from deeplearning4j_tpu.runtime import chaos, trace
from deeplearning4j_tpu.runtime.compile_cache import AotCache
from deeplearning4j_tpu.runtime.profiler import ExchangeStats
from deeplearning4j_tpu.train.checkpoint import (CheckpointListener,
                                                 atomic_save_model,
                                                 load_manifest,
                                                 write_manifest)
from deeplearning4j_tpu.train.fault_tolerance import (HeartbeatMonitor,
                                                      TrainingFailure)

logger = logging.getLogger(__name__)

_HEADER = struct.Struct("<iiIf")  # format, payload nbytes, crc32, local loss


class ExchangeError(RuntimeError):
    """A gradient exchange failed or arrived corrupted. Fatal to the step:
    the worker must die and be restarted from a checkpoint rather than
    train on a partial or garbage combined update."""


# --------------------------------------------------------------------------
# process-group plumbing shared by the supervisor, tests and bench
def free_port() -> str:
    """An OS-assigned free TCP port for the jax.distributed coordinator."""
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return str(s.getsockname()[1])


def worker_env(extra: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    """Environment for a loopback worker subprocess, which is a CPU
    process: ``JAX_PLATFORMS=cpu`` is pinned, not stripped. With libtpu
    installed a child left to auto-detect takes the chip — and a chip
    belongs to one process, so the second worker to start, or any worker
    of a parent that already holds the chip, fails or hangs. Inherited
    device-count flags are dropped (a worker is one host device unless
    ``extra`` says otherwise) and the repo goes on ``PYTHONPATH``."""
    repo_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    if extra:
        env.update(extra)
    return env


_children_lock = threading.Lock()  # guards: (_children pid registry)
_children: List[subprocess.Popen] = []


def _track_child(proc: subprocess.Popen) -> None:
    with _children_lock:
        _children.append(proc)


def live_worker_pids() -> List[int]:
    """PIDs of worker subprocesses launched through this module that are
    still alive — the conftest leak guard polls this after every test so
    no orphaned gloo worker survives a test."""
    with _children_lock:
        _children[:] = [p for p in _children if p.poll() is None]
        return [p.pid for p in _children]


def kill_stray_workers() -> List[int]:
    """Kill any still-live tracked workers (leak-guard teardown); returns
    the PIDs that had to be killed."""
    with _children_lock:
        stray = [p for p in _children if p.poll() is None]
        for p in stray:
            try:
                p.kill()
            except OSError:
                pass
        for p in stray:
            try:
                p.wait(timeout=10)
            except Exception:
                pass
        _children[:] = [p for p in _children if p.poll() is None]
    return [p.pid for p in stray]


# --------------------------------------------------------------------------
# transports
class CollectiveExchange:
    """Real multi-process transport over jax's collectives (gloo on CPU,
    ICI/DCN on TPU). Pure data movement — no arithmetic happens in the
    collective, so gathers are bit-exact and rank-order combination on the
    host is deterministic."""

    def __init__(self):
        import jax
        self._jax = jax
        from jax.experimental import multihost_utils
        self._mu = multihost_utils
        self.world = jax.process_count()
        self.rank = jax.process_index()

    def gather_bytes(self, payload: bytes) -> List[bytes]:
        """Allgather one variable-length byte payload per process. Two
        phases: sizes first, then payloads padded to the round's max —
        the wire cost is ``max_nbytes``, not the dense size."""
        sizes = self._mu.process_allgather(
            np.asarray([len(payload)], np.int64))
        sizes = np.asarray(sizes).reshape(-1)
        cap = int(sizes.max())
        buf = np.zeros(max(cap, 1), np.uint8)
        buf[:len(payload)] = np.frombuffer(payload, np.uint8)
        # single-process allgather returns the array without a process
        # axis; normalize to (world, cap)
        gathered = np.asarray(
            self._mu.process_allgather(buf)).reshape(self.world, -1)
        return [gathered[p, :int(sizes[p])].tobytes()
                for p in range(self.world)]

    def broadcast(self, arr: np.ndarray) -> np.ndarray:
        """Rank 0's array to everyone (parameter re-sync)."""
        return np.asarray(self._mu.broadcast_one_to_all(arr))

    def barrier(self, name: str) -> None:
        self._mu.sync_global_devices(name)


class LoopbackExchange:
    """Single-process stand-in: the trainer in oracle mode hands it every
    simulated rank's payload at once; gathers and broadcasts are list ops.
    Exists so the N-process trajectory has an executable bit-exact
    reference (and so chaos drills on the exchange run tier-1)."""

    def __init__(self, world: int):
        self.world = int(world)
        self.rank = 0

    def gather_bytes(self, payloads: List[bytes]) -> List[bytes]:
        if len(payloads) != self.world:
            raise ExchangeError(
                f"loopback gather got {len(payloads)} payloads for "
                f"world={self.world}")
        return list(payloads)

    def broadcast(self, arr: np.ndarray) -> np.ndarray:
        return arr

    def barrier(self, name: str) -> None:
        pass


# --------------------------------------------------------------------------
# the codec + transport layer
class GradientExchange:
    """Threshold-encoded gradient combine over a transport.

    One instance per *rank state* (a worker owns one; the loopback oracle
    owns one per simulated rank so residuals accumulate exactly as they
    would in the real processes). The wire frame is
    ``<header: format int32, nbytes int32, crc32 uint32, loss f32>``
    followed by the encoded payload; the CRC is computed from the intended
    payload *before* the ``train.distributed.exchange.bytes`` chaos point,
    so injected corruption is exactly what the receiver-side check
    catches."""

    def __init__(self, codec: TreeCodec, stats: Optional[ExchangeStats] = None):
        self.codec = codec
        self.stats = stats or ExchangeStats()
        self.threshold = codec.threshold

    @property
    def dense(self) -> bool:
        return self.threshold == 0.0

    def make_payload(self, flat_contribution: np.ndarray,
                     loss: float) -> bytes:
        """Encode one rank's scaled gradient contribution into a framed
        payload (mutates that rank's residual)."""
        t0 = time.perf_counter()
        if self.dense:
            fmt = TreeCodec.FORMAT_DENSE
            payload = np.ascontiguousarray(
                flat_contribution, np.float32).tobytes()
        else:
            fmt, payload = self.codec.encode(flat_contribution)
        crc = zlib.crc32(payload) & 0xFFFFFFFF
        payload = chaos.transform_bytes(
            "train.distributed.exchange.bytes", payload)
        self.stats.record("encode", time.perf_counter() - t0)
        return _HEADER.pack(fmt, len(payload), crc, float(loss)) + payload

    def combine(self, frames: Sequence[bytes]) -> Tuple[np.ndarray, float]:
        """CRC-check every rank's frame and decode-accumulate in rank
        order. Returns ``(combined flat update, mean loss)`` — identical
        bits on every rank and in the loopback oracle."""
        t0 = time.perf_counter()
        combined = np.zeros(self.codec.size, np.float32)
        loss_sum = 0.0
        for p, frame in enumerate(frames):
            if len(frame) < _HEADER.size:
                raise ExchangeError(
                    f"short exchange frame from rank {p}: {len(frame)} bytes")
            fmt, nbytes, crc, loss = _HEADER.unpack(frame[:_HEADER.size])
            payload = frame[_HEADER.size:]
            if len(payload) != nbytes:
                raise ExchangeError(
                    f"rank {p} frame declares {nbytes} payload bytes, "
                    f"carries {len(payload)}")
            if (zlib.crc32(payload) & 0xFFFFFFFF) != crc:
                raise ExchangeError(
                    f"CRC mismatch in rank {p}'s encoded update — "
                    f"corrupted exchange")
            if fmt == TreeCodec.FORMAT_DENSE:
                contrib = np.frombuffer(payload, np.float32)
                if contrib.size != self.codec.size:
                    raise ExchangeError(
                        f"rank {p} dense frame has {contrib.size} elements, "
                        f"expected {self.codec.size}")
                combined += contrib
            else:
                self.codec.decode_into(fmt, payload, combined)
            loss_sum += loss
        self.stats.record("decode", time.perf_counter() - t0)
        return combined, loss_sum / max(1, len(frames))


# --------------------------------------------------------------------------
# trainer
@dataclasses.dataclass
class DistributedConfig:
    """Knobs for :class:`DistributedTrainer`.

    ``threshold`` is in units of the *scaled* per-rank contribution
    (local gradient / world) — 0.0 selects the dense transport.
    ``resync_every`` re-broadcasts rank 0's parameters every N steps to
    bound drift (0 disables). ``checkpoint_every`` steps between
    crash-safe checkpoints (0 disables; rank 0 writes the model archive,
    every rank persists its own codec residual so a restart resumes the
    encoded stream exactly)."""

    threshold: float = 1e-3
    resync_every: int = 0
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 0
    keep_last: int = 3
    heartbeat_file: Optional[str] = None
    #: ISSUE 20 — encode/exchange vs compute overlap. 0 (default) is the
    #: fully synchronous schedule. 1 enables a one-deep in-flight window:
    #: step k+1's local gradients are computed (and encoded) BEFORE step
    #: k's allgather result is combined and applied, hiding the wire
    #: latency behind the next step's compute. This is an explicit
    #: staleness-1 delayed-update schedule — a DIFFERENT trajectory from
    #: window 0 — and the loopback oracle runs the exact same schedule,
    #: so worker-vs-oracle bit-identity holds at any window setting.
    overlap_window: int = 0


class DistributedTrainer:
    """Data-parallel trainer: N lock-step ranks exchanging
    threshold-encoded gradient updates.

    Worker mode (``world > 1`` inside an ``initialize_multihost`` process
    group, or ``world=1`` standalone): ``fit`` consumes a deterministic
    iterator of GLOBAL batches, slices this rank's shard, computes local
    gradients through the AOT step path, exchanges, and applies the
    combined update through the net's updater chain.

    Loopback-oracle mode (``rank=None``): the same class simulates every
    rank in one process — per-rank model state and codec residuals, the
    same jitted executables, the same rank-order combine — producing the
    bit-exact single-process reference trajectory the multi-process run
    is tested against.

    The net must expose MultiLayerNetwork's step surface
    (``_loss(params, model_state, x, y, rng, fmask, lmask)``, ``_tx``,
    ``_apply_constraints``); single-(x, y) workloads only — the
    multi-input ComputationGraph fit path is future work.
    """

    def __init__(self, net, config: Optional[DistributedConfig] = None,
                 world: Optional[int] = None, rank: Optional[int] = -1,
                 profiler=None, plan=None):
        import jax
        self._jax = jax
        self.net = net
        self.config = config or DistributedConfig()
        if self.config.overlap_window not in (0, 1):
            raise ValueError("overlap_window supports 0 (synchronous) or 1 "
                             "(one-deep in-flight exchange window)")
        self.stats = ExchangeStats()
        self.profiler = profiler
        if profiler is not None:
            profiler.attach_exchange(self.stats)
        self.loopback = rank is None
        if self.loopback:
            if not world or world < 1:
                raise ValueError("loopback mode needs an explicit world size")
            self.world = int(world)
            self.rank = 0
            self.transport = LoopbackExchange(self.world)
        else:
            self.transport = CollectiveExchange()
            self.world = self.transport.world if world is None else int(world)
            self.rank = self.transport.rank if rank == -1 else int(rank)
            if self.world != self.transport.world:
                raise ValueError(
                    f"world={self.world} but jax.process_count() is "
                    f"{self.transport.world}")
        if net.train_state is None:
            net.init()
        # ISSUE 20: an optional ParallelPlan shards the LOCAL step across
        # this process's devices (fsdp/tensor — the cross-process data
        # axis stays the threshold-encoded host exchange, so the combined
        # update is still exchanged ONLY over the data dimension). Pipe
        # plans belong to ParallelWrapper.fit / serving, not here.
        self.plan = plan
        if plan is not None:
            if getattr(plan, "pipe_size", 1) > 1:
                raise NotImplementedError(
                    "DistributedTrainer shards the local step with "
                    "fsdp/tensor axes; pipeline plans train through "
                    "ParallelWrapper.fit")
            from deeplearning4j_tpu.parallel.sharding import shard_train_state
            net.train_state = shard_train_state(net.train_state, plan)
        self._leaves, self._treedef = jax.tree.flatten(net.train_state.params)
        template = [np.asarray(l) for l in self._leaves]
        n_rank_states = self.world if self.loopback else 1
        self._exchanges = [
            GradientExchange(TreeCodec(template, self.config.threshold),
                             stats=self.stats)
            for _ in range(n_rank_states)]
        # per-rank model state: BN running stats etc. evolve from the LOCAL
        # shard (reference semantics too); rank 0's is the state of record
        self._rank_model_states = [net.train_state.model_state
                                   for _ in range(n_rank_states)]
        self._grad_aot = AotCache("distributed.grad")
        self._apply_aot = AotCache("distributed.apply")
        self._grad_fn = None
        self._apply_fn = None
        self.losses: List[float] = []
        # one-deep in-flight exchange window (ISSUE 20, overlap_window=1)
        self._inflight = None
        self._last_mean_loss: Optional[float] = None
        self._xchg_thread: Optional[threading.Thread] = None
        self._xchg_req = None
        self._xchg_res = None
        self._epoch_start_iters: Dict[int, int] = {}
        if self.config.checkpoint_dir:
            os.makedirs(self.config.checkpoint_dir, exist_ok=True)
            self._epoch_start_iters = self._load_epoch_starts()

    # ----------------------------------------------------------- jitted fns
    def _make_grad_fn(self):
        jax = self._jax

        def grad_step(params, model_state, x, y, rng):
            (loss, (new_state, _)), grads = jax.value_and_grad(
                self.net._loss, has_aux=True)(
                    params, model_state, x, y, rng, None, None)
            return loss, grads, new_state

        return jax.jit(grad_step)

    def _make_apply_fn(self):
        import optax

        jax = self._jax
        sizes = [int(np.prod(s)) if s else 1
                 for s in (np.shape(l) for l in self._leaves)]
        offsets = np.cumsum([0] + sizes).tolist()
        shapes = [np.shape(l) for l in self._leaves]
        dtypes = [l.dtype for l in self._leaves]

        def apply_step(ts, model_state, flat_update):
            leaves = [flat_update[lo:lo + sz].reshape(shape).astype(dt)
                      for lo, sz, shape, dt in
                      zip(offsets, sizes, shapes, dtypes)]
            grads = jax.tree.unflatten(self._treedef, leaves)
            updates, new_opt = self.net._tx.update(
                grads, ts.opt_state, ts.params)
            new_params = self.net._apply_constraints(
                optax.apply_updates(ts.params, updates))
            return dataclasses.replace(
                ts, params=new_params, model_state=model_state,
                opt_state=new_opt, step=ts.step + 1)

        return jax.jit(apply_step, donate_argnums=(0,))

    def _local_grad(self, rank_ix: int, x, y, rng):
        """One rank's local (loss, flat scaled gradient, new model state)
        through the AOT dispatch path."""
        if self._grad_fn is None:
            self._grad_fn = self._make_grad_fn()
        if self.plan is not None:
            # commit the local shard to the plan's batch axes so the grad
            # step runs plan-sharded; XLA's psum over those axes IS the
            # within-process reduction, the host exchange stays data-only
            jnp_x = self._jax.device_put(
                np.asarray(x), self.plan.batch_sharding(np.ndim(x)))
            jnp_y = self._jax.device_put(
                np.asarray(y), self.plan.batch_sharding(np.ndim(y)))
        else:
            jnp_x = self._jax.numpy.asarray(x)
            jnp_y = self._jax.numpy.asarray(y)
        key = (tuple(jnp_x.shape), str(jnp_x.dtype), tuple(jnp_y.shape),
               self.plan.signature() if self.plan is not None else None)
        loss, grads, new_state = self._grad_aot.call(
            key, self._grad_fn, self.net.train_state.params,
            self._rank_model_states[rank_ix], jnp_x, jnp_y, rng)
        self._rank_model_states[rank_ix] = new_state
        ex = self._exchanges[rank_ix]
        flat = ex.codec.flatten(
            [np.asarray(g) for g in self._jax.tree.leaves(grads)])
        # scale BEFORE encoding so the decode-accumulated sum approximates
        # the MEAN gradient — same LR semantics as the dense path
        flat /= np.float32(self.world)
        return float(loss), flat, ex

    def _apply(self, combined: np.ndarray) -> None:
        if self._apply_fn is None:
            self._apply_fn = self._make_apply_fn()
        t0 = time.perf_counter()
        ts = self._apply_aot.call(
            (self.plan.signature() if self.plan is not None else None,),
            self._apply_fn, self.net.train_state,
            self._rank_model_states[0], combined)
        if self.plan is not None:
            # re-commit the plan's parameter placement: the combined
            # update arrives replicated, and GSPMD's output choice for
            # params must not drift step over step (the AOT grad
            # executable was compiled against the plan layout)
            ts = dataclasses.replace(
                ts, params=self._jax.device_put(
                    ts.params, self.plan.param_sharding(ts.params)))
        self.net.train_state = ts
        self.stats.record("apply", time.perf_counter() - t0)

    # ----------------------------------------------------------------- step
    def step(self, x: np.ndarray, y: np.ndarray) -> float:
        """One lock-step distributed step over one GLOBAL batch. Returns
        the combined (mean-of-ranks) loss.

        Tracing (ISSUE 9): each step runs inside a ``train.step`` span —
        the :class:`ExchangeStats` stage hooks stamp the encode /
        exchange / decode / apply split onto it as stage events, a chaos
        fault at ``train.distributed.exchange`` is stamped by the
        injector, and tail sampling keeps exactly the interesting steps."""
        with trace.span("train.step") as tsp:
            if tsp.recording:
                tsp.set("rank", "loopback" if self.loopback else self.rank)
                tsp.set("world", self.world)
                tsp.set("step", int(self.net._iteration) + 1)
            return self._step_inner(x, y)

    def _step_inner(self, x: np.ndarray, y: np.ndarray) -> float:
        b = x.shape[0]
        if b % self.world:
            raise ValueError(f"global batch of {b} not divisible by "
                             f"world={self.world}")
        n_local = b // self.world
        rng = self.net.rng.next_key()
        chaos.inject("train.distributed.exchange")
        if self.loopback:
            send = []
            lsum = 0.0
            for r in range(self.world):
                lo = r * n_local
                loss, flat, ex = self._local_grad(
                    r, x[lo:lo + n_local], y[lo:lo + n_local], rng)
                send.append(ex.make_payload(flat, loss))
                lsum += loss
            loss = lsum / self.world
        else:
            lo = self.rank * n_local
            loss, flat, ex = self._local_grad(
                0, x[lo:lo + n_local], y[lo:lo + n_local], rng)
            send = ex.make_payload(flat, loss)
        handle = self._begin_gather(send)
        if self.config.overlap_window:
            # staleness-1 schedule (ISSUE 20): this step's allgather
            # drains behind the NEXT step's compute; what gets combined
            # and applied here is the PREVIOUS step's exchange. The first
            # step has nothing to apply yet — it returns the local loss
            # (in loopback, the mean over simulated ranks, which is the
            # exact value the eventual combine will report).
            prev, self._inflight = self._inflight, handle
            mean_loss = (self._complete_exchange(prev)
                         if prev is not None else float(loss))
        else:
            mean_loss = self._complete_exchange(handle)
        step_no = int(self.net._iteration) + 1
        self.net._iteration = step_no
        self.net._score = mean_loss
        if (self.config.resync_every
                and step_no % self.config.resync_every == 0):
            self.flush()
            self.resync_params()
        if (self.config.checkpoint_every and self.config.checkpoint_dir
                and step_no % self.config.checkpoint_every == 0):
            self.flush()
            self._checkpoint(step_no)
        if self.config.heartbeat_file:
            self._beat(step_no)
        return mean_loss

    # --------------------------------------------------- overlapped exchange
    def _exchange_worker(self) -> None:
        while True:
            item = self._xchg_req.get()
            if item is None:
                return
            try:
                self._xchg_res.put(("ok", self.transport.gather_bytes(item)))
            except BaseException as e:
                self._xchg_res.put(("err", e))

    def _begin_gather(self, send):
        """Dispatch one step's allgather. Loopback's gather is a list op —
        it completes inline; worker mode hands the frame to the exchange
        thread so the collective drains behind the next step's compute."""
        sent = len(send[0]) if isinstance(send, list) else len(send)
        if self.loopback or not self.config.overlap_window:
            t0 = time.perf_counter()
            frames = self.transport.gather_bytes(send)
            self.stats.record("exchange", time.perf_counter() - t0)
            return {"frames": frames, "sent": sent}
        if self._xchg_thread is None:
            import queue
            self._xchg_req = queue.Queue()
            self._xchg_res = queue.Queue()
            self._xchg_thread = threading.Thread(
                target=self._exchange_worker, name="dist-exchange",
                daemon=True)
            self._xchg_thread.start()
        self._xchg_req.put(send)
        return {"frames": None, "sent": sent}

    def _complete_exchange(self, handle) -> float:
        frames = handle["frames"]
        if frames is None:
            t0 = time.perf_counter()
            status, payload = self._xchg_res.get()
            # the recorded exchange time is the WAIT, not the wire time —
            # the overlap benefit shows up as this going to ~0
            self.stats.record("exchange", time.perf_counter() - t0)
            if status == "err":
                raise payload
            frames = payload
        dense_bytes = 4 * self._exchanges[0].codec.size
        # the two-phase gather pads every rank's send to the round max
        wire = max(len(f) for f in frames)
        self.stats.record_bytes(dense_bytes, wire, handle["sent"])
        combined, mean_loss = self._exchanges[0].combine(frames)
        self._apply(combined)
        self.losses.append(mean_loss)
        self._last_mean_loss = mean_loss
        return mean_loss

    def flush(self) -> Optional[float]:
        """Combine + apply any in-flight exchange (``overlap_window`` > 0).
        Runs before every checkpoint/resync and at fit end, so persisted
        or broadcast state never straddles a pending update. Returns the
        applied mean loss, or ``None`` when nothing was pending."""
        if self._inflight is None:
            return None
        handle, self._inflight = self._inflight, None
        return self._complete_exchange(handle)

    def close(self) -> None:
        """Join the overlap exchange thread (no-op when never started)."""
        if self._xchg_thread is not None:
            self._xchg_req.put(None)
            self._xchg_thread.join(timeout=10)
            self._xchg_thread = None

    def resync_params(self) -> None:
        """Re-broadcast rank 0's parameters to every rank — the periodic
        drift bound. A no-op by value when ranks are in lock-step (and in
        loopback mode), but it makes the lock-step invariant *enforced*
        rather than assumed on long runs."""
        jax = self._jax
        ex = self._exchanges[0]
        leaves = [np.asarray(l)
                  for l in jax.tree.leaves(self.net.train_state.params)]
        flat = ex.codec.flatten(leaves)
        synced = self.transport.broadcast(flat)
        if synced is not flat:
            new_leaves = [
                self._jax.numpy.asarray(a.astype(l.dtype))
                for a, l in zip(ex.codec.unflatten(synced), leaves)]
            self.net.train_state = dataclasses.replace(
                self.net.train_state,
                params=jax.tree.unflatten(self._treedef, new_leaves))

    # ------------------------------------------------------------------ fit
    def fit(self, iterator, epochs: int = 1):
        """Supervised epoch loop over a deterministic GLOBAL-batch
        iterator (every rank holds an identical copy — the multi-host
        data contract the round-6 tests established). Resumes exactly:
        with a checkpoint directory, a restarted worker restores the
        newest valid archive + its own residual, and skips the already
        trained leading batches of the in-progress epoch."""
        if self.profiler is not None:
            self.profiler.start()
        try:
            while self.net._epoch < int(epochs):
                e = int(self.net._epoch)
                start_iter = self._epoch_start_iters.get(e)
                if start_iter is None:
                    self._epoch_start_iters[e] = int(self.net._iteration)
                    self._save_epoch_starts()
                    skip = 0
                else:
                    skip = max(0, int(self.net._iteration) - start_iter)
                iterator.reset()
                seen = 0
                while iterator.has_next():
                    ds = iterator.next()
                    seen += 1
                    if seen <= skip:
                        continue  # deterministic replay into the void
                    t0 = time.perf_counter()
                    x = np.asarray(ds.features)
                    y = np.asarray(ds.labels)
                    if self.profiler is not None:
                        self.profiler.record_data_wait(
                            time.perf_counter() - t0)
                        t1 = time.perf_counter()
                        loss = self.step(x, y)
                        # synchronous loop: "dispatch" is the whole step
                        # (same as PR 4's unpipelined fit path) and the
                        # async step stage is deliberately NOT recorded —
                        # step_measured=False flags it as synchronous
                        self.profiler.record_dispatch(
                            time.perf_counter() - t1)
                    else:
                        loss = self.step(x, y)
                    for lst in self.net._listeners:
                        lst.iteration_done(self.net, self.net._iteration,
                                           self.net._epoch, loss)
                self.net._epoch = e + 1
                self.flush()
        finally:
            self.close()
            if self.profiler is not None:
                self.profiler.stop()
        return self.net

    # ---------------------------------------------------------- persistence
    def _beat(self, step_no: int) -> None:
        try:
            with open(self.config.heartbeat_file, "w") as f:
                f.write(str(step_no))
        except OSError:
            logger.warning("could not write heartbeat %s",
                           self.config.heartbeat_file)

    def _residual_path(self, rank: int, step_no: int) -> str:
        return os.path.join(self.config.checkpoint_dir,
                            f"exchange_r{rank}_s{step_no}.npz")

    def _checkpoint(self, step_no: int) -> None:
        """Crash-safe, group-consistent checkpoint. Order matters: every
        rank persists its residual for this step FIRST, then a barrier,
        then rank 0 commits the model archive — so a committed archive at
        step k implies every rank's residual for step k is durable."""
        cfg = self.config
        ranks = range(self.world) if self.loopback else [self.rank]
        for r in ranks:
            ex = self._exchanges[r if self.loopback else 0]
            path = self._residual_path(r, step_no)
            tmp = path + f".tmp.{os.getpid()}.npz"
            np.savez(tmp, residual=ex.codec.residual, step=step_no)
            os.replace(tmp, path)
        self.transport.barrier(f"ckpt-residuals-{step_no}")
        if self.loopback or self.rank == 0:
            archive = os.path.join(cfg.checkpoint_dir,
                                   f"checkpoint_{step_no}_dist.zip")
            entry = atomic_save_model(self.net, archive)
            manifest = load_manifest(cfg.checkpoint_dir)
            manifest[os.path.basename(archive)] = entry
            write_manifest(cfg.checkpoint_dir, manifest)
            self._prune(step_no)
        self.transport.barrier(f"ckpt-archive-{step_no}")

    def _prune(self, newest_step: int) -> None:
        cfg = self.config
        steps = sorted({s for s in (
            _dist_checkpoint_step(f) for f in os.listdir(cfg.checkpoint_dir))
            if s is not None})
        manifest = load_manifest(cfg.checkpoint_dir)
        changed = False
        for s in steps[:-max(1, cfg.keep_last)]:
            for f in os.listdir(cfg.checkpoint_dir):
                if _dist_checkpoint_step(f) == s:
                    changed |= manifest.pop(f, None) is not None
                    try:
                        os.unlink(os.path.join(cfg.checkpoint_dir, f))
                    except OSError:
                        pass
        if changed:
            write_manifest(cfg.checkpoint_dir, manifest)

    def restore(self) -> bool:
        """Restore the newest valid checkpoint (if any): model archive
        into the net, this rank's residual into the codec. Returns True
        when a checkpoint was restored."""
        cfg = self.config
        if not cfg.checkpoint_dir:
            return False
        ckpt = CheckpointListener.last_checkpoint_in(cfg.checkpoint_dir)
        if ckpt is None:
            return False
        logger.warning("rank %d restoring from %s", self.rank, ckpt)
        net = type(self.net).load(ckpt)
        self.net.train_state = net.train_state
        self.net._tx = net._tx
        self.net._iteration = net._iteration
        self.net._epoch = net._epoch
        self.net.rng = net.rng
        self._jit_reset()
        step_no = int(net._iteration)
        ranks = range(self.world) if self.loopback else [self.rank]
        for r in ranks:
            path = self._residual_path(r, step_no)
            ex = self._exchanges[r if self.loopback else 0]
            try:
                blob = np.load(path)
                if int(blob["step"]) != step_no:
                    raise ValueError("stale residual")
                ex.codec.residual = np.ascontiguousarray(
                    blob["residual"], np.float32)
            except (OSError, ValueError, KeyError):
                if not ex.dense:
                    raise TrainingFailure(
                        f"rank {r}: no residual state for checkpoint step "
                        f"{step_no} — cannot exact-resume the encoded "
                        f"stream") from None
        # model state of record is the restored archive's; a restart can
        # never inherit an in-flight exchange window
        self._inflight = None
        self._last_mean_loss = None
        if self.plan is not None:
            from deeplearning4j_tpu.parallel.sharding import shard_train_state
            self.net.train_state = shard_train_state(self.net.train_state,
                                                     self.plan)
        self._rank_model_states = [self.net.train_state.model_state
                                   for _ in self._rank_model_states]
        self._epoch_start_iters = self._load_epoch_starts()
        return True

    def _jit_reset(self) -> None:
        self._grad_fn = None
        self._apply_fn = None
        self._grad_aot.clear()
        self._apply_aot.clear()

    def _epoch_starts_path(self) -> str:
        return os.path.join(self.config.checkpoint_dir, "trainer_state.json")

    def _load_epoch_starts(self) -> Dict[int, int]:
        try:
            with open(self._epoch_starts_path()) as f:
                return {int(k): int(v) for k, v in
                        json.load(f)["epoch_start_iters"].items()}
        except (OSError, ValueError, KeyError, TypeError):
            return {}

    def _save_epoch_starts(self) -> None:
        if not self.config.checkpoint_dir:
            return
        if self.rank != 0 and not self.loopback:
            return
        path = self._epoch_starts_path()
        tmp = path + ".tmp"
        try:
            with open(tmp, "w") as f:
                json.dump({"epoch_start_iters": self._epoch_start_iters}, f)
            os.replace(tmp, path)
        except OSError:
            logger.warning("could not persist trainer state to %s", path)


def _dist_checkpoint_step(filename: str) -> Optional[int]:
    """Step number of a distributed checkpoint artifact (model archive or
    residual), else None."""
    if filename.startswith("checkpoint_") and filename.endswith("_dist.zip"):
        mid = filename[len("checkpoint_"):-len("_dist.zip")]
        return int(mid) if mid.isdigit() else None
    if filename.startswith("exchange_r") and filename.endswith(".npz"):
        parts = filename[:-len(".npz")].split("_s")
        return int(parts[-1]) if parts[-1].isdigit() else None
    return None


# --------------------------------------------------------------------------
# supervisor
class DistributedSupervisor:
    """Launch + watch + restart a local multi-process training group — the
    process-group analog of
    :class:`~deeplearning4j_tpu.train.fault_tolerance.FaultTolerantTrainer`
    (same :class:`HeartbeatMonitor`, same restart-budget escalation), one
    level up: a lost worker stalls every peer inside the collective, so
    recovery is always *kill the group, re-form the mesh on a fresh
    coordinator port, relaunch, restore the newest checkpoint*.

    ``make_argv(rank, port)`` returns the full worker argv (the worker
    script calls ``initialize_multihost`` with that port and runs a
    :class:`DistributedTrainer`). Heartbeat files are written by the
    workers (``DistributedConfig.heartbeat_file``); a worker making step
    progress beats the monitor, so both crashes (exit codes) and stalled
    stragglers (stale heartbeats while processes are alive) trigger a
    restart round."""

    def __init__(self, make_argv: Callable[[int, str], List[str]],
                 num_processes: int, heartbeat_files: Sequence[str],
                 max_restarts: int = 3,
                 restart_window_s: Optional[float] = None,
                 heartbeat_timeout_s: float = 120.0,
                 poll_s: float = 0.2,
                 env: Optional[Dict[str, str]] = None):
        self.make_argv = make_argv
        self.num_processes = int(num_processes)
        self.heartbeat_files = [str(h) for h in heartbeat_files]
        self.max_restarts = int(max_restarts)
        self.restart_window_s = restart_window_s
        self.heartbeat_timeout_s = float(heartbeat_timeout_s)
        self.poll_s = float(poll_s)
        self.env = env
        self.restarts = 0
        self._restart_times: deque = deque()
        self.rounds: List[Dict[str, object]] = []

    # ------------------------------------------------------------- plumbing
    def _launch(self, port: str) -> List[subprocess.Popen]:
        """Spawn one worker per rank. Output goes to temp FILES, not
        pipes: the supervisor doesn't drain during a round, and a worker
        producing more than the OS pipe buffer would block mid-step and
        read as a stalled straggler."""
        import tempfile
        env = self.env if self.env is not None else worker_env()
        procs = []
        for rank in range(self.num_processes):
            out_f = tempfile.NamedTemporaryFile(
                mode="w+", prefix=f"dl4j-dist-r{rank}-out-", delete=False)
            err_f = tempfile.NamedTemporaryFile(
                mode="w+", prefix=f"dl4j-dist-r{rank}-err-", delete=False)
            p = subprocess.Popen(
                self.make_argv(rank, port), env=env, text=True,
                stdout=out_f, stderr=err_f)
            p._dl4j_capture = (out_f, err_f)  # type: ignore[attr-defined]
            _track_child(p)
            procs.append(p)
        return procs

    @staticmethod
    def _collect(p: subprocess.Popen) -> Tuple[str, str]:
        """Reap one exited worker and return its (stdout, stderr)."""
        try:
            p.wait(timeout=60)
        except Exception:
            p.kill()
        texts = []
        for f in getattr(p, "_dl4j_capture", ()):
            try:
                f.flush()
                f.seek(0)
                texts.append(f.read())
            except (OSError, ValueError):
                texts.append("")
            finally:
                try:
                    f.close()
                    os.unlink(f.name)
                except OSError:
                    pass
        return tuple(texts) if len(texts) == 2 else ("", "")

    @classmethod
    def _kill_group(cls, procs: List[subprocess.Popen]
                    ) -> List[Tuple[str, str]]:
        for p in procs:
            if p.poll() is None:
                p.kill()
        return [cls._collect(p) for p in procs]

    def _register_restart(self, cause: str) -> None:
        now = time.monotonic()
        self.restarts += 1
        self._restart_times.append(now)
        if self.restart_window_s is not None:
            while (self._restart_times and
                   now - self._restart_times[0] > self.restart_window_s):
                self._restart_times.popleft()
            recent = len(self._restart_times)
            budget = (f"{self.max_restarts} restarts in "
                      f"{self.restart_window_s:.0f}s")
        else:
            recent = self.restarts
            budget = f"{self.max_restarts} restarts"
        if recent > self.max_restarts:
            raise TrainingFailure(
                f"distributed training giving up after {budget} "
                f"(last cause: {cause})")
        logger.warning("distributed group failed (%s); restart %d within "
                       "budget %s", cause, recent, budget)

    # ------------------------------------------------------------------ run
    def run(self, round_timeout_s: float = 600.0) -> List[Tuple[str, str]]:
        """Supervise until one launch round finishes cleanly (every worker
        exits 0) or the restart budget is exhausted
        (:class:`TrainingFailure`). Returns the successful round's
        per-rank ``(stdout, stderr)``."""
        while True:
            port = free_port()
            procs = self._launch(port)
            monitor = HeartbeatMonitor(self.heartbeat_timeout_s)
            seen: Dict[int, float] = {}
            cause = None
            deadline = time.monotonic() + round_timeout_s
            try:
                while True:
                    for i, hb in enumerate(self.heartbeat_files):
                        try:
                            m = os.stat(hb).st_mtime
                        except OSError:
                            continue
                        if seen.get(i) != m:
                            seen[i] = m
                            monitor.beat()  # any worker progressing = alive
                    codes = [p.poll() for p in procs]
                    if any(c not in (None, 0) for c in codes):
                        cause = (f"worker exited with codes "
                                 f"{[c for c in codes if c is not None]}")
                        break
                    if all(c == 0 for c in codes):
                        outs = [self._collect(p) for p in procs]
                        self.rounds.append(
                            {"port": port, "outcome": "success"})
                        return outs
                    # without heartbeat files there is no straggler signal
                    # — exit codes are the only failure detector, and an
                    # un-beaten monitor must not kill a healthy group
                    if self.heartbeat_files:
                        try:
                            monitor.check()
                        except TrainingFailure as e:
                            cause = f"stalled group: {e}"
                            break
                    if time.monotonic() > deadline:
                        cause = f"round timeout after {round_timeout_s:.0f}s"
                        break
                    time.sleep(self.poll_s)
            finally:
                if cause is not None:
                    self._kill_group(procs)
            self.rounds.append({"port": port, "outcome": cause})
            self._register_restart(cause)
