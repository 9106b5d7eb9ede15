"""ParallelWrapper: multi-device training driver.

Rebuild of upstream ``org.deeplearning4j.parallelism.ParallelWrapper`` — but
where the reference spawns one trainer thread per GPU and averages params (or
exchanges threshold-encoded gradients through host-side accumulators), here
the wrapped network's OWN jitted train step runs SPMD over the mesh: the
batch is sharded on the ``data`` axis, params follow the
:class:`ShardingStrategy` (replicated for DP, sharded for FSDP/TP), and XLA
emits the gradient allreduce over ICI. There are no trainer threads, no
averaging frequency, no encoded updates — one compiled program IS the
distributed trainer, and it is mathematically equivalent to synchronous
all-reduce SGD (averaging every iteration).

The FEED path is a staged pipeline (ISSUE 4, mirroring the serving
executor): a :class:`~deeplearning4j_tpu.train.prefetch.DevicePrefetcher`
coerces batches and issues the sharded ``jax.device_put`` up to
``prefetch_buffer`` batches ahead of the running step (the reference's
``prefetchBuffer`` workspace ring, TPU-native), dispatch is unified onto
``GroupedDispatch`` (honoring ``env.dispatch_unroll`` with an unrolled
sharded step), and listener delivery rides the async completion path so a
listener reading ``float(loss)`` never stalls dispatch. Trajectories are
bit-identical to the synchronous loop — same batch order, same rng-key
sequence, same compiled step.

Multi-node: run the same script per host after
``runtime.mesh.initialize_multihost()`` — the mesh then spans hosts and the
same step runs globally (the reference needed Spark + Aeron for this).
"""

from __future__ import annotations

from typing import Optional

import jax

from deeplearning4j_tpu.parallel.sharding import (ShardingStrategy,
                                                  shard_batch_tree,
                                                  shard_train_state)
from deeplearning4j_tpu.runtime.mesh import DATA_AXIS, MODEL_AXIS, create_mesh
from deeplearning4j_tpu.runtime.state_packing import step_args_signature
from deeplearning4j_tpu.train.fit_engine import run_fit
from deeplearning4j_tpu.train.prefetch import stateless_listeners


class _ShardedSteps:
    """``run_fit``'s dispatcher over a mesh: the network's plain jitted
    step on sharded per-leaf state (sharded state cannot pack, see
    ``runtime/state_packing.py``), batches sharded ahead of the step."""

    def __init__(self, net, strategy: ShardingStrategy):
        self._net = net
        self._strategy = strategy
        # a state-reading listener forces one-at-a-time, inline delivery
        self.grouped = stateless_listeners(net)
        self._step_fn = net._jitted("train_step", net._make_train_step)
        # Lowering captures the committed NamedShardings, so a (graph,
        # shape, mesh) signature maps to exactly one executable. The plan
        # signature joins every key: plan drift (axis added or resized,
        # schedule knob changed) misses the cache and recompiles — never a
        # stale executable for the wrong mesh.
        self._aot = net._aot_cache("__aot_pw__", "pw-step")
        self._plan = strategy.signature()

    def context(self):
        return self._strategy.mesh

    def prepare(self, batch):
        """Host→device for one batch: the network's coercion, the tBPTT
        guard, then the sharded ``jax.device_put`` with the strategy's
        ``NamedSharding``s. Pure with respect to model state, so the
        prefetch worker runs it ahead of the current step."""
        args, n = self._net._prepare_batch(batch)
        if self._net._tbptt_applies(args):
            raise NotImplementedError(
                "tBPTT training under ParallelWrapper is not supported — "
                "the wrapper would run full-sequence BPTT instead of the "
                "model's tBPTT chunking; use the model's own fit(), or "
                "full-sequence BPTT (unset tbptt_fwd_length) to train "
                "sharded")
        return shard_batch_tree(self._strategy, args), n

    def step(self, args):
        net = self._net
        net.train_state, loss = self._aot.call(
            ("pw", self._plan, step_args_signature(args)),
            self._step_fn, net.train_state, *args)
        return loss

    def step_group(self, group):
        """K compatible buffered steps as ONE device dispatch
        (``env.dispatch_unroll``)."""
        net = self._net
        k = len(group)
        net.train_state, losses = self._aot.call(
            ("pw-group", self._plan, k, step_args_signature(group[0])),
            net._jitted_unrolled(k), net.train_state, group)
        return [losses[i] for i in range(k)]

    def sync(self, release: bool = False) -> None:
        pass  # every step leaves its state on the network


class _PipeSteps(_ShardedSteps):
    """Pipe-axis dispatcher: the model's uniform trunk is stage-stacked and
    streamed through the GPipe shift register (``plan_exec``); each pipe
    device holds 1/S of the trunk, the ``data`` axis (if present) shards
    the batch. The stacked state lives here until :meth:`sync` writes the
    trained params back to ``model.train_state``."""

    def __init__(self, net, strategy, executor):
        super().__init__(net, strategy)
        self.grouped = False  # one step at a time, delivered inline
        self._executor = executor
        self._ts, tx = executor.packed_state()
        self._step_fn = jax.jit(executor.make_train_step(tx),
                                donate_argnums=(0,))

    def step(self, args):
        if args[3] is not None:
            raise NotImplementedError(
                "feature masks are not supported under pipe-axis plans")
        self._ts, loss = self._aot.call(
            ("pw-pipe", self._plan, step_args_signature(args)),
            self._step_fn, self._ts, *args)
        return loss

    def sync(self, release: bool = False) -> None:
        # a donated step that raised left no state to write back
        if not any(a.is_deleted() for a in jax.tree.leaves(self._ts)):
            self._executor.sync_back(self._ts)


class ParallelWrapper:
    """Usage (mirrors the reference's builder)::

        pw = (ParallelWrapper.builder(net)
              .workers(8)                      # optional; defaults to all devices
              .strategy("data_parallel")       # or "fsdp" / "tensor_parallel"
              .prefetch_buffer(2)              # sharded device prefetch depth
              .build())
        pw.fit(iterator, epochs=2)
    """

    def __init__(self, model, strategy: Optional[ShardingStrategy] = None,
                 prefetch_buffer: int = 2):
        self.model = model
        if strategy is None:
            strategy = ShardingStrategy.data_parallel(create_mesh())
        self.strategy = strategy
        # batches staged on-device ahead of the step (reference default 2);
        # 0 = fully synchronous feed path (bit-identical either way)
        self.prefetch_buffer = max(0, int(prefetch_buffer))
        self._sharded = False

    # -- builder API (reference parity) --
    class Builder:
        def __init__(self, model):
            self._model = model
            self._workers = None
            self._strategy_name = "data_parallel"
            self._prefetch_buffer = 2

        def workers(self, n: int) -> "ParallelWrapper.Builder":
            self._workers = int(n)
            return self

        def strategy(self, name: str) -> "ParallelWrapper.Builder":
            self._strategy_name = name
            return self

        # reference knobs that are no-ops under sync-SPMD (documented parity):
        def averaging_frequency(self, n: int) -> "ParallelWrapper.Builder":
            return self  # sync allreduce == averaging every iteration

        def prefetch_buffer(self, n: int) -> "ParallelWrapper.Builder":
            """Sharded device-prefetch depth (reference ``prefetchBuffer``);
            0 disables the background stage."""
            self._prefetch_buffer = max(0, int(n))
            return self

        def build(self) -> "ParallelWrapper":
            devs = jax.devices()
            if self._workers:
                devs = devs[: self._workers]
            if self._strategy_name == "tensor_parallel":
                # TP needs a `model` mesh axis; default to all devices on
                # it (Megatron single-node style). Build an explicit
                # data x model ShardingStrategy for hybrid DPxTP.
                mesh = create_mesh({DATA_AXIS: 1, MODEL_AXIS: -1},
                                   devices_=devs)
            else:
                mesh = create_mesh(devices_=devs)
            factory = {
                "data_parallel": ShardingStrategy.data_parallel,
                "fsdp": ShardingStrategy.fsdp,
                "tensor_parallel": ShardingStrategy.tensor_parallel,
            }[self._strategy_name]
            return ParallelWrapper(self._model, factory(mesh),
                                   prefetch_buffer=self._prefetch_buffer)

    @staticmethod
    def builder(model) -> "ParallelWrapper.Builder":
        return ParallelWrapper.Builder(model)

    # -- training --
    def _check_supported(self):
        """ParallelWrapper drives the model's PLAIN jitted SGD step; modes
        the model's own fit() special-cases (tBPTT chunking, legacy
        solvers) would silently train with different gradients here — so
        refuse loudly instead. tBPTT is checked per-batch (the models'
        own fit engages it only for sequence batches)."""
        algo = self.model.conf.global_conf.optimization_algo
        if algo != "STOCHASTIC_GRADIENT_DESCENT":
            raise NotImplementedError(
                f"ParallelWrapper supports optimization_algo=SGD only "
                f"(got {algo!r}); legacy solvers run single-context via "
                "the model's own fit()")

    def _pipe_dispatcher(self):
        from deeplearning4j_tpu.models.multi_layer_network import (
            MultiLayerNetwork)
        from deeplearning4j_tpu.parallel.plan_exec import PipePlanExecutor
        if not isinstance(self.model, MultiLayerNetwork):
            raise NotImplementedError(
                "pipe-axis plans drive MultiLayerNetwork layer stacks; "
                "ComputationGraph topologies have no linear trunk to stage")
        if getattr(self, "_pipe_exec", None) is None:
            self._pipe_exec = PipePlanExecutor(self.model, self.strategy)
        return _PipeSteps(self.model, self.strategy, self._pipe_exec)

    def fit(self, iterator, epochs: int = 1, profiler=None):
        """Distributed fit: same listener/epoch semantics (and bit-identical
        trajectory) as the wrapped model's own ``fit``, with batches sharded
        across the mesh, prefetched ``prefetch_buffer`` deep, and losses
        delivered on the async completion path. ``profiler`` takes a
        :class:`~deeplearning4j_tpu.train.profiler.TrainingProfiler`.

        Plans with a ``pipe`` axis route through the GPipe executor
        (:class:`_PipeSteps`) — same call, pipelined execution."""
        self._check_supported()
        if self.model.train_state is None:
            self.model.init()
        if self.strategy.pipe_size > 1:
            dispatcher = self._pipe_dispatcher()
        else:
            if not self._sharded:
                self.model.train_state = shard_train_state(
                    self.model.train_state, self.strategy)
                self._sharded = True
            dispatcher = _ShardedSteps(self.model, self.strategy)
        run_fit(self.model, iterator, int(epochs), dispatcher,
                self.prefetch_buffer, profiler)
        return self.model
