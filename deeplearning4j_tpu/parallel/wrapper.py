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

from deeplearning4j_tpu.parallel.sharding import (ShardingStrategy, shard_batch,
                                                  shard_batch_tree,
                                                  shard_train_state)
from deeplearning4j_tpu.runtime.environment import get_environment
from deeplearning4j_tpu.runtime.mesh import DATA_AXIS, MODEL_AXIS, create_mesh
from deeplearning4j_tpu.train.listeners import PerformanceListener


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
        conf = getattr(self.model, "conf", None)
        gc = getattr(conf, "global_conf", None)
        algo = getattr(gc, "optimization_algo",
                       "STOCHASTIC_GRADIENT_DESCENT") or \
            "STOCHASTIC_GRADIENT_DESCENT"
        if algo != "STOCHASTIC_GRADIENT_DESCENT":
            raise NotImplementedError(
                f"ParallelWrapper supports optimization_algo=SGD only "
                f"(got {algo!r}); legacy solvers run single-context via "
                "the model's own fit()")

    def _check_not_tbptt(self, x):
        from deeplearning4j_tpu.models._tbptt import is_sequence_array
        if getattr(getattr(self.model, "conf", None),
                   "tbptt_fwd_length", None) and is_sequence_array(x):
            raise NotImplementedError(
                "tBPTT training under ParallelWrapper is not supported — "
                "the wrapper would run full-sequence BPTT instead of the "
                "model's tBPTT chunking; use the model's own fit(), or "
                "full-sequence BPTT (unset tbptt_fwd_length) to train "
                "sharded")

    def _ensure_sharded(self):
        self._check_supported()
        if self.model.train_state is None:
            self.model.init()
        if not self._sharded:
            self.model.train_state = shard_train_state(self.model.train_state, self.strategy)
            self._sharded = True

    def _prepare_batch(self, batch):
        """Host→device for one batch: coercion (shared helper), tBPTT
        guard, then the sharded ``jax.device_put`` with the strategy's
        ``NamedSharding``s. Pure with respect to model state, so the
        prefetch worker runs it ahead of the current step. Returns
        ``(step_args_without_rng, n_examples)`` — MultiLayerNetwork steps
        take (ts, x, y, rng, fmask, lmask); ComputationGraph takes
        (ts, inputs_dict, labels_list, rng, masks)."""
        from deeplearning4j_tpu.train.prefetch import coerce_training_batch
        model = self.model
        if hasattr(model, "_coerce_batch"):  # ComputationGraph
            inputs, labels_, masks = model._coerce_batch(batch)
            for v in inputs.values():
                self._check_not_tbptt(v)
            inputs = shard_batch_tree(self.strategy, inputs)
            labels_ = shard_batch_tree(self.strategy, labels_)
            masks = None if masks is None else shard_batch_tree(
                self.strategy, masks)
            n = next(iter(inputs.values())).shape[0]
            return (inputs, labels_, masks), n
        x, y, fm, lm = coerce_training_batch(model, batch)
        self._check_not_tbptt(x)
        x, y, fm, lm = shard_batch(self.strategy, x, y, fm, lm)
        return (x, y, fm, lm), x.shape[0]

    def _insert_rng(self, args, rng):
        """Step args with the NEXT rng key (drawn at dispatch time) spliced
        in — key order (and so the trajectory) follows submission order,
        never prefetch completion order."""
        if hasattr(self.model, "_coerce_batch"):  # (inputs, labels, rng, masks)
            return (args[0], args[1], rng, args[2])
        return (args[0], args[1], rng, args[2], args[3])

    def _run_group(self, step_fn_unused, group):
        """K compatible buffered steps as ONE device dispatch
        (``env.dispatch_unroll``) — the sharded counterpart of the fit
        loops' packed grouped dispatch (sharded state cannot pack, see
        ``runtime/state_packing.py``)."""
        from deeplearning4j_tpu.runtime.state_packing import (
            make_unrolled_step, step_args_signature)
        model = self.model
        k = len(group)
        fn = model._jitted(
            f"pw_unrolled@k={k}",
            lambda: make_unrolled_step(model._train_step_fn(), k))
        model.train_state, losses = self._aot().call(
            ("pw-group", self.strategy.signature(), k,
             step_args_signature(group[0][0])),
            fn, model.train_state, [args for args, _n in group])
        return [losses[i] for i in range(k)]

    def _aot(self):
        """The sharded-dispatch AOT executable cache, stored in the model's
        jit cache so ``init()`` invalidation covers it. Lowering captures
        the committed NamedShardings, so a (graph, shape, mesh) signature
        maps to exactly one executable."""
        from deeplearning4j_tpu.runtime.compile_cache import AotCache
        return self.model._jit_cache.setdefault(
            "__aot_pw__", AotCache("pw-step"))

    def _fit_pipe(self, iterator, epochs: int, profiler=None):
        """Pipe-axis fit: the model's uniform trunk is stage-stacked and
        streamed through the GPipe shift register (``plan_exec``); each pipe
        device holds 1/S of the trunk, the ``data`` axis (if present) shards
        the batch. Same listener/epoch semantics as the SPMD path; the
        trained params are written back to ``model.train_state``."""
        from deeplearning4j_tpu.parallel.plan_exec import PipePlanExecutor
        from deeplearning4j_tpu.runtime.state_packing import (
            step_args_signature)
        from deeplearning4j_tpu.train.prefetch import batch_source
        self._check_supported()
        model = self.model
        if hasattr(model, "_coerce_batch"):
            raise NotImplementedError(
                "pipe-axis plans drive MultiLayerNetwork layer stacks; "
                "ComputationGraph topologies have no linear trunk to stage")
        if model.train_state is None:
            model.init()
        if getattr(self, "_pipe_exec", None) is None:
            self._pipe_exec = PipePlanExecutor(model, self.strategy)
        ex = self._pipe_exec
        packed_ts, tx = ex.packed_state()
        step_fn = jax.jit(ex.make_train_step(tx), donate_argnums=(0,))
        aot = self._aot()
        plan_sig = self.strategy.signature()
        if profiler is not None:
            profiler.start()

        try:
            with self.strategy.mesh:
                for _ in range(int(epochs)):
                    for lst in model._listeners:
                        lst.on_epoch_start(model, model._epoch)
                    src = batch_source(iterator, self._prepare_batch,
                                       self.prefetch_buffer, profiler)
                    try:
                        for args, n in src:
                            args = self._insert_rng(args,
                                                    model.rng.next_key())
                            if args[3] is not None:
                                raise NotImplementedError(
                                    "feature masks are not supported under "
                                    "pipe-axis plans")
                            packed_ts, loss = aot.call(
                                ("pw-pipe", plan_sig,
                                 step_args_signature(args)),
                                step_fn, packed_ts, *args)
                            model._score = loss
                            model._iteration += 1
                            for lst in model._listeners:
                                if isinstance(lst, PerformanceListener):
                                    lst.record_batch(n)
                                lst.iteration_done(model, model._iteration,
                                                   model._epoch, loss)
                    finally:
                        src.close()
                    for lst in model._listeners:
                        lst.on_epoch_end(model, model._epoch)
                    model._epoch += 1
        finally:
            if profiler is not None:
                profiler.stop()
        ex.sync_back(packed_ts)
        return model

    def fit(self, iterator, epochs: int = 1, profiler=None):
        """Distributed fit: same listener/epoch semantics (and bit-identical
        trajectory) as the wrapped model's own ``fit``, with batches sharded
        across the mesh, prefetched ``prefetch_buffer`` deep, and losses
        delivered on the async completion path. ``profiler`` takes a
        :class:`~deeplearning4j_tpu.train.profiler.TrainingProfiler`.

        Plans with a ``pipe`` axis route through the GPipe executor
        (:meth:`_fit_pipe`) — same call, pipelined execution."""
        if self.strategy.pipe_size > 1:
            return self._fit_pipe(iterator, epochs, profiler)
        from deeplearning4j_tpu.runtime.state_packing import GroupedDispatch
        from deeplearning4j_tpu.train.prefetch import (AsyncLossDelivery,
                                                       batch_source,
                                                       stateless_listeners)
        from deeplearning4j_tpu.train.profiler import (drain_timed,
                                                        submit_timed)
        self._ensure_sharded()
        model = self.model
        step_fn = model._jitted("train_step", model._make_train_step)
        if hasattr(model, "_coerce_batch"):
            from deeplearning4j_tpu.models.computation_graph import (
                _cg_group_compatible as base_compat)
        else:
            from deeplearning4j_tpu.models.multi_layer_network import (
                _group_compatible as base_compat)
        stateless = stateless_listeners(model)
        if profiler is not None:
            profiler.start()

        from deeplearning4j_tpu.runtime.state_packing import (
            step_args_signature)
        aot = self._aot()

        def run_single(item):
            args, _n = item
            # the plan signature joins the key: plan drift (axis added or
            # resized, schedule knob changed) misses the cache and
            # recompiles — never a stale executable for the wrong mesh
            out = aot.call(("pw", self.strategy.signature(),
                            step_args_signature(args)),
                           step_fn, model.train_state, *args)
            model.train_state, loss = out
            return loss

        def deliver(n, loss):
            model._score = loss
            model._iteration += 1
            for lst in model._listeners:
                if isinstance(lst, PerformanceListener):
                    lst.record_batch(n)
                lst.iteration_done(model, model._iteration, model._epoch, loss)

        # async loss readback (see MultiLayerNetwork._fit_epochs): a
        # state-reading listener forces synchronous one-at-a-time delivery;
        # no listeners and no profiler = deliver inline, no thread
        adel = (AsyncLossDelivery(deliver, profiler=profiler)
                if (model._listeners or profiler is not None)
                and stateless else None)
        # only the batch SIZE crosses into the delivery queue — queued step
        # args would pin full sharded batches for up to max_pending steps
        sink = adel.submit if adel is not None else deliver
        gd = GroupedDispatch(
            unroll=(get_environment().dispatch_unroll if stateless else 1),
            compatible=lambda a, b: base_compat(a[0], b[0]),
            run_single=run_single,
            run_group=lambda group: self._run_group(step_fn, group),
            deliver=lambda item, loss: sink(item[1], loss))
        drain = adel.flush if adel is not None else (lambda: None)
        try:
            with self.strategy.mesh:
                for _ in range(int(epochs)):
                    for lst in model._listeners:
                        lst.on_epoch_start(model, model._epoch)
                    src = batch_source(iterator, self._prepare_batch,
                                       self.prefetch_buffer, profiler)
                    try:
                        for args, n in src:
                            submit_timed(
                                gd, model.rng,
                                lambda key: (self._insert_rng(args, key), n),
                                profiler)
                    finally:
                        src.close()
                    drain_timed(gd, drain, profiler)
                    for lst in model._listeners:
                        lst.on_epoch_end(model, model._epoch)
                    model._epoch += 1
        finally:
            gd.drain_on_error()
            if adel is not None:
                adel.shutdown()  # never raises; original errors win
            if profiler is not None:
                profiler.stop()
        if adel is not None:
            adel.raise_pending()
        return model
