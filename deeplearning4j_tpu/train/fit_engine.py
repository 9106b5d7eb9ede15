"""How a prepared batch becomes a dispatched step and a delivered loss.

One decision, owned here and nowhere else:

- :class:`TrainEngine`, the base of ``MultiLayerNetwork`` and
  ``ComputationGraph``: the donated :class:`TrainState`, the jit cache, and
  the train-step factories written once over the engine's ``_loss`` and
  ``_tx``. A step is ``(ts, *batch) -> (new_ts, loss)`` with the batch
  passed through positionally, so MLN's ``(x, y, rng, fmask, lmask)`` and
  CG's ``(inputs, labels, rng, masks)`` need no branch; the rng key is the
  third batch argument in both.
- :func:`run_fit`, the one fit loop: epochs, listeners, the feed path
  (``train.prefetch``), grouped dispatch, async loss delivery, the tBPTT /
  solver diversion and the exit ladder. It drives a *dispatcher*:
  ``prepare(batch) -> (args_without_rng, n)`` (runs on the prefetch
  worker), ``step(args) -> loss``, ``step_group([args]) -> [loss]``,
  ``sync(release=False)``, ``grouped`` (may steps be grouped and delivered
  off-thread) and ``context()`` (the mesh, or nothing).
- :class:`PackedStepLoop`, the dispatcher of a network's own ``fit``
  (packed state or plain). ``parallel/wrapper.py`` holds the other two
  (sharded over a mesh; staged through the pipe executor).

An engine says only what differs: ``_named_layers``, ``_loss``,
``_prepare_batch``, ``_tbptt_plan`` and ``_solver_fit_batch``. Nothing under ``runtime/``
imports this module; ``runtime/state_packing.py`` keeps what knows no
network (``LeafPacker``, the unrolled-step makers, ``GroupedDispatch``).
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax

from deeplearning4j_tpu.runtime.compile_cache import AotCache
from deeplearning4j_tpu.runtime.environment import get_environment
from deeplearning4j_tpu.runtime.rng import RngManager
from deeplearning4j_tpu.runtime.state_packing import (GroupedDispatch,
                                                      LeafPacker,
                                                      make_unrolled_packed_step,
                                                      make_unrolled_step,
                                                      step_args_signature)
from deeplearning4j_tpu.train.listeners import (PerformanceListener,
                                                TrainingListener)
from deeplearning4j_tpu.train.prefetch import (AsyncLossDelivery, batch_source,
                                               stateless_listeners)
from deeplearning4j_tpu.train.profiler import (drain_timed, submit_timed,
                                               sync_timed)
from deeplearning4j_tpu.train.updaters import (Sgd, Updater,
                                               decoupled_weight_decay,
                                               gradient_normalization_transform)

_SGD = "STOCHASTIC_GRADIENT_DESCENT"


def _mask_keys(params, keys):
    """Boolean mask pytree: True where the leaf's dict key is a regularizable
    param name (weight-decay applies to weights, not biases/norm scales)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: any(getattr(p, "key", None) in keys for p in path), params)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    """Donated training state: one pytree through the jitted step."""

    params: Dict[str, Dict[str, jax.Array]]
    model_state: Dict[str, Dict[str, jax.Array]]
    opt_state: Any
    step: jax.Array  # scalar int32


class TrainEngine:
    """What ``MultiLayerNetwork`` and ``ComputationGraph`` share: the state
    a fit reads and writes, the listeners, and the compiled train steps."""

    def __init__(self, seed):
        self.rng = RngManager(seed)
        self.train_state: Optional[TrainState] = None
        self._listeners: List[TrainingListener] = []
        self._iteration = 0
        self._epoch = 0
        self._score = float("nan")
        self._tx: Optional[optax.GradientTransformation] = None
        self._jit_cache: Dict[str, Any] = {}

    # --------------------------------------------- updater, penalty, projection
    def _layer_transform(self, layer) -> optax.GradientTransformation:
        """The optax transform one layer's params train under — shared by
        the standard per-layer-key multi_transform and the pipe executor's
        stage-stacked trunk (``parallel/plan_exec.py``), so packed and
        unpacked updates are the same math."""
        g = self.conf.global_conf
        default_updater: Updater = g.updater if g.updater is not None else Sgd(0.1)
        if layer.frozen:
            return optax.set_to_zero()
        upd = layer.updater if layer.updater is not None else default_updater
        chain = []
        gn = gradient_normalization_transform(
            g.gradient_normalization, g.gradient_normalization_threshold)
        if gn is not None:
            chain.append(gn)
        chain.append(upd.make())
        wd = layer.weight_decay if layer.weight_decay is not None else g.weight_decay
        if wd:
            # Decoupled decay AFTER the updater, scaled by the LR (the
            # reference's WeightDecay with applyLR=true; AdamW-style).
            reg_keys = set(layer.regularizable_params())
            chain.append(decoupled_weight_decay(
                wd, upd._lr(), mask=lambda p, rk=reg_keys: _mask_keys(p, rk)))
        return optax.chain(*chain) if len(chain) > 1 else chain[0]

    def _build_tx(self, params) -> optax.GradientTransformation:
        transforms: Dict[str, optax.GradientTransformation] = {}
        labels = {}
        for k, layer in self._named_layers():
            if k not in params:
                continue
            transforms[k] = self._layer_transform(layer)
            labels[k] = jax.tree.map(lambda _: k, params[k])
        return optax.multi_transform(transforms, labels)

    def _reg_score(self, params):
        """l1/l2 penalty (reference: score includes regularization terms).
        Walks nested param trees (e.g. Bidirectional {'fwd': .., 'bwd': ..})
        by path, matching the weight-decay mask semantics."""
        g = self.conf.global_conf
        total = jnp.zeros((), jnp.float32)
        for k, layer in self._named_layers():
            if k not in params:
                continue
            l1 = layer.l1 if layer.l1 is not None else g.l1
            l2 = layer.l2 if layer.l2 is not None else g.l2
            if not l1 and not l2:
                continue
            reg_keys = set(layer.regularizable_params())
            leaves = jax.tree_util.tree_flatten_with_path(params[k])[0]
            for path, w in leaves:
                if any(getattr(p, "key", None) in reg_keys for p in path):
                    if l1:
                        total = total + l1 * jnp.sum(jnp.abs(w))
                    if l2:
                        total = total + 0.5 * l2 * jnp.sum(w * w)
        return total

    def _apply_constraints(self, params):
        """Post-update projections (reference applyConstraints) — pure ops
        inside the same compiled step."""
        from deeplearning4j_tpu.nn.constraints import apply_layer_constraints
        layers = self._named_layers()
        if not any(getattr(l, "constraints", None)
                   or getattr(l, "bias_constraints", None)
                   for _, l in layers):
            return params
        out = dict(params)
        for k, layer in layers:
            if k in out:
                out[k] = apply_layer_constraints(layer, out[k])
        return out

    # ------------------------------------------------------------ train step
    def _apply_update(self, ts: TrainState, grads, new_state, project):
        with jax.named_scope("updater"):
            updates, new_opt = self._tx.update(grads, ts.opt_state, ts.params)
            new_params = project(optax.apply_updates(ts.params, updates))
        return TrainState(params=new_params, model_state=new_state,
                          opt_state=new_opt, step=ts.step + 1)

    def _train_step_fn(self):
        # The jitted functions keep these names for both engines: the
        # persistent cache key holds the module's name but not its metadata,
        # so a renamed scope needs a renamed step or a cleared cache
        # (docs/observability.md, "Training"). A graph's HLO differs from a
        # stack's, so the shared name collides with nothing.
        def mln_train_step(ts: TrainState, *batch):
            (loss, (new_state, _)), grads = jax.value_and_grad(
                self._loss, has_aux=True)(ts.params, ts.model_state, *batch)
            return self._apply_update(ts, grads, new_state,
                                      self._apply_constraints), loss

        return mln_train_step

    def _make_train_step(self):
        return jax.jit(self._train_step_fn(), donate_argnums=(0,))

    def _make_packed_train_step(self):
        """Train step whose boundary carries flat-packed small leaves
        (see :mod:`deeplearning4j_tpu.runtime.state_packing`): same math,
        bit-identical results, ~4x fewer buffer handles per dispatch."""
        packer = LeafPacker(self.train_state)
        raw = self._train_step_fn()

        def packed_train_step(pts, *batch):
            new_ts, loss = raw(packer.unpack(pts), *batch)
            return packer.pack(new_ts), loss

        return jax.jit(packed_train_step, donate_argnums=(0,)), packer

    def _make_tbptt_step(self):
        """Train step with explicit recurrent carries (truncated BPTT)."""
        def tbptt_train_step(ts: TrainState, carries, *batch):
            (loss, (new_state, new_carries)), grads = jax.value_and_grad(
                self._loss, has_aux=True)(ts.params, ts.model_state, *batch,
                                          carries=carries)
            new_ts = self._apply_update(ts, grads, new_state, lambda p: p)
            return (new_ts, jax.tree.map(jax.lax.stop_gradient, new_carries),
                    loss)

        return jax.jit(tbptt_train_step, donate_argnums=(0, 1))

    def _jitted(self, name: str, factory):
        # remat is read at TRACE time, so flipping env.set_remat() must
        # produce a different cache entry
        name = f"{name}@remat={get_environment().remat_segments}"
        if name not in self._jit_cache:
            self._jit_cache[name] = factory()
        return self._jit_cache[name]

    def _packed_cache_key(self) -> str:
        return f"packed_train_step@remat={get_environment().remat_segments}"

    def _jitted_packed(self):
        """``(packed step, its packer)``; keyed by :meth:`_packed_cache_key`
        so :meth:`_drop_packed_steps` pops what this populates."""
        key = self._packed_cache_key()
        if key not in self._jit_cache:
            self._jit_cache[key] = self._make_packed_train_step()
        return self._jit_cache[key]

    def _jitted_packed_unrolled(self, k: int):
        """K same-shape batches per device dispatch (env.dispatch_unroll).
        Shares the single-step packer, so packed state flows between
        grouped and single dispatches. (Mask presence needs no key
        component: jit retraces on the None-vs-array pytree structure.)"""
        key = f"{self._packed_cache_key()}@unroll={k}"
        if key not in self._jit_cache:
            _, packer = self._jitted_packed()
            self._jit_cache[key] = make_unrolled_packed_step(
                self._train_step_fn(), packer, k)
        return self._jit_cache[key]

    def _jitted_unrolled(self, k: int):
        """The per-leaf counterpart of :meth:`_jitted_packed_unrolled`, for
        state that cannot pack (sharded over a mesh)."""
        return self._jitted(
            f"unrolled_train_steps@k={k}",
            lambda: make_unrolled_step(self._train_step_fn(), k))

    def _drop_packed_steps(self) -> None:
        """Forget the packed steps (their ``@unroll`` variants too): the
        state's tree changed under the packer they were built around."""
        prefix = self._packed_cache_key()
        for k in [k for k in self._jit_cache
                  if isinstance(k, str) and k.startswith(prefix)]:
            del self._jit_cache[k]

    def _aot_cache(self, slot: str, name: str) -> AotCache:
        """A dispatcher's AOT executables live in the network's jit cache,
        so repeated fits reuse them, ``init()`` invalidates them, and the
        readers of ``hlo_texts()`` find them among its values."""
        return self._jit_cache.setdefault(slot, AotCache(name))

    # ------------------------------------------------------------------- fit
    def _fit(self, data, labels, epochs, prefetch_buffer, profiler,
             mask=None, labels_mask=None):
        """``fit``'s body in both engines: ``(x, y)`` arrays become a
        one-batch iterator, and the network's own dispatcher drives
        :func:`run_fit`."""
        if self.train_state is None:
            self.init()
        if labels is not None:
            from deeplearning4j_tpu.data.dataset import DataSet
            from deeplearning4j_tpu.data.iterators import ListDataSetIterator
            ds = DataSet(np.asarray(data), np.asarray(labels),
                         features_mask=mask, labels_mask=labels_mask)
            data = ListDataSetIterator([ds], batch_size=len(ds))
        run_fit(self, data, int(epochs), PackedStepLoop(self),
                int(prefetch_buffer), profiler)
        return self

    # ------------------------------------------------------- out of the step
    @staticmethod
    def _with_rng(args, key):
        """Step arguments with the step's rng key spliced in: third, after
        the features and the labels, in both engines."""
        return (*args[:2], key, *args[2:])

    def _tbptt_applies(self, args) -> bool:
        """Whether ``fit`` trains this batch by truncated BPTT: a length is
        configured and an input has a time axis."""
        if not self.conf.tbptt_fwd_length:
            return False
        from deeplearning4j_tpu.models._tbptt import is_sequence_array
        return any(is_sequence_array(v) for v in jax.tree.leaves(args[0]))

    def _divert(self, args):
        """``None`` for a batch the compiled step trains, else the function
        that trains it outside the step, on a synced ``train_state``: tBPTT
        (delivers each chunk itself, returns nothing) or a solver (returns
        the loss to deliver)."""
        algo = self.conf.global_conf.optimization_algo
        if self._tbptt_applies(args):
            if algo != _SGD:
                raise NotImplementedError(
                    f"truncated BPTT is only supported with {_SGD} "
                    f"(optimization_algo={algo!r}); use it or full-sequence "
                    "BPTT")
            return lambda: self._fit_tbptt(*args)
        if algo != _SGD:
            return lambda: self._solver_fit_batch(*args)
        return None

    def _fit_tbptt(self, *args) -> None:
        """Train on the time axis cut into tbptt-length chunks, carrying
        hidden state between them (reference: truncated BPTT in
        ``MultiLayerNetwork.fitHelper`` and on ``ComputationGraph``)."""
        carries, chunks = self._tbptt_plan(*args)
        step_fn = self._jitted("tbptt_step", self._make_tbptt_step)
        for chunk in chunks:
            self.train_state, carries, loss = step_fn(
                self.train_state, carries,
                *self._with_rng(chunk, self.rng.next_key()))
            self._score = loss
            self._iteration += 1
            for lst in self._listeners:
                lst.iteration_done(self, self._iteration, self._epoch, loss)

    # -------------------------------------------------------------- plumbing
    def set_listeners(self, *listeners: TrainingListener) -> None:
        self._listeners = list(listeners)

    def add_listeners(self, *listeners: TrainingListener) -> None:
        self._listeners.extend(listeners)

    def get_listeners(self) -> Sequence[TrainingListener]:
        return list(self._listeners)

    @property
    def iteration(self) -> int:
        return self._iteration

    @property
    def epoch(self) -> int:
        return self._epoch

    def params(self):
        return self.train_state.params if self.train_state else None

    def set_params(self, params) -> None:
        if self.train_state is None:
            self.init(params=params)
        else:
            self.train_state = dataclasses.replace(self.train_state, params=params)

    def num_params(self) -> int:
        if self.train_state is None:
            return 0
        return int(sum(np.prod(p.shape) for p in jax.tree.leaves(self.train_state.params)))


class PackedStepLoop:
    """The dispatcher of a network's own ``fit``: its jitted train step over
    packed state, or plain when packing is off.

    Lazily packs ``net.train_state`` on the first :meth:`step`; callers must
    :meth:`sync` before anything else reads or writes ``net.train_state``
    (solver and tBPTT branches, ``fit``'s return). ``sync(release=True)``
    additionally drops the packed copy so a subsequent step re-packs from
    the (possibly externally modified) state.

    Dispatch rides the AOT fast path (``env.aot_dispatch``): per step-args
    signature, the loop calls a cached ``lower().compile()`` executable
    with the donated packed buffers instead of re-entering jit dispatch —
    bit-identical trajectories (same trace → same executable).
    """

    context = staticmethod(contextlib.nullcontext)

    def __init__(self, net: TrainEngine):
        self._net = net
        # One listener gate for packing, grouping and async loss delivery,
        # which must never desynchronize: a state-reading listener has to
        # see ITS iteration's post-step train_state, one step at a time.
        self.grouped = stateless_listeners(net)
        self._packs = self.grouped and get_environment().packed_state
        self._packed = None
        self._step_fn = None
        self._packer = None
        self._aot = net._aot_cache("__aot__", "fit-step")

    def prepare(self, batch):
        return self._net._prepare_batch(batch)

    def _pack(self) -> None:
        self._step_fn, self._packer = self._net._jitted_packed()
        self._packed = self._packer.pack_device(self._net.train_state)

    def step(self, args):
        """One train step; returns its loss (a device scalar, lazy)."""
        net = self._net
        if not self._packs:
            if self._step_fn is None:
                self._step_fn = net._jitted("train_step", net._make_train_step)
            net.train_state, loss = self._aot.call(
                ("plain", step_args_signature(args)),
                self._step_fn, net.train_state, *args)
            return loss
        if self._packed is None:
            try:
                self._pack()
            # Structure changed since the packer was built. A changed
            # treedef/dtype raises ValueError; a changed leaf SHAPE with the
            # same treedef surfaces as TypeError from the reshape inside
            # pack — both mean "rebuild the packer".
            except (ValueError, TypeError):
                net._drop_packed_steps()
                # AOT executables were lowered from the stale packed step
                self._aot.clear()
                self._pack()
        self._packed, loss = self._aot.call(
            ("packed", net._packed_cache_key(), step_args_signature(args)),
            self._step_fn, self._packed, *args)
        return loss

    def step_group(self, group):
        """Run a list of per-step argument tuples as ONE unrolled device
        dispatch (env.dispatch_unroll). All tuples in the group share one
        signature (``run_fit`` guarantees it). Returns the per-step losses
        (device scalars, lazy)."""
        if not self._packs or len(group) == 1:
            return [self.step(args) for args in group]
        if self._packed is None:
            # first call packs lazily: run the first batch single-step,
            # then the rest as a (possibly shorter) group
            return [self.step(group[0])] + self.step_group(group[1:])
        net = self._net
        self._packed, losses = self._aot.call(
            ("packed-group", net._packed_cache_key(), len(group),
             step_args_signature(group[0])),
            net._jitted_packed_unrolled(len(group)), self._packed,
            [tuple(args) for args in group])
        return [losses[i] for i in range(len(group))]

    def sync(self, release: bool = False) -> None:
        """Refresh ``net.train_state`` from the packed buffers.

        If a donated step consumed the packed buffers and then raised (NaN
        panic, device error), no post-step state exists anywhere — sync
        drops the dead packed copy WITHOUT raising, so the original
        exception propagates; ``net.train_state`` is then whatever was last
        synced, and recovery is checkpoint restore (reference semantics for
        a crashed fit are the same).
        """
        if self._packed is None:
            return
        if LeafPacker.is_dead(self._packed):
            self._packed = None
            return
        self._net.train_state = self._packer.unpack_device(
            self._packed, donate=release)
        if release:
            self._packed = None


def run_fit(net: TrainEngine, iterator, epochs: int, dispatcher,
            prefetch_buffer: int = 0, profiler=None) -> None:
    """The fit loop of ``MultiLayerNetwork``, ``ComputationGraph`` and
    ``ParallelWrapper``: ``epochs`` passes over ``iterator``, each batch
    prepared by the dispatcher (``prefetch_buffer`` deep ahead of the step
    when > 0), keyed in submission order, dispatched (grouped when the
    dispatcher allows it) and its loss delivered to the listeners in that
    same order. Any exit path, an iterator's or a listener's error and
    KeyboardInterrupt included, leaves ``net.train_state`` reflecting every
    completed step."""
    def deliver(n, loss):
        net._score = loss
        net._iteration += 1
        for lst in net._listeners:
            if isinstance(lst, PerformanceListener):
                lst.record_batch(n)
            lst.iteration_done(net, net._iteration, net._epoch, loss)

    # Async loss readback: with only stateless listeners, delivery moves to
    # a completion thread (same callbacks, same order) so a listener reading
    # float(loss) no longer blocks dispatch of the next step. No listeners
    # and no profiler = nothing worth a thread: deliver inline.
    adel = (AsyncLossDelivery(deliver, profiler=profiler)
            if (net._listeners or profiler is not None) and dispatcher.grouped
            else None)
    # only the batch SIZE crosses into the delivery queue — queued step
    # args would pin full device batches for up to max_pending steps
    sink = adel.submit if adel is not None else deliver
    drain = adel.flush if adel is not None else (lambda: None)
    gd = GroupedDispatch(  # of (step args, batch size) items
        unroll=get_environment().dispatch_unroll if dispatcher.grouped else 1,
        compatible=lambda a, b: (step_args_signature(a[0])
                                 == step_args_signature(b[0])),
        run_single=lambda item: dispatcher.step(item[0]),
        run_group=lambda items: dispatcher.step_group([a for a, _ in items]),
        deliver=lambda item, loss: sink(item[1], loss))
    if profiler is not None:
        profiler.start()
    try:
        with dispatcher.context():
            for _ in range(epochs):
                for lst in net._listeners:
                    lst.on_epoch_start(net, net._epoch)
                src = batch_source(iterator, dispatcher.prepare,
                                   prefetch_buffer, profiler)
                try:
                    for args, n in src:
                        run = net._divert(args)
                        if run is not None:
                            gd.flush()
                            drain()  # tBPTT notifies listeners inline (ordered)
                            dispatcher.sync(release=True)  # run() mutates train_state
                            loss = run()
                            if loss is not None:
                                sink(n, loss)
                            continue
                        submit_timed(
                            gd, net.rng,
                            lambda key: (net._with_rng(args, key), n), profiler)
                finally:
                    src.close()
                drain_timed(gd, drain, profiler)
                # no epoch-end sync: state is held off the network only when
                # every listener is stateless, so nothing reads train_state
                # until fit() returns
                for lst in net._listeners:
                    lst.on_epoch_end(net, net._epoch)
                net._epoch += 1
    finally:
        gd.drain_on_error()
        if adel is not None:
            adel.shutdown()  # never raises; original errors win
        sync_timed(dispatcher, profiler)
        if profiler is not None:
            profiler.stop()
            profiler.record_model_state(net.train_state.model_state)
    if adel is not None:
        adel.raise_pending()
