"""MultiLayerNetwork: linear layer stack with a fully-jitted training engine.

Rebuild of upstream ``org.deeplearning4j.nn.multilayer.MultiLayerNetwork``.
API parity: ``init``, ``fit(iterator)``, ``output``, ``score``, ``evaluate``,
``params``, ``set_listeners``, ``rnn_time_step`` / ``rnn_clear_previous_state``
(stateful inference), truncated BPTT, transfer-learning freeze support.

TPU-first re-architecture (NOT a port — SURVEY.md §7.1):

- The reference dispatches one JNI call per op per layer per step; here the
  ENTIRE step (forward, loss, backward via ``jax.grad``, updater, param
  update) is one XLA program, compiled once, with the state pytree donated —
  the analog of the reference's flat-params buffer reused in place.
- The reference's hand-written ``backpropGradient`` per layer does not exist:
  autodiff of the composed forward provides it.
- Updater state lives next to params in :class:`TrainState` (reference:
  ``UpdaterBlock`` flat views), so checkpoints capture exact resume state.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from deeplearning4j_tpu.nn.base import GlobalConfig, Layer, cast_floating, with_tied
from deeplearning4j_tpu.nn.config import MultiLayerConfiguration
from deeplearning4j_tpu.nn.core_layers import LossLayer, OutputLayer
from deeplearning4j_tpu.models._tbptt import carry_dtype, slice_time
from deeplearning4j_tpu.nn.recurrent_layers import BaseRecurrentLayer
from deeplearning4j_tpu.runtime.environment import get_environment
from deeplearning4j_tpu.train.fit_engine import TrainEngine, TrainState
from deeplearning4j_tpu.train.prefetch import coerce_training_batch
from deeplearning4j_tpu.train.solvers import solver_fit_batch
from deeplearning4j_tpu.train.updaters import Sgd, Updater


def _layer_key(i: int, layer: Layer) -> str:
    return layer.name or f"layer_{i}"


class MultiLayerNetwork(TrainEngine):
    def __init__(self, conf: MultiLayerConfiguration):
        super().__init__(conf.global_conf.seed)
        self.conf = conf
        self.layers: List[Layer] = conf.layers
        for l in self.layers:
            l._g = conf.global_conf
        self._rnn_carries: Optional[Dict[str, Any]] = None

    # ------------------------------------------------------------------ init
    def init(self, params: Optional[Dict] = None) -> "MultiLayerNetwork":
        """Initialise parameters and optimizer state (reference ``init()``)."""
        g = self.conf.global_conf
        if g.dtype is None:
            g = dataclasses.replace(g, dtype=get_environment().default_dtype)
        def init_all(key):
            # one jitted program for ALL param draws — per-param eager init
            # would emit hundreds of tiny kernels (slow under remote compile)
            ps: Dict[str, Dict] = {}
            ss: Dict[str, Dict] = {}
            for i, layer in enumerate(self.layers):
                it = self.conf.layer_input_types[i] if self.conf.layer_input_types else None
                p, s = layer.init(jax.random.fold_in(key, i), it, g)
                k = _layer_key(i, layer)
                if p:
                    ps[k] = p
                if s:
                    ss[k] = s
            return ps, ss

        new_params, model_state = jax.jit(init_all)(jax.random.PRNGKey(g.seed))
        if params is not None:
            new_params = params
        for layer in self.layers:
            with_tied(layer, {}, new_params)  # a tie that names nothing fails here, not in the first step
        self._tx = self._build_tx(new_params)
        trainable = self._trainable(new_params)
        opt_state = self._tx.init(trainable)
        self.train_state = TrainState(
            params=new_params, model_state=model_state, opt_state=opt_state,
            step=jnp.zeros((), jnp.int32))
        self._jit_cache.clear()
        return self

    def _trainable(self, params):
        # Frozen layers keep params but receive zero updates (handled by labels)
        return params

    def _named_layers(self):
        return [(_layer_key(i, layer), layer)
                for i, layer in enumerate(self.layers)]

    # --------------------------------------------------------------- forward
    def _forward(self, params, model_state, x, *, training: bool, rng,
                 fmask=None, carries: Optional[Dict] = None, labels=None):
        """Compose all layers; returns (final_out, pre_output_input, new_state,
        new_carries). ``pre_output_input`` is the input fed to the final
        (output) layer — AFTER that layer's input dropout, so the fused loss
        path and the forward output see the same dropped activations.
        ``fmask``: (batch, time) features mask threaded to sequence layers.
        ``labels`` go to the layers that set ``takes_labels``; a layer's
        ``tied`` parameters are read from their owners (``nn.base.with_tied``)."""
        env = get_environment()
        cdt = env.compute_dtype
        if jnp.issubdtype(x.dtype, jnp.floating) and x.dtype != cdt:
            x = x.astype(cdt)
        params = cast_floating(params, cdt)
        new_state = dict(model_state)
        new_carries = {} if carries is not None else None
        last_input = x
        n = len(self.layers)
        # A pure chain: every layer boundary is a remat cut point. With
        # env.remat_segments on, each hidden layer's activations are
        # recomputed in the backward pass instead of saved — HBM traffic
        # traded for FLOPs (same policy as ComputationGraph._forward_remat).
        # A layer that sets ``remat_in_scopes`` recomputes inside each of its
        # named scopes instead (``nn.attention_layers.scoped``).
        use_remat = (env.remat_segments and training and carries is None
                     and n > 2)
        for i, layer in enumerate(self.layers):
            k = _layer_key(i, layer)
            # the layer boundary of the device trace: every op below is
            # named ``<layer key>.<LayerClass>/...`` (docs/observability.md)
            with jax.named_scope(f"{k}.{type(layer).__name__}"):
                if i in self.conf.preprocessors:
                    x = self.conf.preprocessors[i].pre_process(x, fmask)
                p = with_tied(layer, params.get(k, {}), params)
                s = model_state.get(k, {})
                lrng = jax.random.fold_in(rng, i) if rng is not None else None
                extra = {"labels": labels} if layer.takes_labels else {}
                if training and getattr(layer, "weight_noise", None) is not None:
                    from deeplearning4j_tpu.nn.constraints import apply_weight_noise
                    p = apply_weight_noise(
                        layer, p,
                        None if lrng is None else jax.random.fold_in(lrng, 7919))
                if i == n - 1 and hasattr(layer, "compute_loss"):
                    x = layer._apply_input_dropout(x, layer._g, training, lrng)
                    last_input = x
                    x = layer.activate(p, x)
                elif carries is not None and isinstance(layer, BaseRecurrentLayer):
                    x = layer._apply_input_dropout(x, layer._g, training, lrng)
                    y, c_new = layer.forward_with_carry(
                        p, carries[k], x, training=training, rng=lrng, mask=fmask)
                    new_carries[k] = c_new
                    x = y
                else:
                    if use_remat and i < n - 1 and not getattr(layer, "remat_in_scopes", False):
                        def _fwd(p_, s_, x_, lrng_, fmask_, _l=layer):
                            return _l.forward(p_, s_, x_, training=True,
                                              rng=lrng_, mask=fmask_)
                        x, s_new = jax.checkpoint(_fwd)(p, s, x, lrng, fmask)
                    else:
                        x, s_new = layer.forward(p, s, x, training=training,
                                                 rng=lrng, mask=fmask, **extra)
                    if s:
                        new_state[k] = s_new
                if fmask is not None and hasattr(layer, "transform_mask"):
                    # layers that change the time axis (crop/pad) realign the mask
                    fmask = layer.transform_mask(fmask)
        return x, last_input, new_state, new_carries

    def _loss(self, params, model_state, x, y, rng, fmask=None, lmask=None,
              carries=None, training: bool = True):
        out, last_in, new_state, new_carries = self._forward(
            params, model_state, x, training=training, rng=rng, fmask=fmask,
            carries=carries, labels=y)
        final = self.layers[-1]
        if not hasattr(final, "compute_loss"):
            raise ValueError("Last layer must be an output/loss layer to compute loss")
        k = _layer_key(len(self.layers) - 1, final)
        final_p = cast_floating(with_tied(final, params.get(k, {}), params), get_environment().compute_dtype)
        if training and getattr(final, "weight_noise", None) is not None \
                and rng is not None:
            # SAME noise keys as _forward's output-layer branch, so the loss
            # sees exactly the weights the forward activations used
            from deeplearning4j_tpu.nn.constraints import apply_weight_noise
            lrng = jax.random.fold_in(rng, len(self.layers) - 1)
            final_p = apply_weight_noise(final, final_p,
                                         jax.random.fold_in(lrng, 7919))
        with jax.named_scope("loss"):
            loss = final.compute_loss(final_p, last_in, y, mask=lmask,
                                      state=model_state.get(k, {}))
            if "main_loss" in model_state.get(k, {}):  # an output layer with ``record_loss``
                new_state = {**new_state, k: {**model_state[k], "main_loss": loss.astype(jnp.float32)}}
            if hasattr(final, "loss_state"):  # a head that keeps more of its last step than the loss
                new_state = {**new_state, k: final.loss_state(model_state.get(k, {}), loss, y)}
            loss = loss + self._reg_score(params)
        # differentiable auxiliary losses surfaced by layers through the
        # state channel (e.g. MoE load balancing) — same trace, so grads
        # flow. Training-only: score() reports the data loss, not training
        # regularizers.
        if training:
            for s2 in new_state.values():
                if isinstance(s2, dict) and "_aux_loss" in s2:
                    loss = loss + s2["_aux_loss"]
        if training and hasattr(final, "update_state_with_labels"):
            new_state = dict(new_state)
            new_state[k] = final.update_state_with_labels(
                model_state.get(k, {}), jax.lax.stop_gradient(last_in), y)
        return loss, (new_state, new_carries)

    # ------------------------------------------------------------------- fit
    def fit(self, data, labels=None, epochs: int = 1, mask=None,
            labels_mask=None, prefetch_buffer: int = 0,
            profiler=None) -> "MultiLayerNetwork":
        """``fit(iterator)``, ``fit(iterator, epochs=N)`` or
        ``fit(x, y[, mask, labels_mask])`` (reference overloads —
        ``fit(features, labels, featuresMask, labelsMask)``). ``mask`` is the
        FEATURES mask; the labels mask defaults to it propagated through any
        time-axis-changing layers.

        ``prefetch_buffer > 0`` stages that many coerced batches on-device
        ahead of the step via a background
        :class:`~deeplearning4j_tpu.train.prefetch.DevicePrefetcher`
        (trajectory bit-identical to the synchronous loop); ``profiler``
        takes a :class:`~deeplearning4j_tpu.train.profiler.TrainingProfiler`
        that splits each iteration into data-wait/dispatch/step time."""
        return self._fit(data, labels, epochs, prefetch_buffer, profiler,
                         mask, labels_mask)

    def _prepare_batch(self, batch):
        args = coerce_training_batch(self, batch)
        return args, args[0].shape[0]

    def _divert(self, args):
        # zero-copy ref for listeners that sample activations
        # (StatsListener histograms)
        self._last_batch_features = args[0]
        return super()._divert(args)

    _solver_fit_batch = solver_fit_batch  # (net, x, y, fmask, lmask) -> loss

    def _tbptt_plan(self, x, y, fmask, lmask):
        """Zero carries, and the batch cut along its time axis into
        tbptt-length chunks."""
        L = int(self.conf.tbptt_fwd_length)

        def chunks():
            for t0 in range(0, x.shape[1], L):
                yield (slice_time(x, t0, L),
                       y[:, t0:t0 + L] if y.ndim >= 3 else y,
                       fmask[:, t0:t0 + L] if fmask is not None else None,
                       lmask[:, t0:t0 + L] if lmask is not None else None)

        return self._zero_carries(
            x.shape[0], carry_dtype(x, get_environment().compute_dtype)), chunks()

    # -------------------------------------------------------------- pretrain
    def pretrain(self, iterator, epochs: int = 1) -> "MultiLayerNetwork":
        """Greedy layer-wise unsupervised pretraining (reference
        ``MultiLayerNetwork.pretrain(DataSetIterator)``): every layer exposing
        a ``pretrain_loss`` (VAE, AutoEncoder) is trained in order on the
        unsupervised objective, with the layers below it frozen as a feature
        extractor."""
        for i, layer in enumerate(self.layers):
            if hasattr(layer, "pretrain_loss"):
                self.pretrain_layer(i, iterator, epochs=epochs)
        return self

    def pretrain_layer(self, i: int, iterator, epochs: int = 1) -> "MultiLayerNetwork":
        """Pretrain layer ``i`` only (reference ``pretrainLayer``). One jitted
        donated step: stop-gradient sub-forward through layers < i, then a
        gradient step on layer i's unsupervised loss."""
        if self.train_state is None:
            self.init()
        layer = self.layers[i]
        if not hasattr(layer, "pretrain_loss"):
            return self
        k = _layer_key(i, layer)
        g = self.conf.global_conf
        upd: Updater = layer.updater if layer.updater is not None else (
            g.updater if g.updater is not None else Sgd(0.1))
        tx = upd.make()

        def sub_input(params, model_state, x):
            cur = x
            for j in range(i):
                lay = self.layers[j]
                if j in self.conf.preprocessors:
                    cur = self.conf.preprocessors[j].pre_process(cur, None)
                cur, _ = lay.forward(params.get(_layer_key(j, lay), {}),
                                     model_state.get(_layer_key(j, lay), {}),
                                     cur, training=False, rng=None)
            if i in self.conf.preprocessors:
                cur = self.conf.preprocessors[i].pre_process(cur, None)
            return cur

        def step(layer_params, opt_state, below_params, model_state, x, rng):
            inp = jax.lax.stop_gradient(sub_input(below_params, model_state, x))
            loss, grads = jax.value_and_grad(
                lambda p: layer.pretrain_loss(p, inp, rng))(layer_params)
            updates, opt_state = tx.update(grads, opt_state, layer_params)
            return optax.apply_updates(layer_params, updates), opt_state, loss

        step_fn = self._jitted(f"pretrain_{i}", lambda: jax.jit(step, donate_argnums=(0, 1)))
        layer_params = self.train_state.params[k]
        # layer_params is donated; it must NOT also alias in via below_params
        # (donation frees the buffer — the aliased copy would be deleted)
        below_params = {kk: v for kk, v in self.train_state.params.items() if kk != k}
        opt_state = tx.init(layer_params)
        for _ in range(int(epochs)):
            iterator.reset()
            for batch in iterator:
                x = jnp.asarray(batch.features)
                layer_params, opt_state, loss = step_fn(
                    layer_params, opt_state, below_params,
                    self.train_state.model_state, x, self.rng.next_key())
                self._score = loss
        new_params = dict(self.train_state.params)
        new_params[k] = layer_params
        self.train_state = dataclasses.replace(self.train_state, params=new_params)
        return self

    def _output_time_mask(self, fmask):
        """Features mask propagated through every time-axis-changing layer
        (crop/pad/upsample/strided conv): the default LABELS mask must align
        with the network OUTPUT's time axis, not the input's."""
        if fmask is None:
            return None
        m = fmask
        for layer in self.layers:
            if hasattr(layer, "transform_mask"):
                m = layer.transform_mask(m)
        return m

    def _zero_carries(self, batch: int, dtype) -> Dict[str, Any]:
        carries = {}
        for i, layer in enumerate(self.layers):
            if isinstance(layer, BaseRecurrentLayer):
                carries[_layer_key(i, layer)] = layer.init_carry(batch, dtype)
        return carries

    # ------------------------------------------------------------- inference
    def output(self, x, training: bool = False, mask=None):
        """Forward pass (reference ``output(INDArray)``)."""
        if self.train_state is None:
            self.init()

        def fwd(params, model_state, x_, m_):
            out, _, _, _ = self._forward(params, model_state, x_,
                                         training=False, rng=None, fmask=m_)
            return out

        fn = self._jitted("output", lambda: jax.jit(fwd))
        m = None if mask is None else jnp.asarray(mask)
        return fn(self.train_state.params, self.train_state.model_state,
                  jnp.asarray(x), m)

    def feed_forward(self, x, num_layers: Optional[int] = None):
        """All layer activations (reference ``feedForward``) — not jitted;
        debugging/inspection path. ``num_layers`` stops after that many
        layers (reference ``feedForwardToLayer``)."""
        acts = [jnp.asarray(x)]
        cur = acts[0]
        ts = self.train_state
        stop = len(self.layers) if num_layers is None else int(num_layers)
        for i, layer in enumerate(self.layers[:stop]):
            if i in self.conf.preprocessors:
                cur = self.conf.preprocessors[i].pre_process(cur)
            k = _layer_key(i, layer)
            cur, _ = layer.forward(ts.params.get(k, {}), ts.model_state.get(k, {}),
                                   cur, training=False, rng=None)
            acts.append(cur)
        return acts

    def feed_forward_to_layer(self, layer_num: int, x):
        """Reference ``feedForwardToLayer(layerNum, input)``: activations of
        the input plus layers ``0..layer_num`` inclusive."""
        return self.feed_forward(x, num_layers=layer_num + 1)

    # --------------------------------------------------- external errors
    def backprop_gradient(self, x, epsilon):
        """Reference external-errors mode (``backpropGradient(epsilon)``
        after ``feedForward``): given dL/dOutput produced OUTSIDE this
        network (e.g. this net is an embedded component of a larger system),
        return ``(param_gradients, dL/dInput)`` — one jitted vjp, no update."""
        if self.train_state is None:
            self.init()
        x = jnp.asarray(x)
        epsilon = jnp.asarray(epsilon)

        def fn(params, model_state, x_, eps):
            def f(p, xx):
                out, _, new_state, _ = self._forward(
                    p, model_state, xx, training=True, rng=None)
                return out, new_state
            out, vjp, _ = jax.vjp(f, params, x_, has_aux=True)
            gp, gx = vjp(eps.astype(out.dtype))
            return gp, gx

        fn = self._jitted("backprop_external", lambda: jax.jit(fn))
        return fn(self.train_state.params, self.train_state.model_state,
                  x, epsilon)

    def fit_external(self, x, epsilon):
        """External-errors TRAINING step: backprop ``epsilon`` (dL/dOutput)
        through the net and apply the configured updater — the reference's
        ``computeGradientAndScore``-with-external-errors + updater pattern,
        fused into one jitted donated step."""
        if self.train_state is None:
            self.init()
        x = jnp.asarray(x)
        epsilon = jnp.asarray(epsilon)

        def make():
            def step(ts: TrainState, x_, eps, rng):
                def f(p, xx):
                    out, _, new_state, _ = self._forward(
                        p, ts.model_state, xx, training=True, rng=rng)
                    return out, new_state
                out, vjp, new_state = jax.vjp(f, ts.params, x_, has_aux=True)
                gp, gx = vjp(eps.astype(out.dtype))
                gp = self._trainable(gp)
                updates, new_opt = self._tx.update(gp, ts.opt_state, ts.params)
                new_params = optax.apply_updates(ts.params, updates)
                return TrainState(params=new_params, model_state=new_state,
                                  opt_state=new_opt, step=ts.step + 1), gx
            return jax.jit(step, donate_argnums=(0,))

        fn = self._jitted("fit_external", make)
        self.train_state, gx = fn(self.train_state, x, epsilon,
                                  self.rng.next_key())
        self._iteration += 1
        return gx

    def _rnn_step_fn(self, training: bool = False):
        """The jitted ``(params, model_state, carries, x, rng) ->
        (out, new_carries)`` program behind every stateful-RNN entry point.
        One cache key per ``training`` flag: :meth:`rnn_time_step`,
        :meth:`rnn_activate_using_stored_state` and
        :meth:`rnn_time_step_external` all share the SAME compiled
        executable, so a serving-tier external step is bit-identical to
        the stored-state step at the same program shape."""
        def make():
            def fwd(params, model_state, carries, x_, rng):
                out, _, _, new_carries = self._forward(
                    params, model_state, x_, training=training, rng=rng,
                    carries=carries)
                return out, new_carries
            return jax.jit(fwd)

        return self._jitted(f"rnn_stored_state@train={training}", make)

    def rnn_activate_using_stored_state(self, x, training: bool = False,
                                        store_last_for_tbptt: bool = False):
        """Reference ``rnnActivateUsingStoredState``: forward a sequence
        starting from the STORED recurrent state; optionally keep the final
        state (the tBPTT carry behaviour). Returns the output activations."""
        if self.train_state is None:
            self.init()
        x = jnp.asarray(x)
        if self._rnn_carries is None:
            self._rnn_carries = self._zero_carries(
                x.shape[0], carry_dtype(x, get_environment().compute_dtype))
        fn = self._rnn_step_fn(training)
        rng = self.rng.next_key() if training else None
        out, new_carries = fn(self.train_state.params,
                              self.train_state.model_state,
                              self._rnn_carries, x, rng)
        if store_last_for_tbptt:
            self._rnn_carries = new_carries
        return out

    def score(self, dataset=None) -> float:
        """Loss on a DataSet (inference behaviour: no dropout, running BN
        stats — matching the reference's ``score(DataSet)``), or the most
        recent minibatch score when called with no argument."""
        if dataset is None:
            return float(self._score)
        x, y = jnp.asarray(dataset.features), jnp.asarray(dataset.labels)
        fm = None if dataset.features_mask is None else jnp.asarray(dataset.features_mask)
        lm = jnp.asarray(dataset.labels_mask) if dataset.labels_mask is not None \
            else (self._output_time_mask(fm) if y.ndim == 3 else None)

        def score_fn(params, model_state, x_, y_, fm_, lm_):
            loss, _ = self._loss(params, model_state, x_, y_, None, fm_, lm_,
                                 training=False)
            return loss

        fn = self._jitted("score", lambda: jax.jit(score_fn))
        return float(fn(self.train_state.params, self.train_state.model_state,
                        x, y, fm, lm))

    def evaluate(self, iterator):
        """Classification evaluation over an iterator (reference
        ``evaluate(DataSetIterator)``)."""
        from deeplearning4j_tpu.evaluation.evaluation import Evaluation
        ev = Evaluation()
        iterator.reset()
        for batch in iterator:
            out = self.output(batch.features, mask=batch.features_mask)
            m = batch.labels_mask if batch.labels_mask is not None else (
                None if batch.features_mask is None
                else np.asarray(self._output_time_mask(jnp.asarray(batch.features_mask))))
            ev.eval(np.asarray(batch.labels), np.asarray(out),
                    mask=None if m is None else np.asarray(m))
        return ev

    def evaluate_regression(self, iterator):
        from deeplearning4j_tpu.evaluation.regression import RegressionEvaluation
        ev = RegressionEvaluation()
        iterator.reset()
        for batch in iterator:
            out = self.output(batch.features)
            ev.eval(np.asarray(batch.labels), np.asarray(out))
        return ev

    def evaluate_roc(self, iterator, threshold_steps: int = 0):
        from deeplearning4j_tpu.evaluation.roc import ROC
        roc = ROC(threshold_steps)
        iterator.reset()
        for batch in iterator:
            out = self.output(batch.features)
            roc.eval(np.asarray(batch.labels), np.asarray(out))
        return roc

    # ------------------------------------------------ stateful RNN inference
    def rnn_time_step(self, x):
        """Stateful sequence inference (reference ``rnnTimeStep``): feeds a
        (batch, time, size) chunk, returns output and stores recurrent state
        for the next call. Same compiled program as
        :meth:`rnn_activate_using_stored_state`."""
        return self.rnn_activate_using_stored_state(
            x, training=False, store_last_for_tbptt=True)

    def rnn_clear_previous_state(self) -> None:
        self._rnn_carries = None

    def rnn_get_state(self):
        """Serializable copy of the stored recurrent state (reference
        ``rnnGetPreviousState``, whole network instead of per-layer): a
        pytree with numpy leaves whose dtypes match the carries exactly,
        or ``None`` when no state is stored. Round-trips bit-exactly
        through :meth:`rnn_set_state` — the contract the serving session
        store spills to disk."""
        if self._rnn_carries is None:
            return None
        return jax.tree.map(np.asarray, self._rnn_carries)

    def rnn_set_state(self, state) -> None:
        """Install a recurrent state previously captured with
        :meth:`rnn_get_state` (reference ``rnnSetPreviousState``); ``None``
        clears, like :meth:`rnn_clear_previous_state`. Leaf dtypes are
        preserved as given — no recast — so set(get()) is bit-exact."""
        self._rnn_carries = (None if state is None
                             else jax.tree.map(jnp.asarray, state))

    def rnn_zero_state(self, batch: int, like=None):
        """Fresh zero recurrent state for a ``batch``-row stream: the tree
        :meth:`rnn_time_step` would lazily create on its first call.
        ``like`` (an example input) pins the carry dtype the same way the
        stateful path does; without it the environment compute dtype is
        used."""
        if self.train_state is None:
            self.init()
        dt = (get_environment().compute_dtype if like is None else
              carry_dtype(jnp.asarray(like), get_environment().compute_dtype))
        return self._zero_carries(batch, dt)

    def rnn_time_step_external(self, x, state):
        """Pure-functional ``rnnTimeStep``: advance ``state`` (a tree from
        :meth:`rnn_get_state` / :meth:`rnn_zero_state`, or ``None`` for a
        fresh stream) by one input chunk WITHOUT touching the state stored
        on the network. Returns ``(out, new_state)``. Same compiled
        program as :meth:`rnn_time_step` — at equal program shape the two
        are bit-identical — which is what lets the serving session tier
        batch many independent streams through one executable."""
        if self.train_state is None:
            self.init()
        x = jnp.asarray(x)
        if state is None:
            state = self._zero_carries(
                x.shape[0], carry_dtype(x, get_environment().compute_dtype))
        fn = self._rnn_step_fn(training=False)
        out, new_state = fn(self.train_state.params,
                            self.train_state.model_state, state, x, None)
        return out, new_state

    # -------------------------------------------------------------- plumbing
    def get_layer(self, key) -> Layer:
        """Layer by index or name (reference ``getLayer``)."""
        if isinstance(key, int):
            return self.layers[key]
        for i, l in enumerate(self.layers):
            if _layer_key(i, l) == key or l.name == key:
                return l
        raise KeyError(key)

    def summary(self) -> str:
        """Layer table: name, type, in->out shape, #params (reference
        ``MultiLayerNetwork.summary()``)."""
        if self.train_state is None:
            self.init()
        rows = [("idx", "name", "type", "nIn -> nOut", "params")]
        total = 0
        for i, layer in enumerate(self.layers):
            k = _layer_key(i, layer)
            p = self.train_state.params.get(k, {})
            n = int(sum(np.prod(w.shape) for w in jax.tree.leaves(p)))
            total += n
            it = (self.conf.layer_input_types[i]
                  if self.conf.layer_input_types else None)
            shape = ""
            if it is not None:
                try:
                    shape = f"{it.describe()} -> {layer.output_type(it).describe()}"
                except Exception:
                    shape = ""
            rows.append((str(i), k, type(layer).__name__, shape, f"{n:,}"))
        widths = [max(len(r[c]) for r in rows) for c in range(5)]
        lines = ["  ".join(v.ljust(w) for v, w in zip(r, widths))
                 for r in rows]
        lines.insert(1, "-" * len(lines[0]))
        lines.append(f"Total parameters: {total:,}")
        return "\n".join(lines)

    # serialization (reference ModelSerializer.writeModel / save+load methods)
    def save(self, path: str, save_updater: bool = True) -> None:
        from deeplearning4j_tpu.models.serializer import ModelSerializer
        ModelSerializer.write_model(self, path, save_updater=save_updater)

    @staticmethod
    def load(path: str, load_updater: bool = True) -> "MultiLayerNetwork":
        from deeplearning4j_tpu.models.serializer import ModelSerializer
        return ModelSerializer.restore_multi_layer_network(path, load_updater=load_updater)

    def clone(self) -> "MultiLayerNetwork":
        net = MultiLayerNetwork(MultiLayerConfiguration.from_dict(self.conf.to_dict()))
        if self.train_state is not None:
            net.init(params=jax.tree.map(jnp.copy, self.train_state.params))
            net.train_state = dataclasses.replace(
                net.train_state, model_state=jax.tree.map(jnp.copy, self.train_state.model_state))
        return net
