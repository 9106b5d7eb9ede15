"""ComputationGraph: arbitrary-DAG network with the same jitted engine.

Rebuild of upstream ``org.deeplearning4j.nn.graph.ComputationGraph`` +
``ComputationGraphConfiguration.GraphBuilder``: named inputs, layer nodes and
merge/elementwise/... vertices, multiple outputs, topological execution.
TPU-first: the whole DAG traces into ONE jitted program (the reference walks
the topo order dispatching per-op); multi-output losses sum (with optional
weighting) exactly like the reference's multi-output training.

Usage (mirrors the reference)::

    conf = (NeuralNetConfiguration.builder().updater(Adam(1e-3)).graph_builder()
            .add_inputs("in")
            .add_layer("conv1", ConvolutionLayer(n_out=32, ...), "in")
            .add_layer("fc", DenseLayer(n_out=128, ...), "conv1")
            .add_layer("out", OutputLayer(n_out=10, ...), "fc")
            .set_outputs("out")
            .set_input_types(InputType.convolutional(28, 28, 1))
            .build())
    net = ComputationGraph(conf).init()
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from deeplearning4j_tpu.nn.base import GlobalConfig, Layer
from deeplearning4j_tpu.nn.core_layers import LossLayer, OutputLayer
from deeplearning4j_tpu.nn.graph_vertices import GraphVertex
from deeplearning4j_tpu.nn.inputs import InputType
from deeplearning4j_tpu.nn.base import cast_floating
from deeplearning4j_tpu.models._tbptt import (carry_dtype, is_sequence_array,
                                               seq_length, slice_time)
from deeplearning4j_tpu.nn.recurrent_layers import BaseRecurrentLayer
from deeplearning4j_tpu.runtime.environment import get_environment
from deeplearning4j_tpu.train.fit_engine import TrainEngine, TrainState
from deeplearning4j_tpu.train.solvers import graph_solver_fit_batch
from deeplearning4j_tpu.train.updaters import Updater


@dataclasses.dataclass
class GraphNode:
    name: str
    kind: str  # "layer" | "vertex"
    obj: Any  # Layer or GraphVertex
    inputs: List[str]


class GraphBuilder:
    def __init__(self, g: GlobalConfig):
        self._g = g
        self._inputs: List[str] = []
        self._nodes: List[GraphNode] = []
        self._outputs: List[str] = []
        self._input_types: List[InputType] = []
        self._tbptt_fwd: Optional[int] = None

    def add_inputs(self, *names: str) -> "GraphBuilder":
        self._inputs.extend(names)
        return self

    def add_layer(self, name: str, layer: Layer, *inputs: str) -> "GraphBuilder":
        layer.name = name
        self._nodes.append(GraphNode(name, "layer", layer, list(inputs)))
        return self

    def add_vertex(self, name: str, vertex: GraphVertex, *inputs: str) -> "GraphBuilder":
        self._nodes.append(GraphNode(name, "vertex", vertex, list(inputs)))
        return self

    def set_outputs(self, *names: str) -> "GraphBuilder":
        self._outputs = list(names)
        return self

    def set_input_types(self, *types: InputType) -> "GraphBuilder":
        self._input_types = list(types)
        return self

    def tbptt_fwd_length(self, n: int) -> "GraphBuilder":
        self._tbptt_fwd = int(n)
        return self

    def build(self) -> "ComputationGraphConfiguration":
        conf = ComputationGraphConfiguration(
            global_conf=self._g, inputs=self._inputs, nodes=self._nodes,
            outputs=self._outputs, input_types=self._input_types,
            tbptt_fwd_length=self._tbptt_fwd)
        conf._toposort_and_infer()
        return conf


@dataclasses.dataclass
class ComputationGraphConfiguration:
    global_conf: GlobalConfig
    inputs: List[str]
    nodes: List[GraphNode]
    outputs: List[str]
    input_types: List[InputType] = dataclasses.field(default_factory=list)
    tbptt_fwd_length: Optional[int] = None
    topo_order: List[str] = dataclasses.field(default_factory=list)
    node_input_types: Dict[str, InputType] = dataclasses.field(default_factory=dict)

    def node(self, name: str) -> GraphNode:
        for n in self.nodes:
            if n.name == name:
                return n
        raise KeyError(name)

    def _toposort_and_infer(self) -> None:
        by_name = {n.name: n for n in self.nodes}
        dup = len(by_name) != len(self.nodes)
        if dup:
            raise ValueError("Duplicate node names in graph")
        visited: Dict[str, int] = {}
        order: List[str] = []

        def visit(name: str):
            if name in self.inputs:
                return
            st = visited.get(name, 0)
            if st == 1:
                raise ValueError(f"Cycle detected at {name!r}")
            if st == 2:
                return
            visited[name] = 1
            for dep in by_name[name].inputs:
                visit(dep)
            visited[name] = 2
            order.append(name)

        for out in self.outputs:
            visit(out)
        # include any stragglers (nodes not reachable from outputs)
        for n in self.nodes:
            visit(n.name)
        self.topo_order = order

        # shape inference
        types: Dict[str, InputType] = {}
        for i, name in enumerate(self.inputs):
            if i < len(self.input_types):
                types[name] = self.input_types[i]
        for name in self.topo_order:
            node = by_name[name]
            in_types = [types.get(i) for i in node.inputs]
            if any(t is None for t in in_types):
                self.node_input_types[name] = None
                types[name] = None
                continue
            if node.kind == "layer":
                from deeplearning4j_tpu.nn.config import MultiLayerConfiguration
                pp = MultiLayerConfiguration._auto_preprocessor(in_types[0], node.obj)
                if pp is not None:
                    node.inputs_preprocessor = pp
                    in_types[0] = pp.output_type(in_types[0])
                else:
                    node.inputs_preprocessor = getattr(node, "inputs_preprocessor", None)
                self.node_input_types[name] = in_types[0]
                types[name] = node.obj.output_type(in_types[0])
            else:
                self.node_input_types[name] = in_types[0]
                types[name] = node.obj.output_type(*in_types)
        self.output_types = [types.get(o) for o in self.outputs]

    # ---- serde ----
    def to_dict(self) -> dict:
        g = dataclasses.asdict(self.global_conf)
        if self.global_conf.updater is not None and hasattr(self.global_conf.updater, "to_dict"):
            g["updater"] = self.global_conf.updater.to_dict()
        for k in ("weight_init", "activation"):
            v = g.get(k)
            if hasattr(v, "value"):
                g[k] = v.value
        if g.get("dtype") is not None:
            g["dtype"] = jnp.dtype(g["dtype"]).name
        return {
            "model_type": "ComputationGraph",
            "global_conf": g,
            "inputs": self.inputs,
            "outputs": self.outputs,
            "input_types": [t.to_dict() for t in self.input_types],
            "tbptt_fwd_length": self.tbptt_fwd_length,
            "nodes": [{"name": n.name, "kind": n.kind, "inputs": n.inputs,
                       "obj": n.obj.to_dict()} for n in self.nodes],
        }

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), indent=2)

    @staticmethod
    def from_dict(d: dict) -> "ComputationGraphConfiguration":
        import dataclasses as dc
        g_d = dict(d["global_conf"])
        if isinstance(g_d.get("updater"), dict):
            g_d["updater"] = Updater.from_dict(g_d["updater"])
        if isinstance(g_d.get("dtype"), str):
            g_d["dtype"] = jnp.dtype(g_d["dtype"]).type
        from deeplearning4j_tpu.ops.initializers import WeightInit
        if g_d.get("weight_init"):
            g_d["weight_init"] = WeightInit(g_d["weight_init"])
        g = GlobalConfig(**{k: v for k, v in g_d.items()
                            if k in {f.name for f in dc.fields(GlobalConfig)}})
        nodes = []
        for nd in d["nodes"]:
            obj = Layer.from_dict(nd["obj"]) if nd["kind"] == "layer" \
                else GraphVertex.from_dict(nd["obj"])
            nodes.append(GraphNode(nd["name"], nd["kind"], obj, list(nd["inputs"])))
        conf = ComputationGraphConfiguration(
            global_conf=g, inputs=list(d["inputs"]), nodes=nodes,
            outputs=list(d["outputs"]),
            input_types=[InputType.from_dict(t) for t in d.get("input_types", [])],
            tbptt_fwd_length=d.get("tbptt_fwd_length"))
        conf._toposort_and_infer()
        return conf

    @staticmethod
    def from_json(s: str) -> "ComputationGraphConfiguration":
        return ComputationGraphConfiguration.from_dict(json.loads(s))


class ComputationGraph(TrainEngine):
    def __init__(self, conf: ComputationGraphConfiguration):
        super().__init__(conf.global_conf.seed)
        self.conf = conf
        for n in conf.nodes:
            if n.kind == "layer":
                n.obj._g = conf.global_conf
        self._remat_segs: Optional[List[List[str]]] = None

    @property
    def layers(self):
        return [n.obj for n in self.conf.nodes if n.kind == "layer"]

    # ------------------------------------------------------------------ init
    def init(self, params: Optional[Dict] = None) -> "ComputationGraph":
        g = self.conf.global_conf
        if g.dtype is None:
            g = dataclasses.replace(g, dtype=get_environment().default_dtype)
        def init_all(key):
            ps: Dict[str, Dict] = {}
            ss: Dict[str, Dict] = {}
            for i, name in enumerate(self.conf.topo_order):
                node = self.conf.node(name)
                if node.kind != "layer":
                    continue
                it = self.conf.node_input_types.get(name)
                p, s = node.obj.init(jax.random.fold_in(key, i), it, g)
                if p:
                    ps[name] = p
                if s:
                    ss[name] = s
            return ps, ss

        if params is not None:
            # only the non-trainable state is needed; returning just it lets
            # XLA dead-code-eliminate the (discarded) param initialization
            new_params = params
            model_state = jax.jit(lambda key: init_all(key)[1])(
                jax.random.PRNGKey(g.seed))
        else:
            new_params, model_state = jax.jit(init_all)(jax.random.PRNGKey(g.seed))
        self._tx = self._build_tx(new_params)
        self.train_state = TrainState(
            params=new_params, model_state=model_state,
            opt_state=self._tx.init(new_params), step=jnp.zeros((), jnp.int32))
        self._jit_cache.clear()
        self._rnn_carries = None  # stale hidden state must not cross inits
        return self

    def _named_layers(self):
        return [(n.name, n.obj) for n in self.conf.nodes if n.kind == "layer"]

    # --------------------------------------------------------------- forward
    def _exec_node(self, i: int, name: str, acts, last_inputs, new_state,
                   params, model_state, *, training, rng, masks, carries,
                   output_set):
        """Execute one topo node, mutating acts/last_inputs/new_state.
        Returns the (possibly replaced) carries dict."""
        node = self.conf.node(name)
        # the node boundary of the device trace (see MultiLayerNetwork._forward)
        with jax.named_scope(f"{name}.{type(node.obj).__name__}"):
            return self._exec_node_scoped(
                i, name, node, acts, last_inputs, new_state, params,
                model_state, training=training, rng=rng, masks=masks,
                carries=carries, output_set=output_set)

    def _exec_node_scoped(self, i, name, node, acts, last_inputs, new_state,
                          params, model_state, *, training, rng, masks,
                          carries, output_set):
        ins = [acts[k] for k in node.inputs]
        if node.kind == "vertex":
            acts[name] = node.obj.forward(*ins)
            return carries
        x = ins[0]
        pp = getattr(node, "inputs_preprocessor", None)
        if pp is not None:
            x = pp.pre_process(x)
        mask = None if masks is None else masks.get(name)
        lrng = jax.random.fold_in(rng, i) if rng is not None else None
        if training and getattr(node.obj, "weight_noise", None) is not None:
            from deeplearning4j_tpu.nn.constraints import apply_weight_noise
            params = dict(params)
            params[name] = apply_weight_noise(
                node.obj, params.get(name, {}),
                None if lrng is None else jax.random.fold_in(lrng, 7919))
        if name in output_set and hasattr(node.obj, "compute_loss"):
            # apply input dropout ONCE; loss and forward share the result
            x = node.obj._apply_input_dropout(x, node.obj._g, training, lrng)
            last_inputs[name] = x
            acts[name] = node.obj.activate(params.get(name, {}), x)
            return carries
        last_inputs[name] = x
        if carries is not None and isinstance(node.obj, BaseRecurrentLayer):
            x = node.obj._apply_input_dropout(x, node.obj._g, training, lrng)
            y, c_new = node.obj.forward_with_carry(
                params.get(name, {}), carries[name], x,
                training=training, rng=lrng, mask=mask)
            carries = dict(carries)
            carries[name] = c_new
        else:
            y, s_new = node.obj.forward(params.get(name, {}),
                                        model_state.get(name, {}),
                                        x, training=training, rng=lrng, mask=mask)
            if model_state.get(name):
                new_state[name] = s_new
        acts[name] = y
        return carries

    def _remat_segments(self) -> List[List[str]]:
        """Partition ``topo_order`` into segments at single-tensor cut points
        (DAG articulations: the only value still live is the node itself).
        For ResNet-style graphs the cuts land exactly on the residual-block
        outputs, so ``jax.checkpoint`` around a segment saves ONE boundary
        activation instead of every intra-block tensor. The tail segment
        (containing the output/loss layers) is never rematerialized."""
        if self._remat_segs is not None:
            return self._remat_segs
        topo = self.conf.topo_order
        node_inputs = {n: list(self.conf.node(n).inputs) for n in topo}
        last_use: Dict[str, int] = {}
        for idx, n in enumerate(topo):
            for t in node_inputs[n]:
                last_use[t] = idx
        inf = len(topo) + 1
        for o in self.conf.outputs:  # outputs + their inputs feed the loss
            last_use[o] = inf
            for t in node_inputs.get(o, []):
                last_use[t] = inf
        live: set = {t for t in self.conf.inputs if last_use.get(t, -1) >= 0}
        segs: List[List[str]] = []
        cur: List[str] = []
        for idx, n in enumerate(topo):
            cur.append(n)
            live = {t for t in live if last_use.get(t, -1) > idx}
            if last_use.get(n, -1) > idx:
                live.add(n)
            if live == {n} and idx < len(topo) - 1:
                segs.append(cur)
                cur = []
        if cur:
            segs.append(cur)
        self._remat_segs = segs
        return segs

    def _forward_all(self, params, model_state, inputs: Dict[str, jax.Array], *,
                     training: bool, rng, masks: Optional[Dict[str, Any]] = None,
                     carries: Optional[Dict[str, Any]] = None):
        """Execute the DAG; returns (activations dict incl. pre-output inputs,
        new model state[, new carries when ``carries`` given]) — the carry
        path is the graph analog of the reference's ``rnnTimeStep`` stateful
        inference on ``ComputationGraph``."""
        env = get_environment()
        cdt = env.compute_dtype
        params = cast_floating(params, cdt)
        acts: Dict[str, Any] = {}
        for name, x in inputs.items():
            if jnp.issubdtype(x.dtype, jnp.floating) and x.dtype != cdt:
                x = x.astype(cdt)
            acts[name] = x
        last_inputs: Dict[str, Any] = {}
        new_state = dict(model_state)
        output_set = set(self.conf.outputs)

        use_remat = (env.remat_segments and training and carries is None
                     and masks is None)
        if use_remat:
            return self._forward_remat(params, model_state, acts, last_inputs,
                                       new_state, rng, output_set)

        for i, name in enumerate(self.conf.topo_order):
            carries = self._exec_node(
                i, name, acts, last_inputs, new_state, params, model_state,
                training=training, rng=rng, masks=masks, carries=carries,
                output_set=output_set)
        if carries is not None:
            return acts, last_inputs, new_state, carries
        return acts, last_inputs, new_state

    def _forward_remat(self, params, model_state, acts, last_inputs,
                       new_state, rng, output_set):
        """Training forward with per-segment rematerialization (see
        :meth:`_remat_segments`; the HBM-vs-FLOPs trade the reference's
        workspace system makes by hand, made by the compiler here)."""
        topo = self.conf.topo_order
        base = {n: i for i, n in enumerate(topo)}
        segs = self._remat_segments()
        for k, seg in enumerate(segs):
            is_tail = (k == len(segs) - 1)
            seg_set = set(seg)
            ext = sorted({t for n in seg for t in
                          (self.conf.node(n).inputs or [])
                          if t not in seg_set})
            if is_tail or len(seg) < 2:
                for n in seg:
                    self._exec_node(
                        base[n], n, acts, last_inputs, new_state, params,
                        model_state, training=True, rng=rng, masks=None,
                        carries=None, output_set=output_set)
                continue

            seg_params = {n: params[n] for n in seg if n in params}
            seg_mstate = {n: model_state[n] for n in seg if n in model_state}
            out_name = seg[-1]

            def seg_fn(seg_params, seg_mstate, ext_acts, rng, _seg=seg,
                       _ext=ext, _out=out_name):
                a = dict(zip(_ext, ext_acts))
                li: Dict[str, Any] = {}
                ns = dict(seg_mstate)
                for n in _seg:
                    self._exec_node(
                        base[n], n, a, li, ns, seg_params, seg_mstate,
                        training=True, rng=rng, masks=None, carries=None,
                        output_set=output_set)
                return a[_out], ns

            y, seg_new_state = jax.checkpoint(seg_fn)(
                seg_params, seg_mstate, tuple(acts[t] for t in ext), rng)
            acts[out_name] = y
            for n, s in seg_new_state.items():
                if model_state.get(n):
                    new_state[n] = s
        return acts, last_inputs, new_state

    def _loss(self, params, model_state, inputs, labels, rng, masks=None,
              training: bool = True, carries=None):
        if carries is not None:
            acts, last_inputs, new_state, new_carries = self._forward_all(
                params, model_state, inputs, training=training, rng=rng,
                masks=masks, carries=carries)
        else:
            acts, last_inputs, new_state = self._forward_all(
                params, model_state, inputs, training=training, rng=rng,
                masks=masks)
            new_carries = None
        total = jnp.zeros((), jnp.float32)
        for out_name, y in zip(self.conf.outputs, labels):
            node = self.conf.node(out_name)
            layer = node.obj
            if not hasattr(layer, "compute_loss"):
                raise ValueError(f"Output node {out_name!r} is not an output layer")
            mask = None if masks is None else masks.get(out_name)
            out_p = cast_floating(params.get(out_name, {}),
                                  get_environment().compute_dtype)
            if training and getattr(layer, "weight_noise", None) is not None \
                    and rng is not None:
                # mirror _exec_node's noise keys so loss and activations
                # agree on the perturbed weights
                from deeplearning4j_tpu.nn.constraints import apply_weight_noise
                i_node = self.conf.topo_order.index(out_name)
                lrng = jax.random.fold_in(rng, i_node)
                out_p = apply_weight_noise(layer, out_p,
                                           jax.random.fold_in(lrng, 7919))
            with jax.named_scope("loss"):
                total = total + layer.compute_loss(
                    out_p, last_inputs[out_name], y, mask=mask,
                    state=model_state.get(out_name, {}))
            if training and hasattr(layer, "update_state_with_labels"):
                new_state = dict(new_state)
                new_state[out_name] = layer.update_state_with_labels(
                    model_state.get(out_name, {}),
                    jax.lax.stop_gradient(last_inputs[out_name]), y)
        with jax.named_scope("loss"):
            total = total + self._reg_score(params)
        # layer auxiliary losses (e.g. MoE load balancing) — training only
        if training:
            for s2 in new_state.values():
                if isinstance(s2, dict) and "_aux_loss" in s2:
                    total = total + s2["_aux_loss"]
        return total, (new_state, new_carries)

    # ------------------------------------------------------------ train/fit
    def _coerce_batch(self, batch) -> Tuple[Dict[str, Any], List[Any], Optional[Dict]]:
        from deeplearning4j_tpu.data.dataset import DataSet, MultiDataSet
        if isinstance(batch, MultiDataSet):
            inputs = {n: jnp.asarray(f) for n, f in zip(self.conf.inputs, batch.features)}
            labels = [jnp.asarray(l) for l in batch.labels]
            masks = None
            if batch.labels_masks is not None:
                masks = {o: (None if m is None else jnp.asarray(m))
                         for o, m in zip(self.conf.outputs, batch.labels_masks)}
            return inputs, labels, masks
        ds: DataSet = batch
        inputs = {self.conf.inputs[0]: jnp.asarray(ds.features)}
        labels = [jnp.asarray(ds.labels)]
        masks = None
        if ds.labels_mask is not None:
            masks = {self.conf.outputs[0]: jnp.asarray(ds.labels_mask)}
        return inputs, labels, masks

    def _prepare_batch(self, batch):
        args = self._coerce_batch(batch)
        return args, next(iter(args[0].values())).shape[0]

    def fit(self, data, labels=None, epochs: int = 1,
            prefetch_buffer: int = 0, profiler=None) -> "ComputationGraph":
        """``prefetch_buffer > 0`` stages coerced batches on-device ahead of
        the step (``train.prefetch.DevicePrefetcher``; trajectory
        bit-identical to the synchronous loop); ``profiler`` takes a
        :class:`~deeplearning4j_tpu.train.profiler.TrainingProfiler`."""
        return self._fit(data, labels, epochs, prefetch_buffer, profiler)

    _solver_fit_batch = graph_solver_fit_batch  # (net, inputs, labels, masks) -> loss

    def _tbptt_plan(self, inputs, labels_, masks):
        """Zero carries, and the batch cut along its time axis into
        tbptt-length chunks."""
        L = int(self.conf.tbptt_fwd_length)
        T = max(seq_length(v) for v in inputs.values() if is_sequence_array(v))

        def chunks():
            for t0 in range(0, T, L):
                yield ({k: slice_time(v, t0, L) for k, v in inputs.items()},
                       [y[:, t0:t0 + L] if hasattr(y, "ndim") and y.ndim == 3
                        else y for y in labels_],
                       None if masks is None else {
                           k: (m[:, t0:t0 + L] if hasattr(m, "ndim")
                               and m.ndim >= 2 and m.shape[1] == T else m)
                           for k, m in masks.items()})

        first = next(iter(inputs.values()))
        return self._rnn_zero_carries(
            first.shape[0],
            carry_dtype(first, get_environment().compute_dtype)), chunks()

    # ------------------------------------------------------------- inference
    def output(self, *xs, training: bool = False):
        """Forward; returns list of output arrays (single array if one output)."""
        if self.train_state is None:
            self.init()
        inputs = {n: jnp.asarray(x) for n, x in zip(self.conf.inputs, xs)}

        def fwd(params, model_state, inputs_):
            acts, _, _ = self._forward_all(params, model_state, inputs_,
                                           training=False, rng=None)
            return [acts[o] for o in self.conf.outputs]

        fn = self._jitted("output", lambda: jax.jit(fwd))
        outs = fn(self.train_state.params, self.train_state.model_state, inputs)
        return outs[0] if len(outs) == 1 else outs

    def _coerce_inputs(self, inputs) -> Dict[str, jax.Array]:
        """Accept a dict, a single array (single-input graph), or a
        list/tuple of arrays zipped element-wise against ``conf.inputs``."""
        if isinstance(inputs, dict):
            return {k: jnp.asarray(v) for k, v in inputs.items()}
        if isinstance(inputs, (list, tuple)):
            if len(inputs) != len(self.conf.inputs):
                raise ValueError(
                    f"graph has {len(self.conf.inputs)} inputs "
                    f"{self.conf.inputs}; got {len(inputs)} arrays")
            return {n: jnp.asarray(v)
                    for n, v in zip(self.conf.inputs, inputs)}
        return {self.conf.inputs[0]: jnp.asarray(inputs)}

    # --------------------------------------------------- external errors
    def backprop_gradient(self, inputs, epsilons):
        """Reference ``ComputationGraph`` external-errors mode: given
        dL/dOutput for each graph output (produced OUTSIDE the graph), return
        ``(param_gradients, {input_name: dL/dInput})`` — one jitted vjp."""
        if self.train_state is None:
            self.init()
        inputs = self._coerce_inputs(inputs)
        if not isinstance(epsilons, (list, tuple)):
            epsilons = [epsilons]
        epsilons = [jnp.asarray(e) for e in epsilons]

        def fn(params, model_state, inputs_, eps):
            def f(p, ins):
                acts, _, new_state = self._forward_all(
                    p, model_state, ins, training=True, rng=None)
                return [acts[o] for o in self.conf.outputs], new_state
            outs, vjp, _ = jax.vjp(f, params, inputs_, has_aux=True)
            gp, gin = vjp([e.astype(o.dtype) for e, o in zip(eps, outs)])
            return gp, gin

        fn = self._jitted("backprop_external", lambda: jax.jit(fn))
        return fn(self.train_state.params, self.train_state.model_state,
                  inputs, epsilons)

    def fit_external(self, inputs, epsilons):
        """External-errors TRAINING step on the graph: backprop the provided
        output cotangents and apply the configured updater (one jitted
        donated step). Returns {input_name: dL/dInput}."""
        if self.train_state is None:
            self.init()
        inputs = self._coerce_inputs(inputs)
        if not isinstance(epsilons, (list, tuple)):
            epsilons = [epsilons]
        epsilons = [jnp.asarray(e) for e in epsilons]

        def make():
            def step(ts: TrainState, inputs_, eps, rng):
                def f(p, ins):
                    acts, _, new_state = self._forward_all(
                        p, ts.model_state, ins, training=True, rng=rng)
                    return [acts[o] for o in self.conf.outputs], new_state
                outs, vjp, new_state = jax.vjp(f, ts.params, inputs_,
                                               has_aux=True)
                gp, gin = vjp([e.astype(o.dtype) for e, o in zip(eps, outs)])
                updates, new_opt = self._tx.update(gp, ts.opt_state, ts.params)
                new_params = optax.apply_updates(ts.params, updates)
                return TrainState(params=new_params, model_state=new_state,
                                  opt_state=new_opt, step=ts.step + 1), gin
            return jax.jit(step, donate_argnums=(0,))

        fn = self._jitted("fit_external", make)
        self.train_state, gin = fn(self.train_state, inputs, epsilons,
                                   self.rng.next_key())
        self._iteration += 1
        return gin

    def _rnn_step_fn(self):
        """The jitted ``(params, model_state, inputs, carries) ->
        (outs, new_carries)`` program behind :meth:`rnn_time_step` and
        :meth:`rnn_time_step_external` — one shared cache key, so the
        stateful and pure-functional paths compile once and stay
        bit-identical at equal program shape."""
        def make():
            def fwd(params, model_state, inputs_, carries):
                acts, _, _, new_carries = self._forward_all(
                    params, model_state, inputs_, training=False, rng=None,
                    carries=carries)
                return [acts[o] for o in self.conf.outputs], new_carries
            return jax.jit(fwd)

        return self._jitted("rnn_time_step", make)

    def _rnn_zero_carries(self, batch: int, carry_dt):
        return {n.name: n.obj.init_carry(batch, carry_dt)
                for n in self.conf.nodes
                if n.kind == "layer" and isinstance(n.obj, BaseRecurrentLayer)}

    def rnn_time_step(self, *xs):
        """Stateful step-by-step inference (reference
        ``ComputationGraph.rnnTimeStep``): hidden state carries across calls
        until :meth:`rnn_clear_previous_state`."""
        if self.train_state is None:
            self.init()
        inputs = {n: jnp.asarray(x) for n, x in zip(self.conf.inputs, xs)}
        first = next(iter(inputs.values()))
        carry_dt = carry_dtype(first, get_environment().compute_dtype)
        if getattr(self, "_rnn_carries", None) is None:
            self._rnn_carries = self._rnn_zero_carries(first.shape[0],
                                                       carry_dt)
        fn = self._rnn_step_fn()
        outs, self._rnn_carries = fn(self.train_state.params,
                                     self.train_state.model_state, inputs,
                                     self._rnn_carries)
        return outs[0] if len(outs) == 1 else outs

    def rnn_clear_previous_state(self) -> None:
        self._rnn_carries = None

    def rnn_get_state(self):
        """Serializable copy of the stored recurrent state (reference
        ``rnnGetPreviousState``): numpy-leaved tree, dtype-stable, ``None``
        when no state is stored. Bit-exact round trip through
        :meth:`rnn_set_state`."""
        if getattr(self, "_rnn_carries", None) is None:
            return None
        return jax.tree.map(np.asarray, self._rnn_carries)

    def rnn_set_state(self, state) -> None:
        """Install a state captured with :meth:`rnn_get_state` (reference
        ``rnnSetPreviousState``); ``None`` clears."""
        self._rnn_carries = (None if state is None
                             else jax.tree.map(jnp.asarray, state))

    def rnn_zero_state(self, batch: int, like=None):
        """Fresh zero recurrent state for a ``batch``-row stream — the tree
        :meth:`rnn_time_step` would lazily create on first call."""
        if self.train_state is None:
            self.init()
        dt = (get_environment().compute_dtype if like is None else
              carry_dtype(jnp.asarray(like), get_environment().compute_dtype))
        return self._rnn_zero_carries(batch, dt)

    def rnn_time_step_external(self, *xs, state):
        """Pure-functional ``rnnTimeStep`` on the graph: advance ``state``
        (or ``None`` for a fresh stream) by one chunk without touching the
        stored state; returns ``(out, new_state)``. Shares
        :meth:`rnn_time_step`'s compiled program."""
        if self.train_state is None:
            self.init()
        inputs = {n: jnp.asarray(x) for n, x in zip(self.conf.inputs, xs)}
        first = next(iter(inputs.values()))
        if state is None:
            state = self._rnn_zero_carries(
                first.shape[0],
                carry_dtype(first, get_environment().compute_dtype))
        fn = self._rnn_step_fn()
        outs, new_state = fn(self.train_state.params,
                             self.train_state.model_state, inputs, state)
        return (outs[0] if len(outs) == 1 else outs), new_state

    def score(self, dataset=None) -> float:
        if dataset is None:
            return float(self._score)
        inputs, labels, masks = self._coerce_batch(dataset)

        def score_fn(params, model_state, i_, l_, m_):
            loss, _ = self._loss(params, model_state, i_, l_, None, m_,
                                 training=False)
            return loss

        fn = self._jitted("score", lambda: jax.jit(score_fn))
        return float(fn(self.train_state.params, self.train_state.model_state,
                        inputs, labels, masks))

    def evaluate(self, iterator, output_index: int = 0):
        """Classification eval on one output (reference
        ``evaluate(DataSetIterator)``); handles multi-input MultiDataSets."""
        from deeplearning4j_tpu.evaluation.evaluation import Evaluation
        ev = Evaluation()
        iterator.reset()
        for batch in iterator:
            inputs, labels, _ = self._coerce_batch(batch)
            outs = self.output(*[inputs[n] for n in self.conf.inputs])
            if isinstance(outs, list):
                outs = outs[output_index]
            ev.eval(np.asarray(labels[output_index]), np.asarray(outs))
        return ev

    # -------------------------------------------------------------- plumbing
    def clone(self) -> "ComputationGraph":
        net = ComputationGraph(
            ComputationGraphConfiguration.from_dict(self.conf.to_dict()))
        if self.train_state is not None:
            net.init(params=jax.tree.map(jnp.copy, self.train_state.params))
            import dataclasses as _dc
            net.train_state = _dc.replace(
                net.train_state,
                model_state=jax.tree.map(jnp.copy, self.train_state.model_state))
        return net

    def save(self, path: str, save_updater: bool = True) -> None:
        from deeplearning4j_tpu.models.serializer import ModelSerializer
        ModelSerializer.write_model(self, path, save_updater=save_updater)

    @staticmethod
    def load(path: str, load_updater: bool = True) -> "ComputationGraph":
        from deeplearning4j_tpu.models.serializer import ModelSerializer
        return ModelSerializer.restore_computation_graph(path, load_updater=load_updater)
