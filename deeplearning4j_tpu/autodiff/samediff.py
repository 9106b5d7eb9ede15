"""SameDiff-equivalent declarative graph.

Rebuild of upstream ``org.nd4j.autodiff.samediff.SameDiff`` (the reference's
~10k-line core class) with a compiler at the other end: the op graph records
named registry ops (data, serializable), execution traces the whole graph
into ONE jitted XLA program, and gradients come from ``jax.grad`` of that
program (replacing per-op ``doDiff`` and the topo-walking
``InferenceSession``/``TrainingSession``).

API parity sketch::

    sd = SameDiff.create()
    x = sd.placeholder("x", (None, 784))
    w = sd.var("w", (784, 10))
    b = sd.var("b", (10,))
    logits = x @ w + b                      # operator sugar
    probs = sd.nn.softmax(logits, name="probs")
    labels = sd.placeholder("labels", (None, 10))
    loss = sd.loss.softmax_cross_entropy("loss", labels, logits)
    sd.set_loss_variables("loss")
    sd.set_training_config(TrainingConfig(updater=Adam(1e-3),
                                          data_set_feature_mapping=["x"],
                                          data_set_label_mapping=["labels"]))
    sd.fit(iterator, epochs=2)
    out = sd.output({"x": arr}, "probs")
    sd.save(path); SameDiff.load(path)
"""

from __future__ import annotations

import dataclasses
import enum
import io
import json
import zipfile
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
import optax

from deeplearning4j_tpu.autodiff.ops_registry import OPS, RNG_OPS, get_op
from deeplearning4j_tpu.ops.initializers import WeightInit, init_weights
from deeplearning4j_tpu.train.updaters import Adam, Updater


class VariableType(str, enum.Enum):
    VARIABLE = "variable"      # trainable
    PLACEHOLDER = "placeholder"
    CONSTANT = "constant"
    ARRAY = "array"            # op output


@dataclasses.dataclass
class OpNode:
    op: str                      # registry name
    inputs: List[str]            # input variable names
    outputs: List[str]
    attrs: Dict[str, Any] = dataclasses.field(default_factory=dict)
    out_index: Optional[int] = None  # for multi-output ops: which output


class SDVariable:
    def __init__(self, sd: "SameDiff", name: str, vtype: VariableType,
                 shape: Optional[Tuple] = None, dtype=None):
        self.sd = sd
        self.name = name
        self.vtype = vtype
        self.shape = tuple(shape) if shape is not None else None
        self.dtype = dtype

    # ---- operator sugar (reference SDVariable methods) ----
    def _bin(self, op, other, reverse=False):
        other = self.sd._lift(other)
        a, b = (other, self) if reverse else (self, other)
        return self.sd._apply(op, [a, b])

    def __add__(self, o):
        return self._bin("add", o)
    __radd__ = __add__

    def __sub__(self, o):
        return self._bin("sub", o)

    def __rsub__(self, o):
        return self._bin("sub", o, reverse=True)

    def __mul__(self, o):
        return self._bin("mul", o)
    __rmul__ = __mul__

    def __truediv__(self, o):
        return self._bin("div", o)

    def __rtruediv__(self, o):
        return self._bin("div", o, reverse=True)

    def __pow__(self, o):
        return self._bin("pow", o)

    def __matmul__(self, o):
        return self._bin("matmul", o)

    def __neg__(self):
        return self.sd._apply("neg", [self])

    def __gt__(self, o):
        return self._bin("gt", o)

    def __lt__(self, o):
        return self._bin("lt", o)

    # common instance methods, reference-style
    def add(self, o, name=None):
        return self.sd._apply("add", [self, self.sd._lift(o)], name=name)

    def mmul(self, o, name=None):
        return self.sd._apply("matmul", [self, self.sd._lift(o)], name=name)

    def reshape(self, *shape, name=None):
        return self.sd._apply("reshape", [self], attrs={"shape": shape}, name=name)

    def transpose(self, *perm, name=None):
        return self.sd._apply("transpose", [self],
                              attrs={"perm": perm or None}, name=name)

    def sum(self, axis=None, keepdims=False, name=None):
        return self.sd._apply("reduce_sum", [self],
                              attrs={"axis": axis, "keepdims": keepdims}, name=name)

    def mean(self, axis=None, keepdims=False, name=None):
        return self.sd._apply("reduce_mean", [self],
                              attrs={"axis": axis, "keepdims": keepdims}, name=name)

    def std(self, axis=None, keepdims=False, name=None):
        return self.sd._apply("reduce_std", [self],
                              attrs={"axis": axis, "keepdims": keepdims}, name=name)

    def eval(self, placeholders: Optional[Dict[str, Any]] = None):
        """Evaluate this variable (reference ``SDVariable.eval()``)."""
        return self.sd.output(placeholders or {}, self.name)

    def get_arr(self):
        return self.sd.arrays.get(self.name)

    def set_arr(self, value):
        self.sd.arrays[self.name] = jnp.asarray(value)
        # only a CONSTANT's value is baked into traced train steps —
        # invalidate and EVICT (stale executables pin the old device
        # buffers); VARIABLE/ARRAY values are passed as step arguments
        if self.vtype is VariableType.CONSTANT:
            self.sd._graph_version += 1
            self.sd._jit_cache.clear()

    def rename(self, new_name: str) -> "SDVariable":
        self.sd._rename(self.name, new_name)
        return self

    def __repr__(self):
        return f"SDVariable(name={self.name!r}, type={self.vtype.value}, shape={self.shape})"


class _Namespace:
    """Op namespace (sd.math / sd.nn / sd.cnn / sd.loss / sd.random)."""

    def __init__(self, sd: "SameDiff", ops: Sequence[str], loss_style: bool = False):
        self._sd = sd
        self._ops = set(ops)
        self._loss_style = loss_style

    def __getattr__(self, op):
        if op.startswith("_") or op not in self._ops:
            raise AttributeError(op)

        def call(*args, name=None, **attrs):
            if self._loss_style and args and isinstance(args[0], str) and name is None:
                name, args = args[0], args[1:]
            vars_ = [self._sd._lift(a) for a in args]
            n_out = _MULTI_OUTPUT_OPS.get(op, 1)
            if op == "svd" and attrs.get("compute_uv") is False:
                n_out = 1  # singular values only
            return self._sd._apply(op, vars_, attrs=attrs, name=name,
                                   n_outputs=n_out)

        return call


_MATH_OPS = [n for n in OPS if n not in ("conv2d", "max_pool2d", "avg_pool2d")]
_NN_OPS = ["relu", "relu6", "leaky_relu", "elu", "selu", "gelu", "sigmoid", "tanh",
           "softmax", "log_softmax", "softplus", "softsign", "swish", "mish",
           "hard_sigmoid", "layer_norm", "batch_norm", "bias_add", "linear",
           "dropout", "multi_head_dot_product_attention", "pad", "one_hot"]
_CNN_OPS = ["conv2d", "max_pool2d", "avg_pool2d", "batch_norm",
            "conv1d", "conv3d", "depthwise_conv2d", "max_pool1d",
            "avg_pool1d", "max_pool3d", "avg_pool3d",
            "local_response_normalization", "im2col", "space_to_depth",
            "depth_to_space", "space_to_batch", "batch_to_space",
            "dilation2d"]
_RNN_OPS = ["lstm_layer", "gru", "lstm_cell", "gru_cell"]
# ops whose registry callable returns a tuple (namespace calls unpack them)
_MULTI_OUTPUT_OPS = {"lstm_layer": 3, "gru": 2, "lstm_cell": 2,
                     "svd": 3, "qr": 2, "eigh": 2, "eig": 2,
                     "top_k": 2, "unique": 2, "non_max_suppression": 2,
                     "meshgrid": 2, "moments": 2, "normalize_moments": 2,
                     "lu": 2}
_LOSS_OPS = ["softmax_cross_entropy", "sparse_softmax_cross_entropy",
             "sigmoid_cross_entropy", "mean_squared_error", "mean_absolute_error",
             "l2_loss", "log_loss", "cosine_distance", "hinge_loss", "huber_loss",
             "kl_divergence", "poisson_loss", "mean_pairwise_squared_error",
             "mean_squared_log_error", "mean_absolute_percentage_error",
             "ctc_loss"]
_LINALG_OPS = ["cholesky", "solve", "triangular_solve", "lstsq",
               "matrix_inverse", "matrix_determinant", "logdet", "svd", "qr",
               "eigh", "eig", "matrix_band_part", "cross", "diag", "diag_part",
               "trace", "matmul"]
_BITWISE_OPS = ["bitwise_and", "bitwise_or", "bitwise_xor", "bit_shift",
                "bit_shift_right", "bit_rotl", "bit_rotr"]
_RANDOM_OPS = ["random_uniform", "random_normal", "random_bernoulli",
               "random_exponential", "random_shuffle", "random_gamma",
               "random_poisson", "random_gumbel", "random_laplace",
               "truncated_normal", "random_categorical", "multinomial"]
_IMAGE_OPS = ["resize_bilinear", "resize_nearest", "crop_to_box",
              "flip_left_right", "flip_up_down", "adjust_brightness",
              "adjust_contrast", "adjust_saturation", "rgb_to_grayscale",
              "hsv_to_rgb", "rgb_to_hsv", "crop_and_resize",
              "non_max_suppression"]


@dataclasses.dataclass
class TrainingConfig:
    """Reference ``org.nd4j.autodiff.samediff.TrainingConfig``."""

    updater: Updater = dataclasses.field(default_factory=lambda: Adam(1e-3))
    data_set_feature_mapping: List[str] = dataclasses.field(default_factory=list)
    data_set_label_mapping: List[str] = dataclasses.field(default_factory=list)
    l1: float = 0.0
    l2: float = 0.0

    def to_dict(self):
        return {"updater": self.updater.to_dict(),
                "data_set_feature_mapping": self.data_set_feature_mapping,
                "data_set_label_mapping": self.data_set_label_mapping,
                "l1": self.l1, "l2": self.l2}

    @staticmethod
    def from_dict(d):
        return TrainingConfig(
            updater=Updater.from_dict(d["updater"]),
            data_set_feature_mapping=list(d.get("data_set_feature_mapping", [])),
            data_set_label_mapping=list(d.get("data_set_label_mapping", [])),
            l1=d.get("l1", 0.0), l2=d.get("l2", 0.0))


class History(list):
    """``sd.fit`` return value (reference
    ``org.nd4j.autodiff.listeners.records.History``): behaves as the list of
    per-iteration losses (backward compatible) and exposes the reference's
    curve accessors."""

    def __init__(self, losses, epoch_bounds):
        super().__init__(losses)
        self._bounds = list(epoch_bounds)  # iteration count at each epoch end

    def loss_curve(self):
        return list(self)

    def epoch_losses(self):
        out, start = [], 0
        for end in self._bounds:
            if end > start:
                out.append(sum(self[start:end]) / (end - start))
            start = end
        return out

    def final_loss(self):
        return self[-1] if self else None


class SameDiff:
    def __init__(self):
        self.vars: Dict[str, SDVariable] = {}
        self.ops: List[OpNode] = []
        self.arrays: Dict[str, jax.Array] = {}  # VARIABLE + CONSTANT values
        self.loss_variables: List[str] = []
        self.training_config: Optional[TrainingConfig] = None
        self._name_counter = 0
        self._graph_version = 0  # bumped on any change a traced step closed over
        self._opt_state = None
        self._tx = None
        self._jit_cache: Dict[Any, Any] = {}
        self._rng_key = jax.random.PRNGKey(0)
        self._train_iter = 0  # global step count (rng stream position)
        self._listeners: List[Any] = []
        self.math = _Namespace(self, _MATH_OPS)
        self.nn = _Namespace(self, _NN_OPS)
        self.cnn = _Namespace(self, _CNN_OPS)
        self.rnn = _Namespace(self, _RNN_OPS)
        self.loss = _Namespace(self, _LOSS_OPS, loss_style=True)
        self.linalg = _Namespace(self, _LINALG_OPS)
        self.bitwise = _Namespace(self, _BITWISE_OPS)
        self.random = _Namespace(self, _RANDOM_OPS)
        self.image = _Namespace(self, _IMAGE_OPS)

    @staticmethod
    def create() -> "SameDiff":
        return SameDiff()

    # ------------------------------------------------------------- variables
    def _unique(self, base: str) -> str:
        if base not in self.vars:
            return base
        while True:
            self._name_counter += 1
            cand = f"{base}_{self._name_counter}"
            if cand not in self.vars:
                return cand

    def placeholder(self, name: str, shape=None, dtype=jnp.float32) -> SDVariable:
        v = SDVariable(self, self._unique(name), VariableType.PLACEHOLDER, shape, dtype)
        self.vars[v.name] = v
        return v

    # reference alias
    place_holder = placeholder

    def var(self, name: str, shape=None, weight_init: Union[str, WeightInit] = WeightInit.XAVIER,
            array=None, dtype=jnp.float32) -> SDVariable:
        """Trainable variable; initialised from ``array`` or ``weight_init``."""
        v = SDVariable(self, self._unique(name), VariableType.VARIABLE, shape, dtype)
        self.vars[v.name] = v
        if array is not None:
            self.arrays[v.name] = jnp.asarray(array, dtype)
        else:
            if shape is None:
                raise ValueError("var() needs shape or array")
            self._rng_key, sub = jax.random.split(self._rng_key)
            self.arrays[v.name] = init_weights(sub, shape, WeightInit(weight_init), dtype=dtype)
        return v

    def constant(self, name_or_value, value=None) -> SDVariable:
        if value is None:
            name, value = None, name_or_value
        else:
            name = name_or_value
        value = jnp.asarray(value)
        v = SDVariable(self, self._unique(name or "const"), VariableType.CONSTANT,
                       value.shape, value.dtype)
        self.vars[v.name] = v
        self.arrays[v.name] = value
        return v

    def _lift(self, x) -> SDVariable:
        if isinstance(x, SDVariable):
            return x
        return self.constant(None, x)

    def convert_to_variable(self, *names) -> None:
        """Make CONSTANT variables trainable (reference
        ``sd.convertToVariable``) — the fine-tune-an-imported-graph path:
        ``TFGraphMapper.import_graph`` materialises weights as constants;
        converting them lets ``fit()`` train them."""
        for n in names:
            n = n.name if isinstance(n, SDVariable) else n
            v = self.vars[n]
            if v.vtype == VariableType.VARIABLE:
                continue
            if v.vtype != VariableType.CONSTANT:
                raise ValueError(f"{n!r} is {v.vtype.value}, not a constant")
            v.vtype = VariableType.VARIABLE
        self._jit_cache.clear()

    def convert_to_constant(self, *names) -> None:
        """Freeze VARIABLEs (reference ``sd.convertToConstant``) — e.g. to
        fine-tune only a grafted head on an imported backbone."""
        for n in names:
            n = n.name if isinstance(n, SDVariable) else n
            v = self.vars[n]
            if v.vtype == VariableType.VARIABLE:
                v.vtype = VariableType.CONSTANT
        self._jit_cache.clear()

    def trainable_float_constants(self, min_size: int = 2) -> List[str]:
        """Names of float CONSTANTs big enough to plausibly be weights
        (imported-model helper: everything except scalar/axis-style consts)."""
        out = []
        for n, a in self.arrays.items():
            if (self.vars[n].vtype == VariableType.CONSTANT
                    and jnp.issubdtype(a.dtype, jnp.floating)
                    and a.size >= min_size):
                out.append(n)
        return out

    def _rename(self, old: str, new: str) -> None:
        if new in self.vars:
            raise ValueError(f"Variable {new!r} already exists")
        v = self.vars.pop(old)
        v.name = new
        self.vars[new] = v
        if old in self.arrays:
            self.arrays[new] = self.arrays.pop(old)
        for node in self.ops:
            node.inputs = [new if i == old else i for i in node.inputs]
            node.outputs = [new if o == old else o for o in node.outputs]
        self.loss_variables = [new if n == old else n for n in self.loss_variables]
        self._jit_cache.clear()
        self._graph_version += 1

    # ------------------------------------------------------------------- ops
    def _apply(self, op: str, inputs: List[SDVariable], attrs=None, name=None,
               n_outputs: int = 1) -> Union[SDVariable, Tuple[SDVariable, ...]]:
        get_op(op)  # validate
        attrs = {k: v for k, v in (attrs or {}).items() if v is not None}
        outs = []
        for j in range(n_outputs):
            base = name if (name and n_outputs == 1) else f"{name or op}_{j}" if name else op
            out = SDVariable(self, self._unique(base), VariableType.ARRAY)
            self.vars[out.name] = out
            outs.append(out)
        self.ops.append(OpNode(op=op, inputs=[v.name for v in inputs],
                               outputs=[o.name for o in outs], attrs=attrs))
        self._jit_cache.clear()
        self._graph_version += 1
        return outs[0] if n_outputs == 1 else tuple(outs)

    def invoke(self, op: str, *args, name=None, n_outputs: int = 1, **attrs):
        """Apply any registry op by name (escape hatch / importer path)."""
        return self._apply(op, [self._lift(a) for a in args], attrs=attrs,
                           name=name, n_outputs=n_outputs)

    # ---- control flow (reference: TF-style Switch/Merge/Enter/Exit frames;
    # here structured lax primitives, which is what XLA wants) ----
    def _apply_callable(self, fn, inputs: List[SDVariable], name: str,
                        n_outputs: int = 1):
        outs = []
        for j in range(n_outputs):
            base = name if n_outputs == 1 else f"{name}_{j}"
            out = SDVariable(self, self._unique(base), VariableType.ARRAY)
            self.vars[out.name] = out
            outs.append(out)
        self.ops.append(OpNode(op="__callable__", inputs=[v.name for v in inputs],
                               outputs=[o.name for o in outs], attrs={"fn": fn}))
        self._jit_cache.clear()
        self._graph_version += 1
        return outs[0] if n_outputs == 1 else tuple(outs)

    def cond(self, pred, true_fn, false_fn, *operands, name: str = "cond",
             n_outputs: int = 1):
        """``lax.cond`` over graph values: ``true_fn``/``false_fn`` take the
        operand arrays and return ``n_outputs`` arrays (reference:
        If/Switch-Merge)."""
        def fn(p, *xs, key=None):
            tf_ = ((lambda *a: true_fn(*a, key=key))
                   if getattr(true_fn, "_accepts_rng", False) else true_fn)
            ff_ = ((lambda *a: false_fn(*a, key=key))
                   if getattr(false_fn, "_accepts_rng", False) else false_fn)
            return jax.lax.cond(jnp.reshape(p, ()).astype(bool), tf_, ff_, *xs)

        if any(getattr(f, "_accepts_rng", False) for f in (true_fn, false_fn)):
            fn._accepts_rng = True
        return self._apply_callable(
            fn, [self._lift(pred)] + [self._lift(o) for o in operands], name,
            n_outputs=n_outputs)

    def while_loop(self, cond_fn, body_fn, *init, name: str = "while",
                   max_iterations: Optional[int] = None):
        """``lax.while_loop`` with an N-array carry (reference: While/Enter-
        Exit frames). ``cond_fn(*carry) -> bool``, ``body_fn(*carry) -> carry``.

        Without ``max_iterations`` this lowers to ``lax.while_loop``, which
        supports forward execution only — reverse-mode AD
        (``calculate_gradients`` through the loop) raises, as in JAX. Pass
        ``max_iterations`` (TF's ``maximum_iterations``) to lower to a
        fixed-length ``lax.scan`` with predicate masking, which is fully
        differentiable."""
        n = len(init)

        def fn(*xs, key=None):
            # stochastic bodies: the key is fixed per TRAINING STEP (fresh
            # masks every sd.fit iteration) but constant across loop
            # iterations within the step — per-loop-iteration freshness
            # would need the counter folded in by the body itself
            bf = ((lambda *a: body_fn(*a, key=key))
                  if getattr(body_fn, "_accepts_rng", False) else body_fn)
            cf = ((lambda *a: cond_fn(*a, key=key))
                  if getattr(cond_fn, "_accepts_rng", False) else cond_fn)
            if max_iterations is None:
                out = jax.lax.while_loop(
                    lambda c: jnp.reshape(cf(*c), ()).astype(bool),
                    lambda c: tuple(bf(*c)), tuple(xs))
            else:
                def step(c, _):
                    pred = jnp.reshape(cf(*c), ()).astype(bool)
                    new = tuple(bf(*c))
                    c2 = tuple(jnp.where(pred, b, a) for a, b in zip(c, new))
                    return c2, None

                out, _ = jax.lax.scan(step, tuple(xs), None,
                                      length=max_iterations)
            return out if n > 1 else out[0]

        if any(getattr(f, "_accepts_rng", False) for f in (cond_fn, body_fn)):
            fn._accepts_rng = True
        return self._apply_callable(fn, [self._lift(i) for i in init], name,
                                    n_outputs=n)

    # --------------------------------------------------------------- execute
    def _needed_ops(self, outputs: Sequence[str]) -> List[OpNode]:
        """Ancestor subgraph of ``outputs`` (so executing 'probs' never
        touches the loss op and its label placeholder)."""
        producer = {}
        for node in self.ops:
            for o in node.outputs:
                producer[o] = node
        needed: List[OpNode] = []
        seen = set()
        stack = list(outputs)
        marked = set()
        while stack:
            name = stack.pop()
            if name in marked:
                continue
            marked.add(name)
            node = producer.get(name)
            if node is not None and id(node) not in seen:
                seen.add(id(node))
                needed.append(node)
                stack.extend(node.inputs)
        order = {id(n): i for i, n in enumerate(self.ops)}
        needed.sort(key=lambda n: order[id(n)])
        return needed

    def _exec_graph(self, env: Dict[str, Any], outputs: Sequence[str]):
        # "__rng__" is a RESERVED env entry (never a variable name): when the
        # caller provides it (sd.fit's train step passes a per-iteration
        # key), every stochastic op gets a distinct subkey — fold_in by the
        # node's stable position in self.ops, so two dropout nodes never
        # share a mask and re-traces are deterministic. Without it
        # (output()/eval), RNG ops fall back to their static `seed` attr and
        # dropout is the identity — the reference's inference semantics.
        rng = env.get("__rng__")
        pos = None
        for node in self._needed_ops(outputs):
            if all(o in env for o in node.outputs):
                continue
            fn = node.attrs["fn"] if node.op == "__callable__" else get_op(node.op)
            args = [env[i] for i in node.inputs]
            attrs = {} if node.op == "__callable__" else node.attrs
            if rng is not None and (
                    node.op in RNG_OPS
                    # control-flow callables that declare rng support
                    # (cond/while bodies containing stochastic ops — the
                    # sub-executor re-injects per-node subkeys from this key)
                    or (node.op == "__callable__"
                        and getattr(fn, "_accepts_rng", False))):
                if pos is None:
                    pos = {id(n): i for i, n in enumerate(self.ops)}
                attrs = dict(attrs)
                attrs["key"] = jax.random.fold_in(rng, pos[id(node)])
            res = fn(*args, **attrs)
            if len(node.outputs) == 1:
                env[node.outputs[0]] = res
            else:
                for o, r in zip(node.outputs, res):
                    env[o] = r
        return [env[o] for o in outputs]

    def _build_forward(self, output_names: Tuple[str, ...], ph_names: Tuple[str, ...]):
        # SMALL INTEGER constants are closed over (static): shape chains
        # that mix shape_of results with graph constants (e.g. a Const -1
        # in a computed reshape target) then stay trace-time concrete,
        # which reshape_dynamic requires. Big float constants (imported
        # frozen weights) stay ARGUMENTS — baking them would duplicate the
        # weight set into every cached executable as HLO literals.
        # Consistency: set_arr on a CONSTANT clears the whole jit cache,
        # so baked values never go stale.
        consts = {n: a for n, a in self.arrays.items()
                  if self._baked_const(n)}

        def fn(variables, placeholders):
            env = dict(consts)
            env.update(variables)
            env.update(placeholders)
            return self._exec_graph(env, output_names)

        return jax.jit(fn)

    def _baked_const(self, name: str) -> bool:
        if self.vars[name].vtype != VariableType.CONSTANT:
            return False
        a = self.arrays[name]
        return a.size <= 64 and jnp.issubdtype(a.dtype, jnp.integer)

    def _non_constant_arrays(self) -> Dict[str, Any]:
        """Arrays passed as executable arguments (everything not baked)."""
        return {n: a for n, a in self.arrays.items()
                if not self._baked_const(n)}

    def output(self, placeholders: Dict[str, Any], *outputs: str):
        """Execute and return the requested outputs (reference
        ``sd.output(Map, String...)``). Single name -> single array; a LIST
        of names (reference ``output(Map, List<String>)``) -> name->array
        dict."""
        as_map = len(outputs) == 1 and isinstance(outputs[0], (list, tuple))
        names = tuple(outputs[0]) if as_map else tuple(outputs)
        names = tuple(n.name if isinstance(n, SDVariable) else n for n in names)
        ph = {k: jnp.asarray(v) for k, v in placeholders.items()}
        key = (names, tuple(sorted(ph.keys())))
        if key not in self._jit_cache:
            self._jit_cache[key] = self._build_forward(names, tuple(sorted(ph.keys())))
        res = self._jit_cache[key](self._non_constant_arrays(), ph)
        if as_map:
            return {n: np.asarray(r) for n, r in zip(names, res)}
        return res[0] if len(names) == 1 else res

    def batch_output(self, placeholders, outputs):
        return self.output(placeholders, *outputs)

    # -------------------------------------------------------------- training
    def set_loss_variables(self, *names: str) -> None:
        self.loss_variables = [n.name if isinstance(n, SDVariable) else n for n in names]

    def set_training_config(self, cfg: TrainingConfig) -> None:
        self.training_config = cfg
        self._graph_version += 1
        # a new config means a new updater: rebuild optimizer state lazily
        self._tx = None
        self._opt_state = None

    def set_listeners(self, *listeners) -> None:
        """Training listeners (reference ``sd.setListeners``): objects with
        ``iteration_done(sd, iteration, epoch, loss)`` called per batch.
        Note: reading ``loss`` forces a device sync; listeners receive the
        on-device scalar and may keep it lazy."""
        self._listeners = list(listeners)

    def _trainable(self) -> Dict[str, jax.Array]:
        return {n: a for n, a in self.arrays.items()
                if self.vars[n].vtype == VariableType.VARIABLE}

    def _make_train_step(self, ph_names: Tuple[str, ...], packer=None,
                         unroll: int = 1):
        cfg = self.training_config
        consts = {n: a for n, a in self.arrays.items()
                  if self.vars[n].vtype == VariableType.CONSTANT}
        # Mixed precision (TPU policy): master weights stay f32; the traced
        # program computes in env.compute_dtype (bf16 when enabled via
        # Environment.allow_bfloat16). Grads flow back through the cast, so
        # updates land on the f32 masters.
        from deeplearning4j_tpu.runtime.environment import get_environment
        cdt = get_environment().compute_dtype

        def _c(a):
            if jnp.issubdtype(a.dtype, jnp.floating) and a.dtype != cdt:
                return a.astype(cdt)
            return a

        def loss_fn(trainable, placeholders, rng):
            env = {n: _c(a) for n, a in consts.items()}
            env.update({n: _c(a) for n, a in trainable.items()})
            env.update({n: _c(a) for n, a in placeholders.items()})
            env["__rng__"] = rng
            losses = self._exec_graph(env, self.loss_variables)
            total = sum(jnp.sum(l.astype(jnp.float32)) for l in losses)
            return total

        from deeplearning4j_tpu.runtime.environment import get_environment
        if get_environment().remat_segments:
            # Imported graphs have no layer boundaries to cut at, so use the
            # dots-saveable policy: keep matmul outputs, recompute the
            # elementwise chains in backward. Measured on the imported
            # BERT-base step: bytes-accessed is the limiter (63 GB vs the
            # hand-built model's 35 GB at identical FLOPs), and this trades
            # a few re-FLOPs for most of that traffic.
            loss_fn = jax.checkpoint(
                loss_fn, policy=jax.checkpoint_policies.dots_saveable)

        def loss_with_reg(trainable, placeholders, rng):
            total = loss_fn(trainable, placeholders, rng)
            if cfg.l2:
                total = total + 0.5 * cfg.l2 * sum(
                    jnp.sum(w * w) for w in trainable.values())
            if cfg.l1:
                total = total + cfg.l1 * sum(
                    jnp.sum(jnp.abs(w)) for w in trainable.values())
            return total

        # Per-step randomness: the step takes the GLOBAL iteration index
        # (a 4-byte scalar upload, async, negligible next to the batch) and
        # folds it into a base key on-device. Fresh dropout masks / random
        # draws every iteration; bit-reproducible given the SameDiff seed.
        base_key = self._rng_key

        def step(trainable, opt_state, placeholders, step_idx):
            rng = jax.random.fold_in(base_key, step_idx)
            loss, grads = jax.value_and_grad(loss_with_reg)(
                trainable, placeholders, rng)
            updates, opt_state = self._tx.update(grads, opt_state, trainable)
            return optax.apply_updates(trainable, updates), opt_state, loss

        if packer is None:
            return jax.jit(step, donate_argnums=(0, 1))

        # Packed variant (runtime/state_packing.py): an imported BERT-base
        # carries ~600 (variable + Adam-moment) leaves, mostly small bias/
        # layernorm vectors — one buffer-handle marshal each per dispatch.
        if unroll <= 1:
            def packed_step(packed, placeholders, step_idx):
                trainable, opt_state = packer.unpack(packed)
                new_t, new_o, loss = step(trainable, opt_state, placeholders,
                                          step_idx)
                return packer.pack((new_t, new_o)), loss

            return jax.jit(packed_step, donate_argnums=(0,))

        # Grouped dispatch (env.dispatch_unroll, same mechanism as
        # MultiLayerNetwork.fit): K same-shape batches as ONE unrolled
        # program. The batches arrive as a LIST of placeholder dicts — a
        # plain pytree argument — rather than pre-stacked arrays: stacking
        # on-device would cost ~4 tiny dispatches per placeholder per
        # group, which is the very overhead grouping exists to remove.
        def packed_step_unrolled(packed, ph_list, step_idxs):
            trainable, opt_state = packer.unpack(packed)
            losses = []
            for i in range(unroll):
                trainable, opt_state, loss = step(trainable, opt_state,
                                                  ph_list[i], step_idxs[i])
                losses.append(loss)
            return packer.pack((trainable, opt_state)), jnp.stack(losses)

        return jax.jit(packed_step_unrolled, donate_argnums=(0,))

    def fit(self, data, labels=None, epochs: int = 1, batch_size: Optional[int] = None):
        """Train (reference ``sd.fit(DataSetIterator)``). Accepts a
        DataSetIterator or (features, labels) arrays."""
        if self.training_config is None:
            raise ValueError("Call set_training_config first")
        if not self.loss_variables:
            raise ValueError("Call set_loss_variables first")
        cfg = self.training_config
        from deeplearning4j_tpu.data.dataset import DataSet, MultiDataSet
        if isinstance(data, MultiDataSet):
            from deeplearning4j_tpu.data.iterators import ExistingDataSetIterator
            iterator = ExistingDataSetIterator([data])
        elif labels is not None:
            from deeplearning4j_tpu.data.iterators import ListDataSetIterator
            iterator = ListDataSetIterator(
                [DataSet(np.asarray(data), np.asarray(labels))],
                batch_size=batch_size or len(data))
        else:
            iterator = data
        trainable = self._trainable()
        if self._tx is None:
            self._tx = cfg.updater.make()
            self._opt_state = self._tx.init(trainable)
        ph_names = tuple(cfg.data_set_feature_mapping + cfg.data_set_label_mapping)
        from deeplearning4j_tpu.runtime.environment import get_environment
        # _graph_version covers everything the traced step closes over that
        # the structural key can't see: constant VALUES (set_arr), the
        # training config (l1/l2), graph edits
        # Packing keeps self.arrays stale until fit returns, so it is only
        # safe when no attached listener reads model state mid-fit (same
        # rule as MultiLayerNetwork.fit).
        from deeplearning4j_tpu.train.prefetch import stateless_listeners
        use_packing = (get_environment().packed_state
                       and stateless_listeners(self))
        unroll = max(1, int(get_environment().dispatch_unroll)) \
            if use_packing else 1
        key = ("train_step", ph_names, str(get_environment().compute_dtype),
               get_environment().remat_segments,
               tuple(sorted(trainable)), self._graph_version, use_packing)
        if key not in self._jit_cache:
            if use_packing:
                from deeplearning4j_tpu.runtime.state_packing import LeafPacker
                packer = LeafPacker((trainable, self._opt_state))
                self._jit_cache[key] = (self._make_train_step(ph_names, packer),
                                        packer)
            else:
                self._jit_cache[key] = (self._make_train_step(ph_names), None)
        step, packer = self._jit_cache[key]
        group_step = None
        if unroll > 1:
            gkey = key + ("unroll", unroll)
            if gkey not in self._jit_cache:
                self._jit_cache[gkey] = (
                    self._make_train_step(ph_names, packer, unroll=unroll),
                    packer)
            group_step, _ = self._jit_cache[gkey]
        history = []
        bounds = []
        it_count = 0
        # Host->device transfer cache for this fit call: iterators commonly
        # hand back the SAME numpy arrays every epoch, and the cache skips
        # re-uploading them. The weakref guards against id() reuse after an
        # array dies; the content hash catches iterators that refill one
        # buffer in place; the size cap bounds HBM held for
        # fresh-array-per-batch iterators. Its benefit on this machine is
        # not measured.
        import hashlib
        import weakref
        h2d: Dict[int, Any] = {}

        def _fp(a):
            return hashlib.blake2b(np.ascontiguousarray(a).tobytes(),
                                   digest_size=16).digest()

        def dev(a):
            if isinstance(a, jax.Array):
                return a
            fp = _fp(a)
            ent = h2d.get(id(a))
            if ent is not None and ent[0]() is a and ent[2] == fp:
                return ent[1]
            buf = jnp.asarray(a)
            if len(h2d) > 64:
                h2d.clear()
            try:
                h2d[id(a)] = (weakref.ref(a), buf, fp)
            except TypeError:
                pass
            return buf

        packed = (packer.pack_device((trainable, self._opt_state))
                  if packer is not None else None)
        cur_ep = 0
        # AOT dispatch fast path (env.aot_dispatch): per placeholder-shape
        # signature, the hot loop calls a cached lower().compile()
        # executable with the donated packed buffers instead of re-entering
        # jit dispatch every step — bit-identical (same trace, same
        # executable). The cache lives in _jit_cache, so graph edits /
        # set_arr on constants (which clear it) invalidate executables too.
        from deeplearning4j_tpu.runtime.compile_cache import AotCache
        from deeplearning4j_tpu.runtime.state_packing import (
            step_args_signature)
        aot = self._jit_cache.setdefault("__aot__", AotCache("sd-fit"))

        def run_single(a):
            nonlocal packed
            packed, loss = aot.call(
                ("single", key, step_args_signature((a[0],))),
                step, packed, a[0], np.uint32(a[1]))
            return loss

        def run_group(todo):
            nonlocal packed
            idxs = np.asarray([t[1] for t in todo], np.uint32)
            packed, losses = aot.call(
                ("group", gkey, step_args_signature((todo[0][0],))),
                group_step, packed, [t[0] for t in todo], idxs)
            return [losses[i] for i in range(len(todo))]

        def deliver(args, loss):
            nonlocal it_count
            # keep losses on-device: a float() here would stall the
            # pipeline on every step (one device->host readback per batch)
            history.append(loss)
            it_count += 1
            for lst in self._listeners:
                lst.iteration_done(self, it_count, cur_ep, loss)

        from deeplearning4j_tpu.runtime.state_packing import GroupedDispatch
        gd = GroupedDispatch(
            unroll=unroll,
            compatible=lambda a, b: ({n: v.shape for n, v in a[0].items()}
                                     == {n: v.shape for n, v in b[0].items()}),
            run_single=run_single, run_group=run_group, deliver=deliver)

        try:
            for ep in range(int(epochs)):
                cur_ep = ep
                iterator.reset()
                for batch in iterator:
                    feats = [batch.features] if not isinstance(batch.features, list) else batch.features
                    labs = [batch.labels] if not isinstance(batch.labels, list) else batch.labels
                    ph = {n: dev(a) for n, a in
                          zip(cfg.data_set_feature_mapping, feats)}
                    ph.update({n: dev(a) for n, a in
                               zip(cfg.data_set_label_mapping, labs)})
                    if packer is None:
                        trainable, self._opt_state, loss = step(
                            trainable, self._opt_state, ph,
                            np.uint32(self._train_iter))
                        self._train_iter += 1
                        history.append(loss)
                        it_count += 1
                        for lst in self._listeners:
                            lst.iteration_done(self, it_count, ep, loss)
                        continue
                    gd.submit((ph, self._train_iter))
                    self._train_iter += 1
                gd.flush()
                bounds.append(it_count)
        finally:
            gd.drain_on_error()  # deliver batches buffered before an error
            from deeplearning4j_tpu.runtime.state_packing import LeafPacker
            if packed is not None and not LeafPacker.is_dead(packed):
                # (a raising donated step leaves no newer state to recover)
                trainable, self._opt_state = packer.unpack_device(
                    packed, donate=True)
                self.arrays.update(trainable)  # even on exceptional exit
        if packer is None:
            self.arrays.update(trainable)
        if history:
            # ONE device->host transfer for all losses instead of one
            # readback per scalar. Padded to a power of two so the stack's
            # concatenate compiles once per size CLASS, not once per
            # distinct step count.
            n = len(history)
            size = 1 << max(0, n - 1).bit_length()
            padded = history + [history[-1]] * (size - n)
            history = np.asarray(jnp.stack(padded))[:n].astype(float).tolist()
        return History(history, bounds)

    def evaluate(self, iterator, output_name: str, evaluation=None,
                 label_index: int = 0):
        """Evaluate a graph output against the iterator's labels (reference
        ``sd.evaluate(iterator, outputName, evaluation)``). Feature arrays
        feed ``training_config.data_set_feature_mapping``; labels go to the
        evaluation object, not the graph."""
        if evaluation is None:
            from deeplearning4j_tpu.evaluation import Evaluation
            evaluation = Evaluation()
        cfg = self.training_config
        if cfg is None or not cfg.data_set_feature_mapping:
            raise ValueError("evaluate() needs a TrainingConfig with "
                             "data_set_feature_mapping")
        iterator.reset()
        for batch in iterator:
            feats = [batch.features] if not isinstance(batch.features, list) \
                else batch.features
            labs = [batch.labels] if not isinstance(batch.labels, list) \
                else batch.labels
            ph = {n: jnp.asarray(a) for n, a in
                  zip(cfg.data_set_feature_mapping, feats)}
            pred = self.output(ph, output_name)
            evaluation.eval(np.asarray(labs[label_index]), np.asarray(pred))
        return evaluation

    def calculate_gradients(self, placeholders: Dict[str, Any],
                            *wrt: str) -> Dict[str, jax.Array]:
        """Gradients of the (summed) loss wrt named variables (reference
        ``sd.calculateGradients``)."""
        if not self.loss_variables:
            raise ValueError("Call set_loss_variables first")
        consts = {n: a for n, a in self.arrays.items()
                  if self.vars[n].vtype != VariableType.ARRAY}
        ph = {k: jnp.asarray(v) for k, v in placeholders.items()}
        wrt = tuple(wrt) or tuple(self._trainable().keys())

        def loss_fn(sub):
            env = dict(consts)
            env.update(sub)
            env.update(ph)
            return sum(jnp.sum(l) for l in self._exec_graph(env, self.loss_variables))

        sub = {n: consts[n] for n in wrt}
        grads = jax.grad(loss_fn)(sub)
        return grads

    # ----------------------------------------------------------------- serde
    def to_dict(self) -> dict:
        if any(n.op == "__callable__" for n in self.ops):
            raise ValueError(
                "Graphs containing python control-flow callables (cond/"
                "while_loop) are not serializable; export StableHLO instead")
        return {
            "vars": [{"name": v.name, "type": v.vtype.value,
                      "shape": list(v.shape) if v.shape else None}
                     for v in self.vars.values()],
            "ops": [{"op": n.op, "inputs": n.inputs, "outputs": n.outputs,
                     "attrs": _json_attrs(n.attrs)} for n in self.ops],
            "loss_variables": self.loss_variables,
            "training_config": self.training_config.to_dict() if self.training_config else None,
        }

    def save(self, path: str, save_updater_state: bool = False) -> None:
        """Zip: graph.json + arrays.npz (the ``.fb`` single-artifact analog —
        reference ``sd.save(file, saveUpdaterState)``).

        The RNG stream position (``_train_iter`` + base key) is always
        persisted: now that train-time stochasticity is real, a mid-training
        save/restore must NOT replay dropout masks from step 0. With
        ``save_updater_state=True`` the optimizer state (Adam moments etc.)
        is saved too, giving bit-exact resume — the reference's
        ``sd.save(file, true)`` contract."""
        with zipfile.ZipFile(path, "w", zipfile.ZIP_DEFLATED) as zf:
            zf.writestr("graph.json", json.dumps(self.to_dict(), indent=2))
            buf = io.BytesIO()
            np.savez(buf, **{k: np.asarray(v) for k, v in self.arrays.items()})
            zf.writestr("arrays.npz", buf.getvalue())
            buf = io.BytesIO()
            np.savez(buf, train_iter=np.asarray(self._train_iter, np.int64),
                     rng_key=np.asarray(self._rng_key))
            zf.writestr("training_state.npz", buf.getvalue())
            if save_updater_state and self._opt_state is not None:
                from deeplearning4j_tpu.models.serializer import _save_pytree_npz
                zf.writestr("updaterState.npz",
                            _save_pytree_npz(self._opt_state))

    @staticmethod
    def load(path: str) -> "SameDiff":
        sd = SameDiff()
        with zipfile.ZipFile(path) as zf:
            d = json.loads(zf.read("graph.json").decode())
            z = np.load(io.BytesIO(zf.read("arrays.npz")))
            for vd in d["vars"]:
                v = SDVariable(sd, vd["name"], VariableType(vd["type"]),
                               tuple(vd["shape"]) if vd["shape"] else None)
                sd.vars[v.name] = v
            for od in d["ops"]:
                sd.ops.append(OpNode(op=od["op"], inputs=od["inputs"],
                                     outputs=od["outputs"], attrs=od.get("attrs", {})))
            for k in z.files:
                sd.arrays[k] = jnp.asarray(z[k])
            sd.loss_variables = d.get("loss_variables", [])
            if d.get("training_config"):
                sd.training_config = TrainingConfig.from_dict(d["training_config"])
            if "training_state.npz" in zf.namelist():
                ts = np.load(io.BytesIO(zf.read("training_state.npz")))
                sd._train_iter = int(ts["train_iter"])
                sd._rng_key = jnp.asarray(ts["rng_key"])
            if ("updaterState.npz" in zf.namelist()
                    and sd.training_config is not None):
                # Rebuild the optimizer pytree structure from the config
                # (eval_shape: structure only, no device allocation — a
                # BERT-scale moment set is hundreds of MB), then graft the
                # saved leaves into it via the shared leaf-order protocol.
                from deeplearning4j_tpu.models.serializer import _load_pytree_npz
                sd._tx = sd.training_config.updater.make()
                template = jax.eval_shape(sd._tx.init, sd._trainable())
                sd._opt_state = _load_pytree_npz(
                    zf.read("updaterState.npz"), template)
        return sd

    def export_stablehlo(self, placeholders: Dict[str, Any], *outputs: str) -> str:
        """Lower the graph to StableHLO text via jax.export — the analog of
        the reference's FlatBuffers graph handoff to libnd4j's
        GraphExecutioner (SURVEY.md §3.2), with XLA as the executor."""
        names = tuple(outputs)
        ph = {k: jnp.asarray(v) for k, v in placeholders.items()}
        fn = self._build_forward(names, tuple(sorted(ph.keys())))
        lowered = fn.lower(self._non_constant_arrays(), ph)
        return lowered.as_text()

    # convenience summaries (reference sd.summary())
    def summary(self) -> str:
        lines = [f"SameDiff: {len(self.vars)} variables, {len(self.ops)} ops"]
        for v in self.vars.values():
            if v.vtype != VariableType.ARRAY:
                lines.append(f"  {v.vtype.value:12s} {v.name:24s} {v.shape}")
        for n in self.ops:
            lines.append(f"  op {n.op:24s} {n.inputs} -> {n.outputs}")
        return "\n".join(lines)


def _json_attrs(attrs: Dict[str, Any]) -> Dict[str, Any]:
    out = {}
    for k, v in attrs.items():
        if isinstance(v, (np.ndarray, jax.Array)):
            v = np.asarray(v).tolist()
        elif isinstance(v, tuple):
            v = list(v)
        out[k] = v
    return out
