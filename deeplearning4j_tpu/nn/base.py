"""Layer base class and serde registry.

In the reference every layer is a *pair*: a Jackson-serializable conf class
(``org.deeplearning4j.nn.conf.layers.*``) and a runtime impl
(``org.deeplearning4j.nn.layers.*``) with ``activate()`` /
``backpropGradient()``. Here a layer is ONE dataclass that is both the
serializable config (``to_dict``/``from_dict`` via a name registry, the
Jackson-polymorphism analog) and the pure-functional implementation
(``init``/``forward``); backprop comes from ``jax.grad`` of the composed
forward, so no hand-written backward passes exist anywhere.

Forward contract (uniform across layers so the network can compose them into
one traced program):

    y, new_state = layer.forward(params, state, x, training=..., rng=..., mask=...)

- ``params``: dict of trainable arrays ("W", "b", "gamma", ...). Keys starting
  with "W" or "gamma"-free weight keys are subject to l1/l2 (see
  ``regularizable_params``).
- ``state``:  dict of non-trainable arrays (batch-norm running stats).
- ``rng``:    PRNG key, only consumed when the layer is stochastic + training.
- ``mask``:   optional (batch, time) validity mask for sequence data.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple, Type

import jax

from deeplearning4j_tpu.nn.inputs import InputType
from deeplearning4j_tpu.ops.activations import Activation
from deeplearning4j_tpu.ops.initializers import WeightInit

_LAYER_REGISTRY: Dict[str, Type["Layer"]] = {}


def register_layer(cls: Type["Layer"]) -> Type["Layer"]:
    """Class decorator: registers the layer under its class name for serde
    (the Jackson-polymorphic-type analog)."""
    _LAYER_REGISTRY[cls.__name__] = cls
    return cls


def get_layer_class(name: str) -> Type["Layer"]:
    if name not in _LAYER_REGISTRY:
        raise KeyError(f"Unknown layer type {name!r}; registered: {sorted(_LAYER_REGISTRY)}")
    return _LAYER_REGISTRY[name]


def dropout_mask(rng, keep_prob, shape):
    """Bernoulli keep-mask backed by XLA's ``RngBitGenerator`` (jax "rbg"
    PRNG) instead of the default threefry.

    Dropout is pure traffic — the mask is consumed once — and threefry's
    counter math costs real MXU-adjacent cycles: on the v5e it was measured
    at ~15 ms/step of BERT-base (64x128), ~27% of the whole step. The rbg
    generator is hardware-backed and cut that to noise (1187 -> 1637
    samples/s, v5e, dropout-site-only switch, round 3).
    Only dropout routes through here; weight init and every
    other draw keep the threefry key chain, so seeds/goldens elsewhere are
    unchanged. The incoming key may be a raw uint32 vector (old-style) or a
    typed key; both are folded into the 4-word rbg key format.
    """
    import numpy as np

    import jax.numpy as jnp
    if jnp.issubdtype(rng.dtype, jax.dtypes.prng_key):
        data = jax.random.key_data(rng)
    else:
        data = rng
    data = data.astype(jnp.uint32).reshape(-1)
    if data.shape[0] < 4:
        data = jnp.concatenate([data, data])[:4]
    key = jax.random.wrap_key_data(data[:4], impl="rbg")
    # Draw over the FLATTENED (rows, features) view: profiled on v5e, the
    # 3-D rbg bits tensor's tiling never matches its consumer and XLA
    # inserts a 25 MB u32 layout copy per dropout site (~1 ms/step on
    # BERT-base across 25 sites); the 2-D draw layout-matches and the
    # reshape back is a free bitcast.
    if len(shape) > 2:
        rows = int(np.prod(shape[:-1]))
        return jax.random.bernoulli(key, keep_prob,
                                    shape=(rows, shape[-1])).reshape(shape)
    return jax.random.bernoulli(key, keep_prob, shape=shape)


def cast_floating(tree, dtype):
    """Cast floating-point leaves of a pytree to ``dtype``.

    The mixed-precision policy: master params stay in ``default_dtype``
    (float32); the jitted step casts them to ``compute_dtype`` (bfloat16 on
    TPU) here, right before use. Autodiff transposes the cast, so gradients
    land back in the master dtype and the optimizer update stays full
    precision."""
    import jax.numpy as jnp

    def _c(a):
        if hasattr(a, "dtype") and jnp.issubdtype(a.dtype, jnp.floating) and a.dtype != dtype:
            return a.astype(dtype)
        return a

    return jax.tree.map(_c, tree)


@dataclasses.dataclass
class GlobalConfig:
    """Network-wide defaults that layers inherit when their own field is None.

    Mirrors the fields configured on the outer ``NeuralNetConfiguration.Builder``
    in the reference (seed, weightInit, activation, l1/l2, dropout, ...).
    """

    seed: int = 0
    weight_init: WeightInit = WeightInit.XAVIER
    activation: Any = Activation.IDENTITY
    l1: float = 0.0
    l2: float = 0.0
    weight_decay: float = 0.0
    dropout: Optional[float] = None  # retain probability, DL4J convention
    bias_init: float = 0.0
    updater: Any = None  # train.updaters.Updater; resolved by the training engine
    gradient_normalization: Optional[str] = None
    gradient_normalization_threshold: float = 1.0
    dtype: Any = None  # resolved against runtime Environment
    # Reference OptimizationAlgorithm: STOCHASTIC_GRADIENT_DESCENT (default),
    # LBFGS, CONJUGATE_GRADIENT, LINE_GRADIENT_DESCENT (legacy second-order /
    # line-search solvers; see train/solvers.py).
    optimization_algo: str = "STOCHASTIC_GRADIENT_DESCENT"
    max_num_line_search_iterations: int = 5  # line-search step budget
    solver_iterations: int = 10  # outer LBFGS/CG iterations per batch


@dataclasses.dataclass
class Layer:
    """Base layer config. Subclasses add fields and override the four methods.

    Fields that default to ``None`` inherit from :class:`GlobalConfig` at
    build time (the reference's conf-inheritance or "layer overrides global
    builder" behaviour).
    """

    name: Optional[str] = None
    activation: Any = None
    weight_init: Any = None
    bias_init: Optional[float] = None
    l1: Optional[float] = None
    l2: Optional[float] = None
    weight_decay: Optional[float] = None
    dropout: Optional[float] = None  # retain probability applied to layer INPUT
    updater: Any = None
    frozen: bool = False  # transfer-learning: exclude params from training
    # Post-update projections (reference LayerConstraint) and train-time
    # weight perturbation (reference IWeightNoise / DropConnect)
    constraints: Any = None
    bias_constraints: Any = None
    weight_noise: Any = None
    # Parameters this layer reads but another layer owns: ``{name here:
    # "<layer key>" or "<layer key>/<param>"}`` (see ``with_tied``)
    tied: Optional[Dict[str, str]] = None
    # GlobalConfig attached by the network at build time (not serialized) so
    # forward() needs no extra argument.
    _g: Any = dataclasses.field(default=None, repr=False, compare=False)

    # A layer that sets this gets the batch's labels as ``forward(...,
    # labels=)`` wherever the network has them (its training loss; ``None``
    # at inference), e.g. to train on a second prediction per position.
    takes_labels = False

    # ---- shape inference ----
    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    # ---- parameters ----
    def init(self, key: jax.Array, input_type: InputType, g: GlobalConfig
             ) -> Tuple[Dict[str, jax.Array], Dict[str, jax.Array]]:
        """Return (params, state). Default: parameterless layer."""
        return {}, {}

    def forward(self, params: Dict, state: Dict, x, *, training: bool = False,
                rng: Optional[jax.Array] = None, mask=None) -> Tuple[Any, Dict]:
        raise NotImplementedError

    # ---- regularization ----
    def regularizable_params(self) -> Tuple[str, ...]:
        """Param keys subject to l1/l2/weight-decay (weights, not biases —
        the reference's default regularization split)."""
        return ("W", "W_rec", "W_point", "W_depth", "W_q", "W_k", "W_v", "W_o")

    # ---- inherited-field resolution ----
    def _act(self, g: GlobalConfig):
        return self.activation if self.activation is not None else g.activation

    def _winit(self, g: GlobalConfig):
        return self.weight_init if self.weight_init is not None else g.weight_init

    def _binit(self, g: GlobalConfig) -> float:
        return self.bias_init if self.bias_init is not None else g.bias_init

    def _dropout(self, g: GlobalConfig):
        return self.dropout if self.dropout is not None else g.dropout

    def _apply_input_dropout(self, x, g: GlobalConfig, training: bool, rng):
        """DL4J semantics: ``dropOut(p)`` on a layer drops the layer's INPUT
        with retain probability p, inverted scaling."""
        p = self._dropout(g)
        if not training or p is None or p >= 1.0 or rng is None:
            return x
        keep = dropout_mask(rng, p, x.shape)
        return jax.numpy.where(keep, x / p, 0.0).astype(x.dtype)

    # ---- serde ----
    def to_dict(self) -> dict:
        d = {"@type": type(self).__name__}
        for f in dataclasses.fields(self):
            if f.name.startswith("_"):
                continue
            v = getattr(self, f.name)
            if v is None or v == f.default:
                continue
            if isinstance(v, (Activation, WeightInit)):
                v = v.value
            elif dataclasses.is_dataclass(v) and not isinstance(v, type):
                v = v.to_dict() if hasattr(v, "to_dict") else dataclasses.asdict(v)
            elif hasattr(v, "to_dict"):
                v = v.to_dict()
            elif isinstance(v, (list, tuple)) and v and hasattr(v[0], "to_dict"):
                v = [e.to_dict() for e in v]
            d[f.name] = v
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "Layer":
        d = dict(d)
        typ = d.pop("@type", cls.__name__)
        target = get_layer_class(typ)
        # Delegate to a subclass's overridden from_dict (e.g. wrapper layers
        # that must revive their nested ``underlying`` layer).
        if target.from_dict.__func__ is not cls.from_dict.__func__:
            return target.from_dict({**d, "@type": typ})
        field_names = {f.name for f in dataclasses.fields(target)}
        kwargs = {}
        for k, v in d.items():
            if k not in field_names:
                continue
            if k == "updater" and isinstance(v, dict):
                from deeplearning4j_tpu.train.updaters import Updater
                v = Updater.from_dict(v)
            elif k in ("constraints", "bias_constraints") and v is not None:
                from deeplearning4j_tpu.nn.constraints import Constraint
                vs = v if isinstance(v, list) else [v]
                v = [Constraint.from_dict(e) if isinstance(e, dict) else e
                     for e in vs]
            elif k == "weight_noise" and isinstance(v, dict):
                from deeplearning4j_tpu.nn.constraints import (DropConnect,
                                                               WeightNoise)
                v = (DropConnect if v.get("type") == "DropConnect"
                     else WeightNoise)(**{a: b for a, b in v.items()
                                          if a != "type"})
            kwargs[k] = v
        return target(**kwargs)


def with_tied(layer: Layer, own: Dict, all_params: Dict) -> Dict:
    """``own`` (the layer's parameters) plus what ``layer.tied`` names of
    other layers' parameters, each under its name here.

    A tied parameter is ONE leaf of the network's parameter tree, held by
    the layer that owns it; every layer that names it reads that leaf
    inside the same traced step, so its gradient is the sum over its uses
    and the updater moves it once (weight tying: an output head on the
    embedding's table, a second prediction layer on the trunk's head)."""
    if not layer.tied:
        return own
    out = dict(own)
    for here, path in layer.tied.items():
        node = all_params
        for part in path.split("/"):
            if not isinstance(node, dict) or part not in node:
                raise KeyError(f"{type(layer).__name__}: tied parameter {here!r} names {path!r}, "
                               f"which is not in the network's parameters ({sorted(all_params)})")
            node = node[part]
        out[here] = node
    return out


def spectral_key(key: jax.Array, i: int) -> jax.Array:
    """Deterministic per-index subkey (used to give each layer its own stream)."""
    return jax.random.fold_in(key, i)
