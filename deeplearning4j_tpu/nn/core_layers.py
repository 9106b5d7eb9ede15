"""Core feed-forward layers.

Rebuild of upstream ``org.deeplearning4j.nn.conf.layers`` core set:
``DenseLayer``, ``OutputLayer``, ``LossLayer``, ``ActivationLayer``,
``DropoutLayer``, ``EmbeddingLayer``, ``EmbeddingSequenceLayer``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.base import (GlobalConfig, Layer, dropout_mask,
                                        register_layer)
from deeplearning4j_tpu.nn.inputs import InputType
from deeplearning4j_tpu.ops.activations import get_activation
from deeplearning4j_tpu.ops.initializers import init_weights
from deeplearning4j_tpu.ops.losses import LossFunction, compute_loss


@register_layer
@dataclasses.dataclass
class DenseLayer(Layer):
    """Fully-connected layer: y = act(x @ W + b). W: (nIn, nOut)."""

    n_out: int = 0
    n_in: Optional[int] = None  # inferred from input type when None
    has_bias: bool = True

    def output_type(self, input_type: InputType) -> InputType:
        if input_type.kind == "recurrent":
            # time-distributed dense, like the reference's dense-on-rank3
            return InputType.recurrent(self.n_out, input_type.timesteps)
        return InputType.feed_forward(self.n_out)

    def _nin(self, input_type: InputType) -> int:
        if self.n_in is not None:
            return self.n_in
        return input_type.size if input_type.kind in ("feedforward", "recurrent") \
            else input_type.flat_size()

    def init(self, key, input_type, g: GlobalConfig):
        n_in = self._nin(input_type)
        k1, _ = jax.random.split(key)
        params = {"W": init_weights(k1, (n_in, self.n_out), self._winit(g),
                                    fan=(n_in, self.n_out), dtype=g.dtype)}
        if self.has_bias:
            params["b"] = jnp.full((self.n_out,), self._binit(g), dtype=g.dtype)
        return params, {}

    def forward(self, params, state, x, *, training=False, rng=None, mask=None):
        x = self._apply_input_dropout(x, self._g, training, rng)
        y = x @ params["W"]
        if self.has_bias:
            y = y + params["b"]
        return get_activation(self._act(self._g))(y), state


@register_layer
@dataclasses.dataclass
class OutputLayer(DenseLayer):
    """Dense + loss head (reference ``OutputLayer``): the network's training
    loss is computed from this layer's *pre-activation* with the configured
    loss function fused with the activation for numerical stability."""

    loss: Any = LossFunction.MCXENT
    # keep the last step's data loss of this head in the model state, as the
    # float32 leaf ``main_loss`` (``MultiLayerNetwork._loss`` writes it): for
    # a network whose training loss has further terms (``_aux_loss``)
    record_loss: bool = False

    def init(self, key, input_type, g: GlobalConfig):
        params, state = super().init(key, input_type, g)
        if self.record_loss:
            state = {**state, "main_loss": jnp.zeros((), jnp.float32)}
        return params, state

    def forward(self, params, state, x, *, training=False, rng=None, mask=None):
        x = self._apply_input_dropout(x, self._g, training, rng)
        y = x @ params["W"]
        if self.has_bias:
            y = y + params["b"]
        # Activation applied here for inference; training loss uses preoutput.
        return get_activation(self._act(self._g))(y), state

    def preoutput(self, params, x):
        y = x @ params["W"]
        if self.has_bias:
            y = y + params["b"]
        return y

    def activate(self, params, x):
        """Forward WITHOUT input dropout — used by the network after it has
        already applied this layer's input dropout (so the training loss and
        the forward output see the same dropped input)."""
        return get_activation(self._act(self._g))(self.preoutput(params, x))

    def compute_loss(self, params, x, labels, mask=None, state=None):
        return compute_loss(self.loss, labels, self.preoutput(params, x),
                            activation=self._act(self._g), mask=mask)


@register_layer
@dataclasses.dataclass
class LossLayer(Layer):
    """Loss without params (reference ``LossLayer``): applies activation +
    loss to its input directly."""

    loss: Any = LossFunction.MCXENT

    def forward(self, params, state, x, *, training=False, rng=None, mask=None):
        return get_activation(self._act(self._g))(x), state

    def activate(self, params, x):
        return get_activation(self._act(self._g))(x)

    def compute_loss(self, params, x, labels, mask=None, state=None):
        return compute_loss(self.loss, labels, x, activation=self._act(self._g), mask=mask)



@register_layer
@dataclasses.dataclass
class ActivationLayer(Layer):
    """Standalone activation (reference ``ActivationLayer``)."""

    def forward(self, params, state, x, *, training=False, rng=None, mask=None):
        return get_activation(self._act(self._g))(x), state



@register_layer
@dataclasses.dataclass
class DropoutLayer(Layer):
    """Standalone dropout (reference ``DropoutLayer``). ``dropout`` field is
    the retain probability (DL4J convention)."""

    def forward(self, params, state, x, *, training=False, rng=None, mask=None):
        p = self._dropout(self._g) or 0.5
        if not training or rng is None or p >= 1.0:
            return x, state
        keep = dropout_mask(rng, p, x.shape)
        return jnp.where(keep, x / p, 0.0).astype(x.dtype), state



@register_layer
@dataclasses.dataclass
class EmbeddingLayer(Layer):
    """Index -> vector lookup (reference ``EmbeddingLayer``): input is
    (batch,) or (batch, 1) int indices; output (batch, nOut). Equivalent to a
    one-hot matmul but executed as a gather."""

    n_in: int = 0  # vocab size
    n_out: int = 0
    has_bias: bool = False

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.feed_forward(self.n_out)

    def init(self, key, input_type, g: GlobalConfig):
        params = {"W": init_weights(key, (self.n_in, self.n_out), self._winit(g),
                                    fan=(self.n_in, self.n_out), dtype=g.dtype)}
        if self.has_bias:
            params["b"] = jnp.full((self.n_out,), self._binit(g), dtype=g.dtype)
        return params, {}

    def forward(self, params, state, x, *, training=False, rng=None, mask=None):
        idx = x.astype(jnp.int32)
        if idx.ndim == 2 and idx.shape[-1] == 1:
            idx = idx[..., 0]
        y = jnp.take(params["W"], idx, axis=0)
        if self.has_bias:
            y = y + params["b"]
        return get_activation(self._act(self._g))(y), state



@register_layer
@dataclasses.dataclass
class EmbeddingSequenceLayer(Layer):
    """Sequence of indices -> sequence of vectors (reference
    ``EmbeddingSequenceLayer``): (batch, time) ints -> (batch, time, nOut)."""

    n_in: int = 0
    n_out: int = 0

    def output_type(self, input_type: InputType) -> InputType:
        t = input_type.timesteps if input_type.kind == "recurrent" else None
        return InputType.recurrent(self.n_out, t)

    def init(self, key, input_type, g: GlobalConfig):
        return {"W": init_weights(key, (self.n_in, self.n_out), self._winit(g),
                                  fan=(self.n_in, self.n_out), dtype=g.dtype)}, {}

    def forward(self, params, state, x, *, training=False, rng=None, mask=None):
        y = jnp.take(params["W"], x.astype(jnp.int32), axis=0)
        return get_activation(self._act(self._g))(y), state

