"""Recurrent layers.

Rebuild of upstream ``org.deeplearning4j.nn.conf.layers`` recurrent set:
``LSTM``, ``GravesLSTM`` (peepholes), ``SimpleRnn``, ``GRU``-equivalent,
``Bidirectional`` wrapper, ``LastTimeStep``, ``RnnOutputLayer``.

TPU-first design: the whole sequence runs as ONE ``lax.scan`` inside the
jitted step (the reference needed ``CudnnLSTMHelper`` to fuse the sequence;
under XLA the scan body — a single (batch, 4H) matmul pair per step — is
already the fused form). Gate weights are packed ``(nIn, 4H)`` so each step
is one MXU matmul. Sequence layout is (batch, time, features); masks are
(batch, time) and masked steps carry state through unchanged (matches the
reference's masking semantics).

Stateful inference (reference ``rnnTimeStep``/``rnnClearPreviousState``) is
supported through the explicit carry API: ``init_carry`` +
``forward_with_carry``; ``MultiLayerNetwork`` owns the stored carries.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.nn.base import GlobalConfig, Layer, register_layer
from deeplearning4j_tpu.nn.core_layers import OutputLayer
from deeplearning4j_tpu.nn.inputs import InputType
from deeplearning4j_tpu.ops.activations import get_activation
from deeplearning4j_tpu.ops.initializers import init_weights
from deeplearning4j_tpu.ops.losses import LossFunction

# Scan-body unroll factor. Measured on v5e (queue-drained timing, 2-layer
# H=512 char-RNN): unroll 1/8/32 are within 5% — the recurrence is matmul-
# bound, not loop-overhead-bound — so default 1 for fastest compiles. Kept as
# a knob because CPU and future backends may differ.
_SCAN_UNROLL = 1


@dataclasses.dataclass
class BaseRecurrentLayer(Layer):
    n_out: int = 0
    n_in: Optional[int] = None

    def _cell_act(self):
        """Cell-output activation: the layer's own setting wins; an explicit
        non-identity GLOBAL activation is honored; otherwise tanh — the
        reference's recurrent default (the global default identity would
        silently change the cell to h = o*c)."""
        from deeplearning4j_tpu.ops.activations import Activation
        if self.activation is not None:
            return get_activation(self.activation)
        g_act = self._g.activation if self._g is not None else None
        if g_act not in (None, Activation.IDENTITY, "identity"):
            return get_activation(g_act)
        return get_activation("tanh")

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, input_type.timesteps)

    def _nin(self, input_type: InputType) -> int:
        return self.n_in if self.n_in is not None else input_type.size

    def init_carry(self, batch: int, dtype=jnp.float32):
        raise NotImplementedError

    def forward_with_carry(self, params, carry, x, *, training=False, rng=None, mask=None):
        raise NotImplementedError

    def forward(self, params, state, x, *, training=False, rng=None, mask=None):
        x = self._apply_input_dropout(x, self._g, training, rng)
        carry = self.init_carry(x.shape[0], x.dtype)
        y, _ = self.forward_with_carry(params, carry, x, training=training, rng=rng, mask=mask)
        return y, state


@register_layer
@dataclasses.dataclass
class LSTM(BaseRecurrentLayer):
    """LSTM with packed gates [i, f, g, o]; forget-gate bias init (reference
    ``LSTM.forgetGateBiasInit``, default 1.0)."""

    forget_gate_bias_init: float = 1.0
    gate_activation: Any = "sigmoid"

    def init(self, key, input_type, g: GlobalConfig):
        n_in, H = self._nin(input_type), self.n_out
        k1, k2 = jax.random.split(key)
        b = jnp.zeros((4 * H,), g.dtype or jnp.float32)
        b = b.at[H:2 * H].set(self.forget_gate_bias_init)
        return {
            "W": init_weights(k1, (n_in, 4 * H), self._winit(g), fan=(n_in, H), dtype=g.dtype),
            "W_rec": init_weights(k2, (H, 4 * H), self._winit(g), fan=(H, H), dtype=g.dtype),
            "b": b,
        }, {}

    def init_carry(self, batch: int, dtype=jnp.float32):
        H = self.n_out
        return (jnp.zeros((batch, H), dtype), jnp.zeros((batch, H), dtype))

    def _step(self, params, h, c, zx_t):
        """One recurrence step. ``zx_t`` is the PRE-COMPUTED input projection
        ``x_t @ W + b`` — hoisting it out of the scan turns T small matmuls
        into one whole-sequence (B*T, nIn)@(nIn, 4H) MXU matmul (the same
        restructuring cuDNN's fused LSTM does), leaving only the unavoidable
        sequential ``h @ W_rec`` inside the loop."""
        H = self.n_out
        act = self._cell_act()
        gate = get_activation(self.gate_activation)
        z = zx_t + h @ params["W_rec"]
        i = gate(z[:, :H])
        f = gate(z[:, H:2 * H])
        g_ = jnp.tanh(z[:, 2 * H:3 * H])
        o = gate(z[:, 3 * H:])
        c_new = f * c + i * g_
        h_new = o * act(c_new)
        return h_new, c_new

    def _kernel_act_ok(self) -> bool:
        """The Pallas kernels implement the default activations only."""
        return (get_activation(self.gate_activation)
                is get_activation("sigmoid")
                and self._cell_act() is get_activation("tanh"))

    def _kernel_eligible(self, mask) -> bool:
        """Plain persistent kernel: default cell, no peepholes, unmasked.
        Masked sequences and GravesLSTM route to the generalised
        peephole+mask kernel (fused_lstm_graves) instead."""
        return mask is None and type(self) is LSTM and self._kernel_act_ok()

    def forward_with_carry(self, params, carry, x, *, training=False, rng=None, mask=None):
        zx = x @ params["W"] + params["b"]  # (batch, time, 4H): one big matmul
        zxs = jnp.swapaxes(zx, 0, 1)  # (time, batch, 4H)
        ms = None if mask is None else jnp.swapaxes(mask, 0, 1)

        if self._kernel_eligible(mask):
            from deeplearning4j_tpu.ops.pallas.fused_lstm import (
                fused_lstm, fused_lstm_compatible)
            h0, c0 = carry
            if fused_lstm_compatible(zxs, h0):
                ys, h, c = fused_lstm(zxs, params["W_rec"],
                                      h0.astype(zxs.dtype),
                                      c0.astype(zxs.dtype))
                return jnp.swapaxes(ys, 0, 1), (h, c)
        elif type(self) in _GRAVES_KERNEL_TYPES and self._kernel_act_ok():
            # GravesLSTM (any mask) and masked plain LSTM: the generalised
            # kernel (zero peepholes == plain cell)
            from deeplearning4j_tpu.ops.pallas.fused_lstm_graves import (
                fused_graves_lstm, fused_graves_lstm_compatible)
            h0, c0 = carry
            if fused_graves_lstm_compatible(zxs, h0):
                H = self.n_out
                peep = params.get("peephole")
                if peep is None:
                    peep = jnp.zeros((3 * H,), zxs.dtype)
                m = jnp.ones(zxs.shape[:2], zxs.dtype) if ms is None \
                    else ms.astype(zxs.dtype)
                ys, h, c = fused_graves_lstm(
                    zxs, params["W_rec"], peep.astype(zxs.dtype),
                    h0.astype(zxs.dtype), c0.astype(zxs.dtype), m)
                return jnp.swapaxes(ys, 0, 1), (h, c)

        def step(hc, inp):
            h, c = hc
            zx_t = inp[0] if ms is not None else inp
            h_new, c_new = self._step(params, h, c, zx_t)
            if ms is not None:
                m = inp[1][:, None].astype(h.dtype)
                h_new = m * h_new + (1 - m) * h
                c_new = m * c_new + (1 - m) * c
            return (h_new, c_new), h_new

        inputs = (zxs, ms) if ms is not None else zxs
        (h, c), ys = lax.scan(step, carry, inputs, unroll=_SCAN_UNROLL)
        return jnp.swapaxes(ys, 0, 1), (h, c)


@register_layer
@dataclasses.dataclass
class GravesLSTM(LSTM):
    """LSTM with peephole connections (reference ``GravesLSTM``). Routes to
    the fused peephole Pallas kernel when shapes allow."""

    def init(self, key, input_type, g: GlobalConfig):
        params, state = super().init(key, input_type, g)
        H = self.n_out
        # peephole columns live in the recurrent weight matrix in the
        # reference and draw from the configured weight-init distribution
        params["peephole"] = init_weights(
            jax.random.fold_in(key, 3), (3 * H,), self._winit(g),
            fan=(H, H), dtype=g.dtype)
        return params, state

    def _step(self, params, h, c, zx_t):
        H = self.n_out
        act = self._cell_act()
        gate = get_activation(self.gate_activation)
        p = params["peephole"]
        z = zx_t + h @ params["W_rec"]
        i = gate(z[:, :H] + c * p[:H])
        f = gate(z[:, H:2 * H] + c * p[H:2 * H])
        g_ = jnp.tanh(z[:, 2 * H:3 * H])
        c_new = f * c + i * g_
        o = gate(z[:, 3 * H:] + c_new * p[2 * H:])
        h_new = o * act(c_new)
        return h_new, c_new


# Types served by the generalised peephole+mask kernel. Subclasses of these
# may change the math arbitrarily, so membership is exact-type.
_GRAVES_KERNEL_TYPES = (LSTM, GravesLSTM)


@register_layer
@dataclasses.dataclass
class SimpleRnn(BaseRecurrentLayer):
    """Vanilla RNN: h' = act(x W + h W_rec + b) (reference ``SimpleRnn``,
    default activation tanh)."""

    def init(self, key, input_type, g: GlobalConfig):
        n_in, H = self._nin(input_type), self.n_out
        k1, k2 = jax.random.split(key)
        return {
            "W": init_weights(k1, (n_in, H), self._winit(g), fan=(n_in, H), dtype=g.dtype),
            "W_rec": init_weights(k2, (H, H), self._winit(g), fan=(H, H), dtype=g.dtype),
            "b": jnp.full((H,), self._binit(g), g.dtype or jnp.float32),
        }, {}

    def init_carry(self, batch: int, dtype=jnp.float32):
        return (jnp.zeros((batch, self.n_out), dtype),)

    def forward_with_carry(self, params, carry, x, *, training=False, rng=None, mask=None):
        act = self._cell_act()
        zxs = jnp.swapaxes(x @ params["W"] + params["b"], 0, 1)  # hoisted
        ms = None if mask is None else jnp.swapaxes(mask, 0, 1)

        def step(hs, inp):
            (h,) = hs
            zx_t = inp[0] if ms is not None else inp
            h_new = act(zx_t + h @ params["W_rec"])
            if ms is not None:
                m = inp[1][:, None].astype(h.dtype)
                h_new = m * h_new + (1 - m) * h
            return (h_new,), h_new

        inputs = (zxs, ms) if ms is not None else zxs
        (h,), ys = lax.scan(step, carry, inputs, unroll=_SCAN_UNROLL)
        return jnp.swapaxes(ys, 0, 1), (h,)


@register_layer
@dataclasses.dataclass
class GRU(BaseRecurrentLayer):
    """GRU with packed gates [r, u, n].

    ``reset_after=True`` (default) is the CuDNN/modern-Keras cell
    (``n = tanh(x_n + r * (h @ U_n [+ b_rn]))``); ``reset_after=False`` is
    the classic reset-BEFORE variant (``n = tanh(x_n + (r*h) @ U_n)``) —
    Keras 1's GRU and Keras 2 with ``reset_after=False``. An optional
    ``b_rec`` param (recurrent bias, CuDNN's second bias set) is applied
    inside the reset product, matching Keras's dual-bias semantics."""

    reset_after: bool = True
    gate_activation: Any = "sigmoid"

    def init(self, key, input_type, g: GlobalConfig):
        n_in, H = self._nin(input_type), self.n_out
        k1, k2 = jax.random.split(key)
        return {
            "W": init_weights(k1, (n_in, 3 * H), self._winit(g), fan=(n_in, H), dtype=g.dtype),
            "W_rec": init_weights(k2, (H, 3 * H), self._winit(g), fan=(H, H), dtype=g.dtype),
            "b": jnp.zeros((3 * H,), g.dtype or jnp.float32),
        }, {}

    def init_carry(self, batch: int, dtype=jnp.float32):
        return (jnp.zeros((batch, self.n_out), dtype),)

    def forward_with_carry(self, params, carry, x, *, training=False, rng=None, mask=None):
        H = self.n_out
        gate = get_activation(self.gate_activation)
        act = self._cell_act()
        zxs = jnp.swapaxes(x @ params["W"] + params["b"], 0, 1)  # hoisted
        ms = None if mask is None else jnp.swapaxes(mask, 0, 1)
        b_rec = params.get("b_rec")

        if mask is None and type(self) is GRU and self.reset_after \
                and b_rec is None \
                and gate is get_activation("sigmoid") \
                and act is get_activation("tanh"):  # kernel's fixed cell
            from deeplearning4j_tpu.ops.pallas.fused_gru import (
                fused_gru, fused_gru_compatible)
            (h0,) = carry
            if fused_gru_compatible(zxs, h0):
                ys, h = fused_gru(zxs, params["W_rec"], h0.astype(zxs.dtype))
                return jnp.swapaxes(ys, 0, 1), (h,)

        def step(hs, inp):
            (h,) = hs
            zx = inp[0] if ms is not None else inp
            # reset-before only needs the r/u thirds of the recurrent
            # matmul here — the n third runs on (r*h) below
            W_ru = params["W_rec"] if self.reset_after \
                else params["W_rec"][:, :2 * H]
            zh = h @ W_ru
            if b_rec is not None:
                zh = zh + (b_rec if self.reset_after else b_rec[:2 * H])
            r = gate(zx[:, :H] + zh[:, :H])
            u = gate(zx[:, H:2 * H] + zh[:, H:2 * H])
            if self.reset_after:
                n = act(zx[:, 2 * H:] + r * zh[:, 2 * H:])
            else:
                zn = (r * h) @ params["W_rec"][:, 2 * H:]
                if b_rec is not None:
                    zn = zn + b_rec[2 * H:]
                n = act(zx[:, 2 * H:] + zn)
            h_new = (1 - u) * n + u * h
            if ms is not None:
                m = inp[1][:, None].astype(h.dtype)
                h_new = m * h_new + (1 - m) * h
            return (h_new,), h_new

        inputs = (zxs, ms) if ms is not None else zxs
        (h,), ys = lax.scan(step, carry, inputs, unroll=_SCAN_UNROLL)
        return jnp.swapaxes(ys, 0, 1), (h,)


@register_layer
@dataclasses.dataclass
class Bidirectional(Layer):
    """Bidirectional wrapper (reference ``Bidirectional``): runs the wrapped
    recurrent layer forward and on the time-reversed sequence; merge modes
    CONCAT / ADD / MUL / AVERAGE."""

    layer: Any = None  # a BaseRecurrentLayer (or dict after deserialization)
    mode: str = "concat"

    def __post_init__(self):
        if isinstance(self.layer, dict):
            self.layer = Layer.from_dict(self.layer)

    def output_type(self, input_type: InputType) -> InputType:
        inner = self.layer.output_type(input_type)
        if self.mode.lower() == "concat":
            return InputType.recurrent(inner.size * 2, inner.timesteps)
        return inner

    def init(self, key, input_type, g: GlobalConfig):
        self.layer._g = g
        k1, k2 = jax.random.split(key)
        fwd, _ = self.layer.init(k1, input_type, g)
        bwd, _ = self.layer.init(k2, input_type, g)
        return {"fwd": fwd, "bwd": bwd}, {}

    def forward(self, params, state, x, *, training=False, rng=None, mask=None):
        self.layer._g = self._g
        y_f, _ = self.layer.forward(params["fwd"], {}, x, training=training, rng=rng, mask=mask)
        x_rev = jnp.flip(x, axis=1)
        m_rev = None if mask is None else jnp.flip(mask, axis=1)
        y_b, _ = self.layer.forward(params["bwd"], {}, x_rev, training=training, rng=rng, mask=m_rev)
        y_b = jnp.flip(y_b, axis=1)
        mode = self.mode.lower()
        if mode == "concat":
            return jnp.concatenate([y_f, y_b], axis=-1), state
        if mode == "add":
            return y_f + y_b, state
        if mode == "mul":
            return y_f * y_b, state
        return 0.5 * (y_f + y_b), state


@register_layer
@dataclasses.dataclass
class LastTimeStep(Layer):
    """Extract the last (mask-aware) timestep (reference ``LastTimeStep``)."""

    layer: Any = None

    def __post_init__(self):
        if isinstance(self.layer, dict):
            self.layer = Layer.from_dict(self.layer)

    def output_type(self, input_type: InputType) -> InputType:
        inner = self.layer.output_type(input_type) if self.layer else input_type
        return InputType.feed_forward(inner.size)

    def init(self, key, input_type, g: GlobalConfig):
        if self.layer is None:
            return {}, {}
        self.layer._g = g
        return self.layer.init(key, input_type, g)

    def forward(self, params, state, x, *, training=False, rng=None, mask=None):
        if self.layer is not None:
            self.layer._g = self._g
            x, state = self.layer.forward(params, state, x, training=training, rng=rng, mask=mask)
        if mask is not None:
            idx = jnp.maximum(jnp.sum(mask.astype(jnp.int32), axis=1) - 1, 0)
            return x[jnp.arange(x.shape[0]), idx], state
        return x[:, -1], state


@register_layer
@dataclasses.dataclass
class RnnOutputLayer(OutputLayer):
    """Time-distributed output head (reference ``RnnOutputLayer``): dense +
    loss applied at every timestep of (batch, time, nIn)."""

    loss: Any = LossFunction.MCXENT

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, input_type.timesteps)

    def preoutput(self, params, x):
        # the head's matmul over every position, apart from the loss it feeds
        # (``loss/lm_head`` in the device trace)
        with jax.named_scope("lm_head"):
            return super().preoutput(params, x)


@register_layer
@dataclasses.dataclass
class TiedRnnOutputLayer(RnnOutputLayer):
    """``RnnOutputLayer`` whose weight is the embedding's table read
    transposed, ``logits = x E^T`` (weight tying): ``E`` (vocabulary, nIn)
    is owned by the embedding layer and named in ``tied`` as ``{"W":
    "<embedding layer's key>/W"}``. The head owns no weight of its own (a
    bias where ``has_bias``); ``E`` is one leaf of the network's parameters,
    so its gradient is the sum of the lookup's and the head's and the
    updater moves it once."""

    def init(self, key, input_type, g: GlobalConfig):
        params, state = super().init(key, input_type, g)
        del params["W"]
        return params, state

    def preoutput(self, params, x):
        with jax.named_scope("lm_head"):
            y = x @ params["W"].T
            return y + params["b"] if self.has_bias else y


@register_layer
@dataclasses.dataclass
class BlockDiffusionLoss(RnnOutputLayer):
    """Output head of a language model trained by diffusion over blocks
    (BD3-LM's training form, arXiv:2503.09573 section 3; SDAR,
    arXiv:2510.06303). The network ran once on ``[noisy ; clean]``: its input
    here is (batch, 2 T, nIn), of which the first ``T`` hidden states, the
    noisy half's, are scored, unshifted: position ``i`` predicts token ``i``.
    Labels are (batch, T) integers: the clean token where the noisy half
    holds the MASK id, and a negative number elsewhere (not trained). A
    trained position of block ``b`` (``block_length`` positions) weighs ``1 /
    t_b = block_length / m_b``, ``m_b`` the block's number of trained
    positions, worked out from the labels; the weighted negative
    log-likelihoods are summed and divided by ``T``, and averaged over rows.

    The model state keeps the last step's ``diffusion_loss`` and
    ``masked_positions`` (trained positions a row). At inference the layer
    gives the noisy half's distributions."""

    loss: Any = LossFunction.SPARSE_MCXENT
    block_length: int = 4

    def init(self, key, input_type, g: GlobalConfig):
        params, state = super().init(key, input_type, g)
        zero = jnp.zeros((), jnp.float32)
        return params, {**state, "diffusion_loss": zero, "masked_positions": zero}

    def activate(self, params, x):
        return super().activate(params, x[:, :x.shape[1] // 2])

    def compute_loss(self, params, x, labels, mask=None, state=None):
        b, t = labels.shape
        labels = labels.astype(jnp.int32)
        trained = labels >= 0 if mask is None else (labels >= 0) & mask.astype(bool)
        per_block = jnp.sum(trained.reshape(b, t // self.block_length, self.block_length), -1)
        weight = jnp.repeat(self.block_length / jnp.maximum(per_block, 1).astype(jnp.float32), self.block_length, axis=1)
        logp = jax.nn.log_softmax(self.preoutput(params, x[:, :t]).astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
        return jnp.sum(jnp.where(trained, weight * nll, 0.0)) / (b * t)

    def loss_state(self, state, loss, labels):
        """The head's state after a step whose data loss was ``loss``."""
        return {**state, "diffusion_loss": loss.astype(jnp.float32),
                "masked_positions": jnp.sum(labels >= 0).astype(jnp.float32) / labels.shape[0]}
