"""Attention / transformer layers.

The reference's attention surface is the SameDiff op
``multiHeadDotProductAttention`` (upstream
``org.nd4j.linalg.api.ops.impl.transforms.custom.MultiHeadDotProductAttention``,
used by imported BERT) plus the DL4J layers ``SelfAttentionLayer`` /
``LearnedSelfAttentionLayer`` (beta4+). Here attention is first-class: a
layer-API multi-head self-attention whose inner product can route through the
Pallas flash-attention kernel (``ops.pallas.flash_attention``) when shapes
warrant, and a full pre/post-LN transformer encoder block used by the zoo's
BERT.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.base import (GlobalConfig, Layer, dropout_mask,
                                        register_layer)
from deeplearning4j_tpu.nn.inputs import InputType
from deeplearning4j_tpu.ops.activations import get_activation
from deeplearning4j_tpu.ops.initializers import init_weights
from deeplearning4j_tpu.runtime.environment import get_environment


def layer_norm(x, gamma, beta, eps=1e-12):
    # Shifted single-pass stats in f32 — one fused read of x (see
    # ops.activations.single_pass_norm_stats for the numerics rationale).
    from deeplearning4j_tpu.ops.activations import single_pass_norm_stats
    mean, var = single_pass_norm_stats(x, -1)
    y = (x.astype(jnp.float32) - mean) * jax.lax.rsqrt(var + eps)
    return (y.astype(x.dtype)) * gamma + beta


def dot_product_attention(q, k, v, mask=None, use_flash: bool = True,
                          causal: bool = False, block_diffusion=None):
    """(batch, heads, time, d) attention. Routes through the Pallas flash
    kernel when ``flash_attention_compatible`` accepts the shapes, mask
    family and platform (key-padding masks, causal, and
    ``block_diffusion=(t, block)``: the block-diffusion mask over ``[noisy ;
    clean]`` of ``2 t`` positions); an incompatible call takes the XLA
    softmax form below. ``k`` and ``v`` may have fewer heads than ``q``
    (query head ``i`` reads key/value head ``i // group``): the kernels
    index them so under ``block_diffusion``, the XLA form repeats them. A
    kernel that raises is a bug and surfaces — nothing here catches it."""
    from deeplearning4j_tpu.ops.pallas.flash_attention import (
        block_diffusion_allowed, flash_attention, flash_attention_compatible)
    if use_flash and flash_attention_compatible(q, k, v, mask, causal=causal, block_diffusion=block_diffusion):
        # one kernel does scores, softmax and context: one scope
        with jax.named_scope("flash"):
            return flash_attention(q, k, v, mask, causal=causal, block_diffusion=block_diffusion)
    d = q.shape[-1]
    if k.shape[1] != q.shape[1]:
        k, v = (jnp.repeat(a, q.shape[1] // k.shape[1], axis=1) for a in (k, v))
    with jax.named_scope("scores"):
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(jnp.asarray(d, q.dtype))
        if block_diffusion is not None:
            at = jnp.arange(q.shape[2])
            allowed = block_diffusion_allowed(at[:, None], at[None, :], *block_diffusion)
            scores = jnp.where(allowed[None, None], scores, jnp.asarray(-1e9, scores.dtype))
        if mask is not None:
            if mask.ndim == 2:  # (batch, t_k) key-padding form
                mask = mask[:, None, None, :]
            scores = jnp.where(mask, scores, jnp.asarray(-1e9, scores.dtype))
        if causal:
            t_q, t_k = q.shape[2], k.shape[2]
            # bottom-right aligned triangle: for KV-cache decode (t_q < t_k) the
            # last query row attends every key (offset = t_k - t_q)
            tri = jnp.tril(jnp.ones((t_q, t_k), bool), k=t_k - t_q)
            scores = jnp.where(tri[None, None], scores,
                               jnp.asarray(-1e9, scores.dtype))
    with jax.named_scope("softmax"):
        weights = jax.nn.softmax(scores, axis=-1)
    with jax.named_scope("context"):
        return jnp.einsum("bhqk,bhkd->bhqd", weights, v)


@register_layer
@dataclasses.dataclass
class SelfAttentionLayer(Layer):
    """Multi-head self-attention over (batch, time, size) (reference
    ``SelfAttentionLayer`` / ``multiHeadDotProductAttention``)."""

    n_heads: int = 8
    head_size: Optional[int] = None  # default size/n_heads
    n_out: Optional[int] = None  # projection output, default = input size
    with_projection: bool = True

    def output_type(self, input_type: InputType) -> InputType:
        out = self.n_out or input_type.size
        return InputType.recurrent(out, input_type.timesteps)

    def init(self, key, input_type, g: GlobalConfig):
        d_model = input_type.size
        hs = self.head_size or d_model // self.n_heads
        inner = self.n_heads * hs
        out = self.n_out or d_model
        ks = jax.random.split(key, 4)
        params = {
            "W_q": init_weights(ks[0], (d_model, inner), self._winit(g), fan=(d_model, inner), dtype=g.dtype),
            "W_k": init_weights(ks[1], (d_model, inner), self._winit(g), fan=(d_model, inner), dtype=g.dtype),
            "W_v": init_weights(ks[2], (d_model, inner), self._winit(g), fan=(d_model, inner), dtype=g.dtype),
            "b_q": jnp.zeros((inner,), g.dtype or jnp.float32),
            "b_k": jnp.zeros((inner,), g.dtype or jnp.float32),
            "b_v": jnp.zeros((inner,), g.dtype or jnp.float32),
        }
        if self.with_projection:
            params["W_o"] = init_weights(ks[3], (inner, out), self._winit(g), fan=(inner, out), dtype=g.dtype)
            params["b_o"] = jnp.zeros((out,), g.dtype or jnp.float32)
        return params, {}

    def forward(self, params, state, x, *, training=False, rng=None, mask=None):
        from deeplearning4j_tpu.ops.pallas.fused_attention import (
            fused_attention, fused_attention_compatible)
        b, t, _ = x.shape
        h = self.n_heads
        # A sequence that fits one block (T <= 512) goes to the resident
        # fused kernel as the projections write it and W_o reads it:
        # decided here, from shapes, dtype and mask form, before anything
        # is split into (b, h, t, d), so that no head transpose is emitted
        inner = jax.ShapeDtypeStruct(
            (b, t, params["W_q"].shape[1]),
            jnp.result_type(x.dtype, params["W_q"].dtype,
                            params["b_q"].dtype))
        if fused_attention_compatible(inner, mask, heads=h):
            with jax.named_scope("qkv"):
                q = x @ params["W_q"] + params["b_q"]
                k = x @ params["W_k"] + params["b_k"]
                v = x @ params["W_v"] + params["b_v"]
            with jax.named_scope("fused_attention"):
                y = fused_attention(q, k, v, mask, h)
            with jax.named_scope("out_proj"):
                if self.with_projection:
                    y = y @ params["W_o"] + params["b_o"]
            return y, state
        # NOTE on fused QKV: concatenating W_q|W_k|W_v into one matmul was
        # measured SLOWER on v5e (43.7 GB vs 40.5 GB accessed, 40.4 vs
        # 39.1 ms/step on BERT-base) — the fused weight and its gradient
        # materialize as extra traffic while XLA already schedules the three
        # shared-LHS matmuls back-to-back. Kept unfused deliberately.
        with jax.named_scope("qkv"):
            q = (x @ params["W_q"] + params["b_q"]).reshape(b, t, h, -1).transpose(0, 2, 1, 3)
            k = (x @ params["W_k"] + params["b_k"]).reshape(b, t, h, -1).transpose(0, 2, 1, 3)
            v = (x @ params["W_v"] + params["b_v"]).reshape(b, t, h, -1).transpose(0, 2, 1, 3)
        attn_mask = None
        if mask is not None:
            attn_mask = mask[:, None, None, :].astype(bool)  # key-side padding mask
        y = dot_product_attention(q, k, v, attn_mask)
        with jax.named_scope("out_proj"):
            y = y.transpose(0, 2, 1, 3).reshape(b, t, -1)
            if self.with_projection:
                y = y @ params["W_o"] + params["b_o"]
        return y, state


@register_layer
@dataclasses.dataclass
class TransformerEncoderBlock(Layer):
    """Post-LN transformer encoder block (BERT-style): MHA + residual + LN,
    FFN(gelu) + residual + LN."""

    n_heads: int = 12
    ffn_size: int = 3072
    dropout_rate: float = 0.1  # drop probability (transformer convention)
    layer_norm_eps: float = 1e-12

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def init(self, key, input_type, g: GlobalConfig):
        d = input_type.size
        attn = SelfAttentionLayer(n_heads=self.n_heads)
        attn._g = g
        ks = jax.random.split(key, 3)
        attn_params, _ = attn.init(ks[0], input_type, g)
        f = jnp.float32 if g.dtype is None else g.dtype
        params = {
            "attn": attn_params,
            "ln1_gamma": jnp.ones((d,), f), "ln1_beta": jnp.zeros((d,), f),
            "ln2_gamma": jnp.ones((d,), f), "ln2_beta": jnp.zeros((d,), f),
            "W_ff1": init_weights(ks[1], (d, self.ffn_size), self._winit(g), fan=(d, self.ffn_size), dtype=g.dtype),
            "b_ff1": jnp.zeros((self.ffn_size,), f),
            "W_ff2": init_weights(ks[2], (self.ffn_size, d), self._winit(g), fan=(self.ffn_size, d), dtype=g.dtype),
            "b_ff2": jnp.zeros((d,), f),
        }
        self._attn = attn
        return params, {}

    def _dropout_fn(self, x, training, rng):
        if not training or rng is None or self.dropout_rate <= 0.0:
            return x
        keep = 1.0 - self.dropout_rate
        mask = dropout_mask(rng, keep, x.shape)
        return jnp.where(mask, x / keep, 0.0).astype(x.dtype)

    def forward(self, params, state, x, *, training=False, rng=None, mask=None):
        attn = getattr(self, "_attn", None)
        if attn is None:
            attn = SelfAttentionLayer(n_heads=self.n_heads)
            self._attn = attn
        attn._g = self._g
        r1, r2 = (jax.random.split(rng) if rng is not None else (None, None))
        a, _ = attn.forward(params["attn"], {}, x, training=training, rng=None, mask=mask)
        with jax.named_scope("ln1"):
            x = layer_norm(x + self._dropout_fn(a, training, r1),
                           params["ln1_gamma"], params["ln1_beta"], self.layer_norm_eps)
        with jax.named_scope("ffn"):
            h = get_activation("gelu")(x @ params["W_ff1"] + params["b_ff1"])
            h = h @ params["W_ff2"] + params["b_ff2"]
        with jax.named_scope("ln2"):
            x = layer_norm(x + self._dropout_fn(h, training, r2),
                           params["ln2_gamma"], params["ln2_beta"], self.layer_norm_eps)
        return x, state

    def regularizable_params(self):
        return ("W_ff1", "W_ff2")


@register_layer
@dataclasses.dataclass
class TransformerEncoderStack(Layer):
    """``n_layers`` identical post-LN encoder blocks executed as ONE
    ``lax.scan`` over layer-stacked parameters.

    What it does: one stacked parameter tree instead of per-layer trees
    (~400 buffer handles per BERT-base step collapse to ~30) and a scan
    body that traces once, so compile time stops growing with depth. Why
    it is NOT the zoo default: measured 48 vs 37 ms/step on v5e at
    BERT-base shape — ``lax.scan`` blocks XLA's inter-layer fusion/overlap
    and the scan backward stacks extra residual copies. Its compile-time
    and dispatch benefit on this machine is not measured. Same math as a
    stack of ``TransformerEncoderBlock``s; init draws the same
    distributions via a vmapped per-layer key split (exact draws differ
    from the sequential form).

    Per-layer dropout keys are folded from the step key inside the scan.
    """

    n_layers: int = 12
    n_heads: int = 12
    ffn_size: int = 3072
    dropout_rate: float = 0.1
    layer_norm_eps: float = 1e-12

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def _block(self, g) -> TransformerEncoderBlock:
        blk = TransformerEncoderBlock(
            n_heads=self.n_heads, ffn_size=self.ffn_size,
            dropout_rate=self.dropout_rate,
            layer_norm_eps=self.layer_norm_eps)
        blk._g = g
        return blk

    def init(self, key, input_type, g: GlobalConfig):
        blk = self._block(g)

        def one(k):
            p, _ = blk.init(k, input_type, g)
            return p

        params = jax.vmap(one)(jax.random.split(key, self.n_layers))
        return {"stack": params}, {}

    def forward(self, params, state, x, *, training=False, rng=None, mask=None):
        blk = self._block(self._g)
        stack = params["stack"]
        if rng is not None:
            keys = jax.random.split(rng, self.n_layers)

            def body(carry, per):
                p, k = per
                y, _ = blk.forward(p, {}, carry, training=training,
                                   rng=k, mask=mask)
                return y, None

            y, _ = jax.lax.scan(body, x, (stack, keys))
        else:
            def body(carry, p):
                y, _ = blk.forward(p, {}, carry, training=training,
                                   rng=None, mask=mask)
                return y, None

            y, _ = jax.lax.scan(body, x, stack)
        return y, state

    def regularizable_params(self):
        # W_ff1/W_ff2 live under the stacked subtree, but both the l1/l2
        # walk and the weight-decay mask match by PATH COMPONENT, so the
        # per-block keys reach the stacked leaves; sum-of-squares over the
        # stacked array equals the per-layer sum — same penalty as the
        # discrete-block stack.
        return ("W_ff1", "W_ff2")


@register_layer
@dataclasses.dataclass
class BertEmbeddingLayer(Layer):
    """BERT input embeddings: token + learned position + segment embeddings,
    LayerNorm, dropout. Input: (batch, time) int32 token ids (single-segment;
    pair tasks feed segment ids via ComputationGraph with a second
    EmbeddingSequenceLayer). Reference path: TF-imported BERT's embedding
    lookup subgraph (SURVEY.md §3.3)."""

    vocab_size: int = 30522
    d_model: int = 768
    max_len: int = 512
    type_vocab_size: int = 2
    dropout_rate: float = 0.1
    layer_norm_eps: float = 1e-12

    def output_type(self, input_type: InputType) -> InputType:
        t = input_type.timesteps if input_type is not None else None
        return InputType.recurrent(self.d_model, t)

    def init(self, key, input_type, g: GlobalConfig):
        ks = jax.random.split(key, 3)
        f = jnp.float32 if g.dtype is None else g.dtype
        return {
            "tok": init_weights(ks[0], (self.vocab_size, self.d_model), self._winit(g),
                                fan=(self.vocab_size, self.d_model), dtype=g.dtype),
            "pos": init_weights(ks[1], (self.max_len, self.d_model), self._winit(g),
                                fan=(self.max_len, self.d_model), dtype=g.dtype),
            "seg": init_weights(ks[2], (self.type_vocab_size, self.d_model), self._winit(g),
                                fan=(self.type_vocab_size, self.d_model), dtype=g.dtype),
            "ln_gamma": jnp.ones((self.d_model,), f),
            "ln_beta": jnp.zeros((self.d_model,), f),
        }, {}

    def forward(self, params, state, x, *, training=False, rng=None, mask=None):
        ids = x.astype(jnp.int32)
        t = ids.shape[1]
        with jax.named_scope("embed"):
            y = jnp.take(params["tok"], ids, axis=0)
            y = y + params["pos"][None, :t, :] + params["seg"][0][None, None, :]
        with jax.named_scope("ln"):
            y = layer_norm(y, params["ln_gamma"], params["ln_beta"], self.layer_norm_eps)
        if training and rng is not None and self.dropout_rate > 0:
            keep = 1.0 - self.dropout_rate
            keep_mask = dropout_mask(rng, keep, y.shape)
            y = jnp.where(keep_mask, y / keep, 0.0).astype(y.dtype)
        return y, state

    def regularizable_params(self):
        return ()


@register_layer
@dataclasses.dataclass
class ClsPoolingLayer(Layer):
    """Extract one timestep (default 0 — BERT's [CLS]) from (batch, time, d)."""

    index: int = 0

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.feed_forward(input_type.size)

    def forward(self, params, state, x, *, training=False, rng=None, mask=None):
        return x[:, self.index], state


@register_layer
@dataclasses.dataclass
class LearnedPositionalEmbeddingLayer(Layer):
    """Adds learned positional embeddings (BERT position table)."""

    max_len: int = 512

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def init(self, key, input_type, g: GlobalConfig):
        d = input_type.size
        return {"P": init_weights(key, (self.max_len, d), self._winit(g), fan=(self.max_len, d), dtype=g.dtype)}, {}

    def forward(self, params, state, x, *, training=False, rng=None, mask=None):
        t = x.shape[1]
        return x + params["P"][None, :t, :], state


# ---------------------------------------------------------------------------
# Pre-norm decoder layers (RMSNorm, SwiGLU, latent attention, the block)
# ---------------------------------------------------------------------------


def rms_norm(x, w, eps=1e-5):
    """``x / sqrt(mean(x^2) + eps) * w`` over the last axis; statistics in
    float32, result in ``x``'s dtype."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def scoped(name: str, fn, *args):
    """``fn(*args)`` under ``jax.named_scope(name)``. Under
    ``Environment.set_remat`` the call is a ``jax.checkpoint`` made *inside*
    the scope: only ``args`` are kept for the backward pass, and every op of
    it, recomputed or not, still carries ``<layer>/<name>`` as its first two
    scopes. A layer whose work all goes through here sets
    ``remat_in_scopes``, and the network then leaves out its own checkpoint
    around that layer (which would recompute the layer a second time and
    name its backward pass ``<layer>/<layer>/checkpoint/...``)."""
    with jax.named_scope(name):
        return (jax.checkpoint(fn) if get_environment().remat_segments else fn)(*args)


def _sub_init(layer: Layer, key, input_type, g):
    layer._g = g
    return layer.init(key, input_type, g)


@register_layer
@dataclasses.dataclass
class RMSNormLayer(Layer):
    """Root-mean-square norm with a learned scale over the feature axis."""

    eps: float = 1e-5
    remat_in_scopes = True

    def init(self, key, input_type, g: GlobalConfig):
        return {"w": jnp.ones((input_type.size,), g.dtype or jnp.float32)}, {}

    def forward(self, params, state, x, *, training=False, rng=None, mask=None):
        return scoped("norm", lambda x_, w: rms_norm(x_, w, self.eps), x, params["w"]), state

    def regularizable_params(self):
        return ()


@register_layer
@dataclasses.dataclass
class GatedMLP(Layer):
    """SwiGLU feed-forward: ``W_d (SiLU(x W_g) * x W_u)``, no biases."""

    hidden_size: int = 0

    def init(self, key, input_type, g: GlobalConfig):
        d, f = input_type.size, self.hidden_size
        kg, ku, kd = jax.random.split(key, 3)
        w = self._winit(g)
        return {"W_g": init_weights(kg, (d, f), w, fan=(d, f), dtype=g.dtype),
                "W_u": init_weights(ku, (d, f), w, fan=(d, f), dtype=g.dtype),
                "W_d": init_weights(kd, (f, d), w, fan=(f, d), dtype=g.dtype)}, {}

    @staticmethod
    def apply(p, x):
        return (jax.nn.silu(x @ p["W_g"]) * (x @ p["W_u"])) @ p["W_d"]

    def forward(self, params, state, x, *, training=False, rng=None, mask=None):
        return scoped("mlp", self.apply, params, x), state

    def regularizable_params(self):
        return ("W_g", "W_u", "W_d")


def rotary(x, positions, theta: float):
    """Rotary position embedding over the whole last axis of ``x`` (..., t,
    d) or (b, t, h, d) with time on axis 1, in the half-split pairing:
    channel ``i`` turns with channel ``i + d/2`` by the angle ``positions *
    theta^(-2i/d)``. Angles and rotation in float32; the result in ``x``'s
    dtype."""
    half = x.shape[-1] // 2
    freqs = jnp.exp(-jnp.log(jnp.float32(theta)) * (jnp.arange(half, dtype=jnp.float32) / half))
    angles = positions.astype(jnp.float32)[:, None] * freqs  # (t, d/2)
    shape = (1, angles.shape[0]) + (1,) * (x.ndim - 3) + (half,)
    cos, sin = jnp.cos(angles).reshape(shape), jnp.sin(angles).reshape(shape)
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(x.dtype)


@register_layer
@dataclasses.dataclass
class LatentAttention(Layer):
    """Multi-head latent attention (MLA), causal.

    Keys and values come up from one ``kv_rank``-wide normed latent per
    token; each head's key is its own ``qk_nope_dim`` part beside one
    ``qk_shared_dim`` part that all heads share. With ``q_rank`` the queries
    come up from a normed latent of that width too (``W_qa``, ``q_norm``,
    ``W_qb`` in place of ``W_q``). With ``rope_theta`` the ``qk_shared_dim``
    parts of queries and keys are turned by their position (``rotary``,
    under a scope ``rope``); without it the layer has no notion of position
    (NoPE). The T x T part goes through ``dot_product_attention`` and the
    routing it owns (the flash kernel takes the q.k head of ``qk_nope_dim +
    qk_shared_dim`` against a v head of ``v_dim``: two sizes, nothing
    padded)."""

    n_heads: int = 32
    kv_rank: int = 512
    qk_nope_dim: int = 128
    qk_shared_dim: int = 64
    v_dim: int = 128
    eps: float = 1e-5
    q_rank: Optional[int] = None
    rope_theta: Optional[float] = None

    def init(self, key, input_type, g: GlobalConfig):
        d, h = input_type.size, self.n_heads
        qk = self.qk_nope_dim + self.qk_shared_dim
        queries = ({"W_q": (d, h * qk)} if self.q_rank is None
                   else {"W_qa": (d, self.q_rank), "W_qb": (self.q_rank, h * qk)})
        shapes = {**queries, "W_kva": (d, self.kv_rank + self.qk_shared_dim),
                  "W_kvb": (self.kv_rank, h * (self.qk_nope_dim + self.v_dim)),
                  "W_o": (h * self.v_dim, d)}
        params = {name: init_weights(k, shape, self._winit(g), fan=shape, dtype=g.dtype)
                  for (name, shape), k in zip(shapes.items(), jax.random.split(key, len(shapes)))}
        params["kv_norm"] = jnp.ones((self.kv_rank,), g.dtype or jnp.float32)
        if self.q_rank is not None:
            params["q_norm"] = jnp.ones((self.q_rank,), g.dtype or jnp.float32)
        return params, {}

    def _project(self, p, x):
        """(q (b, t, h, dn + dr), [k_n | v] (b, t, h, dn + dv), the keys' shared part (b, t, dr))."""
        b, t, _ = x.shape
        h, dn, dr = self.n_heads, self.qk_nope_dim, self.qk_shared_dim
        if self.q_rank is None:
            q = (x @ p["W_q"]).reshape(b, t, h, dn + dr)
        else:
            q = (rms_norm(x @ p["W_qa"], p["q_norm"], self.eps) @ p["W_qb"]).reshape(b, t, h, dn + dr)
        latent = x @ p["W_kva"]
        c, k_shared = latent[..., :self.kv_rank], latent[..., self.kv_rank:]
        kv = (rms_norm(c, p["kv_norm"], self.eps) @ p["W_kvb"]).reshape(b, t, h, dn + self.v_dim)
        return q, kv, k_shared

    def _join(self, q, kv, k_shared):
        """q, k, v as (b, h, t, d): every head's key gets the shared part."""
        b, t, h, _ = q.shape
        dn, dr = self.qk_nope_dim, self.qk_shared_dim
        k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_shared[:, :, None, :], (b, t, h, dr))], -1)
        return tuple(a.transpose(0, 2, 1, 3) for a in (q, k, kv[..., dn:]))

    def _qkv(self, p, x):
        return self._join(*self._project(p, x))

    def _rotate_and_join(self, q, kv, k_shared):
        dn, positions = self.qk_nope_dim, jnp.arange(q.shape[1])
        q = jnp.concatenate([q[..., :dn], rotary(q[..., dn:], positions, self.rope_theta)], -1)
        return self._join(q, kv, rotary(k_shared, positions, self.rope_theta))

    def forward(self, params, state, x, *, training=False, rng=None, mask=None):
        if self.rope_theta is None:
            q, k, v = scoped("mla_qkv", self._qkv, params, x)
        else:
            q, k, v = scoped("rope", self._rotate_and_join, *scoped("mla_qkv", self._project, params, x))
        key_mask = None if mask is None else mask[:, None, None, :].astype(bool)
        y = dot_product_attention(q, k, v, key_mask, causal=True)

        def out(p, y):
            b, h, t, dv = y.shape
            return y.transpose(0, 2, 1, 3).reshape(b, t, h * dv) @ p["W_o"]

        return scoped("mla_out", out, params, y), state

    def regularizable_params(self):
        return ("W_q", "W_qa", "W_qb", "W_kva", "W_kvb", "W_o")


@register_layer
@dataclasses.dataclass
class GroupedQueryAttention(Layer):
    """Multi-head attention in which ``n_heads`` query heads share
    ``n_kv_heads`` key/value heads (query head ``i`` reads head ``i //
    (n_heads / n_kv_heads)``), with a learned RMSNorm over each query's and
    each key's head (Qwen3's form) and rotary positions, no biases::

        q = rope(RMSNorm_head(x W_q), pos);  k = rope(RMSNorm_head(x W_k), pos);  v = x W_v
        out = softmax(q k^T / sqrt(head_dim) + M) v  W_o

    Causal unless ``block_diffusion`` is set to a block length: the input
    is then ``[noisy ; clean]``, two halves of ``t / 2`` positions that carry
    the same positions ``0 .. t/2 - 1``, under the block-diffusion mask
    (``ops.pallas.flash_attention.block_diffusion_allowed``). The T x T part
    goes through ``dot_product_attention`` and the routing it owns; K and V
    are handed over at their own number of heads."""

    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e6
    eps: float = 1e-6
    block_diffusion: Optional[int] = None

    def init(self, key, input_type, g: GlobalConfig):
        d, h, kv, hd = input_type.size, self.n_heads, self.n_kv_heads, self.head_dim
        shapes = {"W_q": (d, h * hd), "W_k": (d, kv * hd), "W_v": (d, kv * hd), "W_o": (h * hd, d)}
        params = {name: init_weights(k, shape, self._winit(g), fan=shape, dtype=g.dtype)
                  for (name, shape), k in zip(shapes.items(), jax.random.split(key, len(shapes)))}
        params["q_norm"] = jnp.ones((hd,), g.dtype or jnp.float32)
        params["k_norm"] = jnp.ones((hd,), g.dtype or jnp.float32)
        return params, {}

    def forward(self, params, state, x, *, training=False, rng=None, mask=None):
        b, t, _ = x.shape
        hd = self.head_dim
        if self.block_diffusion is None:
            positions, family = jnp.arange(t), dict(causal=True)
        else:
            half = jnp.arange(t // 2)
            positions, family = jnp.concatenate([half, half]), dict(block_diffusion=(t // 2, self.block_diffusion))

        def qkv(p, x_):
            return tuple((x_ @ p[w]).reshape(b, t, -1, hd) for w in ("W_q", "W_k", "W_v"))

        def qk_norm(p, q, k):
            return rms_norm(q, p["q_norm"], self.eps), rms_norm(k, p["k_norm"], self.eps)

        def rope(q, k, v):  # and the join into (b, h, t, d)
            q, k = rotary(q, positions, self.rope_theta), rotary(k, positions, self.rope_theta)
            return tuple(a.transpose(0, 2, 1, 3) for a in (q, k, v))

        q, k, v = scoped("qkv", qkv, params, x)
        q, k = scoped("qk_norm", qk_norm, params, q, k)
        q, k, v = scoped("rope", rope, q, k, v)
        key_mask = None if mask is None else mask[:, None, None, :].astype(bool)
        y = dot_product_attention(q, k, v, key_mask, **family)

        def out(p, y_):
            return y_.transpose(0, 2, 1, 3).reshape(b, t, -1) @ p["W_o"]

        return scoped("out_proj", out, params, y), state

    def regularizable_params(self):
        return ("W_q", "W_k", "W_v", "W_o")


@register_layer
@dataclasses.dataclass
class DecoderBlock(Layer):
    """Pre-norm decoder block: ``h = x + mixer(norm(x))``, ``y = h +
    mlp(norm(h))``, with RMSNorm and any two layers that keep the width:
    ``mixer`` (``LatentAttention``, ``KimiDeltaAttention``, ...) and ``mlp``
    (``GatedMLP``, ``MixtureOfExperts``). Each opens its own scopes, which so
    sit directly under this block's."""

    mixer: Any = None
    mlp: Any = None
    eps: float = 1e-5
    remat_in_scopes = True  # mixer and MLP recompute inside their scopes (``scoped``)

    def __post_init__(self):
        for name in ("mixer", "mlp"):
            if isinstance(getattr(self, name), dict):
                setattr(self, name, Layer.from_dict(getattr(self, name)))

    def init(self, key, input_type, g: GlobalConfig):
        k_mixer, k_mlp = jax.random.split(key)
        ones = jnp.ones((input_type.size,), g.dtype or jnp.float32)
        mixer, mixer_state = _sub_init(self.mixer, k_mixer, input_type, g)
        mlp, mlp_state = _sub_init(self.mlp, k_mlp, input_type, g)
        state = {name: s for name, s in (("mixer", mixer_state), ("mlp", mlp_state)) if s}
        if any("_aux_loss" in s for s in state.values()):
            # the network adds up ``_aux_loss`` entries it finds at a layer's top level
            state["_aux_loss"] = jnp.zeros((), jnp.float32)
        return {"norm1": ones, "mixer": mixer, "norm2": ones, "mlp": mlp}, state

    def forward(self, params, state, x, *, training=False, rng=None, mask=None):
        new_state = dict(state)
        for name, norm in (("mixer", "norm1"), ("mlp", "norm2")):
            sub = getattr(self, name)
            sub._g = self._g
            with jax.named_scope("norm"):
                normed = rms_norm(x, params[norm], self.eps)
            y, s = sub.forward(params[name], state.get(name, {}), normed,
                               training=training, rng=rng, mask=mask)
            x = x + y
            if name in state:
                new_state[name] = s
        if "_aux_loss" in state:
            new_state["_aux_loss"] = sum(s["_aux_loss"] for s in new_state.values()
                                         if isinstance(s, dict) and "_aux_loss" in s)
        return x, new_state

    def regularizable_params(self):
        return tuple(set(self.mixer.regularizable_params()) | set(self.mlp.regularizable_params()))


@register_layer
@dataclasses.dataclass
class MultiTokenPrediction(Layer):
    """A second prediction per position (DeepSeek-V3, arXiv:2412.19437 §2.2):
    from the trunk's last hidden state at ``i`` and the embedding of the
    token at ``i + 1`` (the batch's label at ``i``), one more decoder block
    predicts the token at ``i + 2`` (the label at ``i + 1``)::

        u_i = W_eh [RMSNorm_e(Emb(label_i)) ; RMSNorm_h(x_i)]
        z = block(u);  logits'_i = head(RMSNorm_s(z_i));  L_mtp = CE(logits'_i, label_(i+1))

    over the positions that have a label after theirs (all but the last of
    a row; with a mask, those whose own and whose next position are valid).

    The layer sits between the last block and the final norm and hands its
    input on unchanged. It owns ``enorm``, ``hnorm``, ``W_eh``, the block
    and ``norm``; the embedding table and the head are the trunk's, named in
    ``tied`` as ``{"embed": "<embedding layer's key>", "head": "<output
    layer's key>"}``: one leaf each in the network's parameters, which the
    trunk and this layer both read, so each gets the sum of its two
    gradients and one update. ``head`` is the output layer's configuration
    (it scores both predictions). ``weight * L_mtp`` joins the training loss
    through the state channel (``_aux_loss``); ``mtp_loss`` keeps the last
    step's ``L_mtp``. Without labels (inference) the layer does nothing."""

    block: Any = None  # a DecoderBlock
    head: Any = None   # the network's output layer, as configured there
    weight: float = 0.3
    eps: float = 1e-5
    remat_in_scopes = True
    takes_labels = True

    def __post_init__(self):
        for name in ("block", "head"):
            if isinstance(getattr(self, name), dict):
                setattr(self, name, Layer.from_dict(getattr(self, name)))

    def init(self, key, input_type, g: GlobalConfig):
        d = input_type.size
        k_eh, k_block = jax.random.split(key)
        ones = jnp.ones((d,), g.dtype or jnp.float32)
        block, block_state = _sub_init(self.block, k_block, input_type, g)
        params = {"enorm": ones, "hnorm": ones, "norm": ones, "block": block,
                  "W_eh": init_weights(k_eh, (2 * d, d), self._winit(g), fan=(2 * d, d), dtype=g.dtype)}
        state = {"_aux_loss": jnp.zeros((), jnp.float32), "mtp_loss": jnp.zeros((), jnp.float32)}
        if block_state:
            state["block"] = block_state
        return params, state

    def forward(self, params, state, x, *, training=False, rng=None, mask=None, labels=None):
        if labels is None:
            return x, state
        self.block._g = self.head._g = self._g

        def mtp_in(p, table, x_, labels_):
            e = jnp.take(table, labels_.astype(jnp.int32), axis=0)
            both = jnp.concatenate([rms_norm(e, p["enorm"], self.eps), rms_norm(x_, p["hnorm"], self.eps)], -1)
            return both @ p["W_eh"]

        own = {name: params[name] for name in ("enorm", "hnorm", "W_eh")}
        u = scoped("mtp_in", mtp_in, own, params["embed"]["W"], x, labels)
        z, block_state = self.block.forward(params["block"], state.get("block", {}), u,
                                            training=training, rng=rng, mask=mask)
        valid = jnp.ones(labels.shape, jnp.float32) if mask is None else mask.astype(jnp.float32)
        # position i is scored against the label at i + 1: the last has none
        valid = (valid * jnp.roll(valid, -1, axis=1)).at[:, -1].set(0.0)

        def score(head_p, w, z_, labels_, valid_):
            return self.head.compute_loss(head_p, rms_norm(z_, w, self.eps), jnp.roll(labels_, -1, axis=1),
                                          mask=valid_)

        loss = scoped("lm_head", score, params["head"], params["norm"], z, labels, valid).astype(jnp.float32)
        new_state = dict(state, mtp_loss=loss, _aux_loss=self.weight * loss)
        if "block" in state:
            new_state["block"] = block_state
            if "_aux_loss" in block_state:  # the block's own (an expert layer's balancing term)
                new_state["_aux_loss"] = new_state["_aux_loss"] + block_state["_aux_loss"]
        return x, new_state

    def regularizable_params(self):
        return ("W_eh",) + tuple(self.block.regularizable_params())
