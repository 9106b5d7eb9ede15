"""Linear-time sequence mixers: the gated delta rule with a per-channel
decay (Kimi Delta Attention, KDA; Kimi Linear, arXiv:2510.26692), and the
gated short convolution of the LFM2 family (``GatedShortConv``).

Per head, with a state ``S`` of (d_k, d_v) that starts at zero::

    S' = diag(exp(g_t)) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

``chunk_kda`` computes it a chunk of tokens at a time. Within a chunk, with
``G`` the running sum of ``g`` from the chunk's start::

    A_ij = beta_i sum_c k_ic k_jc exp(G_ic - G_jc)      (i > j)
    B_ij =        sum_c q_ic k_jc exp(G_ic - G_jc)      (i >= j)
    (I + A) [W | U0] = beta [k exp(G) | v]              (unit lower triangular)
    U = U0 - W S,  O = (q exp(G)) S + B U,  S <- exp(G_end) S + (k exp(G_end - G))^T U

No exponent is ever positive: ``A`` and ``B`` are built from sub-blocks of
``SUB`` rows, an off-diagonal one through the decays measured from the row
block's first token (a matmul), a diagonal one from the differences
``G_i - G_j`` themselves, so a decay of any strength neither overflows nor
loses a small term against a large one.

Two forms of the one algorithm share these equations and the constants
``CHUNK`` and ``SUB``. ``chunk_kda_xla`` is the XLA form: the CPU path, the
oracle of the kernel's tests and what the kernel is measured against. The
Pallas kernel pair ``chunk_kda_fwd`` / ``chunk_kda_bwd``
(``ops/pallas/chunk_kda.py``) carries the state in VMEM from chunk to chunk;
``chunk_kda`` takes it by what it can see in its input (a platform with
kernels, head sizes that are lane tiles, whole chunks in blocks of whole
lane tiles, a block that fits VMEM): no option, no environment variable.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.attention_layers import rms_norm, scoped
from deeplearning4j_tpu.nn.base import GlobalConfig, Layer, register_layer
from deeplearning4j_tpu.ops.initializers import init_weights
from deeplearning4j_tpu.ops.pallas.chunk_kda import CHUNK, SUB, chunk_kda_compatible, chunk_kda_pallas

GROUP = 16   # chunks the XLA form works on together: bounds what its backward pass holds


def _intra_chunk(q, k, G, beta, mm):
    """``A`` (strictly lower, times beta) and ``B`` (lower with diagonal) of
    every chunk: float32 (..., C, K) x 3 and (..., C) -> two (..., C, C) in
    float32; ``mm`` is the dtype of the matmuls' operands."""
    *lead, c, _ = q.shape
    n_sub = c // SUB
    sub = lambda a: a.reshape(*lead, n_sub, SUB, a.shape[-1])
    qs, ks, Gs = sub(q), sub(k), sub(G)
    # G just before each sub-block's first row (0 for the chunk's first)
    start = jnp.concatenate([jnp.zeros_like(Gs[..., :1, -1, :]), Gs[..., :-1, -1, :]], -2)
    row_decay = jnp.exp(Gs - start[..., None, :])                    # rows, from their block's start
    col_decay = jnp.exp(jnp.minimum(start[..., None, :] - G[..., None, :, :], 0.0))  # (.., n_sub, C, K)
    k_cols = (k[..., None, :, :] * col_decay).astype(mm)
    rows = jnp.arange(c)
    earlier = (rows[None, :] < (rows[:, None] // SUB) * SUB)          # column in an earlier sub-block
    in_block = rows[:, None] // SUB == rows[None, :] // SUB
    # within a sub-block: the differences themselves, masked before the exponential
    lower = jnp.tril(jnp.ones((SUB, SUB), bool))
    diff = jnp.where(lower[..., None], Gs[..., :, None, :] - Gs[..., None, :, :], 0.0)
    decay = jnp.where(lower[..., None], jnp.exp(diff), 0.0)           # (.., n_sub, SUB, SUB, K)

    def pairs(a):
        off = jnp.einsum("...sik,...sjk->...sij", (a * row_decay).astype(mm), k_cols,
                         preferred_element_type=jnp.float32).reshape(*lead, c, c)
        diag = jnp.sum(a[..., :, None, :] * ks[..., None, :, :] * decay, -1)       # (.., n_sub, SUB, SUB)
        diag = jnp.einsum("...sij,st->...sitj", diag, jnp.eye(n_sub, dtype=diag.dtype)).reshape(*lead, c, c)
        return jnp.where(earlier, off, 0.0) + jnp.where(in_block, diag, 0.0)

    strict = rows[:, None] > rows[None, :]
    return jnp.where(strict, pairs(ks) * beta[..., None], 0.0), pairs(qs)


def _group(S, xs):
    """One group of chunks: the chunk-local parts for all of them at once,
    then the state through them in turn (unrolled: no loop inside the loop).
    ``S``: (b, h, K, V) float32; ``xs``: q, k, v, g of (b, h, n, C, .) and
    beta of (b, h, n, C)."""
    q, k, v, g, beta = xs
    mm, c = q.dtype, q.shape[-2]
    qf, kf = q.astype(jnp.float32), k.astype(jnp.float32)
    G = jnp.cumsum(g, axis=-2)
    A, B = _intra_chunk(qf, kf, G, beta, mm)
    decay_in = jnp.exp(G)
    rhs = jnp.concatenate([kf * decay_in, v.astype(jnp.float32)], -1) * beta[..., None]
    eye = jnp.eye(c, dtype=A.dtype)
    solved = jax.lax.linalg.triangular_solve(A + eye, rhs, left_side=True, lower=True, unit_diagonal=True)
    W, U0 = solved[..., :k.shape[-1]], solved[..., k.shape[-1]:]
    G_end = G[..., -1:, :]
    Wq = jnp.concatenate([W, qf * decay_in], -2).astype(mm)      # one product with the state gives W S and q S
    k_out = (kf * jnp.exp(G_end - G)).astype(mm)
    keep = jnp.exp(G_end[..., 0, :])[..., None]                  # (b, h, n, K, 1)
    B = B.astype(mm)
    out = []
    for i in range(q.shape[2]):
        both = jnp.einsum("bhck,bhkv->bhcv", Wq[:, :, i], S.astype(mm), preferred_element_type=jnp.float32)
        U = (U0[:, :, i] - both[..., :c, :]).astype(mm)
        out.append(both[..., c:, :] + jnp.einsum("bhcj,bhjv->bhcv", B[:, :, i], U,
                                                 preferred_element_type=jnp.float32))
        S = keep[:, :, i] * S + jnp.einsum("bhck,bhcv->bhkv", k_out[:, :, i], U, preferred_element_type=jnp.float32)
    return S, jnp.stack(out, 2).astype(v.dtype)


def chunk_kda(q, k, v, g, beta, chunk: int = CHUNK):
    """The gated delta rule over (b, t, h, d) ``q`` (already scaled), ``k``
    (both L2-normed), ``v``, the float32 log-decay ``g`` <= 0 per channel and
    ``beta`` (b, t, h) in (0, 1). Returns (b, t, h, d_v) in ``v``'s dtype.

    Matmul operands take ``q``'s dtype and accumulate in float32; the state,
    the decays and the triangular solve are float32. The kernel pair where
    ``chunk_kda_compatible`` takes the call, under the ``kda_scan`` scope
    (forward and backward kernel alike), else ``chunk_kda_xla``."""
    b, t, h, _ = q.shape
    if t % chunk:
        raise ValueError(f"chunk_kda: {t} tokens are no multiple of the chunk of {chunk}")
    if not chunk_kda_compatible(q, v, chunk):
        return chunk_kda_xla(q, k, v, g, beta, chunk)
    # (b, h * d, t): XLA keeps these activations time-minor, so the transposes are layout requests, not copies
    time_minor = lambda a: a.reshape(b, t, -1).transpose(0, 2, 1)
    with jax.named_scope("kda_scan"):
        o = chunk_kda_pallas(time_minor(q), time_minor(k), time_minor(v), time_minor(g.astype(jnp.float32)),
                             beta.astype(jnp.float32), h)
        return o.transpose(0, 2, 1).reshape(b, t, h, -1)


def chunk_kda_xla(q, k, v, g, beta, chunk: int = CHUNK):
    """``chunk_kda`` in XLA ops. One ``lax.scan`` over groups of ``GROUP``
    chunks, its body under ``jax.checkpoint``: the backward pass keeps the
    state at every group and recomputes inside one.

    Scopes: the re-layouts are ``kda_scan``; the scan itself is opened under
    no scope of its own, so that the device trace names the ops of its body
    ``<layer>/while/...`` and the ``while`` op, whose span on the device
    covers all of them a second time, plain ``<layer>``: a table that adds
    device ops up by their first two scopes then shows the recurrence once,
    under ``while``, and its duplicate in the row of the bare layer
    (docs/observability.md)."""
    b, t, h, _ = q.shape
    n = t // chunk
    group = GROUP if n % GROUP == 0 else 1

    def groups(a):  # (b, t, h, .) -> (groups, b, h, chunks of a group, chunk, .)
        return a.reshape(b, n // group, group, chunk, h, -1).transpose(1, 0, 4, 2, 3, 5)

    with jax.named_scope("kda_scan"):
        xs = (groups(q), groups(k), groups(v), groups(g.astype(jnp.float32)),
              groups(beta.astype(jnp.float32)[..., None])[..., 0])
        S0 = jnp.zeros((b, h, q.shape[-1], v.shape[-1]), jnp.float32)
    _, out = jax.lax.scan(jax.checkpoint(_group), S0, xs)      # (groups, b, h, group, chunk, d_v)
    with jax.named_scope("kda_scan"):
        return out.transpose(1, 0, 3, 4, 2, 5).reshape(b, t, h, -1)


def causal_conv(x, w):
    """Depthwise causal convolution over time: ``y_t = sum_i w[i] x_(t-n+1+i)``
    with ``w`` of (n, channels) and zeros before the first token."""
    n, t = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (n - 1, 0), (0, 0)))
    return sum(padded[:, i:i + t] * w[i] for i in range(n))


@register_layer
@dataclasses.dataclass
class GatedShortConv(Layer):
    """The gated short convolution of the LFM2 family (its ``conv``
    operator), no biases::

        [B | C | x~] = x W_in            (W_in: d x 3d, split in this order)
        y = (C * conv(B * x~)) W_out     (W_out: d x d)

    ``conv`` is :func:`causal_conv` with ``kernel_size`` taps a channel
    (``conv``: (kernel_size, d)) and zeros before the first token; no
    activation. The gates and the taps compute in float32 and hand
    ``W_out`` the input's dtype. Scopes: ``short_conv/in_proj``,
    ``short_conv/conv`` (both gates and the convolution) and
    ``short_conv/out_proj``."""

    kernel_size: int = 3

    def init(self, key, input_type, g: GlobalConfig):
        d = input_type.size
        shapes = {"W_in": (d, 3 * d), "conv": (self.kernel_size, d), "W_out": (d, d)}
        return {name: init_weights(k, shape, self._winit(g), fan=shape, dtype=g.dtype)
                for (name, shape), k in zip(shapes.items(), jax.random.split(key, len(shapes)))}, {}

    @staticmethod
    def gated_conv(w, bcx):
        b, c, x = jnp.split(bcx.astype(jnp.float32), 3, axis=-1)
        return (c * causal_conv(b * x, w.astype(jnp.float32))).astype(bcx.dtype)

    def forward(self, params, state, x, *, training=False, rng=None, mask=None):
        with jax.named_scope("short_conv"):
            bcx = scoped("in_proj", lambda w, x_: x_ @ w, params["W_in"], x)
            y = scoped("conv", self.gated_conv, params["conv"], bcx)
            return scoped("out_proj", lambda w, y_: y_ @ w, params["W_out"], y), state

    def regularizable_params(self):
        return ("W_in", "W_out")


def l2_normalize(x, eps=1e-6):
    xf = x.astype(jnp.float32)
    return (xf * jax.lax.rsqrt(jnp.sum(jnp.square(xf), -1, keepdims=True) + eps)).astype(x.dtype)


@register_layer
@dataclasses.dataclass
class KimiDeltaAttention(Layer):
    """Kimi Delta Attention: q, k, v through a causal depthwise convolution
    and SiLU, L2-normed q and k, a low-rank per-channel log-decay gate, a
    per-head ``beta``, the gated delta rule (``chunk_kda``), then a per-head
    RMSNorm gated by a low-rank sigmoid gate, and the output projection."""

    n_heads: int = 32
    head_dim: int = 128
    conv_size: int = 4
    gate_rank: int = 128
    eps: float = 1e-5

    def init(self, key, input_type, g: GlobalConfig):
        d, inner, r = input_type.size, self.n_heads * self.head_dim, self.gate_rank
        f = g.dtype or jnp.float32
        shapes = {"W_q": (d, inner), "W_k": (d, inner), "W_v": (d, inner), "W_o": (inner, d),
                  "W_fa": (d, r), "W_fb": (r, inner), "W_ga": (d, r), "W_gb": (r, inner),
                  "W_b": (d, self.n_heads),
                  "conv_q": (self.conv_size, inner), "conv_k": (self.conv_size, inner),
                  "conv_v": (self.conv_size, inner)}
        keys = iter(jax.random.split(key, len(shapes) + 2))
        params = {name: init_weights(next(keys), shape, self._winit(g), fan=shape, dtype=g.dtype)
                  for name, shape in shapes.items()}
        # decay rates log-uniform in [1, 16]; softplus(dt_bias) uniform in [0.001, 0.1]
        params["A_log"] = jnp.log(jax.random.uniform(next(keys), (self.n_heads,), f, 1.0, 16.0))
        dt = jax.random.uniform(next(keys), (inner,), f, 0.001, 0.1)
        params["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))
        params["b_g"] = jnp.zeros((inner,), f)
        params["norm_w"] = jnp.ones((self.head_dim,), f)
        return params, {}

    def _in(self, p, x):
        b, t, _ = x.shape
        heads = lambda a: a.reshape(b, t, self.n_heads, self.head_dim)
        q, k, v = (heads(jax.nn.silu(causal_conv(x @ p[f"W_{n}"], p[f"conv_{n}"]))) for n in "qkv")
        q = l2_normalize(q) * self.head_dim ** -0.5
        k = l2_normalize(k)
        f32 = jnp.float32
        rate = jnp.exp(p["A_log"].astype(f32))[:, None]
        g = -rate * jax.nn.softplus(heads((x @ p["W_fa"]) @ p["W_fb"]).astype(f32)
                                    + p["dt_bias"].astype(f32).reshape(self.n_heads, self.head_dim))
        beta = jax.nn.sigmoid((x @ p["W_b"]).astype(f32))
        gate = heads((x @ p["W_ga"]) @ p["W_gb"] + p["b_g"])
        return q, k, v, g, beta, gate

    def _out(self, p, o, gate):
        b, t = o.shape[:2]
        y = rms_norm(o, p["norm_w"], self.eps) * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(o.dtype)
        return y.reshape(b, t, -1) @ p["W_o"]

    def forward(self, params, state, x, *, training=False, rng=None, mask=None):
        q, k, v, g, beta, gate = scoped("kda_in", self._in, params, x)
        o = chunk_kda(q, k, v, g, beta)  # opens its own scope, ``kda_scan`` (the XLA form also ``while``)
        return scoped("kda_out", self._out, params, o, gate), state

    def regularizable_params(self):
        return ("W_q", "W_k", "W_v", "W_o", "W_fa", "W_fb", "W_ga", "W_gb", "W_b")
