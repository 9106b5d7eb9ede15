"""Pallas kernel tests (interpreter mode on CPU; real compilation exercised
on TPU by the benchmarks)."""

import os
import re

import numpy as np
import pytest

os.environ["DL4J_TPU_PALLAS_INTERPRET"] = "1"


def _ref_attention(q, k, v):
    d = q.shape[-1]
    s = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
    w = np.exp(s - s.max(-1, keepdims=True))
    w = w / w.sum(-1, keepdims=True)
    return np.einsum("bhqk,bhkd->bhqd", w, v)


def test_flash_attention_matches_reference():
    from deeplearning4j_tpu.ops.pallas.flash_attention import (
        flash_attention, flash_attention_compatible)
    rng = np.random.default_rng(0)
    B, H, T, D = 2, 2, 256, 64
    q = rng.normal(0, 1, (B, H, T, D)).astype(np.float32)
    k = rng.normal(0, 1, (B, H, T, D)).astype(np.float32)
    v = rng.normal(0, 1, (B, H, T, D)).astype(np.float32)
    import jax.numpy as jnp
    assert flash_attention_compatible(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    out = np.asarray(flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)))
    np.testing.assert_allclose(out, _ref_attention(q, k, v), rtol=2e-4, atol=2e-5)


def test_flash_attention_gradients():
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.ops.pallas.flash_attention import flash_attention
    rng = np.random.default_rng(1)
    B, H, T, D = 1, 1, 128, 64
    q = jnp.asarray(rng.normal(0, 1, (B, H, T, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(0, 1, (B, H, T, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(0, 1, (B, H, T, D)).astype(np.float32))

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v) ** 2)

    def loss_ref(q, k, v):
        d = q.shape[-1]
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(jnp.asarray(d, jnp.float32))
        w = jax.nn.softmax(s, axis=-1)
        return jnp.sum(jnp.einsum("bhqk,bhkd->bhqd", w, v) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-4)


def test_flash_attention_chunked_backward_matches_reference(monkeypatch):
    """The long-context CHUNKED backward kernels (round 5: stream Q/dO and
    K/V through VMEM over a third grid dim with scratch accumulators) must
    match the XLA oracle — exercised by lowering the chunk sizes so a
    small T runs multiple chunks, incl. accumulate/flush and the causal
    chunk-skip arithmetic."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.ops.pallas import flash_attention as fa

    monkeypatch.setattr(fa, "BWD_CHUNK_THRESHOLD", 256)
    monkeypatch.setattr(fa, "BWD_CHUNK", 512)
    rng = np.random.default_rng(7)
    B, H, T, D = 1, 2, 1024, 64  # 1024 rows -> 2 chunks of 512
    q = jnp.asarray(rng.normal(0, 1, (B, H, T, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(0, 1, (B, H, T, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(0, 1, (B, H, T, D)).astype(np.float32))
    mask = jnp.asarray((rng.random((B, T)) > 0.2).astype(np.float32))

    for causal, m in ((False, None), (True, None), (False, mask),
                      (True, mask)):
        def loss_flash(q, k, v):
            return jnp.sum(fa.flash_attention(q, k, v, mask=m,
                                              causal=causal) ** 2)

        def loss_ref(q, k, v):
            d = q.shape[-1]
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(
                jnp.asarray(d, jnp.float32))
            if m is not None:
                s = jnp.where(m[:, None, None, :].astype(bool), s, -1e30)
            if causal:
                tq = s.shape[2]
                tri = jnp.tril(jnp.ones((tq, tq), bool))
                s = jnp.where(tri[None, None], s, -1e30)
            w = jax.nn.softmax(s, axis=-1)
            return jnp.sum(jnp.einsum("bhqk,bhkd->bhqd", w, v) ** 2)

        g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-4,
                                       err_msg=f"causal={causal} mask={m is not None}")


def test_incompatible_shapes_fall_back():
    import jax.numpy as jnp
    from deeplearning4j_tpu.ops.pallas.flash_attention import flash_attention_compatible
    q = jnp.zeros((1, 1, 100, 64))  # T not block-divisible
    assert not flash_attention_compatible(q, q, q)
    q2 = jnp.zeros((1, 1, 128, 64))
    # key-padding masks ARE kernel-compatible now
    assert flash_attention_compatible(q2, q2, q2, mask=jnp.ones((1, 1, 1, 128)))
    # full (b, 1, t_q, t_k) masks are not
    assert not flash_attention_compatible(q2, q2, q2,
                                          mask=jnp.ones((1, 1, 128, 128)))


def test_flash_attention_fused_backward_cross_and_bf16():
    """The backward is now its own pair of Pallas kernels (dq / dkv) — check
    them against the XLA softmax form for cross-attention shapes and bf16."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.ops.pallas.flash_attention import flash_attention
    rng = np.random.default_rng(2)
    B, H, TQ, TK, D = 1, 2, 128, 256, 64

    def make(dtype):
        q = jnp.asarray(rng.normal(0, 1, (B, H, TQ, D)), dtype)
        k = jnp.asarray(rng.normal(0, 1, (B, H, TK, D)), dtype)
        v = jnp.asarray(rng.normal(0, 1, (B, H, TK, D)), dtype)
        return q, k, v

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v).astype(jnp.float32) ** 2)

    def loss_ref(q, k, v):
        d = q.shape[-1]
        s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                       k.astype(jnp.float32)) / np.sqrt(d)
        w = jax.nn.softmax(s, axis=-1)
        return jnp.sum(jnp.einsum("bhqk,bhkd->bhqd", w,
                                  v.astype(jnp.float32)) ** 2)

    q, k, v = make(jnp.float32)
    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)

    qb, kb, vb = make(jnp.bfloat16)
    gb = jax.grad(loss_flash, argnums=(0, 1, 2))(qb, kb, vb)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(
        qb.astype(jnp.float32), kb.astype(jnp.float32), vb.astype(jnp.float32))
    for a, b in zip(gb, gr):
        np.testing.assert_allclose(np.asarray(a).astype(np.float32),
                                   np.asarray(b), rtol=0.1, atol=0.5)


def test_fused_lstm_matches_scan():
    """Persistent-LSTM kernel (fwd + reverse-time bwd) vs the pure-scan
    reference recurrence: outputs, final carries, and ALL gradients."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.ops.pallas.fused_lstm import (
        fused_lstm, fused_lstm_compatible)

    T, B, H = 12, 8, 128
    rng = np.random.default_rng(3)
    zx = jnp.asarray(rng.normal(0, 1, (T, B, 4 * H)), jnp.float32)
    w_rec = jnp.asarray(rng.normal(0, 0.3, (H, 4 * H)), jnp.float32)
    h0 = jnp.asarray(rng.normal(0, 1, (B, H)), jnp.float32)
    c0 = jnp.asarray(rng.normal(0, 1, (B, H)), jnp.float32)
    assert fused_lstm_compatible(zx, h0)

    def scan_lstm(zx, w_rec, h0, c0):
        def step(hc, zx_t):
            h, c = hc
            z = zx_t + h @ w_rec
            i = jax.nn.sigmoid(z[:, :H])
            f = jax.nn.sigmoid(z[:, H:2 * H])
            g = jnp.tanh(z[:, 2 * H:3 * H])
            o = jax.nn.sigmoid(z[:, 3 * H:])
            c_new = f * c + i * g
            h_new = o * jnp.tanh(c_new)
            return (h_new, c_new), h_new
        (h, c), ys = jax.lax.scan(step, (h0, c0), zx)
        return ys, h, c

    ys1, h1, c1 = fused_lstm(zx, w_rec, h0, c0)
    ys2, h2, c2 = scan_lstm(zx, w_rec, h0, c0)
    np.testing.assert_allclose(np.asarray(ys1), np.asarray(ys2), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(c1), np.asarray(c2), rtol=1e-5, atol=1e-5)

    tgt = jnp.asarray(rng.normal(0, 1, (T, B, H)), jnp.float32)

    def loss(fn):
        def f(zx, w_rec, h0, c0):
            ys, hT, cT = fn(zx, w_rec, h0, c0)
            return (jnp.sum(ys * tgt) + jnp.sum(hT ** 2) + 0.5 * jnp.sum(cT ** 2))
        return f

    g1 = jax.grad(loss(fused_lstm), argnums=(0, 1, 2, 3))(zx, w_rec, h0, c0)
    g2 = jax.grad(loss(scan_lstm), argnums=(0, 1, 2, 3))(zx, w_rec, h0, c0)
    for name, a, b in zip(["dzx", "dw_rec", "dh0", "dc0"], g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


def test_lstm_layer_routes_through_fused_kernel():
    """The LSTM layer picks the Pallas kernel when eligible and must produce
    the same outputs/gradients as the scan path (GravesLSTM keeps scan)."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.nn.recurrent_layers import LSTM, GravesLSTM
    from deeplearning4j_tpu.nn.base import GlobalConfig
    from deeplearning4j_tpu.nn.inputs import InputType

    B, T, NIN, H = 8, 6, 16, 128
    layer = LSTM(n_out=H)
    g = GlobalConfig()
    layer._g = g
    params, state = layer.init(jax.random.PRNGKey(0), InputType.recurrent(NIN, T), g)
    x = jnp.asarray(np.random.default_rng(0).normal(0, 1, (B, T, NIN)), jnp.float32)

    assert layer._kernel_eligible(None)
    assert not GravesLSTM(n_out=H)._kernel_eligible(None)

    y_kernel, _ = layer.forward(params, state, x)

    # force the scan path by pretending the kernel is unavailable
    import deeplearning4j_tpu.ops.pallas.fused_lstm as fl
    orig = fl.fused_lstm_compatible
    try:
        fl.fused_lstm_compatible = lambda *a, **k: False
        y_scan, _ = layer.forward(params, state, x)
    finally:
        fl.fused_lstm_compatible = orig
    np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_scan),
                               rtol=1e-5, atol=1e-5)


def test_flash_attention_padding_mask_and_causal():
    """Key-padding mask and causal triangle vs the XLA reference form,
    forward AND gradients."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.ops.pallas.flash_attention import flash_attention
    rng = np.random.default_rng(4)
    B, H, T, D = 2, 2, 256, 64
    q = jnp.asarray(rng.normal(0, 1, (B, H, T, D)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (B, H, T, D)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (B, H, T, D)), jnp.float32)
    # ragged valid lengths per batch row
    lens = np.array([200, 131])
    kmask = jnp.asarray(np.arange(T)[None, :] < lens[:, None])

    def ref(q, k, v, mask2d=None, causal=False):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
        if mask2d is not None:
            s = jnp.where(mask2d[:, None, None, :], s, -1e30)
        if causal:
            tri = jnp.tril(jnp.ones((T, T), bool))
            s = jnp.where(tri[None, None], s, -1e30)
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", w, v)

    # forward parity: padding mask (both mask layouts)
    out = flash_attention(q, k, v, mask=kmask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref(q, k, v, kmask)),
                               rtol=2e-4, atol=2e-5)
    out4 = flash_attention(q, k, v, mask=kmask[:, None, None, :])
    np.testing.assert_allclose(np.asarray(out4), np.asarray(out), atol=1e-6)

    # forward parity: causal
    outc = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(outc),
                               np.asarray(ref(q, k, v, causal=True)),
                               rtol=2e-4, atol=2e-5)

    # gradients: masked and causal
    for kwargs, ref_kwargs in [({"mask": kmask}, {"mask2d": kmask}),
                               ({"causal": True}, {"causal": True})]:
        g1 = jax.grad(lambda *a: jnp.sum(flash_attention(*a, **kwargs) ** 2),
                      argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(lambda *a: jnp.sum(ref(*a, **ref_kwargs) ** 2),
                      argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-4)


_SPLIT = {  # id -> (dtype, T, BLOCK_Q, BLOCK_K, BWD_CHUNK or None for the resident pair, q.k head, v head, causal, padded)
    # one block straddles the diagonal; resident, then chunks of three blocks:
    # the diagonal at a chunk's first block, inside it and at its last
    "resident-f32": ("float32", 512, 128, 128, None, 64, 64, True, False),
    "resident-bf16": ("bfloat16", 512, 128, 128, None, 64, 64, True, False),
    "resident-f32-padded": ("float32", 512, 128, 128, None, 64, 64, True, True),
    "chunked-f32": ("float32", 768, 128, 128, 384, 64, 64, True, False),
    "chunked-bf16": ("bfloat16", 768, 128, 128, 384, 64, 64, True, False),
    "chunked-f32-padded": ("float32", 768, 128, 128, 384, 64, 64, True, True),
    # two blocks straddle it (two key blocks a query block, two query blocks a key block)
    "resident-q256-k128": ("float32", 512, 256, 128, None, 64, 64, True, False),
    "resident-q128-k256": ("float32", 512, 128, 256, None, 64, 64, True, True),
    "chunked-q256-k128": ("float32", 1024, 256, 128, 512, 64, 64, True, True),
    "chunked-q128-k256": ("float32", 1024, 128, 256, 512, 64, 64, True, False),
    # the cells' heads: 192^-0.5 stays on the scores, 256^-0.5 is folded into q and k
    "resident-192-128-bf16": ("bfloat16", 512, 128, 128, None, 192, 128, True, False),
    "chunked-192-128-f32": ("float32", 768, 128, 128, 384, 192, 128, True, False),
    "resident-256-256-f32-padded": ("float32", 512, 128, 128, None, 256, 256, True, True),
    "chunked-256-256-bf16": ("bfloat16", 768, 128, 128, 384, 256, 256, True, False),
    # no diagonal: one loop, no mask, as before
    "resident-full-f32-padded": ("float32", 512, 128, 128, None, 64, 64, False, True),
    "chunked-full-f32": ("float32", 768, 128, 128, 384, 64, 64, False, False),
    "chunked-full-256-256-bf16-padded": ("bfloat16", 768, 128, 128, 384, 256, 256, False, True),
}


def _patched_flash(monkeypatch, block_q, block_k, chunk):
    from deeplearning4j_tpu.ops.pallas import flash_attention as fa
    monkeypatch.setattr(fa, "BLOCK_Q", block_q)
    monkeypatch.setattr(fa, "BLOCK_K", block_k)
    if chunk is not None:
        monkeypatch.setattr(fa, "BWD_CHUNK_THRESHOLD", 128)
        monkeypatch.setattr(fa, "BWD_CHUNK", chunk)
    return fa


@pytest.mark.parametrize("case", list(_SPLIT))
def test_flash_attention_split_loops_match_the_xla_form(monkeypatch, case):
    """Under ``causal`` each kernel masks only the blocks the diagonal
    crosses and runs the blocks under it through a body without a mask; the
    output and all three gradients against the XLA softmax form in float32,
    wherever the diagonal falls in a chunk and however many blocks it
    crosses, at both cells' head sizes, with and without a key-padding mask."""
    import jax
    import jax.numpy as jnp
    dtype, t, block_q, block_k, chunk, d_qk, d_v, causal, padded = _SPLIT[case]
    fa = _patched_flash(monkeypatch, block_q, block_k, chunk)
    ks = jax.random.split(jax.random.PRNGKey(len(case)), 4)
    q, k = (jax.random.normal(key, (1, 2, t, d_qk)).astype(dtype) for key in ks[:2])
    v, w = (jax.random.normal(key, (1, 2, t, d_v)).astype(dtype) for key in ks[2:])
    mask = (jnp.arange(t)[None, :] < t - 150) if padded else None  # ends inside a block

    def xla(q, k, v):
        f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
        s = jnp.einsum("bhqd,bhkd->bhqk", f32(q), f32(k), precision="highest") * d_qk ** -0.5
        if padded:
            s = jnp.where(mask[:, None, None, :], s, -1e30)
        if causal:
            s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -1e30)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), f32(v), precision="highest")

    def both(f):
        return jax.grad(lambda *a: jnp.sum(f(*a).astype(jnp.float32) * w), argnums=(0, 1, 2))(q, k, v)

    flash = lambda q, k, v: fa.flash_attention(q, k, v, mask=mask, causal=causal)  # noqa: E731
    text = jax.jit(jax.grad(lambda *a: jnp.sum(flash(*a)), argnums=(0, 1, 2))).lower(q, k, v).as_text(debug_info=True)
    assert ("flash_attention_bwd_dkv_chunked" in text) == (chunk is not None)
    np.testing.assert_allclose(flash(q, k, v).astype(jnp.float32), xla(q, k, v), rtol=0,
                               atol=2e-5 if dtype == "float32" else 2e-2)
    for got, want in zip(both(flash), both(xla)):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        np.testing.assert_allclose(got, want, rtol=0, atol=(1e-5 if dtype == "float32" else 2e-2) * np.abs(want).max())


@pytest.mark.parametrize("chunk", [None, 384], ids=["resident", "chunked"])
@pytest.mark.parametrize("last", [127, 300, 383])
def test_flash_attention_is_causal_bit_for_bit(monkeypatch, chunk, last):
    """Keys and values after position ``last`` reach no row up to it: the
    output and dq there are the same bits whatever stands behind; and with a
    cotangent on those rows alone, dk and dv behind ``last`` are exact zeros
    (an unmasked block on the wrong side of the diagonal would show in
    either)."""
    import jax
    import jax.numpy as jnp
    fa = _patched_flash(monkeypatch, 128, 128, chunk)
    t = 768
    ks = jax.random.split(jax.random.PRNGKey(last), 6)
    q, k, v, w, k2, v2 = (jax.random.normal(key, (1, 2, t, 64)).astype(jnp.bfloat16) for key in ks)
    early = (jnp.arange(t) <= last)[None, None, :, None]
    w = jnp.where(early, w, 0)

    def run(k, v):
        out, vjp = jax.vjp(lambda q, k, v: fa.flash_attention(q, k, v, causal=True), q, k, v)
        return (out, *vjp(w))

    out, dq, dk, dv = run(k, v)
    out2, dq2, _, _ = run(jnp.where(early, k, k2), jnp.where(early, v, v2))
    np.testing.assert_array_equal(out[:, :, :last + 1], out2[:, :, :last + 1])
    np.testing.assert_array_equal(dq[:, :, :last + 1], dq2[:, :, :last + 1])
    assert float(jnp.abs(out[:, :, last + 1:] - out2[:, :, last + 1:]).max()) > 0.1
    for grad in (dk, dv):
        assert not np.asarray(grad[:, :, last + 1:], np.float32).any()
        assert np.asarray(grad[:, :, :last + 1], np.float32).any()


_GQA = {  # id -> (dtype, T, BLOCK_Q, BLOCK_K, BWD_CHUNK or None for the resident pair, query heads, key/value heads)
    "resident-g4-f32": ("float32", 512, 128, 128, None, 8, 2),
    "resident-g4-bf16": ("bfloat16", 512, 128, 128, None, 8, 2),
    "resident-g4-q256-k128": ("float32", 512, 256, 128, None, 4, 1),
    "resident-g4-q128-k256": ("float32", 512, 128, 256, None, 4, 1),
    # two chunks a query head: the group's heads one after another along the chunk axis
    "chunked-g4-f32": ("float32", 768, 128, 128, 384, 8, 2),
    "chunked-g4-bf16": ("bfloat16", 768, 128, 128, 384, 8, 2),
    "chunked-g4-q256-k128": ("float32", 1024, 256, 128, 512, 4, 1),
    "chunked-g2-q128-k256": ("float32", 1024, 128, 256, 512, 4, 2),
}


@pytest.mark.parametrize("case", list(_GQA))
def test_causal_flash_with_grouped_heads_matches_the_xla_form(monkeypatch, case):
    """Query heads a multiple of the key/value heads under ``causal``, heads
    of 64: the output and all three gradients against the XLA softmax form,
    which repeats K and V, resident and chunked, wherever the diagonal falls;
    dk and dv come back at the key/value heads' count, each the sum over its
    group; the runs keep the causal kernels' names."""
    import jax
    import jax.numpy as jnp
    dtype, t, block_q, block_k, chunk, heads, kv_heads = _GQA[case]
    fa = _patched_flash(monkeypatch, block_q, block_k, chunk)
    ks = jax.random.split(jax.random.PRNGKey(len(case)), 4)
    q, w = (jax.random.normal(key, (1, heads, t, 64)).astype(dtype) for key in (ks[0], ks[3]))
    k, v = (jax.random.normal(key, (1, kv_heads, t, 64)).astype(dtype) for key in ks[1:3])
    assert fa.flash_attention_compatible(q, k, v, causal=True)
    assert not fa.flash_attention_compatible(q, k, v) and not fa.flash_attention_compatible(
        q, k, v, jnp.ones((1, t), bool), causal=True)

    def xla(q, k, v):
        f32 = lambda a: jnp.repeat(a.astype(jnp.float32), heads // kv_heads, axis=1)  # noqa: E731
        s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), f32(k), precision="highest") * 64 ** -0.5
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -1e30)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), f32(v), precision="highest")

    def both(f):
        return jax.grad(lambda *a: jnp.sum(f(*a).astype(jnp.float32) * w), argnums=(0, 1, 2))(q, k, v)

    flash = lambda q, k, v: fa.flash_attention(q, k, v, causal=True)  # noqa: E731
    text = jax.jit(jax.grad(lambda *a: jnp.sum(flash(*a)), argnums=(0, 1, 2))).lower(q, k, v).as_text(debug_info=True)
    assert ("flash_attention_bwd_dkv_chunked" in text) == (chunk is not None) and "bd_flash" not in text
    np.testing.assert_allclose(flash(q, k, v).astype(jnp.float32), xla(q, k, v), rtol=0,
                               atol=2e-5 if dtype == "float32" else 2e-2)
    got, want = both(flash), both(xla)
    assert got[1].shape == got[2].shape == k.shape
    for a, b in zip(got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        np.testing.assert_allclose(a, b, rtol=0, atol=(1e-5 if dtype == "float32" else 2e-2) * np.abs(b).max())
    with pytest.raises(ValueError, match="grouped query heads"):
        fa.flash_attention(q, k, v)


def _dkv_grid(q, kv):
    """The dk/dv pass's ``pallas_call`` in the causal gradient at these
    shapes (traced, nothing runs) and the blocks its index maps give for each
    operand, step by step in the pipeline's order (the last axis fastest)."""
    import jax
    import jax.numpy as jnp
    from jax.extend import core as jcore

    from deeplearning4j_tpu.ops.pallas import flash_attention as fa

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for param in eqn.params.values():
                for sub in param if isinstance(param, (tuple, list)) else [param]:
                    if isinstance(sub, (jcore.ClosedJaxpr, jcore.Jaxpr)):
                        yield from walk(getattr(sub, "jaxpr", sub))

    traced = jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(fa.flash_attention(*a, causal=True)), argnums=(0, 1, 2)))(
        q, kv, kv)
    eqn = next(e for e in walk(traced.jaxpr) if e.params["name"].startswith("flash_attention_bwd_dkv"))
    grid = eqn.params["grid_mapping"]
    at = np.indices(grid.grid).reshape(len(grid.grid), -1)
    blocks = [np.stack(jax.vmap(jcore.jaxpr_as_fun(m.index_map_jaxpr))(*jnp.asarray(at, jnp.int32)), axis=1)
              for m in grid.block_mappings]
    return eqn.params["name"], grid.grid, at, blocks


def test_the_grouped_causal_dkv_pass_fetches_a_query_heads_q_and_do_once():
    """At the LFM2 cell's shape (32 query heads of 64 over 8 key/value heads,
    T = 8192) the resident dk/dv pass runs its grid over (key/value head,
    query head, key tile): Q, dO and the two row statistics change block 32
    times a run, once a query head (1 MB of Q and of dO each time), and dk and
    dv are written once a key tile, when the group's last query head is
    through with it, the last key tile first and the first last. One query
    head a key/value head keeps the grid it had (GLM's 20 heads of 256:
    chunked, 320 fetches)."""
    import jax
    import jax.numpy as jnp
    q = jax.ShapeDtypeStruct((1, 32, 8192, 64), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 8, 8192, 64), jnp.bfloat16)
    name, grid, at, blocks = _dkv_grid(q, kv)
    assert name == "flash_attention_bwd_dkv" and grid == (8, 4, 16) and len(blocks) == 8
    fetched = lambda b: np.append(np.any(b[1:] != b[:-1], axis=1), True)  # noqa: E731
    for q_do_lse_delta in (0, 3, 4, 5):
        assert fetched(blocks[q_do_lse_delta]).sum() == 32
    for dk_dv in (6, 7):
        written = fetched(blocks[dk_dv])
        assert written.sum() == 8 * 16 and (at[1][written] == 3).all()
        assert [tuple(b) for b in blocks[dk_dv][written][:16]] == [(0, tile, 0) for tile in range(15, -1, -1)]
    glm = jax.ShapeDtypeStruct((1, 20, 8192, 256), jnp.bfloat16)
    name, grid, _, blocks = _dkv_grid(glm, glm)
    assert name == "flash_attention_bwd_dkv_chunked" and grid == (20, 16, 2)
    assert np.append(np.any(blocks[0][1:] != blocks[0][:-1], axis=1), True).sum() == 320


def test_dot_product_attention_fallback_mask_forms_and_decode_causal():
    """XLA fallback must accept the same mask family as the kernel and use
    bottom-right-aligned causal masking for KV-cache decode shapes."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.nn.attention_layers import dot_product_attention
    rng = np.random.default_rng(5)
    B, H, T, D = 2, 2, 16, 8  # tiny: kernel gate rejects, fallback runs
    q = jnp.asarray(rng.normal(0, 1, (B, H, T, D)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (B, H, T, D)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (B, H, T, D)), jnp.float32)
    kmask = jnp.asarray(np.arange(T)[None, :] < np.array([12, 9])[:, None])
    out2d = dot_product_attention(q, k, v, mask=kmask, use_flash=False)
    out4d = dot_product_attention(q, k, v, mask=kmask[:, None, None, :],
                                  use_flash=False)
    np.testing.assert_allclose(np.asarray(out2d), np.asarray(out4d), atol=1e-6)

    # decode: one query over T keys with causal=True attends ALL past keys
    q1 = q[:, :, -1:, :]
    dec = dot_product_attention(q1, k, v, causal=True, use_flash=False)
    full = dot_product_attention(q, k, v, causal=True, use_flash=False)
    np.testing.assert_allclose(np.asarray(dec[:, :, 0]),
                               np.asarray(full[:, :, -1]), atol=1e-5)


def test_fused_gru_matches_scan():
    """Persistent-GRU kernel (fwd + reverse-time bwd) vs the scan reference:
    outputs, final carry, and ALL gradients (incl. the reset-gated n-path)."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.ops.pallas.fused_gru import (fused_gru,
                                                         fused_gru_compatible)

    T, B, H = 10, 8, 128
    rng = np.random.default_rng(6)
    zx = jnp.asarray(rng.normal(0, 1, (T, B, 3 * H)), jnp.float32)
    w_rec = jnp.asarray(rng.normal(0, 0.3, (H, 3 * H)), jnp.float32)
    h0 = jnp.asarray(rng.normal(0, 1, (B, H)), jnp.float32)
    assert fused_gru_compatible(zx, h0)

    def scan_gru(zx, w_rec, h0):
        def step(h, zx_t):
            zh = h @ w_rec
            r = jax.nn.sigmoid(zx_t[:, :H] + zh[:, :H])
            u = jax.nn.sigmoid(zx_t[:, H:2 * H] + zh[:, H:2 * H])
            n = jnp.tanh(zx_t[:, 2 * H:] + r * zh[:, 2 * H:])
            h_new = (1 - u) * n + u * h
            return h_new, h_new
        h, ys = jax.lax.scan(step, h0, zx)
        return ys, h

    ys1, h1 = fused_gru(zx, w_rec, h0)
    ys2, h2 = scan_gru(zx, w_rec, h0)
    np.testing.assert_allclose(np.asarray(ys1), np.asarray(ys2), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), rtol=1e-5, atol=1e-5)

    tgt = jnp.asarray(rng.normal(0, 1, (T, B, H)), jnp.float32)

    def loss(fn):
        def f(zx, w_rec, h0):
            ys, hT = fn(zx, w_rec, h0)
            return jnp.sum(ys * tgt) + jnp.sum(hT ** 2)
        return f

    g1 = jax.grad(loss(fused_gru), argnums=(0, 1, 2))(zx, w_rec, h0)
    g2 = jax.grad(loss(scan_gru), argnums=(0, 1, 2))(zx, w_rec, h0)
    for name, a, b in zip(["dzx", "dw_rec", "dh0"], g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


def test_gru_layer_routes_through_fused_kernel():
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.nn.recurrent_layers import GRU
    from deeplearning4j_tpu.nn.base import GlobalConfig
    from deeplearning4j_tpu.nn.inputs import InputType

    B, T, NIN, H = 8, 6, 16, 128
    layer = GRU(n_out=H)
    g = GlobalConfig()
    layer._g = g
    params, state = layer.init(jax.random.PRNGKey(0), InputType.recurrent(NIN, T), g)
    x = jnp.asarray(np.random.default_rng(0).normal(0, 1, (B, T, NIN)), jnp.float32)

    import deeplearning4j_tpu.ops.pallas.fused_gru as fg
    calls = []
    orig_fused, orig_compat = fg.fused_gru, fg.fused_gru_compatible
    try:
        fg.fused_gru = lambda *a: (calls.append(1), orig_fused(*a))[1]
        y_kernel, _ = layer.forward(params, state, x)
        assert calls, "fused GRU kernel was not selected"
        fg.fused_gru_compatible = lambda *a, **k: False
        y_scan, _ = layer.forward(params, state, x)
    finally:
        fg.fused_gru, fg.fused_gru_compatible = orig_fused, orig_compat
    np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_scan),
                               rtol=1e-5, atol=1e-5)


def test_fused_graves_lstm_matches_scan():
    """Peephole+mask kernel (fwd + reverse-time bwd) vs the pure-scan
    reference: outputs, final carries, all gradients incl. peepholes —
    with a ragged mask AND nonzero peepholes at once."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.ops.pallas.fused_lstm_graves import (
        fused_graves_lstm, fused_graves_lstm_compatible)

    T, B, H = 12, 8, 128
    rng = np.random.default_rng(5)
    zx = jnp.asarray(rng.normal(0, 1, (T, B, 4 * H)), jnp.float32)
    w_rec = jnp.asarray(rng.normal(0, 0.3, (H, 4 * H)), jnp.float32)
    peep = jnp.asarray(rng.normal(0, 0.3, (3 * H,)), jnp.float32)
    h0 = jnp.asarray(rng.normal(0, 1, (B, H)), jnp.float32)
    c0 = jnp.asarray(rng.normal(0, 1, (B, H)), jnp.float32)
    lens = rng.integers(3, T + 1, B)
    mask = jnp.asarray((np.arange(T)[:, None] < lens[None, :]).astype(np.float32))
    assert fused_graves_lstm_compatible(zx, h0)

    def scan_graves(zx, w_rec, peep, h0, c0, mask):
        def step(hc, inp):
            h, c = hc
            zx_t, m = inp
            z = zx_t + h @ w_rec
            i = jax.nn.sigmoid(z[:, :H] + c * peep[:H])
            f = jax.nn.sigmoid(z[:, H:2 * H] + c * peep[H:2 * H])
            g = jnp.tanh(z[:, 2 * H:3 * H])
            c_til = f * c + i * g
            o = jax.nn.sigmoid(z[:, 3 * H:] + c_til * peep[2 * H:])
            h_til = o * jnp.tanh(c_til)
            mm = m[:, None]
            h_new = mm * h_til + (1 - mm) * h
            c_new = mm * c_til + (1 - mm) * c
            return (h_new, c_new), h_new
        (h, c), ys = jax.lax.scan(step, (h0, c0), (zx, mask))
        return ys, h, c

    ys1, h1, c1 = fused_graves_lstm(zx, w_rec, peep, h0, c0, mask)
    ys2, h2, c2 = scan_graves(zx, w_rec, peep, h0, c0, mask)
    np.testing.assert_allclose(np.asarray(ys1), np.asarray(ys2), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(c1), np.asarray(c2), rtol=1e-5, atol=1e-5)

    tgt = jnp.asarray(rng.normal(0, 1, (T, B, H)), jnp.float32)

    def loss(fn):
        def f(zx, w_rec, peep, h0, c0):
            ys, hT, cT = fn(zx, w_rec, peep, h0, c0, mask)
            return jnp.sum(ys * tgt) + jnp.sum(hT ** 2) + 0.5 * jnp.sum(cT ** 2)
        return f

    g1 = jax.grad(loss(fused_graves_lstm), argnums=(0, 1, 2, 3, 4))(
        zx, w_rec, peep, h0, c0)
    g2 = jax.grad(loss(scan_graves), argnums=(0, 1, 2, 3, 4))(
        zx, w_rec, peep, h0, c0)
    for name, a, b in zip(["dzx", "dw_rec", "dpeep", "dh0", "dc0"], g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-3, err_msg=name)


def test_graves_layer_routes_through_fused_kernel():
    """GravesLSTM (peepholes) and masked plain LSTM both route through the
    generalised kernel and must match their scan paths exactly."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.nn.base import GlobalConfig
    from deeplearning4j_tpu.nn.inputs import InputType
    from deeplearning4j_tpu.nn.recurrent_layers import LSTM, GravesLSTM
    import deeplearning4j_tpu.ops.pallas.fused_lstm_graves as fg

    B, T, NIN, H = 8, 6, 16, 128
    g = GlobalConfig()
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(0, 1, (B, T, NIN)), jnp.float32)
    mask = jnp.asarray((np.arange(T)[None, :]
                        < rng.integers(2, T + 1, B)[:, None]).astype(np.float32))

    for layer, m in ((GravesLSTM(n_out=H), None),
                     (GravesLSTM(n_out=H), mask),
                     (LSTM(n_out=H), mask)):
        layer._g = g
        params, state = layer.init(jax.random.PRNGKey(1),
                                   InputType.recurrent(NIN, T), g)
        if "peephole" in params:
            params["peephole"] = jnp.asarray(
                rng.normal(0, 0.3, (3 * H,)), jnp.float32)
        y_kernel, _ = layer.forward(params, state, x, mask=m)
        orig = fg.fused_graves_lstm_compatible
        try:
            fg.fused_graves_lstm_compatible = lambda *a, **k: False
            y_scan, _ = layer.forward(params, state, x, mask=m)
        finally:
            fg.fused_graves_lstm_compatible = orig
        np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_scan),
                                   rtol=1e-5, atol=1e-5,
                                   err_msg=f"{type(layer).__name__} mask={m is not None}")


# ----------------------------------------------------------- fused dropout
def test_fused_dropout_statistics_and_determinism():
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops.pallas.fused_dropout import (
        fused_dropout, fused_dropout_add, fused_dropout_compatible,
        seed_from_key)
    h = jnp.asarray(np.random.default_rng(0).normal(0, 1, (1024, 256)),
                    jnp.float32)
    seed = seed_from_key(jax.random.PRNGKey(1))
    assert fused_dropout_compatible(h, 0.5)
    assert not fused_dropout_compatible(h, 0.0)   # rate 0: no kernel needed
    assert not fused_dropout_compatible(h[:100], 0.5)  # rows not blockable
    y = fused_dropout(h, seed, 0.5)
    frac = float(jnp.mean((y == 0)))
    assert 0.45 < frac < 0.55, frac
    # kept elements are scaled by 1/keep
    kept = np.asarray(y != 0)
    np.testing.assert_allclose(np.asarray(y)[kept],
                               np.asarray(h)[kept] * 2.0, rtol=1e-6)
    # determinism given the seed; sensitivity to the seed
    assert bool(jnp.all(y == fused_dropout(h, seed, 0.5)))
    y2 = fused_dropout(h, seed + 1, 0.5)
    assert not bool(jnp.all((y == 0) == (y2 == 0)))


def test_fused_dropout_backward_mask_matches_forward():
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops.pallas.fused_dropout import (
        fused_dropout, fused_dropout_add, seed_from_key)
    h = jnp.asarray(np.random.default_rng(2).normal(0, 1, (512, 128)),
                    jnp.float32)
    x = jnp.asarray(np.random.default_rng(3).normal(0, 1, (512, 128)),
                    jnp.float32)
    seed = seed_from_key(jax.random.PRNGKey(7))
    y = fused_dropout(h, seed, 0.3)
    g = jax.grad(lambda h: jnp.sum(fused_dropout(h, seed, 0.3)))(h)
    # the regenerated backward mask must be the SAME mask
    assert bool(jnp.all((g != 0) == (y != 0)))
    kept = np.asarray(y != 0)
    np.testing.assert_allclose(np.asarray(g)[kept], 1.0 / 0.7, rtol=1e-6)
    # residual-add form: dx is the identity
    gx = jax.grad(lambda x: jnp.sum(fused_dropout_add(x, h, seed, 0.3)))(x)
    np.testing.assert_allclose(np.asarray(gx), 1.0)


# ------------------------------------------- resident fused attention
def _attention_layer(d_model, heads, t, seed=0):
    import jax

    from deeplearning4j_tpu.nn.attention_layers import SelfAttentionLayer
    from deeplearning4j_tpu.nn.base import GlobalConfig
    from deeplearning4j_tpu.nn.inputs import InputType
    layer = SelfAttentionLayer(n_heads=heads)
    layer._g = GlobalConfig()
    params, _ = layer.init(jax.random.PRNGKey(seed),
                           InputType.recurrent(d_model, t), layer._g)
    return layer, params


def _xla_attention(q, k, v, mask, heads):
    """The XLA softmax form on (b, t, h*d) operands, head transposes and all."""
    from deeplearning4j_tpu.nn.attention_layers import dot_product_attention
    b, t, _ = q.shape
    split = lambda y: y.reshape(b, t, heads, -1).transpose(0, 2, 1, 3)  # noqa: E731
    m = None if mask is None else mask.astype(bool)
    y = dot_product_attention(split(q), split(k), split(v), m, use_flash=False)
    return y.transpose(0, 2, 1, 3).reshape(b, t, -1)


def _xla_layer(params, x, mask, heads):
    """``SelfAttentionLayer.forward`` written out over the XLA softmax form."""
    y = _xla_attention(x @ params["W_q"] + params["b_q"],
                       x @ params["W_k"] + params["b_k"],
                       x @ params["W_v"] + params["b_v"], mask, heads)
    return y @ params["W_o"] + params["b_o"]


@pytest.mark.parametrize("case,d_model,heads,t,mask_shape,kernel", [
    ("t512", 768, 12, 512, "bt", "fused_attention_fwd"),
    ("t128_no_mask", 128, 2, 128, None, "fused_attention_fwd"),
    ("t_not_128_multiple", 128, 2, 192, "bt", None),
    ("mask_not_key_padding", 128, 2, 128, "b1", None),
    # the old route; under the interpreter that is the flash kernel
    ("width_not_128_multiple", 192, 3, 128, "bt", "flash_attention_fwd"),
    ("t1024_one_head_pair", 128, 2, 1024, "bt", "flash_attention_fwd"),
])
def test_fused_attention_route_by_shape(case, d_model, heads, t, mask_shape, kernel):
    """The route is decided from what the layer sees; the lowered program
    (never run here) names the kernel it holds."""
    import jax
    import jax.numpy as jnp
    layer, params = _attention_layer(d_model, heads, t)
    x = jnp.zeros((2, t, d_model), jnp.float32)
    mask = {None: None, "bt": jnp.ones((2, t)), "b1": jnp.ones((2, 1))}[mask_shape]
    text = jax.jit(lambda p, x: layer.forward(p, {}, x, mask=mask)[0]).lower(
        params, x).as_text(debug_info=True)
    for name in ("fused_attention_fwd", "flash_attention_fwd"):
        assert (name in text) == (name == kernel), (case, name)
    # the fused route never forms the (b, h, t, d) view
    import re
    assert bool(re.search(r"tensor<\d+x\d+x\d+x\d+x", text)) == (
        kernel != "fused_attention_fwd")


def test_fused_attention_refuses_causal():
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops.pallas.fused_attention import (
        fused_attention_compatible)
    q = jax.ShapeDtypeStruct((2, 128, 128), jnp.float32)
    assert fused_attention_compatible(q, None, heads=2)
    assert not fused_attention_compatible(q, None, heads=2, causal=True)
    assert not fused_attention_compatible(
        jax.ShapeDtypeStruct((2, 128, 128), jnp.float16), None, heads=2)


@pytest.mark.parametrize("t", [128, 256, 512, 640])
def test_fused_attention_compiled_size_rule(monkeypatch, t):
    """Compiled (no interpreter, a TPU backend) the route has a lower edge
    measured on the chip and an upper edge where the block leaves VMEM."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops.pallas import fused_attention as fa
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    monkeypatch.setattr(fa, "kernels_available", lambda: True)
    q = jax.ShapeDtypeStruct((16384 // t, t, 768), jnp.bfloat16)
    assert fa.fused_attention_compatible(q, None, heads=12) == (
        fa.MIN_SEQ_FOR_KERNEL <= t <= fa.MAX_SEQ)
    monkeypatch.setattr(fa, "kernels_available", lambda: False)
    assert not fa.fused_attention_compatible(q, None, heads=12)


_MASKS = {"no_mask": None, "ragged": (100, 128, 37), "empty_row": (100, 0, 128)}


@pytest.mark.parametrize("mask_case", list(_MASKS))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_fused_attention_layer_matches_xla(dtype, mask_case):
    """Output and every parameter gradient of the routed layer against the
    layer written out over ``dot_product_attention(use_flash=False)`` in f32."""
    import jax
    import jax.numpy as jnp
    d_model, heads, t = 128, 2, 128
    layer, params = _attention_layer(d_model, heads, t, seed=3)
    rng = np.random.default_rng(4)
    params = {k: (p if k.startswith("W") else
                  jnp.asarray(rng.normal(0, 0.1, p.shape), jnp.float32))
              for k, p in params.items()}
    x = jnp.asarray(rng.normal(0, 1, (3, t, d_model)), jnp.float32)
    lens = _MASKS[mask_case]
    mask = None if lens is None else jnp.asarray(
        (np.arange(t)[None, :] < np.array(lens)[:, None]).astype(np.float32))
    cast = lambda tree: jax.tree.map(lambda a: a.astype(dtype), tree)  # noqa: E731

    def routed(p, x):
        y = layer.forward(cast(p), {}, x.astype(dtype), mask=mask)[0]
        return jnp.sum(y.astype(jnp.float32) ** 2), y

    def xla(p, x):
        y = _xla_layer(p, x, mask, heads)
        return jnp.sum(y ** 2), y

    assert "fused_attention_bwd" in str(jax.make_jaxpr(jax.grad(
        lambda p: routed(p, x)[0]))(params))
    (_, y1), g1 = jax.value_and_grad(routed, has_aux=True)(params, x)
    (_, y2), g2 = jax.value_and_grad(xla, has_aux=True)(params, x)
    # the whole layer runs in ``dtype``, projections too: hold each tensor
    # to a share of its own scale, chip_smoke's tolerance for bf16 kernels
    share = 1e-4 if dtype == "float32" else 0.05
    for name, a, b in [("y", y1, y2)] + [(n, g1[n], g2[n]) for n in g2]:
        a, b = np.asarray(a, np.float32), np.asarray(b)
        # softmax ignores a key bias: b_k's gradient is the rounding noise
        # of dk summed over rows, so it is held to b_q's scale
        scale = np.max(np.abs(np.asarray(g2["b_q"] if name == "b_k" else b)))
        assert np.all(np.isfinite(a)), name
        assert np.max(np.abs(a - b)) <= share * max(scale, 1.0), name


@pytest.mark.parametrize("heads,d", [(4, 64), (2, 128)])
def test_fused_attention_btd_layout_matches_transposed(heads, d):
    """The kernel alone, a head pair per block and one wide head per block,
    against the XLA form on transposed operands; masked keys weigh nothing."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops.pallas.fused_attention import (
        fused_attention, fused_attention_compatible)
    rng = np.random.default_rng(2)
    B, T = 2, 128
    q, k, v = (jnp.asarray(rng.normal(0, 1, (B, T, heads * d)), jnp.float32)
               for _ in range(3))
    mask = jnp.asarray(np.arange(T)[None, :] < np.array([100, T])[:, None])
    assert fused_attention_compatible(q, mask, heads=heads)
    assert fused_attention_compatible(q, mask[:, None, None, :], heads=heads)

    def xla(q, k, v):
        return _xla_attention(q, k, v, mask, heads)

    np.testing.assert_allclose(np.asarray(fused_attention(q, k, v, mask, heads)),
                               np.asarray(xla(q, k, v)), rtol=2e-5, atol=2e-5)
    # a masked key's value cannot reach the output
    v_poked = v.at[0, 100:].set(1e6)
    np.testing.assert_array_equal(
        np.asarray(fused_attention(q, k, v_poked, mask, heads))[0],
        np.asarray(fused_attention(q, k, v, mask, heads))[0])
    g1 = jax.grad(lambda q, k, v: jnp.sum(fused_attention(q, k, v, mask, heads) ** 2),
                  argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda q, k, v: jnp.sum(xla(q, k, v) ** 2),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("compute_dtype", ["float32", "bfloat16"])
def test_fused_attention_bert_fit_step_matches_xla(monkeypatch, compute_dtype):
    """One step of a tiny BERT through ``fit``: the routed step against the
    same step over the XLA softmax form."""
    import jax

    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.data.iterators import ListDataSetIterator
    from deeplearning4j_tpu.ops.pallas import flash_attention as fl
    from deeplearning4j_tpu.ops.pallas import fused_attention as fa
    from deeplearning4j_tpu.runtime.environment import get_environment
    from deeplearning4j_tpu.zoo import Bert
    env = get_environment()
    before = env.compute_dtype
    rng = np.random.default_rng(5)
    b, t = 4, 128
    valid = np.array([128, 90, 40, 128])
    data = DataSet(rng.integers(0, 1000, (b, t)).astype(np.int32),
                   np.eye(2, dtype=np.float32)[rng.integers(0, 2, b)],
                   features_mask=(np.arange(t)[None, :] < valid[:, None]).astype(np.float32))

    def one_step():
        net = Bert.small(dropout_rate=0.0, seed=11).init()
        net.fit(ListDataSetIterator([data], batch_size=b))
        return float(net.score()), jax.tree.map(np.asarray, net.train_state.params)

    try:
        env.set_compute_dtype(compute_dtype)
        loss_routed, leaves_routed = one_step()
        monkeypatch.setattr(fa, "fused_attention_compatible", lambda *a, **k: False)
        monkeypatch.setattr(fl, "flash_attention_compatible", lambda *a, **k: False)
        loss_xla, leaves_xla = one_step()
    finally:
        env.set_compute_dtype(before)
    tol = dict(rtol=1e-3, atol=1e-5) if compute_dtype == "float32" else dict(rtol=0.1, atol=5e-3)
    np.testing.assert_allclose(loss_routed, loss_xla, **tol)
    flat_a = jax.tree_util.tree_leaves_with_path(leaves_routed)
    flat_b = jax.tree.leaves(leaves_xla)
    assert len(flat_a) == len(flat_b) > 20
    for (path, a), b_ in zip(flat_a, flat_b):
        if "b_k" in jax.tree_util.keystr(path):
            continue  # softmax ignores a key bias: its gradient is rounding noise
        np.testing.assert_allclose(a, b_, err_msg=jax.tree_util.keystr(path), **tol)


# ------------------------------------- compiled for the chip, without the chip
@pytest.fixture(scope="module")
def v5e_chip():
    """One chip of a described (not attached) v5e host: the TPU compiler is
    installed here, so Mosaic's own refusals (tiling, VMEM) show at no chip
    time. Only this fixture describes the topology, and only in the worker
    that runs this file."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"  # else the compiler logs under /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = log_dir
    return SingleDeviceSharding(topo.devices[0])


def test_fused_attention_compiles_for_v5e_at_the_cells_shape(v5e_chip, monkeypatch):
    """Forward and backward kernels at 32 x 512 x (12 x 64) bf16 with a
    key-padding mask, through Mosaic: two custom calls, nothing refused."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops.pallas.fused_attention import fused_attention
    monkeypatch.delenv("DL4J_TPU_PALLAS_INTERPRET")
    q = jax.ShapeDtypeStruct((32, 512, 768), jnp.bfloat16, sharding=v5e_chip)
    mask = jax.ShapeDtypeStruct((32, 512), jnp.float32, sharding=v5e_chip)

    def loss(q, k, v, mask):
        return jnp.sum(fused_attention(q, k, v, mask, 12).astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, q, q, mask).compile()
    assert compiled.as_text().count("tpu_custom_call") == 2


@pytest.mark.parametrize("heads,qk_dim,v_dim", [(32, 192, 128), (20, 256, 256)])
def test_flash_attention_compiles_for_v5e_with_two_head_sizes_at_t8192(v5e_chip, monkeypatch, heads, qk_dim, v_dim):
    """Latent attention's call at the Kimi Linear cell's shape (32 heads x
    8192 tokens, a q.k head of 192 against a v head of 128) and at the
    GLM-4.7-Flash cell's (20 heads, 192 + 64 against 256: the largest head
    the route admits), causal, bf16. The resident backward kernels wanted
    32.8 MB of scoped VMEM at the first (Mosaic refuses above 32) and more
    at the second, with the row statistics a row a sublane in both passes;
    the route's rule stands (PR 36 left it: at T = 8192 the forms cost the
    same), it takes the chunked pair, and all three kernels compile."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops.pallas import flash_attention as fa
    monkeypatch.delenv("DL4J_TPU_PALLAS_INTERPRET")
    qk = jax.ShapeDtypeStruct((1, heads, 8192, qk_dim), jnp.bfloat16, sharding=v5e_chip)
    v = jax.ShapeDtypeStruct((1, heads, 8192, v_dim), jnp.bfloat16, sharding=v5e_chip)
    assert fa._resident_bwd_bytes(8192, qk_dim, v_dim, 2) > fa.RESIDENT_BWD_VMEM >= fa._resident_bwd_bytes(8192, 64, 64, 2)

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, causal=True).astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(qk, qk, v).compile().as_text()
    assert text.count("tpu_custom_call") == 3
    assert "flash_attention_bwd_dq_chunked" in text and "flash_attention_bwd_dkv_chunked" in text


@pytest.mark.parametrize("t,form", [(8192, "resident"), (16384, "chunked")])
def test_causal_flash_with_grouped_heads_compiles_for_v5e(v5e_chip, monkeypatch, t, form):
    """The LFM2 cell's attention, 32 query heads over 8 key/value heads of
    64, causal, bf16: at T = 8192 the resident backward (the dk/dv pass holds
    a query head's Q and dO, double-buffered, beside dk and dv of the whole
    key/value head in float32: 8.4 MB), at 16384 the chunked one; three Mosaic
    calls under the causal kernels' names."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops.pallas import flash_attention as fa
    monkeypatch.delenv("DL4J_TPU_PALLAS_INTERPRET")
    q = jax.ShapeDtypeStruct((1, 32, t, 64), jnp.bfloat16, sharding=v5e_chip)
    kv = jax.ShapeDtypeStruct((1, 8, t, 64), jnp.bfloat16, sharding=v5e_chip)
    assert fa._by_query_head_bytes(8192, 64, 64, 2) <= fa.RESIDENT_BWD_VMEM

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, causal=True).astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, kv, kv).compile().as_text()
    assert text.count("tpu_custom_call") == 3 and "bd_flash" not in text
    suffix = "_chunked" if form == "chunked" else ""
    for name in ("flash_attention_fwd", "flash_attention_bwd_dq" + suffix, "flash_attention_bwd_dkv" + suffix):
        assert name in text
    assert ("_chunked" in text) == (form == "chunked")


def test_bd_flash_attention_compiles_for_v5e_at_the_sdar_cells_shape(v5e_chip, monkeypatch):
    """The block-diffusion family at the SDAR cell's shape: 32 query heads
    over 4 key/value heads of 128, [xt ; x0] of 2 x 4096 positions in blocks
    of 4, bf16. K and V of a head stay whole in VMEM in the forward and the
    dq pass (2 x 2 MB, double-buffered), a query head's Q and dO in the dk/dv
    pass, fetched once a query head (its grid runs over key/value head,
    query head, key tile) beside dk and dv of the whole key/value head in
    float32 scratch (2 x 4 MB): three Mosaic calls under their own names,
    none of the causal family's."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops.pallas import flash_attention as fa
    monkeypatch.delenv("DL4J_TPU_PALLAS_INTERPRET")
    q = jax.ShapeDtypeStruct((1, 32, 8192, 128), jnp.bfloat16, sharding=v5e_chip)
    kv = jax.ShapeDtypeStruct((1, 4, 8192, 128), jnp.bfloat16, sharding=v5e_chip)

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, block_diffusion=(4096, 4)).astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, kv, kv).compile().as_text()
    assert text.count("tpu_custom_call") == 3
    for name in ("bd_flash_attention_fwd", "bd_flash_attention_bwd_dq", "bd_flash_attention_bwd_dkv"):
        assert name in text
    assert "_chunked" not in text and '"flash_attention_fwd' not in text


def test_flash_attention_adds_no_layout_copy_to_a_glm_step(v5e_chip, monkeypatch):
    """The dk/dv pass computes its score tile transposed and takes the row
    statistics along lanes (PR 36); what it saves in VMEM must not come back
    as re-layouts in HBM (the forward stores ``lse`` both ways itself: sliced
    out of the padded (b*h, T, 8) array in XLA it was one copy a block). The
    step of ``chip_smoke``'s small GLM-4.7-Flash (2 x 1024, heads of 192 + 64
    against 256, bf16 compute, every scope recomputed), compiled for the
    described chip: 60 Mosaic calls, twelve of them the three flash kernels
    in its four blocks, six the sum of rows into tokens of its three expert
    layers (``combine`` forward, ``dispatch`` backward), and no more
    top-level copies than the step held before the flash kernels' layouts
    were chosen."""
    import jax
    import jax.numpy as jnp

    import chip_smoke
    from deeplearning4j_tpu.runtime.environment import get_environment
    from deeplearning4j_tpu.zoo import GlmMoeLite
    monkeypatch.delenv("DL4J_TPU_PALLAS_INTERPRET")
    env = get_environment()
    dtype, remat = env.compute_dtype, env.remat_segments
    try:
        env.set_compute_dtype("bfloat16")
        env.set_remat(True)
        net = GlmMoeLite.tiny(**chip_smoke.Preset().glm).init()
        step, packer = net._jitted_packed()
        spec = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e_chip)  # noqa: E731
        ids = jax.ShapeDtypeStruct((2, 1024), jnp.int32, sharding=v5e_chip)
        args = (jax.tree.map(spec, packer.pack_device(net.train_state)), ids, ids, spec(jax.random.PRNGKey(0)),
                None, None)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the route's platform probe sees the described chip
        text = step.lower(*args).compile().as_text()
    finally:
        env.set_compute_dtype(dtype)
        env.set_remat(remat)
    entry = _entry_instructions(text)
    ops = [op for _, op, _ in entry.values()]
    assert ops.count("tpu_custom_call") == 60
    assert len(re.findall(r"/rows_to_tokens/pallas_call", text[text.index("\nENTRY "):])) == 6
    assert ops.count("copy") <= 285, ops.count("copy")
    flash = [m.group(1) for line in text[text.index("\nENTRY "):].splitlines() if "tpu_custom_call" in line
             for m in [re.search(r"/(flash_attention_\w+)/pallas_call", line)] if m]
    assert sorted(flash) == sorted(4 * ["flash_attention_bwd_dkv", "flash_attention_bwd_dq", "flash_attention_fwd"])


@pytest.mark.parametrize("d,experts,top_k,held_rows,shared", [(2048, 128, 8, 12288, 0), (2304, 256, 8, 4096, 1)])
def test_an_expert_layer_sums_rows_into_tokens_without_a_scatter_for_v5e(v5e_chip, monkeypatch, d, experts, top_k,
                                                                          held_rows, shared):
    """An expert layer of the SDAR cell (softmax top-8 of 128, 8 held, 12288
    rows of 2048) and of the Kimi cell (sigmoid top-8 of 256, 8 held and the
    shared one, 4096 rows of 2304), forward and backward at 8192 tokens in
    bf16, every scope recomputed, compiled for the described chip: the sum
    into tokens is the ``rows_to_tokens`` kernel twice, once under
    ``combine`` (forward) and once under ``dispatch`` (the gather's
    gradient), and no scatter writes a (tokens, width) array."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn import MixtureOfExperts
    from deeplearning4j_tpu.nn.base import GlobalConfig
    from deeplearning4j_tpu.nn.inputs import InputType
    from deeplearning4j_tpu.runtime.environment import get_environment
    monkeypatch.delenv("DL4J_TPU_PALLAS_INTERPRET", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the route's platform probe sees the described chip
    layer = MixtureOfExperts(n_out=d, n_experts=experts, held=(0, 8), top_k=top_k, router="sigmoid" if shared else "softmax",
                             hidden_size=768, held_rows=held_rows, n_shared=shared, gated=True, activation="swish",
                             aux_loss_coef=0.0)
    g = GlobalConfig(dtype=jnp.float32)
    layer._g = g
    params, state = jax.eval_shape(lambda k: layer.init(k, InputType.recurrent(d, 8192), g), jax.random.PRNGKey(0))
    spec = lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16 if a.ndim > 1 else a.dtype, sharding=v5e_chip)  # noqa: E731
    x = jax.ShapeDtypeStruct((1, 8192, d), jnp.bfloat16, sharding=v5e_chip)

    def loss(p, s_, x_):
        return jnp.sum(layer.forward(p, s_, x_, training=True)[0].astype(jnp.float32) ** 2)

    env = get_environment()
    remat = env.remat_segments
    try:
        env.set_remat(True)
        text = jax.jit(jax.grad(loss, argnums=(0, 2))).lower(jax.tree.map(spec, params), jax.tree.map(spec, state),
                                                             x).compile().as_text()
    finally:
        env.set_remat(remat)
    kernels = re.findall(r'op_name="([^"]*rows_to_tokens/pallas_call)"', text[text.index("\nENTRY "):])
    assert len(kernels) == 2, kernels
    assert sum("jvp(combine)" in k and "transpose" not in k for k in kernels) == 1
    assert sum("transpose(jvp(dispatch))" in k for k in kernels) == 1
    assert not re.search(rf"bf16\[8192,{d}\]\S* scatter\(", text)


def test_fused_attention_costs_no_layout_copy_in_a_bert_step(v5e_chip, monkeypatch):
    """What decided PR 27 end to end: XLA keeps this model's activations
    time-minor, and a custom call that asks for another layout is paid for
    in 25 MB copies around every matmul next to it (nine a block with
    (b, t, h*d) blocks). The step of a one-block BERT-base at 32 x 512,
    compiled for the described chip, holds the kernel pair and no such copy."""
    import re

    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.runtime.environment import get_environment
    from deeplearning4j_tpu.zoo import Bert
    monkeypatch.delenv("DL4J_TPU_PALLAS_INTERPRET")
    env = get_environment()
    before = env.compute_dtype
    try:
        env.set_compute_dtype("bfloat16")
        net = Bert(d_model=768, n_layers=1, n_heads=12, ffn_size=3072,
                   vocab_size=1000, max_len=512, dropout_rate=0.0).init()
        step, packer = net._jitted_packed()
        spec = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e_chip)  # noqa: E731
        args = (jax.tree.map(spec, packer.pack_device(net.train_state)),
                jax.ShapeDtypeStruct((32, 512), jnp.int32, sharding=v5e_chip),
                jax.ShapeDtypeStruct((32, 2), jnp.float32, sharding=v5e_chip),
                spec(jax.random.PRNGKey(0)),
                jax.ShapeDtypeStruct((32, 512), jnp.float32, sharding=v5e_chip), None)
        # the route's platform probe sees the chip the program is compiled for
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        text = step.lower(*args).compile().as_text()
    finally:
        env.set_compute_dtype(before)
    entry = text[text.index("\nENTRY "):]
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"", entry)) == 2
    copies = [line for line in entry.splitlines()
              if re.match(r"\s+%?copy[.\d]* = bf16\[32,(512,768|768,512)\]", line)]
    beside_a_matmul = [line for line in copies if "dot_general" in line]
    assert not beside_a_matmul, beside_a_matmul[0][:400]


@pytest.mark.parametrize("tokens,why", [(64, "one chunk, a block of one"), (256, "one block of four chunks")])
def test_chunk_kda_kernel_pair_matches_the_xla_form(tokens, why):
    """``chunk_kda_fwd`` / ``chunk_kda_bwd`` under the interpreter at the
    published head size against the XLA form of the same algorithm, outputs
    and all five gradients, two rows and two heads (the heads' states share
    one VMEM scratch). Several blocks, a strong decay and the token
    recurrence: ``tests/test_kimi_linear.py``."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.linear_attention_layers import chunk_kda, chunk_kda_xla
    from deeplearning4j_tpu.ops.pallas.chunk_kda import chunk_kda_compatible
    ks = jax.random.split(jax.random.PRNGKey(tokens), 5)
    shape = (2, tokens, 2, 128)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    args = (unit(jax.random.normal(ks[0], shape)) * 128 ** -0.5, unit(jax.random.normal(ks[1], shape)),
            jax.random.normal(ks[2], shape), -jax.random.uniform(ks[3], shape, maxval=0.05),
            jax.nn.sigmoid(jax.random.normal(ks[4], shape[:3])))
    assert chunk_kda_compatible(args[0], args[2]), why

    def both(f):
        return jax.value_and_grad(lambda *a: jnp.sum(jnp.sin(3 * f(*a))), argnums=(0, 1, 2, 3, 4))(*args)

    for got, want in zip(jax.tree.leaves(both(chunk_kda)), jax.tree.leaves(both(chunk_kda_xla))):
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-4 * float(jnp.max(jnp.abs(want))))


def _delta_rule_shapes(v5e_chip, tokens=8192, heads=32, d=128):
    import jax
    import jax.numpy as jnp
    x = jax.ShapeDtypeStruct((1, tokens, heads, d), jnp.bfloat16, sharding=v5e_chip)
    g = jax.ShapeDtypeStruct((1, tokens, heads, d), jnp.float32, sharding=v5e_chip)
    beta = jax.ShapeDtypeStruct((1, tokens, heads), jnp.float32, sharding=v5e_chip)
    return x, x, x, g, beta


@pytest.mark.parametrize("layers,compile_it", [(1, True), (4, False)])
def test_chunk_kda_at_the_kimi_cells_shape_for_v5e(v5e_chip, monkeypatch, layers, compile_it):
    """The gated delta rule at 1 x 8192 x (32 x 128) bf16, forward and
    backward. One call, through Mosaic: exactly the two kernels, no XLA loop
    left. Four calls, as the cell's four KDA layers make them, lowered only:
    **a guard on set-up that is a size, not a time.** A ``pallas_call`` is
    traced and lowered to a Mosaic module in every process before the
    compile cache's key exists, so what the step's module holds of it is
    paid in every warm start: the lowered text of the gradient through four
    layers holds each kernel's module once (the layers share one traced
    function) in 52,657 bytes, where the XLA form's eight loop bodies take
    1,086,758 (PR 32). A kernel body that unrolls its chunks, or an entry
    that is traced again at every site, fails here and not in the driver's
    check of ``setup_s``."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.linear_attention_layers import chunk_kda
    monkeypatch.delenv("DL4J_TPU_PALLAS_INTERPRET")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the route's platform probe sees the described chip

    def loss(q, k, v, g, beta):
        for _ in range(layers):
            v = chunk_kda(q, k, v, g, beta)
        return jnp.sum(v.astype(jnp.float32) ** 2)

    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(*_delta_rule_shapes(v5e_chip))
    text = lowered.as_text()
    assert text.count("tpu_custom_call") == 2 and "stablehlo.while" not in text
    assert len(text) < 120_000
    if compile_it:
        hlo = lowered.compile().as_text()
        assert hlo.count('custom_call_target="tpu_custom_call"') == 2
        assert "chunk_kda_fwd" in hlo and "chunk_kda_bwd" in hlo
        assert not re.search(r" while\(", hlo)


@pytest.mark.parametrize("d_k,d_v", [(256, 256), (256, 128)])
def test_chunk_kda_compiles_for_v5e_at_the_widest_heads_the_route_admits(v5e_chip, monkeypatch, d_k, d_v):
    """``_vmem_bytes`` lets the route take 32 heads of 256 at T = 8192
    (15.66 of 15.73 MB at 256 / 256; PR 38's count of the (SUB, SUB, d_k)
    temporaries refused both shapes): what it admits has to compile through
    Mosaic, where a count too low fails the step and does not fall back to
    the XLA form."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.linear_attention_layers import chunk_kda
    from deeplearning4j_tpu.ops.pallas.chunk_kda import chunk_kda_compatible
    monkeypatch.delenv("DL4J_TPU_PALLAS_INTERPRET")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q, _, _, g, beta = _delta_rule_shapes(v5e_chip, d=d_k)
    v = _delta_rule_shapes(v5e_chip, d=d_v)[2]
    assert chunk_kda_compatible(q, v)

    def loss(q, k, v, g, beta):
        return jnp.sum(chunk_kda(q, k, v, g, beta).astype(jnp.float32) ** 2)

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(q, q, v, g, beta).compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2


def _entry_instructions(hlo):
    """The ENTRY computation of a compiled module's text as ``name ->
    (type, op, operands that ENTRY defines)``; a Mosaic kernel's op reads
    ``tpu_custom_call``."""
    lines = hlo[hlo.index("\nENTRY "):].splitlines()
    parsed = [re.match(r"\s+(?:ROOT )?%([\w.\-]+) = (.+?) ([a-z][a-z\-]*)\((.*)$", line) for line in lines]
    found = {m.group(1): m.groups()[1:] for m in parsed if m}
    mosaic = 'custom_call_target="tpu_custom_call"'
    return {name: (kind, "tpu_custom_call" if mosaic in rest else op,
                   [o for o in re.findall(r"%([\w.\-]+)", rest) if o in found])
            for name, (kind, op, rest) in found.items()}


def test_chunk_kda_costs_no_layout_copy_in_a_kimi_linear_step(v5e_chip, monkeypatch):
    """PR 27's finding a second time (PR 34): XLA lays this model's
    (1, 8192, 32 x 128) activations out for its own fusions (head by head,
    time-minor beside the matmuls), a ``pallas_call`` pins row-major
    operands, and a pair on (b, t, h * d) blocks had every operand and
    result re-laid in HBM, 36 copies of 67 and 134 MB a step in the cell's
    four layers; on (b, h * d, t) the fusions write and read the kernels'
    layout themselves. The step of a one-KDA-layer decoder at the cell's
    widths (1 x 8192, hidden 2304, 32 heads x 128, bf16 compute, every scope
    recomputed), compiled for the described chip, holds the two kernels and
    no copy of an (8192, 4096)-sized array that feeds, or is fed by, either."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.runtime.environment import get_environment
    from deeplearning4j_tpu.zoo import KimiLinear
    monkeypatch.delenv("DL4J_TPU_PALLAS_INTERPRET")
    env = get_environment()
    dtype, remat = env.compute_dtype, env.remat_segments
    try:
        env.set_compute_dtype("bfloat16")
        env.set_remat(True)
        net = KimiLinear(vocab_size=1024, d_model=2304, n_layers=1, kda_layers=[1], full_attn_layers=[],
                         n_heads=32, kda_head_dim=128, dense_size=1024).init()
        step, packer = net._jitted_packed()
        spec = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e_chip)  # noqa: E731
        ids = jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=v5e_chip)
        args = (jax.tree.map(spec, packer.pack_device(net.train_state)), ids, ids, spec(jax.random.PRNGKey(0)),
                None, None)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the route's platform probe sees the described chip
        entry = _entry_instructions(step.lower(*args).compile().as_text())
    finally:
        env.set_compute_dtype(dtype)
        env.set_remat(remat)
    kernels = [name for name, (_, op, _) in entry.items() if op == "tpu_custom_call"]
    assert sorted(name.split(".")[0] for name in kernels) == ["chunk_kda_bwd", "chunk_kda_fwd"]
    users = {name: [] for name in entry}
    for name, (_, _, operands) in entry.items():
        for operand in operands:
            users[operand].append(name)

    def beyond_views(names, onward):
        """What ``names`` reach through bitcasts and tuple elements."""
        for name in names:
            if entry[name][1] in ("bitcast", "get-tuple-element"):
                yield from beyond_views(onward(name), onward)
            else:
                yield name

    def elements(kind):
        return int(np.prod([int(d) for d in re.match(r"\w+\[([\d,]*)\]", kind).group(1).split(",") if d] or [0]))

    beside = [n for k in kernels for n in (*beyond_views(entry[k][2], lambda n: entry[n][2]),
                                           *beyond_views(users[k], lambda n: users[n]))]
    copies = [f"{n} = {entry[n][0]}" for n in beside if entry[n][1] == "copy" and elements(entry[n][0]) >= 8192 * 4096]
    assert len(beside) >= 18 and not copies, copies
