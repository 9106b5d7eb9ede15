"""Chip smoke: the system's main path, once, on the TPU, through the entry
points a user calls. The quickest proof that the program still starts on
the chip; the driver runs it after every PR.

    python chip_smoke.py          # on the machine with the chip

One process, no children (a chip belongs to one process). It fails — exit
code other than 0, no result line — when JAX finds no TPU, when the
``device_kind`` is not in its table, when ``DL4J_TPU_PALLAS_INTERPRET`` is
set, or when any phase fails; nothing is caught and dropped. Phases, all at
the full width of zoo ``Bert.base()`` (12 layers, hidden 768, 12 heads,
vocab 30522; batch 64 x seq 128, bf16 compute, library defaults otherwise):

1. *kernels*: ``fused_lstm`` / ``fused_lstm_graves`` / ``fused_gru`` fwd+bwd
   at (T 256, B 64, H 512) against an XLA scan, and
   ``dot_product_attention`` fwd+bwd at T=2048 (padding mask; causal) and
   causal T=16384 (the chunked backward) against its own XLA softmax path,
   and ``fused_attention`` fwd+bwd at the shape ``SelfAttentionLayer``
   routes to it (T=512, 12 heads x 64, ragged key-padding mask, bf16;
   2 Mosaic calls a block) against the same XLA form on transposed operands —
   at the tolerances ``bench.verify_kernels`` uses, no timing. Each compiled
   program must contain a Mosaic custom call: the kernel was compiled, not
   routed around.
2. *char-RNN*: ``TextGenerationLSTM(vocab 96, hidden 512, layers 2,
   graves=True).fit`` at B=64, T=256 (routes ``fused_lstm_graves`` in its
   model).
3. *trainer*: ``Bert.base().fit(ListDataSetIterator(...))`` on seeded
   synthetic SST-2-shaped batches with a padded tail; then *kimi_linear*:
   ``KimiLinear.tiny(...)`` at the published head sizes (KDA 128; latent
   attention 128 + 64 against 128) trained on next tokens at T=1024, where
   ``chunk_kda`` routes the delta rule's kernel pair (held against its XLA
   form first), ``dot_product_attention`` the flash kernel with two head
   sizes and the experts their grouped matmul; no assignment beyond the
   buffer; then *glm_moe_lite*: ``GlmMoeLite.tiny(...)`` at the published
   head sizes (rotary latent attention 192 + 64 against 256, a low-rank
   query) with its prediction layer on the tied table and head, where the
   flash kernel routes at 256 / 256 in every block; both loss terms finite;
   then *sdar_moe* (from ``main``, after the phases ``run`` drives):
   ``SdarMoe`` at the benchmark cell's sizes (8 layers of published widths,
   8 of 128 softmax-routed experts, a row of 4096 clean tokens = 8192
   positions) trained by diffusion over blocks, where grouped-query
   attention routes the ``bd_flash_attention_*`` kernels; no assignment
   beyond the buffer, 2560 masked positions.
4. *server*: ``ModelSerializer.write_model`` -> ``ModelRegistry.load`` with
   one replica per device -> ``ModelServer`` -> ``POST
   /v1/models/bert/predict`` with mixed row counts, ``/healthz``,
   ``/metrics``; answers bit-equal to ``model.output(pad_to_bucket(x))[:n]``.
5. *four chips* (only with >= 4 devices; otherwise skipped with a printed
   line): ``ParallelWrapper.builder(net).workers(4)`` on the same BERT-base
   at global batch 256, every device holding a shard of every batch.

It asserts that the device did the work: parameters live on TPU devices,
losses are finite and fall, ``aot_fallbacks == 0``, nothing compiles after
warm-up. It reports, under names that say ``smoke_`` (information, never a
benchmark metric): cold compile seconds per phase, cache hits/misses, peak
device bytes. The last line of standard output is the result object.

Not covered here, left to the first ``benchmark`` PR: the TF-import path
(BASELINE config #4, minutes of host graph building) and ResNet-50.

The check functions take a :class:`Preset` so ``tests/test_chip_smoke.py``
can rehearse them at a tiny size on the CPU mesh with interpreted kernels;
the script itself has no CPU mode.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import json
import os
import sys
import tempfile
import time
import urllib.request
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

#: the table of device kinds the smoke knows (both spellings of the v5e);
#: a kind that is not here is an error, not a default
DEVICE_KINDS = ("TPU v5 lite", "TPU v5e")

OUT_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "chiprun_out")


@dataclasses.dataclass
class Preset:
    """Sizes of one smoke run. The defaults ARE the smoke; the CPU
    rehearsal in the tests shrinks them."""

    platform: str = "tpu"          # where every parameter must live
    expect_mosaic: bool = True     # compiled programs hold tpu_custom_call
    # BERT trainer/server (zoo Bert.base widths; depth may be cut)
    bert: Dict[str, Any] = dataclasses.field(default_factory=lambda: dict(
        vocab_size=30522, d_model=768, n_layers=12, n_heads=12,
        ffn_size=3072, max_len=512))
    batch: int = 64
    seq: int = 128
    train_batches: int = 4
    train_epochs: int = 25         # 2 warm-up steps + 100 counted
    serve_rows: tuple = (1, 3, 8, 2, 5, 1, 4, 7)
    max_batch_size: int = 8
    # recurrent kernels / char-RNN
    rnn_t: int = 256
    rnn_b: int = 64
    rnn_h: int = 512
    rnn_vocab: int = 96
    rnn_steps: int = 4
    # attention: (batch, heads, T, d) resident-backward shape + long T
    attn_shape: tuple = (4, 8, 2048, 64)
    attn_long: tuple = (1, 1, 16384, 64)
    # the resident fused kernel's routed shape: (batch, T, heads, d)
    attn_fused: tuple = (8, 512, 12, 64)
    # KimiLinear.tiny at the published head sizes; T routes the flash kernel
    kimi: Dict[str, Any] = dataclasses.field(default_factory=lambda: dict(
        d_model=256, kda_head_dim=128, qk_nope_dim=128, qk_shared_dim=64,
        v_dim=128, kv_rank=128, vocab_size=512))
    kimi_batch: int = 2
    kimi_seq: int = 1024
    # GlmMoeLite.tiny at the published head sizes (q.k 192 + 64, v 256)
    glm: Dict[str, Any] = dataclasses.field(default_factory=lambda: dict(
        d_model=256, q_rank=96, kv_rank=128, qk_nope_dim=192,
        qk_shared_dim=64, v_dim=256, vocab_size=512))
    # SdarMoe at the benchmark cell's sizes: published widths, its share of
    # depth, experts and vocabulary; one row of ``sdar_seq`` clean tokens
    sdar: Dict[str, Any] = dataclasses.field(default_factory=lambda: dict(
        vocab_size=18992, layers_here=tuple(range(8)), held_experts=(0, 8),
        held_rows=12288))
    sdar_seq: int = 4096


# ------------------------------------------------------------------ helpers
def _log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def _cache_stats() -> Dict[str, Any]:
    from deeplearning4j_tpu.runtime import compile_cache
    return compile_cache.stats()


def _compile_requests() -> int:
    """Programs handed to the compiler so far, whether the persistent cache
    answered or the backend compiled (with the cache on, every compile
    request is one cache lookup)."""
    s = _cache_stats()
    return s["hits"] + s["misses"] + s["corrupt_entries"]


@contextlib.contextmanager
def _phase(report: Dict[str, Any], name: str):
    """Times a phase and records what it compiled; a failing phase
    propagates and records nothing."""
    t0, s0 = time.perf_counter(), _cache_stats()
    _log(f"phase {name} ...")
    yield
    s1 = _cache_stats()
    mem = jax.devices()[0].memory_stats() or {}
    out = {
        "smoke_wall_s": round(time.perf_counter() - t0, 2),
        "smoke_cold_compile_s": round(
            s1["compile_seconds"] - s0["compile_seconds"], 2),
        "smoke_cache_retrieval_s": round(
            s1["retrieval_seconds"] - s0["retrieval_seconds"], 2),
        "smoke_cache_hits": s1["hits"] - s0["hits"],
        "smoke_cache_misses": s1["misses"] - s0["misses"],
        "smoke_peak_bytes_in_use": mem.get("peak_bytes_in_use"),
    }
    report["phases"][name] = out
    _log(f"phase {name} ok: {json.dumps(out)}")


def _on_platform(tree, platform: str) -> bool:
    return all(d.platform == platform
               for leaf in jax.tree.leaves(tree) for d in leaf.devices())


def _mosaic_calls(compiled, p: Preset, at_least: int, what: str) -> int:
    """Count Mosaic custom calls in a compiled program's HLO."""
    n = compiled.as_text().count("tpu_custom_call")
    if p.expect_mosaic:
        assert n >= at_least, (
            f"{what}: {n} Mosaic custom call(s) in the compiled program, "
            f"expected >= {at_least} — the kernel was routed around")
    return n


def _max_err(a, b) -> float:
    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


def _scale(tree) -> float:
    return max(float(jnp.max(jnp.abs(x.astype(jnp.float32))))
               for x in jax.tree.leaves(tree))


# ---------------------------------------------------------- phase 1: kernels
def _scan_lstm(zx, w_rec, peep, h0, c0, mask):
    """XLA reference of the (peephole, masked) LSTM recurrence; zero
    peepholes and an all-ones mask give the plain cell."""
    H = h0.shape[-1]

    def step(hc, inp):
        h, c = hc
        z, m = inp
        z = z + h @ w_rec
        i = jax.nn.sigmoid(z[:, :H] + c * peep[:H])
        f = jax.nn.sigmoid(z[:, H:2 * H] + c * peep[H:2 * H])
        g = jnp.tanh(z[:, 2 * H:3 * H])
        c_til = f * c + i * g
        o = jax.nn.sigmoid(z[:, 3 * H:] + c_til * peep[2 * H:])
        h_til = o * jnp.tanh(c_til)
        mm = m[:, None]
        h_new = mm * h_til + (1 - mm) * h
        return (h_new, mm * c_til + (1 - mm) * c), h_new

    (hT, cT), ys = jax.lax.scan(step, (h0, c0), (zx, mask))
    return ys, hT, cT


def _scan_gru(zx, w_rec, h0):
    H = h0.shape[-1]

    def step(h, z):
        zh = h @ w_rec
        r = jax.nn.sigmoid(z[:, :H] + zh[:, :H])
        u = jax.nn.sigmoid(z[:, H:2 * H] + zh[:, H:2 * H])
        n = jnp.tanh(z[:, 2 * H:] + r * zh[:, 2 * H:])
        h = (1.0 - u) * n + u * h
        return h, h

    hT, ys = jax.lax.scan(step, h0, zx)
    return ys, hT


def _fwd_bwd(fn, argnums):
    """jit(value_and_grad) of sum(first_output^2): forward and backward of
    ``fn`` in one compiled program."""
    def loss(*args):
        out = fn(*args)
        y = out[0] if isinstance(out, tuple) else out
        return jnp.sum(y.astype(jnp.float32) ** 2), y

    return jax.jit(jax.value_and_grad(loss, argnums=argnums, has_aux=True))


def _compare(name, kernel_fn, ref_fn, args, ref_args, argnums, p: Preset,
             fwd_tol, bwd_tol, calls: int) -> Dict[str, Any]:
    """One kernel against its XLA path: compile the kernel program AOT
    (so the HLO that is checked is the program that runs), run both, hold
    the errors to the tolerance."""
    compiled = _fwd_bwd(kernel_fn, argnums).lower(*args).compile()
    n_calls = _mosaic_calls(compiled, p, calls, name)
    (_, yk), gk = compiled(*args)
    (_, yx), gx = _fwd_bwd(ref_fn, argnums)(*ref_args)
    err_f = _max_err(yk, yx)
    err_b = max(_max_err(a, b) for a, b in zip(gk, gx))
    lim_f, lim_b = fwd_tol(_scale(yx)), bwd_tol(_scale(gx))
    assert np.isfinite(err_f) and err_f <= lim_f, \
        f"{name} fwd mismatch: {err_f} > {lim_f}"
    assert np.isfinite(err_b) and err_b <= lim_b, \
        f"{name} bwd mismatch: {err_b} > {lim_b}"
    _log(f"  {name}: fwd_err={err_f:.3g} bwd_err={err_b:.3g} "
         f"mosaic_calls={n_calls}")
    return {"fwd_err": err_f, "bwd_err": err_b, "mosaic_calls": n_calls}


def kernel_checks(p: Preset):
    """``(name, check)`` for every routed kernel; each check builds its own
    seeded inputs, compares kernel and XLA path, and returns its record."""
    from deeplearning4j_tpu.nn.attention_layers import dot_product_attention
    from deeplearning4j_tpu.ops.pallas.flash_attention import (
        BWD_CHUNK_THRESHOLD, flash_attention_compatible)
    from deeplearning4j_tpu.ops.pallas.fused_attention import (
        fused_attention, fused_attention_compatible)
    from deeplearning4j_tpu.ops.pallas.fused_gru import (fused_gru,
                                                         fused_gru_compatible)
    from deeplearning4j_tpu.ops.pallas.fused_lstm import (
        fused_lstm, fused_lstm_compatible)
    from deeplearning4j_tpu.ops.pallas.fused_lstm_graves import (
        fused_graves_lstm, fused_graves_lstm_compatible)

    T, B, H = p.rnn_t, p.rnn_b, p.rnn_h
    # bench.verify_kernels' tolerances: recurrent kernels in f32, flash in
    # bf16 against an f32 reference, the long oracle absolute
    rnn_f = lambda s: 1e-3                                      # noqa: E731
    rnn_b = lambda s: 1e-3 * max(s, 1.0)                        # noqa: E731
    flash_tol = lambda s: 0.05 * max(s, 1.0)                    # noqa: E731
    long_tol = lambda s: 0.1                                    # noqa: E731

    def rnn_inputs(gates):
        rng = np.random.default_rng(0)
        zx = jnp.asarray(rng.normal(0, 1, (T, B, gates * H)), jnp.float32)
        w = jnp.asarray(rng.normal(0, 0.02, (H, gates * H)), jnp.float32)
        return rng, zx, w, jnp.zeros((B, H), jnp.float32)

    def lstm():
        _, zx, w, h0 = rnn_inputs(4)
        assert fused_lstm_compatible(zx, h0), "fused_lstm ineligible"
        ones, no_peep = jnp.ones((T, B)), jnp.zeros((3 * H,))
        return _compare(
            "fused_lstm", lambda zx, w: fused_lstm(zx, w, h0, h0),
            lambda zx, w: _scan_lstm(zx, w, no_peep, h0, h0, ones),
            (zx, w), (zx, w), (0, 1), p, rnn_f, rnn_b, calls=2)

    def graves():
        rng, zx, w, h0 = rnn_inputs(4)
        peep = jnp.asarray(rng.normal(0, 0.1, (3 * H,)), jnp.float32)
        lens = rng.integers(T // 2, T + 1, B)  # ragged sequence ends
        mask = jnp.asarray((np.arange(T)[:, None] < lens[None, :])
                           .astype(np.float32))
        assert fused_graves_lstm_compatible(zx, h0), \
            "fused_lstm_graves ineligible"
        return _compare(
            "fused_lstm_graves",
            lambda zx, w, pp: fused_graves_lstm(zx, w, pp, h0, h0, mask),
            lambda zx, w, pp: _scan_lstm(zx, w, pp, h0, h0, mask),
            (zx, w, peep), (zx, w, peep), (0, 1, 2), p, rnn_f, rnn_b, calls=2)

    def gru():
        _, zx, w, h0 = rnn_inputs(3)
        assert fused_gru_compatible(zx, h0), "fused_gru ineligible"
        return _compare(
            "fused_gru", lambda zx, w: fused_gru(zx, w, h0),
            lambda zx, w: _scan_gru(zx, w, h0),
            (zx, w), (zx, w), (0, 1), p, rnn_f, rnn_b, calls=2)

    def flash(name, shape, causal, padded, tol, chunked):
        # through the routing function; its own XLA softmax path
        # (use_flash=False) on f32 copies of the inputs is the reference
        def check():
            rng = np.random.default_rng(0)
            b, _, t, _ = shape
            q, k, v = (jnp.asarray(rng.normal(0, 1, shape), jnp.bfloat16)
                       for _ in range(3))
            mask = None
            if padded:
                klens = rng.integers(t // 2, t + 1, b)
                mask = jnp.asarray(np.arange(t)[None, :] < klens[:, None])
            assert (t > BWD_CHUNK_THRESHOLD) == chunked, \
                f"{name}: T={t} takes the wrong backward"
            assert flash_attention_compatible(q, k, v, mask, causal=causal), \
                f"{name}: flash kernel ineligible"

            def attn(use_flash):
                return lambda q, k, v: dot_product_attention(
                    q, k, v, mask, use_flash=use_flash, causal=causal)

            return _compare(
                name, attn(True), attn(False), (q, k, v),
                tuple(x.astype(jnp.float32) for x in (q, k, v)), (0, 1, 2),
                p, tol, tol, calls=3)
        return check

    def fused():
        # the shape SelfAttentionLayer routes: operands as the projections
        # write them, ragged key-padding mask; the XLA softmax form on
        # transposed f32 copies is the reference. One forward and one
        # backward kernel: 2 Mosaic calls a block.
        rng = np.random.default_rng(0)
        b, t, h, d = p.attn_fused
        q, k, v = (jnp.asarray(rng.normal(0, 1, (b, t, h * d)), jnp.bfloat16)
                   for _ in range(3))
        mask = jnp.asarray(np.arange(t)[None, :]
                           < rng.integers(t // 4, t + 1, b)[:, None])
        assert fused_attention_compatible(q, mask, heads=h), \
            "fused_attention ineligible"

        def xla(q, k, v):
            split = lambda x: x.reshape(b, t, h, d).transpose(0, 2, 1, 3)  # noqa: E731
            y = dot_product_attention(split(q), split(k), split(v), mask,
                                      use_flash=False)
            return y.transpose(0, 2, 1, 3).reshape(b, t, h * d)

        return _compare(
            "fused_attention",
            lambda q, k, v: fused_attention(q, k, v, mask, h), xla, (q, k, v),
            tuple(x.astype(jnp.float32) for x in (q, k, v)), (0, 1, 2),
            p, flash_tol, flash_tol, calls=2)

    return [
        ("fused_lstm", lstm),
        ("fused_lstm_graves", graves),
        ("fused_gru", gru),
        ("flash_padding_mask",
         flash("flash_padding_mask", p.attn_shape, False, True, flash_tol,
               chunked=False)),
        ("flash_causal",
         flash("flash_causal", p.attn_shape, True, False, flash_tol,
               chunked=False)),
        # the chunked backward; one head, because the dense XLA oracle
        # holds T x T f32 scores per head
        ("flash_causal_chunked",
         flash("flash_causal_chunked", p.attn_long, True, False, long_tol,
               chunked=True)),
        ("fused_attention", fused),
    ]


def check_kernels(p: Preset) -> Dict[str, Any]:
    return {name: check() for name, check in kernel_checks(p)}


# --------------------------------------------------------- phase 2: char-RNN
def check_char_rnn(p: Preset) -> Dict[str, Any]:
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.train.listeners import CollectScoresListener
    from deeplearning4j_tpu.zoo import TextGenerationLSTM

    V, B, T = p.rnn_vocab, p.rnn_b, p.rnn_t
    net = TextGenerationLSTM(vocab_size=V, hidden=p.rnn_h, layers=2,
                             tbptt_length=T, graves=True).init()
    scores = CollectScoresListener()
    net.set_listeners(scores)
    rng = np.random.default_rng(0)
    # a learnable stream: the next char is the current one plus one
    start = rng.integers(0, V, (p.rnn_steps, B, 1))
    ids = (start + np.arange(T + 1)[None, None, :]) % V
    eye = np.eye(V, dtype=np.float32)
    batches = [DataSet(eye[i[:, :-1]], eye[i[:, 1:]]) for i in ids]
    losses = _fit_counted(net.fit, batches[:1], batches[1:], B, 1, scores,
                          "char-RNN fit")
    assert _on_platform(net.train_state.params, p.platform), \
        "char-RNN parameters are not on the device"
    # the compiled train step holds the kernel (two layers, fwd + bwd)
    from deeplearning4j_tpu.models._tbptt import carry_dtype
    from deeplearning4j_tpu.runtime.environment import get_environment
    x, y = jnp.asarray(batches[0].features), jnp.asarray(batches[0].labels)
    carries = net._zero_carries(
        B, carry_dtype(x, get_environment().compute_dtype))
    step = net._jitted("tbptt_step", net._make_tbptt_step)
    compiled = step.lower(net.train_state, carries, x, y,
                          jax.random.PRNGKey(0), None, None).compile()
    n_calls = _mosaic_calls(compiled, p, 4, "char-RNN train step")
    _log(f"  char-RNN train step: mosaic_calls={n_calls}")
    return {"steps": len(losses), "first_loss": losses[0],
            "last_loss": losses[-1], "mosaic_calls": n_calls}


# ---------------------------------------------------------- phase 3: trainer
def _bert_batches(p: Preset, n: int, batch: int, seed: int):
    """Seeded synthetic SST-2-shaped batches with a padded tail (as
    examples/bert_finetune.py). The first token names the label, so a few
    steps can lower the loss."""
    from deeplearning4j_tpu.data.dataset import DataSet
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        tokens = rng.integers(0, p.bert["vocab_size"],
                              (batch, p.seq)).astype(np.int32)
        # seven in eight are class 1. Whatever an untrained net predicts,
        # its loss is the entropy of that prior (0.38) plus its distance
        # from it, and the first thing training finds is the prior — a
        # fall that hangs neither on the seed nor on learning the rule
        tokens[:, 0] = 1 + (rng.permutation(batch) % 8 != 0)
        labels = np.eye(2, dtype=np.float32)[tokens[:, 0] - 1]
        fmask = np.ones((batch, p.seq), np.float32)
        fmask[:, p.seq - p.seq // 4:] = 0.0
        out.append(DataSet(tokens, labels, features_mask=fmask))
    return out


def _fit_counted(fit, warm, counted, batch_size: int, epochs: int, scores,
                 what: str) -> List[float]:
    """``fit`` the ``warm`` batches, then ``epochs`` passes over
    ``counted`` that must not compile anything. Returns every step's
    loss: all finite, the last lower than the first."""
    from deeplearning4j_tpu.data.iterators import ListDataSetIterator
    fit(ListDataSetIterator(warm, batch_size=batch_size), epochs=1)
    requests = _compile_requests()
    fit(ListDataSetIterator(counted, batch_size=batch_size), epochs=epochs)
    assert _compile_requests() == requests, \
        f"{what}: {_compile_requests() - requests} compile(s) after warm-up"
    losses = [s for _, s in scores.scores]
    assert len(losses) == len(warm) + epochs * len(counted), len(losses)
    assert np.all(np.isfinite(losses)), f"{what}: non-finite loss {losses}"
    _log(f"  {what}: steps={len(losses)} every 10th loss "
         f"{[round(x, 4) for x in losses[::10]]} last {losses[-1]:.4f}")
    assert 0.0 < losses[-1] < losses[0], \
        f"{what}: loss did not fall, {losses[0]} -> {losses[-1]}"
    return losses


def _bert_fit(fit, batches, batch_size, p: Preset, scores, what):
    """Two warm-up steps, then passes that end on the batch the warm-up
    began with: the first and the last loss are of the same examples."""
    return _fit_counted(fit, batches[:2], batches[1:] + batches[:1],
                        batch_size, p.train_epochs, scores, what)


def check_bert_train(p: Preset):
    from deeplearning4j_tpu.train.listeners import CollectScoresListener
    from deeplearning4j_tpu.zoo import Bert

    net = Bert(**p.bert).init()
    scores = CollectScoresListener()
    net.set_listeners(scores)
    batches = _bert_batches(p, p.train_batches, p.batch, seed=0)
    losses = _bert_fit(net.fit, batches, p.batch, p, scores, "BERT fit")
    assert _on_platform(net.train_state, p.platform), \
        "BERT train state is not on the device"
    return net, {"steps": len(losses), "first_loss": losses[0],
                 "last_loss": losses[-1],
                 "compiles_after_warmup": 0}


def _recomputing(check):
    """``check`` under ``Environment.set_remat(True)``, as the benchmark's
    cell runs the decoder: each named scope recomputed; put back after."""
    @functools.wraps(check)
    def run(p: Preset) -> Dict[str, Any]:
        from deeplearning4j_tpu.runtime.environment import get_environment
        env = get_environment()
        was = env.remat_segments
        env.set_remat(True)
        try:
            return check(p)
        finally:
            env.set_remat(was)
    return run


def _next_token_fit(net, p: Preset, what: str, at_least: int):
    """Six counted next-token steps of a decoder through ``fit`` at the
    preset's batch and length, then its compiled step: (losses, the
    compiled step's text, its Mosaic calls, ``at_least`` expected)."""
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.train.listeners import CollectScoresListener
    scores = CollectScoresListener()
    net.set_listeners(scores)
    ids = np.random.default_rng(0).integers(
        0, net.layers[0].n_in, (p.kimi_batch, p.kimi_seq + 1), dtype=np.int32)
    batch = DataSet(np.ascontiguousarray(ids[:, :-1]),
                    np.ascontiguousarray(ids[:, 1:]))
    losses = _fit_counted(net.fit, [batch], [batch], p.kimi_batch, 6, scores,
                          f"{what} fit")
    assert _on_platform(net.train_state, p.platform), \
        f"{what} train state is not on the device"
    step, packer = net._jitted_packed()
    compiled = step.lower(
        packer.pack_device(net.train_state), jnp.asarray(batch.features),
        jnp.asarray(batch.labels), jax.random.PRNGKey(0), None, None).compile()
    return losses, compiled.as_text(), _mosaic_calls(
        compiled, p, at_least, f"{what} train step")


@_recomputing
def check_kimi_linear(p: Preset) -> Dict[str, Any]:
    """The delta rule's kernel pair against its XLA form, then a few
    next-token steps of a small ``KimiLinear`` through ``fit``: KDA through
    that pair, latent attention through the flash kernel (a q.k head of two
    parts against a smaller v head), experts through the grouped matmul,
    held to: finite falling loss, nothing compiled after warm-up, the
    kernels in the compiled step, no assignment left out."""
    from deeplearning4j_tpu.nn.linear_attention_layers import (chunk_kda,
                                                               chunk_kda_xla)
    from deeplearning4j_tpu.ops.pallas.chunk_kda import chunk_kda_compatible
    from deeplearning4j_tpu.zoo import KimiLinear

    # the layer's operands: unit q (scaled) and k, log-decay <= 0, beta in (0, 1)
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    d = p.kimi["kda_head_dim"]
    shape = (p.kimi_batch, p.kimi_seq, 2, d)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    q = (unit(jax.random.normal(ks[0], shape)) * d ** -0.5).astype(jnp.bfloat16)
    k = unit(jax.random.normal(ks[1], shape)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], shape).astype(jnp.bfloat16)
    g = -jax.random.uniform(ks[3], shape, maxval=0.5)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], shape[:3]))
    assert chunk_kda_compatible(q, v), "chunk_kda: the kernel pair is ineligible"
    tol = lambda s: 0.05 * max(s, 1.0)                          # noqa: E731
    delta_rule = _compare(
        "chunk_kda", chunk_kda, chunk_kda_xla, (q, k, v, g, beta),
        tuple(x.astype(jnp.float32) for x in (q, k, v)) + (g, beta),
        (0, 1, 2, 3, 4), p, tol, tol, calls=2)

    net = KimiLinear.tiny(**p.kimi).init()
    # flash forward and its two backward passes and the delta rule's pair
    # in every KDA layer, at the least
    losses, text, n_calls = _next_token_fit(net, p, "KimiLinear", 5)
    overflow = {k: float(s["mlp"]["overflow"])
                for k, s in net.train_state.model_state.items()}
    assert overflow and not any(overflow.values()), \
        f"KimiLinear: assignments beyond the experts' buffer {overflow}"
    if p.expect_mosaic:
        for kernel in ("flash_attention_fwd", "chunk_kda_fwd", "chunk_kda_bwd"):
            assert kernel in text, \
                f"KimiLinear train step: {kernel} was routed around"
    _log(f"  KimiLinear train step: mosaic_calls={n_calls}")
    return {"steps": len(losses), "first_loss": losses[0],
            "last_loss": losses[-1], "mosaic_calls": n_calls,
            "chunk_kda": delta_rule}


@_recomputing
def check_glm_moe_lite(p: Preset) -> Dict[str, Any]:
    """A few steps of a small ``GlmMoeLite`` through ``fit`` with its
    multi-token-prediction loss: rotary latent attention through the flash
    kernel at a q.k head of 192 + 64 against a v head of 256 in all four
    blocks (the prediction layer's among them), held to: finite falling
    loss, both terms finite and summing to it, nothing compiled after
    warm-up, the kernel in the compiled step, no assignment left out."""
    from deeplearning4j_tpu.zoo import GlmMoeLite

    zoo = GlmMoeLite.tiny(**p.glm)
    net = zoo.init()
    # flash forward and its two backward passes in each of the four blocks
    losses, text, n_calls = _next_token_fit(net, p, "GlmMoeLite", 12)
    state = net.train_state.model_state
    counters = [s["mlp"] for s in state.values() if "mlp" in s] + \
               [s["block"]["mlp"] for s in state.values() if "block" in s]
    overflow = [float(c["overflow"]) for c in counters]
    assert len(overflow) == 3 and not any(overflow), \
        f"GlmMoeLite: assignments beyond the experts' buffer {overflow}"
    main, = (float(s["main_loss"]) for s in state.values() if "main_loss" in s)
    mtp, = (float(s["mtp_loss"]) for s in state.values() if "mtp_loss" in s)
    assert np.isfinite(main) and np.isfinite(mtp) and main > 0 and mtp > 0, \
        f"GlmMoeLite: loss terms {main}, {mtp}"
    total = main + zoo.mtp_weight * mtp
    assert abs(total - losses[-1]) <= 0.02 * losses[-1], \
        f"GlmMoeLite: {main} + {zoo.mtp_weight} x {mtp} is not the last loss {losses[-1]}"
    if p.expect_mosaic:
        assert "flash_attention_fwd" in text, \
            "GlmMoeLite train step: flash_attention_fwd was routed around"
    _log(f"  GlmMoeLite train step: mosaic_calls={n_calls} "
         f"main_loss={main:.4f} mtp_loss={mtp:.4f}")
    return {"steps": len(losses), "first_loss": losses[0],
            "last_loss": losses[-1], "main_loss": main, "mtp_loss": mtp,
            "mosaic_calls": n_calls}


@_recomputing
def check_sdar_moe(p: Preset) -> Dict[str, Any]:
    """A few steps of ``SdarMoe`` through ``fit`` on one row ``[xt ; x0]``
    whose blocks mask 1..B positions, equally many blocks each: attention
    through the block-diffusion flash kernels with 8 query heads a key/value
    head, held to: finite falling loss, nothing compiled after warm-up, no
    AOT fallback, the kernels in the compiled step and no ``scores`` op, no
    assignment left out, the head's count of masked positions.

    The weights take the scales of the benchmark's cell (PERF.md section 6,
    PR 38): this share holds 8 of 128 experts, and under the zoo's own
    N(0, 0.02) an attention-only residual stream collapses onto one direction,
    every position routes alike and the buffer overflows (15,621 assignments
    in one layer, 49 in another, on the chip). So the vocabulary's embedding
    rows are scaled to a deviation of 4, the MASK row stays, the per-head
    norms' gains are 1.5, and the rate is a fine-tuning rate."""
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.train.listeners import CollectScoresListener
    from deeplearning4j_tpu.train.updaters import Adam
    from deeplearning4j_tpu.zoo import SdarMoe

    zoo = SdarMoe(updater=Adam(1e-5, beta2=0.95), **p.sdar)
    net = zoo.init()
    scores = CollectScoresListener()
    net.set_listeners(scores)
    t, block, mask_id = p.sdar_seq, zoo.block_length, zoo.vocab_size - 1
    params = dict(net.train_state.params)
    table = params["layer_0"]["W"]
    rows = jnp.full((zoo.vocab_size, 1), 4.0 / jnp.std(table)).at[mask_id].set(1.0)
    params["layer_0"] = {"W": rows * table}
    for key, block_params in params.items():
        if "mixer" in block_params:
            mixer = dict(block_params["mixer"], q_norm=1.5 * block_params["mixer"]["q_norm"],
                         k_norm=1.5 * block_params["mixer"]["k_norm"])
            params[key] = dict(block_params, mixer=mixer)
    net.set_params(params)
    rng = np.random.default_rng(0)
    clean = rng.integers(0, mask_id, (1, t), dtype=np.int32)
    per_block = rng.permutation(np.repeat(np.arange(1, block + 1), t // block // block))
    masked = (rng.random((t // block, block)).argsort(-1).argsort(-1) < per_block[:, None]).reshape(1, t)
    batch = DataSet(np.concatenate([np.where(masked, mask_id, clean), clean], 1).astype(np.int32),
                    np.where(masked, clean, -1).astype(np.int32))
    fallbacks = _cache_stats()["aot_fallbacks"]
    losses = _fit_counted(net.fit, [batch], [batch], 1, 6, scores, "SdarMoe fit")
    assert _cache_stats()["aot_fallbacks"] == fallbacks, "SdarMoe: an AOT executable refused its arguments"
    assert _on_platform(net.train_state, p.platform), "SdarMoe train state is not on the device"
    state = net.train_state.model_state
    overflow = [float(s["mlp"]["overflow"]) for s in state.values() if "mlp" in s]
    assigned = [float(jnp.sum(s["mlp"]["assigned"])) for s in state.values() if "mlp" in s]
    assert len(overflow) == len(zoo.layers_here) and not any(overflow), \
        f"SdarMoe: assignments beyond the experts' buffer {overflow} (assigned {assigned})"
    head, = (s for s in state.values() if "masked_positions" in s)
    want = t // block * (block + 1) // 2
    assert float(head["masked_positions"]) == want, f"SdarMoe: {head['masked_positions']} masked positions, not {want}"
    assert abs(float(head["diffusion_loss"]) - losses[-1]) <= 0.02 * losses[-1]
    step, packer = net._jitted_packed()
    compiled = step.lower(packer.pack_device(net.train_state), jnp.asarray(batch.features),
                          jnp.asarray(batch.labels), jax.random.PRNGKey(0), None, None).compile()
    text = compiled.as_text()
    # forward and two backward passes in every block
    n_calls = _mosaic_calls(compiled, p, 3 * len(zoo.layers_here), "SdarMoe train step")
    if p.expect_mosaic:
        for kernel in ("bd_flash_attention_fwd", "bd_flash_attention_bwd_dq", "bd_flash_attention_bwd_dkv"):
            assert kernel in text, f"SdarMoe train step: {kernel} was routed around"
        assert "/scores/" not in text and "/softmax/" not in text, "SdarMoe train step: the XLA attention ran"
    _log(f"  SdarMoe train step: mosaic_calls={n_calls} assigned a layer {assigned}")
    return {"steps": len(losses), "first_loss": losses[0], "last_loss": losses[-1], "mosaic_calls": n_calls,
            "assigned": assigned, "masked_positions": float(head["masked_positions"])}


# ----------------------------------------------------------- phase 4: server
def _pad_rows(x: np.ndarray, bucket: int) -> np.ndarray:
    return np.concatenate(
        [x, np.zeros((bucket - x.shape[0],) + x.shape[1:], x.dtype)], axis=0)


def _http(port: int, path: str, body=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=None if body is None else json.dumps(body).encode())
    with urllib.request.urlopen(req, timeout=120) as resp:
        return resp.status, resp.read()


def check_bert_serve(p: Preset, net, workdir: str) -> Dict[str, Any]:
    from deeplearning4j_tpu.models.serializer import ModelSerializer
    from deeplearning4j_tpu.serving import ModelRegistry, ModelServer

    n_dev = len(jax.devices())
    archive = os.path.join(workdir, "bert.zip")
    ModelSerializer.write_model(net, archive, save_updater=False)
    rng = np.random.default_rng(1)
    xs = [rng.integers(0, p.bert["vocab_size"], (n, p.seq)).astype(np.int32)
          for n in p.serve_rows * n_dev]
    registry = ModelRegistry()
    server = ModelServer(registry)
    try:
        served = registry.load("bert", archive, warmup_example=xs[0][:1],
                               max_batch_size=p.max_batch_size,
                               replicas=n_dev)
        batcher = served.batcher
        assert batcher.replica_count == n_dev, batcher.replica_count
        for r in batcher._pool.replicas:
            assert r.device.platform == p.platform and all(
                leaf.devices() == {r.device}
                for leaf in jax.tree.leaves(r.params)), \
                f"replica {r.index} parameters are not on {r.device}"
        port = server.start(0)
        status, _ = _http(port, "/healthz")
        assert status == 200, f"/healthz -> {status}"
        compiles = batcher.compile_count()
        warm = _compile_requests()
        answers = []
        for x in xs:  # one at a time: round-robin walks every replica
            status, raw = _http(port, "/v1/models/bert/predict",
                                {"inputs": x.tolist(), "dtype": "int32"})
            assert status == 200, f"predict -> {status}: {raw[:200]!r}"
            answers.append(np.asarray(json.loads(raw)["outputs"], np.float32))
        assert batcher.compile_count() == compiles, \
            "the batcher compiled on traffic"
        assert _compile_requests() == warm, \
            f"{_compile_requests() - warm} compile(s) in the request phase"
        status, metrics = _http(port, "/metrics")
        assert status == 200 and b"compile_cache_hits_total" in metrics
        replica_batches = served.metrics.snapshot()["replica_batches"]
        counts = [replica_batches.get(r.index, 0)
                  for r in batcher._pool.replicas]
        assert all(c > 0 for c in counts), \
            f"a replica served nothing: {replica_batches}"
        buckets = list(batcher.buckets)
    finally:
        server.stop(shutdown_registry=True)

    # the invariant tests/test_serving.py pins: every answer is bit-equal
    # to model.output at one of the buckets that could have served it
    ref = ModelSerializer.restore_multi_layer_network(archive,
                                                      load_updater=False)
    for x, got in zip(xs, answers):
        n = x.shape[0]
        assert got.shape == (n, 2) and np.all(np.isfinite(got)), got
        assert any(np.array_equal(
            got, np.asarray(ref.output(_pad_rows(x, bk)), np.float32)[:n])
            for bk in buckets if bk >= n), \
            f"{n}-row answer is bit-equal at no candidate bucket"
    _log(f"  server: {len(answers)} requests answered, replicas={n_dev}, "
         f"replica_batches={counts}, buckets={buckets}")
    return {"requests": len(answers), "replicas": n_dev,
            "replica_batches": counts, "buckets": buckets,
            "compiles_on_traffic": 0}


# ------------------------------------------------------- phase 5: four chips
def check_four_chips(p: Preset) -> Dict[str, Any]:
    """Data-parallel fit of the same BERT over 4 devices at global batch
    4 x ``p.batch``: every device holds a shard of every batch and a
    non-trivial share of memory."""
    from deeplearning4j_tpu.parallel.sharding import shard_batch
    from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper
    from deeplearning4j_tpu.train.listeners import CollectScoresListener
    from deeplearning4j_tpu.zoo import Bert

    devs = jax.devices()[:4]
    net = Bert(**p.bert).init()
    scores = CollectScoresListener()
    net.set_listeners(scores)
    pw = ParallelWrapper.builder(net).workers(4).build()
    gb = 4 * p.batch
    batches = _bert_batches(p, p.train_batches, gb, seed=2)
    losses = _bert_fit(pw.fit, batches, gb, p, scores, "ParallelWrapper fit")
    ds = batches[0]
    for a in shard_batch(pw.strategy, ds.features, ds.labels,
                         ds.features_mask, None)[:3]:
        shards = {s.device: s.data.shape[0] for s in a.addressable_shards}
        assert shards == {d: p.batch for d in devs}, \
            f"batch is not split over the four devices: {shards}"
    for leaf in jax.tree.leaves(net.train_state.params):
        assert leaf.devices() == set(devs), \
            f"a parameter lives on {leaf.devices()}, not on all four"
    param_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(net.train_state.params))
    in_use = []
    for d in devs:
        stats = d.memory_stats()
        if stats is not None:  # the CPU rehearsal reports none
            assert stats["bytes_in_use"] >= param_bytes, \
                f"{d} holds {stats['bytes_in_use']} bytes, less than the " \
                f"{param_bytes} bytes of one parameter copy"
            in_use.append(int(stats["bytes_in_use"]))
    _log(f"  four chips: steps={len(losses)} loss {losses[0]:.4f} -> "
         f"{losses[-1]:.4f} bytes_in_use={in_use}")
    return {"devices": [str(d) for d in devs], "steps": len(losses),
            "first_loss": losses[0], "last_loss": losses[-1],
            "bytes_in_use": in_use}


# ---------------------------------------------------------------------- main
def run(p: Preset, report: Dict[str, Any], workdir: str) -> None:
    """Every phase in order. Any failure propagates."""
    import gc

    from deeplearning4j_tpu.runtime.environment import get_environment

    get_environment().allow_bfloat16()
    # the counters are the process's: this run answers for their change
    before = _cache_stats()
    with _phase(report, "kernels"):
        report["kernels"] = check_kernels(p)
    gc.collect()
    with _phase(report, "char_rnn"):
        report["char_rnn"] = check_char_rnn(p)
    gc.collect()
    with _phase(report, "bert_train"):
        net, report["bert_train"] = check_bert_train(p)
    with _phase(report, "bert_serve"):
        report["bert_serve"] = check_bert_serve(p, net, workdir)
    del net
    gc.collect()
    with _phase(report, "kimi_linear"):
        report["kimi_linear"] = check_kimi_linear(p)
    gc.collect()
    with _phase(report, "glm_moe_lite"):
        report["glm_moe_lite"] = check_glm_moe_lite(p)
    gc.collect()
    if len(jax.devices()) >= 4:
        with _phase(report, "four_chips"):
            report["four_chips"] = check_four_chips(p)
    else:
        _log(f"four-chip leg SKIPPED: {len(jax.devices())} device(s)")
        report["four_chips"] = "skipped: fewer than 4 devices"
    stats = _cache_stats()
    for counter in ("aot_fallbacks", "corrupt_entries"):
        stats[counter] -= before[counter]
    assert stats["aot_fallbacks"] == 0, \
        f"aot_fallbacks={stats['aot_fallbacks']}: an AOT executable " \
        f"refused its arguments and the jit path ran instead"
    assert stats["corrupt_entries"] == 0, \
        f"{stats['corrupt_entries']} unreadable compile-cache entries"
    report["compile_cache"] = stats


def main() -> int:
    t0 = time.perf_counter()
    if os.environ.get("DL4J_TPU_PALLAS_INTERPRET"):
        print("chip_smoke: DL4J_TPU_PALLAS_INTERPRET is set — the smoke "
              "runs compiled kernels only", file=sys.stderr)
        return 2
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    _log(f"jax {jax.__version__} platform={dev.platform} "
         f"device_kind={dev.device_kind!r} devices={len(jax.devices())}")
    if dev.platform != "tpu":
        print(f"chip_smoke: no accelerator — JAX found platform "
              f"{dev.platform!r}, need 'tpu'", file=sys.stderr)
        return 2
    if dev.device_kind not in DEVICE_KINDS:
        print(f"chip_smoke: device_kind {dev.device_kind!r} is not in the "
              f"table {sorted(DEVICE_KINDS)}", file=sys.stderr)
        return 2

    from deeplearning4j_tpu.runtime import compile_cache
    cache_dir = compile_cache.enable()
    _log(f"compile cache at {cache_dir}")
    report: Dict[str, Any] = {"jax": jax.__version__, "device": device,
                              "cache_dir": cache_dir, "phases": {}}
    os.makedirs(OUT_DIR, exist_ok=True)
    with tempfile.TemporaryDirectory(dir=OUT_DIR) as workdir:
        run(Preset(), report, workdir)
    import gc
    gc.collect()
    with _phase(report, "sdar_moe"):
        report["sdar_moe"] = check_sdar_moe(Preset())
    report["smoke_wall_s"] = round(time.perf_counter() - t0, 2)
    with open(os.path.join(OUT_DIR, "chip_smoke.json"), "w") as f:
        json.dump(report, f, indent=2, default=str)
    s = report["compile_cache"]
    _log(f"aot_fallbacks={s['aot_fallbacks']} cache hits={s['hits']} "
         f"misses={s['misses']} compile_s={s['compile_seconds']} "
         f"wall_s={report['smoke_wall_s']}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
