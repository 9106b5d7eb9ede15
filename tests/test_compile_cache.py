"""Cold-start & dispatch fast-path tests (ISSUE 5): persistent executable
cache (hit/miss accounting, corrupt-entry fallback via the
``runtime.compile_cache.load`` chaos point), warmup-manifest recording and
replay (compiles on replay <= recorded pairs), and AOT-dispatch
bit-identity vs the jit path for MLN / ComputationGraph / sd.fit /
ParallelWrapper / the serving batcher.

All tier-1 (CPU, no ``slow`` marker); the cache tests use a tmp_path cache
directory and detach it on the way out so the rest of the suite is
unaffected.
"""

import glob
import json
import logging
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.data.iterators import ListDataSetIterator
from deeplearning4j_tpu.models import MultiLayerNetwork
from deeplearning4j_tpu.models.computation_graph import ComputationGraph
from deeplearning4j_tpu.nn import (DenseLayer, InputType,
                                   NeuralNetConfiguration, OutputLayer)
from deeplearning4j_tpu.runtime import chaos, compile_cache
from deeplearning4j_tpu.runtime.environment import get_environment
from deeplearning4j_tpu.serving import ContinuousBatcher, ModelRegistry
from deeplearning4j_tpu.serving.manifest import (WarmupManifest,
                                                 manifest_path)
from deeplearning4j_tpu.train import Sgd


# ------------------------------------------------------------ helpers
def _mln_conf(seed=7, n_in=8):
    return (NeuralNetConfiguration.builder().seed(seed).updater(Sgd(0.1))
            .list()
            .layer(DenseLayer(n_out=16, activation="tanh"))
            .layer(OutputLayer(n_out=4, activation="softmax"))
            .set_input_type(InputType.feed_forward(n_in))
            .build())


def _graph_conf(seed=5):
    return (NeuralNetConfiguration.builder().seed(seed).updater(Sgd(0.05))
            .graph_builder()
            .add_inputs("in")
            .add_layer("h", DenseLayer(n_out=12, activation="relu"), "in")
            .add_layer("out", OutputLayer(n_out=3, activation="softmax",
                                          loss="mcxent"), "h")
            .set_outputs("out")
            .set_input_types(InputType.feed_forward(6))
            .build())


def _iterator(n=24, n_in=8, n_out=4, batch=8, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(0, 1, (n, n_in)).astype(np.float32)
    y = np.eye(n_out, dtype=np.float32)[rng.integers(0, n_out, n)]
    return ListDataSetIterator([DataSet(x, y)], batch_size=batch)


def _probe_fn():
    """A fresh jit wrapper of the SAME program each call — forces the
    persistent-cache path (a new wrapper has no in-memory executable) with
    a stable cache key (same HLO)."""
    def cc_probe(x):
        return (x * 2.0 + 1.0) @ x.T
    return jax.jit(cc_probe)


@pytest.fixture
def cache_dir(tmp_path):
    d = compile_cache.enable(str(tmp_path / "executable-cache"))
    compile_cache.reset_stats()
    yield d
    compile_cache.disable()


@pytest.fixture
def aot_toggle():
    """Restore the process-wide AOT knob after a test flips it."""
    env = get_environment()
    before = env.aot_dispatch
    yield env
    env.aot_dispatch = before


# ----------------------------------------------------- persistent cache
def test_enable_uses_directory_as_given_and_counts_hits_and_misses(
        cache_dir, tmp_path):
    # no framework/version sub-directory: jax's key covers version+backend
    assert cache_dir == str(tmp_path / "executable-cache")
    assert jax.config.jax_compilation_cache_dir == cache_dir
    x = jnp.ones((32, 16))
    r1 = np.asarray(_probe_fn()(x))
    s1 = compile_cache.stats()
    assert s1["enabled"] and s1["misses"] >= 1
    assert glob.glob(cache_dir + "/*-cache"), "no entries persisted"
    hits_before = s1["hits"]
    r2 = np.asarray(_probe_fn()(x))  # same HLO, fresh wrapper -> cache hit
    s2 = compile_cache.stats()
    assert s2["hits"] > hits_before
    assert (r1 == r2).all(), "cached executable changed results"
    # the same counters ride the profiler facade and serving /metrics
    from deeplearning4j_tpu.runtime.profiler import compile_cache_stats
    assert compile_cache_stats()["hits"] == s2["hits"]


def test_corrupt_entry_falls_back_to_compile(cache_dir):
    x = jnp.ones((16, 8))
    r1 = np.asarray(_probe_fn()(x))
    for p in glob.glob(cache_dir + "/*-cache"):  # bit-rot every entry
        with open(p, "r+b") as f:
            f.write(b"\xff\x00garbage" * 4)
    r2 = np.asarray(_probe_fn()(x))
    s = compile_cache.stats()
    assert s["corrupt_entries"] >= 1, "corruption not detected/counted"
    assert (r1 == r2).all(), "fallback compile changed results"


def test_chaos_load_fault_falls_back_to_compile(cache_dir):
    x = jnp.ones((16, 8))
    r1 = np.asarray(_probe_fn()(x))  # populate the cache
    before = compile_cache.stats()["corrupt_entries"]
    with chaos.ChaosController(seed=3) as c:
        c.on("runtime.compile_cache.load", chaos.FailNth(1, every=True))
        r2 = np.asarray(_probe_fn()(x))
        assert c.count("runtime.compile_cache.load") >= 1
    assert compile_cache.stats()["corrupt_entries"] > before
    assert (r1 == r2).all(), "chaos fallback changed results"
    # controller gone: the next lookup is a clean hit again
    hits = compile_cache.stats()["hits"]
    np.asarray(_probe_fn()(x))
    assert compile_cache.stats()["hits"] > hits


def test_cached_compile_with_cache_errors_fatal(cache_dir):
    """A drift between our load-path wrapper and jax's signature must be
    an error, not the UserWarning jax downgrades it to — with
    ``jax_raise_persistent_cache_errors`` a read that raised would fail
    the compile instead of silently missing forever."""
    jax.config.update("jax_raise_persistent_cache_errors", True)
    try:
        x = jnp.ones((24, 8))
        r1 = np.asarray(_probe_fn()(x))
        r2 = np.asarray(_probe_fn()(x))
    finally:
        jax.config.update("jax_raise_persistent_cache_errors", False)
    s = compile_cache.stats()
    assert s["hits"] >= 1 and s["corrupt_entries"] == 0, s
    assert (r1 == r2).all()


# ------------------------------------------------- where the cache lives
_REPO = pathlib.Path(__file__).resolve().parents[1]


def test_resolve_dir_defaults_to_fixed_path_in_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    assert compile_cache.resolve_dir() == str(_REPO / ".jax_cache")
    assert compile_cache.resolve_dir() == compile_cache.resolve_dir()


def test_resolve_dir_honours_explicit_dir_only_without_env(
        monkeypatch, tmp_path, caplog):
    explicit, placed = str(tmp_path / "mine"), str(tmp_path / "outside")
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    assert compile_cache.resolve_dir(explicit) == explicit
    monkeypatch.setenv(compile_cache.ENV_VAR, placed)
    with caplog.at_level(logging.WARNING,
                         logger=compile_cache.logger.name):
        assert compile_cache.resolve_dir(explicit) == placed
    assert any("ignoring" in r.getMessage() for r in caplog.records)
    assert compile_cache.resolve_dir() == placed


def test_enable_refuses_env_var_set_after_jax_import(monkeypatch, tmp_path):
    """jax reads the variable at import; setting it later places nothing,
    and enable() says so rather than cache somewhere else."""
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path / "late"))
    with pytest.raises(RuntimeError, match="before jax is imported"):
        compile_cache.enable()
    assert not compile_cache.is_enabled()


_PLACED_FROM_OUTSIDE = """
import glob, os, sys
import jax, jax.numpy as jnp
real_update = jax.config.update
def guarded(name, value):
    assert name != "jax_compilation_cache_dir", "our code moved the cache"
    return real_update(name, value)
jax.config.update = guarded
from deeplearning4j_tpu.runtime import compile_cache
placed = os.environ["JAX_COMPILATION_CACHE_DIR"]
assert compile_cache.enable(sys.argv[1]) == placed      # explicit ignored
assert jax.config.jax_compilation_cache_dir == placed
def fresh():  # a new def per call: same HLO, no in-memory executable
    def probe(x):
        return (x * 3.0 + 1.0) @ x.T
    return jax.jit(probe)
fresh()(jnp.ones((8, 4))).block_until_ready()
fresh()(jnp.ones((8, 4))).block_until_ready()
compile_cache.disable()                                  # not ours to detach
assert jax.config.jax_compilation_cache_dir == placed
assert glob.glob(placed + "/*-cache"), os.listdir(placed)
assert not os.path.exists(sys.argv[1])
print("HITS", compile_cache.stats()["hits"])
"""


def test_cache_placed_from_outside_is_used_as_given(tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` set before start-up: entries land in
    exactly that directory, nothing calls ``jax.config.update`` on the
    cache dir, an explicit directory is ignored — and the cache hits."""
    placed = tmp_path / "placed"
    placed.mkdir()
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(placed),
               PYTHONPATH=str(_REPO) + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, "-c", _PLACED_FROM_OUTSIDE,
         str(tmp_path / "explicit")],
        env=env, capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert int(proc.stdout.split("HITS")[-1]) >= 1, proc.stdout


# ----------------------------------------------------------- AOT cache
def test_aot_cache_bit_identity_and_signature_fallback(aot_toggle):
    aot_toggle.set_aot_dispatch(True)
    fitted = jax.jit(lambda s, x: (s + 1.0, (s @ x.T).sum()))
    s0 = jnp.full((4, 8), 2.0)
    x16 = jnp.ones((16, 8))
    aot = compile_cache.AotCache("test")
    got = aot.call("k16", fitted, s0, x16)
    ref = fitted(s0, x16)
    assert (np.asarray(got[0]) == np.asarray(ref[0])).all()
    assert float(got[1]) == float(ref[1])
    assert len(aot) == 1
    # a colliding key (different avals, same key) must fall back, not fail
    fb_before = compile_cache.stats()["aot_fallbacks"]
    x8 = jnp.ones((8, 8))
    got2 = aot.call("k16", fitted, s0, x8)
    assert float(got2[1]) == float(fitted(s0, x8)[1])
    assert compile_cache.stats()["aot_fallbacks"] > fb_before
    # knob off: no executables minted, jit path used
    aot_toggle.set_aot_dispatch(False)
    aot2 = compile_cache.AotCache("off")
    aot2.call("k", fitted, s0, x16)
    assert len(aot2) == 0


# ------------------------------------------------------------ manifests
def test_manifest_roundtrip_and_corrupt_tolerance(tmp_path):
    m = WarmupManifest.from_example(
        {"a": np.zeros((1, 3, 4), np.float32),
         "b": np.zeros((1, 2), np.int32)},
        buckets=[1, 2, 4], replicas=2,
        pairs=[(1, 0, "float32"), (1, 1, "float32")],
        max_batch_size=4, model="ComputationGraph")
    path = manifest_path(str(tmp_path / "model.zip"))
    m.save(path)
    back = WarmupManifest.load(path)
    assert back.buckets == [1, 2, 4] and back.replicas == 2
    assert back.max_batch_size == 4 and back.pairs == m.pairs
    ex = back.example(rows=4)
    assert ex["a"].shape == (4, 3, 4) and ex["a"].dtype == np.float32
    assert ex["b"].shape == (4, 2) and ex["b"].dtype == np.int32
    # corrupt manifest: load_for_archive degrades to None, never raises
    with open(path, "w") as f:
        f.write('{"format": "torn')
    assert WarmupManifest.load_for_archive(str(tmp_path / "model.zip")) is None
    assert WarmupManifest.load_for_archive(str(tmp_path / "no.zip")) is None


def test_registry_load_replays_manifest_compiles_bounded(tmp_path):
    archive = str(tmp_path / "model.zip")
    MultiLayerNetwork(_mln_conf()).init().save(archive)
    x = np.random.default_rng(0).normal(0, 1, (48, 8)).astype(np.float32)

    reg1 = ModelRegistry()
    served1 = reg1.load("m", archive, max_batch_size=8, batch_timeout_ms=1.0,
                        pipeline_depth=0,
                        warmup_example=x[:1])
    assert served1.metrics.snapshot()["warmup_seconds"] > 0
    base = np.asarray(served1.predict(x[:3]))
    oversized = np.asarray(served1.predict(x))  # 48 rows -> mints bucket 64
    minted_buckets = list(served1.batcher.buckets)
    assert 64 in minted_buckets
    reg1.shutdown()  # graceful: refreshes the manifest with the mint

    manifest = WarmupManifest.load(manifest_path(archive))
    assert manifest.buckets == minted_buckets
    assert manifest.max_batch_size == 8

    reg2 = ModelRegistry()
    served2 = reg2.load("m", archive, batch_timeout_ms=1.0, pipeline_depth=0)
    try:
        # replay: recorded buckets (incl. the traffic-minted 64) pre-warmed
        assert list(served2.batcher.buckets) == minted_buckets
        assert served2.batcher.max_batch_size == 8
        ready_compiles = served2.batcher.compile_count()
        assert ready_compiles <= len(manifest.pairs)
        # the restart serves the SAME traffic without minting a compile
        # and bit-identical to the recording process
        assert (np.asarray(served2.predict(x[:3])) == base).all()
        assert (np.asarray(served2.predict(x)) == oversized).all()
        assert served2.batcher.compile_count() == ready_compiles, \
            "manifest replay still compiled on live traffic"
    finally:
        reg2.shutdown()


def test_hot_swap_inherits_live_manifest(tmp_path):
    reg = ModelRegistry()
    x = np.random.default_rng(1).normal(0, 1, (40, 8)).astype(np.float32)
    reg.register("m", MultiLayerNetwork(_mln_conf()).init(),
                 max_batch_size=8, batch_timeout_ms=1.0, pipeline_depth=0,
                 warmup_example=x[:1])
    try:
        reg.predict("m", x)  # mints bucket 64 under live traffic
        v1_buckets = list(reg.get("m").batcher.buckets)
        assert 64 in v1_buckets
        # hot-swap with no explicit warmup: the replacement must inherit
        # the live bucket set, pre-warmed before it takes traffic
        served2 = reg.register("m", MultiLayerNetwork(_mln_conf(seed=9)).init())
        assert list(served2.batcher.buckets) == v1_buckets
        c0 = served2.batcher.compile_count()
        reg.predict("m", x)  # same oversized traffic: nothing new compiles
        assert served2.batcher.compile_count() == c0
    finally:
        reg.shutdown()


# -------------------------------------------- fast-path bit-identity
def _params_bytes(net):
    return b"".join(np.ascontiguousarray(np.asarray(l)).tobytes()
                    for l in jax.tree.leaves(net.train_state.params))


def _fit_mln(aot: bool, conf_fn=_mln_conf, **fit_kw):
    env = get_environment()
    before = env.aot_dispatch
    env.set_aot_dispatch(aot)
    try:
        net = MultiLayerNetwork(conf_fn()).init()
        net.fit(_iterator(), epochs=2, **fit_kw)
        return _params_bytes(net)
    finally:
        env.aot_dispatch = before


def test_mln_fit_fast_path_bit_identical_to_jit(aot_toggle):
    assert _fit_mln(True) == _fit_mln(False)
    assert compile_cache.stats()["aot_compiles"] > 0


def test_mln_fit_fast_path_bit_identical_grouped_dispatch(aot_toggle):
    env = get_environment()
    unroll = env.dispatch_unroll
    env.set_dispatch_unroll(2)
    try:
        assert _fit_mln(True) == _fit_mln(False)
    finally:
        env.dispatch_unroll = unroll


def test_cg_fit_fast_path_bit_identical_to_jit(aot_toggle):
    def fit(aot):
        env = get_environment()
        env.set_aot_dispatch(aot)
        net = ComputationGraph(_graph_conf()).init()
        rng = np.random.default_rng(3)
        x = rng.normal(0, 1, (24, 6)).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 24)]
        net.fit(ListDataSetIterator([DataSet(x, y)], batch_size=8), epochs=2)
        return _params_bytes(net)

    assert fit(True) == fit(False)


def test_sd_fit_fast_path_bit_identical_to_jit(aot_toggle):
    from deeplearning4j_tpu.autodiff.samediff import SameDiff, TrainingConfig

    def fit(aot):
        get_environment().set_aot_dispatch(aot)
        sd = SameDiff.create()
        x = sd.placeholder("x", (None, 6))
        w = sd.var("w", (6, 3))
        b = sd.var("b", (3,))
        logits = x @ w + b
        labels = sd.placeholder("labels", (None, 3))
        sd.loss.softmax_cross_entropy("loss", labels, logits)
        sd.set_loss_variables("loss")
        sd.set_training_config(TrainingConfig(
            updater=Sgd(0.1), data_set_feature_mapping=["x"],
            data_set_label_mapping=["labels"]))
        rng = np.random.default_rng(5)
        xs = rng.normal(0, 1, (24, 6)).astype(np.float32)
        ys = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 24)]
        hist = sd.fit(ListDataSetIterator([DataSet(xs, ys)], batch_size=8),
                      epochs=2)
        return (np.asarray(sd.arrays["w"]).tobytes(),
                np.asarray(sd.arrays["b"]).tobytes(),
                [float(v) for v in hist])

    w1, b1, h1 = fit(True)
    w2, b2, h2 = fit(False)
    assert w1 == w2 and b1 == b2 and h1 == h2


def test_parallel_wrapper_fast_path_bit_identical_to_jit(aot_toggle):
    from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper

    def fit(aot):
        get_environment().set_aot_dispatch(aot)
        net = MultiLayerNetwork(_mln_conf()).init()
        pw = ParallelWrapper.builder(net).workers(2).build()
        pw.fit(_iterator(n=32, batch=16), epochs=2)
        return _params_bytes(net)

    assert fit(True) == fit(False)


def test_batcher_fast_path_bit_identical_and_counted(aot_toggle):
    aot_toggle.set_aot_dispatch(True)
    net = MultiLayerNetwork(_mln_conf()).init()
    ref = MultiLayerNetwork(_mln_conf()).init()
    x = np.random.default_rng(2).normal(0, 1, (16, 8)).astype(np.float32)
    b = ContinuousBatcher(net, max_batch_size=16, batch_timeout_ms=1.0,
                          pipeline_depth=0, warmup_example=x[:1])
    try:
        assert b._pool.aot_count() == len(b.buckets)  # warmed through AOT
        assert b.compile_count() == len(b.buckets)
        for n in (1, 3, 8, 16):
            got = np.asarray(b.submit(x[:n]))
            bucket = min(bk for bk in b.buckets if bk >= n)
            pad = np.concatenate(
                [x[:n], np.zeros((bucket - n, 8), np.float32)])
            exp = np.asarray(ref.output(pad))[:n]
            assert (got == exp).all(), f"rows={n} not bit-identical"
        assert b.compile_count() == len(b.buckets)
    finally:
        b.shutdown()


def test_batcher_float64_request_mints_no_duplicate_executable(aot_toggle):
    """An f64 request (e.g. JSON via HTTP) lands on the SAME f32 program
    jit would canonicalize it onto — a raw-dtype AOT key would mint a
    duplicate executable and break the compiles <= buckets x replicas
    ledger (regression: examples/model_serving.py HTTP predict)."""
    aot_toggle.set_aot_dispatch(True)
    net = MultiLayerNetwork(_mln_conf()).init()
    x32 = np.random.default_rng(4).normal(0, 1, (4, 8)).astype(np.float32)
    b = ContinuousBatcher(net, max_batch_size=4, batch_timeout_ms=1.0,
                          pipeline_depth=0, warmup_example=x32[:1])
    try:
        warmed = b.compile_count()
        got64 = np.asarray(b.submit(x32[:2].astype(np.float64)))
        got32 = np.asarray(b.submit(x32[:2]))
        assert b.compile_count() == warmed, "f64 request minted a compile"
        assert (got64 == got32).all()
    finally:
        b.shutdown()


def test_parallel_wrapper_fsdp_sharding_drift_falls_back(aot_toggle):
    """FSDP state shardings evolve after the first step (XLA re-assigns
    replicated biases to sharded) — the AOT entry compiled at step 1 must
    fall back cleanly and re-lower, never crash the fit (regression:
    examples/model_sharding.py)."""
    from deeplearning4j_tpu.parallel.sharding import ShardingStrategy
    from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper
    from deeplearning4j_tpu.runtime.mesh import create_mesh

    def fit(aot):
        get_environment().set_aot_dispatch(aot)
        net = MultiLayerNetwork(_mln_conf()).init()
        pw = ParallelWrapper(net, ShardingStrategy.fsdp(create_mesh()))
        pw.fit(_iterator(n=32, batch=16), epochs=2)
        return _params_bytes(net)

    assert fit(True) == fit(False)


def test_metrics_render_warmup_and_compile_cache(tmp_path):
    from deeplearning4j_tpu.serving import ModelServer
    import urllib.request

    reg = ModelRegistry()
    x = np.zeros((1, 8), np.float32)
    reg.register("m", MultiLayerNetwork(_mln_conf()).init(),
                 max_batch_size=4, batch_timeout_ms=1.0, warmup_example=x)
    srv = ModelServer(reg)
    port = srv.start(0)
    try:
        text = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5).read().decode()
        assert 'serving_warmup_seconds{model="m"}' in text
        assert "compile_cache_hits_total" in text
        assert "compile_cache_corrupt_entries_total" in text
        assert "aot_dispatch_executables_total" in text
    finally:
        srv.stop(shutdown_registry=True)
