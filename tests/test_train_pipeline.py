"""Overlapped training pipeline (ISSUE 4): sharded device prefetch, async
loss readback, step-time profiler — trajectory must stay bit-identical to
the synchronous loop, listeners must observe identical ordered callbacks,
and every background stage must die with the fit that started it."""

import threading
import time

import numpy as np
import pytest

from deeplearning4j_tpu.data import NumpyDataSetIterator
from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.data.iterators import (AsyncDataSetIterator,
                                               ListDataSetIterator)
from deeplearning4j_tpu.models import MultiLayerNetwork
from deeplearning4j_tpu.nn import (DenseLayer, InputType,
                                   NeuralNetConfiguration, OutputLayer)
from deeplearning4j_tpu.parallel import ParallelWrapper
from deeplearning4j_tpu.runtime.chaos import ChaosController, ChaosError, FailNth
from deeplearning4j_tpu.runtime.environment import get_environment
from deeplearning4j_tpu.train import (Adam, CollectScoresListener,
                                      PerformanceListener, Sgd,
                                      TrainingListener, TrainingProfiler)


def _conf(seed=7):
    return (NeuralNetConfiguration.builder()
            .seed(seed)
            .updater(Sgd(0.1))
            .list()
            .layer(DenseLayer(n_out=16, activation="tanh"))
            .layer(OutputLayer(n_out=4, activation="softmax"))
            .set_input_type(InputType.feed_forward(8))
            .build())


def _data(n=64, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(0, 1, (n, 8)).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, n)]
    return x, y


def _params(net):
    return np.asarray(net.params()["layer_0"]["W"])


class _OrderListener(TrainingListener):
    """Records every callback with its arguments; deliberately slow in
    iteration_done so an ordering bug in the completion path would show."""

    needs_model_state = False

    def __init__(self):
        self.events = []

    def iteration_done(self, model, iteration, epoch, score):
        time.sleep(0.002)
        self.events.append(("iter", iteration, epoch, float(score)))

    def on_epoch_start(self, model, epoch):
        self.events.append(("start", epoch))

    def on_epoch_end(self, model, epoch):
        self.events.append(("end", epoch))


# --------------------------------------------------------- bit-identity
def test_mln_prefetched_fit_bit_identical():
    """MLN fit with DevicePrefetcher + async readback reproduces the
    synchronous loop's loss trajectory and final params EXACTLY."""
    x, y = _data()
    cs, cp = CollectScoresListener(), CollectScoresListener()

    ns = MultiLayerNetwork(_conf()).init()
    ns.set_listeners(cs)
    ns.fit(NumpyDataSetIterator(x, y, batch_size=16), epochs=3)

    prof = TrainingProfiler()
    np_ = MultiLayerNetwork(_conf()).init()
    np_.set_listeners(cp)
    np_.fit(NumpyDataSetIterator(x, y, batch_size=16), epochs=3,
            prefetch_buffer=3, profiler=prof)

    assert cs.scores == cp.scores  # float-exact trajectory
    assert (_params(ns) == _params(np_)).all()
    r = prof.report()
    assert r["iterations"] == 12
    assert 0.0 <= r["data_wait_fraction"] <= 1.0


def test_parallel_wrapper_prefetched_fit_bit_identical():
    """ParallelWrapper with the sharded device prefetch (builder knob) and
    async completion matches its own synchronous feed path bit-for-bit."""
    x, y = _data()
    n0 = MultiLayerNetwork(_conf()).init()
    (ParallelWrapper.builder(n0).strategy("data_parallel")
     .prefetch_buffer(0).build()
     .fit(NumpyDataSetIterator(x, y, batch_size=32), epochs=3))

    n2 = MultiLayerNetwork(_conf()).init()
    prof = TrainingProfiler()
    (ParallelWrapper.builder(n2).strategy("data_parallel")
     .prefetch_buffer(3).build()
     .fit(NumpyDataSetIterator(x, y, batch_size=32), epochs=3,
          profiler=prof))

    assert (_params(n0) == _params(n2)).all()
    assert prof.report()["iterations"] == 6


def test_parallel_wrapper_unrolled_dispatch_bit_identical():
    """env.dispatch_unroll > 1 routes ParallelWrapper through the unrolled
    SHARDED step (make_unrolled_step) — same trajectory as single steps."""
    x, y = _data()
    n1 = MultiLayerNetwork(_conf()).init()
    ParallelWrapper.builder(n1).build().fit(
        NumpyDataSetIterator(x, y, batch_size=32), epochs=4)

    env = get_environment()
    env.set_dispatch_unroll(2)
    try:
        n2 = MultiLayerNetwork(_conf()).init()
        ParallelWrapper.builder(n2).build().fit(
            NumpyDataSetIterator(x, y, batch_size=32), epochs=4)
    finally:
        env.set_dispatch_unroll(1)
    assert (_params(n1) == _params(n2)).all()


def test_parallel_wrapper_composes_with_async_dataset_iterator():
    """Two-stage feed: AsyncDataSetIterator (host ETL) under the
    DevicePrefetcher (device staging) — still bit-identical."""
    x, y = _data()
    n1 = MultiLayerNetwork(_conf()).init()
    (ParallelWrapper.builder(n1).prefetch_buffer(0).build()
     .fit(NumpyDataSetIterator(x, y, batch_size=32), epochs=3))

    n2 = MultiLayerNetwork(_conf()).init()
    ait = AsyncDataSetIterator(
        NumpyDataSetIterator(x, y, batch_size=32), queue_size=2)
    try:
        (ParallelWrapper.builder(n2).prefetch_buffer(2).build()
         .fit(ait, epochs=3))
    finally:
        ait.close()
    assert (_params(n1) == _params(n2)).all()


def test_computation_graph_prefetched_fit_bit_identical():
    from deeplearning4j_tpu.models.computation_graph import ComputationGraph

    def conf():
        return (NeuralNetConfiguration.builder().seed(5).updater(Adam(1e-2))
                .graph_builder()
                .add_inputs("in")
                .add_layer("h", DenseLayer(n_out=32, activation="relu"), "in")
                .add_layer("out", OutputLayer(n_out=3, activation="softmax",
                                              loss="mcxent"), "h")
                .set_outputs("out")
                .set_input_types(InputType.feed_forward(12))
                .build())

    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 12)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 64)]
    cs, cp = CollectScoresListener(), CollectScoresListener()

    g1 = ComputationGraph(conf()).init()
    g1.set_listeners(cs)
    g1.fit(NumpyDataSetIterator(x, y, batch_size=16), epochs=3)
    g2 = ComputationGraph(conf()).init()
    g2.set_listeners(cp)
    g2.fit(NumpyDataSetIterator(x, y, batch_size=16), epochs=3,
           prefetch_buffer=2)

    assert cs.scores == cp.scores
    assert (np.asarray(g1.params()["h"]["W"])
            == np.asarray(g2.params()["h"]["W"])).all()


# ---------------------------------------------------------- the one loop
class _RecordingListener(PerformanceListener):
    """A PerformanceListener that writes down what the loop tells it."""

    def __init__(self):
        super().__init__()
        self.events = []

    def record_batch(self, n_examples):
        self.events.append(("batch", n_examples))

    def iteration_done(self, model, iteration, epoch, score):
        self.events.append(("iter", iteration, epoch, float(score)))

    def on_epoch_start(self, model, epoch):
        self.events.append(("start", epoch))

    def on_epoch_end(self, model, epoch):
        self.events.append(("end", epoch))


def _graph_conf(seed=7):
    """_conf() as a linear ComputationGraph: same node keys, same draws."""
    return (NeuralNetConfiguration.builder().seed(seed).updater(Sgd(0.1))
            .graph_builder().add_inputs("in")
            .add_layer("layer_0", DenseLayer(n_out=16, activation="tanh"), "in")
            .add_layer("layer_1", OutputLayer(n_out=4, activation="softmax"),
                       "layer_0")
            .set_outputs("layer_1")
            .set_input_types(InputType.feed_forward(8)).build())


def _one_loop_fit(path, prefetch=2):
    """(network, fit) for one of the three ways into run_fit. The wrapper
    prefetches by default, and a fault then surfaces ahead of the batches
    staged behind it: ``prefetch=0`` makes where it stops exact."""
    from deeplearning4j_tpu.models.computation_graph import ComputationGraph
    if path == "graph":
        net = ComputationGraph(_graph_conf()).init()
        return net, net.fit
    net = MultiLayerNetwork(_conf()).init()
    if path == "wrapper":
        return net, (ParallelWrapper.builder(net).workers(1)
                     .prefetch_buffer(prefetch).build().fit)
    return net, net.fit


class _BreaksAfter(ListDataSetIterator):
    """Yields ``good`` batches, then raises."""

    def __init__(self, datasets, good):
        super().__init__(datasets)
        self._good = good

    def next(self):
        if self._pos == self._good:
            raise RuntimeError("iterator boom")
        return super().next()


@pytest.mark.parametrize("path", ["mln", "graph", "wrapper"])
def test_one_loop_serves_both_engines_and_the_wrapper(path):
    """The same tiny model and batches through MultiLayerNetwork.fit, the
    equivalent linear ComputationGraph.fit and ParallelWrapper.fit on a
    one-device mesh: the same losses, the same listener calls in the same
    order (``record_batch(n)`` before each ``iteration_done``), and a
    ``train_state`` that holds every completed step after the iterator
    raises mid-epoch."""
    x, y = _data(n=56)  # 3 batches of 16 and one of 8
    want_net = MultiLayerNetwork(_conf()).init()
    want = _RecordingListener()
    want_net.set_listeners(want)
    want_net.fit(NumpyDataSetIterator(x, y, batch_size=16), epochs=2)
    assert [e[1] for e in want.events if e[0] == "batch"] == [16, 16, 16, 8] * 2

    net, fit = _one_loop_fit(path)
    got = _RecordingListener()
    net.set_listeners(got)
    fit(NumpyDataSetIterator(x, y, batch_size=16), epochs=2)
    assert got.events == want.events
    assert (_params(net) == _params(want_net)).all()

    net, fit = _one_loop_fit(path, prefetch=0)
    net.set_listeners(_RecordingListener())
    batches = [DataSet(x[i:i + 16], y[i:i + 16]) for i in (0, 16, 32)]
    with pytest.raises(RuntimeError, match="iterator boom"):
        fit(_BreaksAfter(batches, good=2), epochs=1)
    assert int(net.train_state.step) == 2 and net.iteration == 2
    ref = MultiLayerNetwork(_conf()).init()
    ref.fit(ListDataSetIterator(batches[:2]), epochs=1)
    assert (_params(net) == _params(ref)).all()


# ------------------------------------------------- async listener delivery
def test_listener_ordering_identical_under_async_readback():
    """Every callback (iteration_done / epoch start / epoch end), its
    arguments, and its ORDER must match the synchronous loop exactly, even
    with a slow listener that syncs on the score."""
    x, y = _data()
    ls, la = _OrderListener(), _OrderListener()

    ns = MultiLayerNetwork(_conf()).init()
    ns.set_listeners(ls)
    ns.fit(NumpyDataSetIterator(x, y, batch_size=16), epochs=2)

    na = MultiLayerNetwork(_conf()).init()
    na.set_listeners(la)
    na.fit(NumpyDataSetIterator(x, y, batch_size=16), epochs=2,
           prefetch_buffer=2)

    assert ls.events == la.events
    # sanity on the shape of the stream: start, 4 iters, end, per epoch
    assert ls.events[0] == ("start", 0)
    assert [e[0] for e in ls.events].count("iter") == 8


def test_listener_exception_propagates_from_async_delivery():
    """A listener raising on the completion thread must fail fit() (and
    leave no worker behind — covered by the conftest guard)."""

    class Boom(TrainingListener):
        needs_model_state = False

        def iteration_done(self, model, iteration, epoch, score):
            if iteration == 3:
                raise ValueError("listener boom")

    x, y = _data()
    net = MultiLayerNetwork(_conf()).init()
    net.set_listeners(Boom())
    with pytest.raises(ValueError, match="listener boom"):
        net.fit(NumpyDataSetIterator(x, y, batch_size=16), epochs=5,
                prefetch_buffer=2)


def test_stateful_listener_forces_synchronous_delivery():
    """A listener with needs_model_state=True must observe ITS iteration's
    post-step state — delivery happens before the next dispatch."""

    class StateReader(TrainingListener):
        needs_model_state = True  # default, explicit for the test

        def __init__(self):
            self.steps = []

        def iteration_done(self, model, iteration, epoch, score):
            self.steps.append(int(model.train_state.step))

    x, y = _data()
    net = MultiLayerNetwork(_conf()).init()
    sr = StateReader()
    net.set_listeners(sr)
    net.fit(NumpyDataSetIterator(x, y, batch_size=16), epochs=2,
            prefetch_buffer=2)
    assert sr.steps == list(range(1, 9))


# ------------------------------------------------------------ chaos drill
def test_chaos_prefetch_fetch_fails_fit_cleanly():
    """An injected train.prefetch.fetch fault must fail the fit with the
    chaos error (not a hang, not a swallowed stop) and leave no prefetch
    or delivery thread alive."""
    x, y = _data()
    net = MultiLayerNetwork(_conf()).init()
    with ChaosController(seed=3) as c:
        c.on("train.prefetch.fetch", FailNth(3))
        with pytest.raises(ChaosError, match="train.prefetch.fetch"):
            net.fit(NumpyDataSetIterator(x, y, batch_size=16), epochs=2,
                    prefetch_buffer=2)
        assert c.count("train.prefetch.fetch") == 3
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        stray = [t for t in threading.enumerate()
                 if t.name.startswith(("train-prefetch",
                                       "train-listener-delivery"))]
        if not stray:
            break
        time.sleep(0.05)
    assert not stray, f"hung pipeline threads: {[t.name for t in stray]}"


def test_chaos_prefetch_fetch_fails_parallel_wrapper_cleanly():
    x, y = _data()
    net = MultiLayerNetwork(_conf()).init()
    pw = ParallelWrapper.builder(net).prefetch_buffer(2).build()
    with ChaosController(seed=3) as c:
        c.on("train.prefetch.fetch", FailNth(2))
        with pytest.raises(ChaosError, match="train.prefetch.fetch"):
            pw.fit(NumpyDataSetIterator(x, y, batch_size=32), epochs=2)
    # the wrapper stays usable after the drill (fresh epoch, fresh worker)
    pw.fit(NumpyDataSetIterator(x, y, batch_size=32), epochs=1)
    assert np.isfinite(net.score())


# ------------------------------------------- AsyncDataSetIterator repairs
class _CountingIter(ListDataSetIterator):
    """Counts (and slows) base pulls so a drain-on-reset is measurable."""

    def __init__(self, datasets):
        super().__init__(datasets)
        self.pulls = 0

    def next(self):
        self.pulls += 1
        time.sleep(0.005)
        return super().next()


def _batches(n=16):
    x, y = _data(n * 4)
    return [DataSet(x[i * 4:(i + 1) * 4], y[i * 4:(i + 1) * 4])
            for i in range(n)]


def test_async_iterator_reset_stops_worker_without_draining_base():
    """reset() signals the stop event instead of pulling every remaining
    batch of the base iterator through the queue (the old reset paid the
    whole epoch's ETL to throw it away)."""
    base = _CountingIter(_batches(16))
    ait = AsyncDataSetIterator(base, queue_size=2)
    try:
        assert ait.has_next()
        ait.next()
        ait.next()
        pulled = base.pulls
        ait.reset()
        # worker restarted for the new pass; the OLD pass pulled at most
        # consumed + queue depth + 1 in-flight, nowhere near all 16
        assert base.pulls <= pulled + 4, \
            f"reset drained the base iterator ({base.pulls} pulls)"
        n = 0
        while ait.has_next():
            ait.next()
            n += 1
        assert n == 16  # fresh full pass after reset
    finally:
        ait.close()


def test_async_iterator_error_surfaces_before_buffered_batches():
    """A mid-stream worker fault surfaces on the NEXT has_next()/next(),
    discarding batches buffered behind it — not after the sentinel."""

    class FailingIter(ListDataSetIterator):
        def __init__(self, datasets, fail_at):
            super().__init__(datasets)
            self.fail_at = fail_at
            self.n = 0

        def next(self):
            self.n += 1
            if self.n == self.fail_at:
                raise RuntimeError("etl boom")
            return super().next()

    ait = AsyncDataSetIterator(FailingIter(_batches(16), fail_at=3),
                               queue_size=8)
    got = 0
    with pytest.raises(RuntimeError, match="etl boom"):
        # let the worker run ahead into the fault with batches buffered
        time.sleep(0.2)
        while ait.has_next():
            ait.next()
            got += 1
    assert got <= 2, f"error only surfaced after {got} buffered batches"
    # after the raise the iterator reports exhausted, and reset() recovers
    assert not ait.has_next()
    ait.close()


def test_async_iterator_close_is_idempotent_and_restartable():
    base = _CountingIter(_batches(8))
    ait = AsyncDataSetIterator(base, queue_size=2)
    assert ait.has_next()
    ait.close()
    ait.close()
    # reset after close starts a fresh pass
    n = 0
    while ait.has_next():
        ait.next()
        n += 1
    assert n == 8
    ait.close()


# ----------------------------------------------------------------- ISSUE 26
# Fit stages that tile the loop (train/profiler.py ``stage``).

def _wide_conf(seed=7):
    """Steps heavy enough (a few ms on the CPU) that the loop's own
    bookkeeping between stages is small beside them."""
    return (NeuralNetConfiguration.builder().seed(seed).updater(Sgd(0.1)).list()
            .layer(DenseLayer(n_out=1024, activation="tanh"))
            .layer(DenseLayer(n_out=1024, activation="tanh"))
            .layer(OutputLayer(n_out=4, activation="softmax"))
            .set_input_type(InputType.feed_forward(8)).build())


@pytest.mark.parametrize("prefetch", [0, 2])
def test_stages_tile_the_fit_loop(prefetch):
    x, y = _data(512)
    net = MultiLayerNetwork(_wide_conf()).init()
    net.fit(NumpyDataSetIterator(x, y, batch_size=64), epochs=1)  # compiles
    prof = TrainingProfiler()
    net.fit(NumpyDataSetIterator(x, y, batch_size=64), epochs=2,
            prefetch_buffer=prefetch, profiler=prof)
    r = prof.report()
    steps = 16
    assert r["iterations"] == steps
    with prof._lock:
        counts, totals = dict(prof._counts), dict(prof._totals)
    assert counts["dispatch"] == counts["rng"] == counts["h2d"] == counts["step"] == steps
    assert counts["drain"] == 2 and counts["sync"] == 1
    if prefetch:
        # the queue wait alone; the copy runs on the prefetch worker
        assert totals["data_wait"] == pytest.approx(totals["next_batch"])
        assert counts["next_batch"] == steps + 2  # and each epoch's end
    else:
        assert totals["data_wait"] == pytest.approx(totals["next_batch"] + totals["h2d"])
        assert counts["next_batch"] == counts["data_wait"] == steps
    tiled = sum(totals[s] for s in TrainingProfiler.TILE)
    assert r["fit_total_s"] == r["elapsed_s"] > 0
    assert r["unattributed_s"] == pytest.approx(r["fit_total_s"] - tiled, abs=2e-4)
    assert 0.0 <= r["unattributed_fraction"] < 0.25
    assert r["aot_compiles"] == 0 and r["aot_fallbacks"] == 0 and r["compile_in_fit_s"] == 0
    for stage in ("next_batch", "h2d", "rng", "drain", "sync"):
        assert {f"{stage}_total_s", f"{stage}_mean_ms"} <= set(r)
        assert f"{stage}_p99_ms" not in r


def test_profiler_reports_compiles_inside_fit():
    """``aot_compiles`` / ``compile_in_fit_s`` are the change of
    ``compile_cache.stats()`` over the fit call: a first fit compiles."""
    x, y = _data(32)
    prof = TrainingProfiler()
    MultiLayerNetwork(_conf()).init().fit(
        NumpyDataSetIterator(x, y, batch_size=16), profiler=prof)
    r = prof.report()
    assert r["aot_compiles"] == 1 and r["aot_fallbacks"] == 0
    assert r["compile_in_fit_s"] > 0


def test_without_a_profiler_no_stage_is_entered(monkeypatch):
    """``profiler=None`` costs one ``is None`` branch a site: no stage, no
    annotation."""
    import jax
    from deeplearning4j_tpu.models import ComputationGraph
    from deeplearning4j_tpu.train import profiler as train_profiler

    def boom(*a, **k):
        raise AssertionError("a stage was entered without a profiler")

    monkeypatch.setattr(train_profiler.TrainingProfiler, "stage", boom)
    monkeypatch.setattr(train_profiler, "_Stage", boom)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", boom)
    x, y = _data(32)
    it = lambda: NumpyDataSetIterator(x, y, batch_size=16)
    MultiLayerNetwork(_conf()).init().fit(it(), epochs=1)
    MultiLayerNetwork(_conf()).init().fit(it(), epochs=1, prefetch_buffer=2)
    ParallelWrapper.builder(MultiLayerNetwork(_conf()).init()).build().fit(it())
    g = (NeuralNetConfiguration.builder().seed(1).updater(Sgd(0.1))
         .graph_builder().add_inputs("in"))
    g.add_layer("d", DenseLayer(n_out=8, activation="tanh"), "in")
    g.add_layer("out", OutputLayer(n_out=4, activation="softmax"), "d")
    g.set_outputs("out")
    g.set_input_types(InputType.feed_forward(8))
    ComputationGraph(g.build()).init().fit(it())


def _reader(name):
    import importlib.util
    import pathlib
    path = pathlib.Path(__file__).resolve().parent.parent / "benchmark" / "readers" / f"{name}.py"
    spec = importlib.util.spec_from_file_location("reader_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


@pytest.mark.parametrize("name,report,want", [
    ("fit_unattributed_share.train", {"iterations": 232, "unattributed_fraction": 0.0123}, 1.23),
    ("h2d_ms.train", {"iterations": 232, "h2d_mean_ms": 0.31}, 0.31),
    ("fit_unattributed_share.train", {"iterations": 0, "unattributed_fraction": 0.0}, None),
    ("h2d_ms.train", {"iterations": 0, "h2d_mean_ms": 0.0}, None),
    # the parent's report has no such key: nothing to read, no raise
    ("fit_unattributed_share.train", {"iterations": 232, "data_wait_fraction": 0.01}, None),
    ("h2d_ms.train", {"iterations": 232, "dispatch_mean_ms": 1.4}, None),
    ("h2d_ms.train", None, None),
])
def test_stage_readers_of_the_benchmark(name, report, want):
    got = _reader(name)({"profiler": report}, None, None, None)
    assert got == (None if want is None else pytest.approx(want))
