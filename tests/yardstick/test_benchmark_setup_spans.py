"""The three readers of set-up (``setup_trace_lower_s.train``,
``setup_compile_s.train``, ``setup_cache_misses.train``): what they read off a
run made by hand and off the program's own ``TrainingProfiler.report()``, and
which cells the manifest gives them. The GLM and SDAR cells keep the reader
sets their own yardstick tests pin.
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench  # noqa: E402

MANIFEST = bench.load_json(os.path.join(ROOT, "BENCHMARK.json"))
READERS = ("setup_trace_lower_s.train", "setup_compile_s.train", "setup_cache_misses.train")
CELLS = ["bert-base-ft-b32-s512", "kimi-linear-pt-b1-s8192"]


def _read(name, run):
    return bench.load_module("readers", name).read(run, None, None, None)


def test_the_readers_read_a_run_made_by_hand():
    setup = {"trace_seconds": 3.25, "lower_seconds": 11.5, "compile_seconds": 0.75,
             "retrieval_seconds": 2.125, "hits": 40, "misses": 3, "compiles": 3, "programs": {}}
    run = {"profiler": {"setup": setup}}
    assert _read("setup_trace_lower_s.train", run) == pytest.approx(14.75)
    assert _read("setup_compile_s.train", run) == pytest.approx(2.875)
    assert _read("setup_cache_misses.train", run) == 3
    # nothing to read returns nothing, never 0: an untraced run, a program without the record
    for empty in ({"profiler": None}, {"profiler": {"dispatch_mean_ms": 1.5}}, {"profiler": {"setup": None}}):
        assert [_read(name, empty) for name in READERS] == [None, None, None]


def test_the_manifest_gives_the_readers_exactly_two_cells():
    entries = {m["name"]: m for m in MANIFEST["per_layer"] if m["name"] in READERS}
    assert set(entries) == set(READERS)
    assert [m["name"] for m in MANIFEST["per_layer"][-3:]] == list(READERS)  # appended at the end
    for m in entries.values():
        assert m["workloads"] == CELLS and m["moves"] == "setup_s" and m["layer"] == "compile cache"
        assert m["better"] == "lower"
    assert {m["source"] for m in entries.values()} == {"program_span", "program_counter"}
    for cell in CELLS:
        assert set(READERS) <= {m["name"] for m in bench.resolve(cell).per_layer}
    pinned = {"mfu.train", "device_idle_share.train", "train_step_roofline", "dispatch_ms.train",
              "data_wait_share.train", "compiles_in_window.train", "fit_unattributed_share.train", "h2d_ms.train",
              "attention_share.train"}
    assert {m["name"] for m in bench.resolve("glm-4.7-flash-pt-b1-s8192").per_layer} == pinned | {
        "mla_share.train", "mtp_share.train"}
    assert {m["name"] for m in bench.resolve("sdar-30b-a3b-bd-b1-s4096").per_layer} == pinned | {
        "bd_flash_attention_roofline", "gqa_share.train", "expert_share.train", "diffusion_head_share.train",
        "held_assignments.train"}


def test_the_readers_read_the_programs_own_report():
    import numpy as np
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.data.iterators import ListDataSetIterator
    from deeplearning4j_tpu.models import MultiLayerNetwork
    from deeplearning4j_tpu.nn import DenseLayer, InputType, NeuralNetConfiguration, OutputLayer
    from deeplearning4j_tpu.runtime import compile_cache
    from deeplearning4j_tpu.train import Sgd, TrainingProfiler
    conf = (NeuralNetConfiguration.builder().seed(3).updater(Sgd(0.1)).list()
            .layer(DenseLayer(n_out=8, activation="tanh")).layer(OutputLayer(n_out=2, activation="softmax"))
            .set_input_type(InputType.feed_forward(4)).build())
    rng = np.random.default_rng(0)
    data = lambda: ListDataSetIterator([DataSet(rng.normal(size=(8, 4)).astype(np.float32),
                                                np.eye(2, dtype=np.float32)[rng.integers(0, 2, 8)])], batch_size=8)
    net = MultiLayerNetwork(conf).init()
    net.fit(data())  # set-up: the step is traced, lowered and compiled
    before = compile_cache.stats()
    profiler = TrainingProfiler()
    net.fit(data(), profiler=profiler)  # the window
    run = {"profiler": profiler.report()}
    assert _read("setup_trace_lower_s.train", run) == pytest.approx(
        before["trace_seconds"] + before["lower_seconds"], abs=1e-4)
    assert _read("setup_trace_lower_s.train", run) > 0
    assert _read("setup_compile_s.train", run) == pytest.approx(
        before["compile_seconds"] + before["retrieval_seconds"], abs=1e-4)
    assert _read("setup_cache_misses.train", run) == before["misses"]
