"""The benchmark's own tests (CPU, toy sizes passed in by the tests).

What they hold: ``run.py`` finds every file of every cell by name and the
manifest keeps to its contract; the FLOP functions agree with numbers
worked by hand at the published widths; the trace reduction reads busy,
idle and a gap's label off a small synthetic trace; a run without a listed
TPU fails rather than falls back; a toy run prints the contract's last
line with ``correct`` true; the control (the reference in fp8 put in the
program's place) and each fault a training cell can have come out as not
correct.
"""

import json
import os
import re
import subprocess
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import reference_train, trace_reduce  # noqa: E402
from benchmark import run as bench  # noqa: E402

MANIFEST = bench.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELLS = [w["name"] for w in MANIFEST["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")

TINY = {
    "bert": ({"family": "bert", "hidden_size": 32, "num_hidden_layers": 2, "num_attention_heads": 2,
              "intermediate_size": 64, "vocab_size": 100, "max_position_embeddings": 16,
              "type_vocab_size": 2, "layer_norm_eps": 1e-12, "initializer_range": 0.02,
              "hidden_dropout_prob": 0.0, "num_labels": 2,
              "optimizer": {"name": "adam", "lr": 2e-5, "b1": 0.9, "b2": 0.999, "eps": 1e-8},
              "precision": {"compute": "float32"}},
             {"runner": "train_fit", "batch": 8, "seq_len": 16, "valid_min": 4, "count": 4,
              "check_steps": 3, "workers": 1}),
    # 96 px and 16 rows: the last stage's batch norm still sees 144 values a
    # channel (with fewer, float32 and float64 gradients of the reference
    # itself differ by 3-4%); lr 0.001, since at 0.1 a toy net's loss goes
    # 2.6 -> 97 in one step and the later steps are chaos on either side
    "resnet50": ({"family": "resnet50", "image_size": 96, "num_classes": 10, "bn_eps": 1e-5,
                  "bn_decay": 0.9, "optimizer": {"name": "nesterov", "lr": 0.001, "momentum": 0.9},
                  "precision": {"compute": "float32"}},
                 {"runner": "train_fit", "batch": 16, "count": 3, "check_steps": 3, "workers": 1}),
}
# toy-size limits: float32 program against float32 reference on the CPU reads
# ~1e-6 for BERT (measured), the fp8 control 4e-4 / 2e-2 / 4e-2
TINY_LIMITS = {"bert": {"loss_gap": 1e-4, "grad_norm_gap": 5e-3, "delta_norm_gap": 5e-3},
               "resnet50": {"loss_gap": 0.5, "grad_norm_gap": 0.2, "delta_norm_gap": 0.5}}
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def tiny_cell(family, **traffic_overrides):
    config, traffic = TINY[family]
    return types.SimpleNamespace(
        name="tiny", chips=1, config=config, traffic=dict(traffic, **traffic_overrides),
        limits=TINY_LIMITS[family], family=bench.load_module("families", family),
        runner=bench.load_module("runners", "train_fit"),
        end_to_end=[m for m in MANIFEST["end_to_end"]], per_layer=[])


def tiny_ctx(cell, seed=5):
    return types.SimpleNamespace(config=cell.config, traffic=cell.traffic, family=cell.family, seed=seed)


# ---------------------------------------------------------------- manifest

def manifest_keeps_to_the_contract():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs", "workloads",
                             "end_to_end", "per_layer"}
    assert MANIFEST["command"][:2] == ["python3", "benchmark/run.py"]
    assert 1 <= MANIFEST["run_seconds"] <= 51
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    for m in MANIFEST["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for m in MANIFEST["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["moves"] in e2e and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= set(CELLS)
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer") for x in MANIFEST[k]]
    assert all(NAME.match(n) for n in names)
    assert len({w["name"] for w in MANIFEST["workloads"]}) == len(MANIFEST["workloads"])
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) <= max(1, len(CELLS) // 4)
    for c in MANIFEST["configs"]:
        assert any(c["file"].startswith(p + "/") for p in MANIFEST["paths"])
        assert any(w["config"] == c["name"] for w in MANIFEST["workloads"])


def run_finds_every_file_of_a_cell_by_its_name(name):
    cell = bench.resolve(name)
    for fn in ("build", "init_params", "batches", "flops_per_step", "least_bytes_per_step",
               "samples_per_step", "reference_loss"):
        assert callable(getattr(cell.family, fn))
    assert callable(cell.runner.run)
    assert cell.limits and all(limit > 0 for limit in cell.limits.values())
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s", "train_samples_per_s"}
    assert cell.per_layer
    for metric in cell.per_layer:
        assert callable(bench.load_module("readers", metric["name"]).read)
    config_name = next(w["config"] for w in MANIFEST["workloads"] if w["name"] == name)
    entry = next(c for c in MANIFEST["configs"] if c["name"] == config_name)
    assert set(entry["reduced"]) == set(cell.config["reduced"])


def an_unknown_cell_is_an_error():
    with pytest.raises(KeyError, match="no workload"):
        bench.resolve("no-such-cell")


# ------------------------------------------------------------------- FLOPs

def bert_flops_against_a_number_worked_by_hand():
    # per layer, forward, 16,384 tokens of width 768: QKVO 4 x 768^2 and the
    # FFN 2 x 768 x 3072 multiply-adds a token, plus QK^T and PV: 2 x 512 x 768
    # a token. x2 FLOPs, x12 layers, + the pooler, x3 for the backward pass.
    by_hand = 3 * (12 * (2 * 16384 * (4 * 768 ** 2 + 2 * 768 * 3072) + 2 * 16384 * 2 * 512 * 768)
                   + 2 * 32 * (768 ** 2 + 768 * 2))
    cell = bench.resolve("bert-base-ft-b32-s512")
    got = cell.family.flops_per_step(cell.config, cell.traffic)
    assert got == pytest.approx(by_hand, rel=1e-9) and got == pytest.approx(9.277e12, rel=1e-3)
    assert got == pytest.approx(9.43e12, rel=0.03)  # XLA's count for the compiled step (ISSUE 24)
    assert cell.family.n_params(cell.config) == pytest.approx(109.48e6, rel=1e-3)


def resnet50_flops_against_a_number_worked_by_hand():
    # the family, configuration and traffic files of the ResNet-50 cell that PERF.md section 7 keeps
    # for a later PR, which adds it with its limits file and its manifest entries
    config = bench.load_json(os.path.join(ROOT, "benchmark", "configs", "resnet50-v1.json"))
    cell = types.SimpleNamespace(config=config, family=bench.load_module("families", config["family"]),
                                 traffic=bench.load_json(os.path.join(ROOT, "benchmark", "traffic", "train-b256.json")))
    # stage 0's first bottleneck at 56 x 56 by hand: 1x1 64->64, 3x3 64->64,
    # 1x1 64->256 and the projected shortcut 1x1 64->256
    block = 2 * 56 * 56 * (64 * 64 + 9 * 64 * 64 + 64 * 256 + 64 * 256)
    from_plan = sum(2 * side * side * k * k * cin * cout
                    for name, _, k, cin, cout, side in cell.family.convs(cell.config)
                    if name.startswith("s0b0_"))
    assert from_plan == block
    per_image_forward = cell.family.flops_per_step(cell.config, cell.traffic) / 256 / 3
    assert per_image_forward == pytest.approx(2 * 3.86e9, rel=0.01)  # 3.86 G multiply-adds, v1
    assert cell.family.flops_per_step(cell.config, cell.traffic) == pytest.approx(5.83e12, rel=0.03)
    assert cell.family.n_params(cell.config) == pytest.approx(25.56e6, rel=1e-3)


# ------------------------------------------------------------------- trace

def trace_reduce_reads_busy_idle_and_a_gaps_label():
    ms = 1_000_000
    planes = [
        ("/device:TPU:0", [
            ("XLA Ops", [("fusion.1", 0, 10 * ms), ("fusion.2", 5 * ms, 10 * ms),  # overlap: 15 busy
                         ("copy.3", 40 * ms, 5 * ms), ("fusion.1", 50 * ms, 10 * ms)]),
            ("XLA Modules", [("jit_step(1)", 0, 15 * ms), ("jit_tiny(2)", 40 * ms, 5 * ms),
                             ("jit_step(1)", 50 * ms, 10 * ms)])]),
        ("/host:CPU", [("python3", [("feed", 16 * ms, 20 * ms), ("other", 0, 60 * ms), ("measure", 0, 0)])]),
    ]
    assert trace_reduce.reduce_planes([planes[0], ("/host:CPU", [])]) is None  # no mark, no window
    out = trace_reduce.reduce_planes(planes)
    assert out["busy_s"] == pytest.approx(0.030)
    assert out["program"] == "jit_step(1)" and out["program_runs"] == 2
    assert out["program_mean_s"] == pytest.approx(0.0125)
    assert out["device_ops"][0] == ["fusion.*x2", pytest.approx(0.030)]  # kinds first, then single ops
    assert ["fusion.1", pytest.approx(0.020)] in out["device_ops"]
    assert out["idle_gaps"] == [["feed", pytest.approx(0.025)], ["fit", pytest.approx(0.005)]]
    assert out["gap_hosts"][0]["host"][0] == ["other", pytest.approx(0.025)]
    assert trace_reduce.reduce_planes([("/host:CPU", [])]) is None
    # a ``measure`` mark on the host cuts off what came before it: the first program run counts
    # by its part after the mark, and only the whole run sets the mean
    planes[1][1][0][1][-1] = ("measure", 10 * ms, 0)
    cut = trace_reduce.reduce_planes(planes)
    assert cut["busy_s"] == pytest.approx(0.020)
    assert cut["program_runs"] == pytest.approx(1 + 5 / 15) and cut["program_mean_s"] == pytest.approx(0.010)
    assert cut["idle_gaps"][0] == ["feed", pytest.approx(0.025)]


# --------------------------------------------------------- no TPU, no number

def run_fails_on_a_cpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", CELLS[0],
                          "--seed", "1", "--seconds", "1", "--trace", "0"],
                         cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "not 'tpu'" in out.stderr
    assert not any(line.startswith("{") for line in out.stdout.splitlines())


def an_unknown_device_kind_is_an_error(monkeypatch):
    import jax
    fake = types.SimpleNamespace(platform="tpu", device_kind="TPU v9 imaginary")
    monkeypatch.setattr(jax, "devices", lambda: [fake])
    peaks = bench.load_json(os.path.join(ROOT, "benchmark", "peaks.json"))
    with pytest.raises(SystemExit, match="not in peaks.json"):
        bench.find_device(1, peaks)
    monkeypatch.setattr(jax, "devices", lambda: [types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")])
    with pytest.raises(SystemExit, match="asks for 4 chips"):
        bench.find_device(4, peaks)


# ------------------------------------------------------------ a whole run

def check_last_line(result, metrics):
    assert list(result)[-1] == "checks"
    assert set(result) >= {"correct", "attempted", "failed", "metrics", "device", "checks"}
    assert set(result["metrics"]) == set(metrics)
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert result["attempted"] > 0 and result["failed"] == 0
    json.dumps(result)


def a_toy_run_prints_the_contracts_last_line_and_is_correct(family):
    result = bench.run_cell(tiny_cell(family), 2 ** 31 + 7, 0.2, 0, CPU, None)
    check_last_line(result, {"train_samples_per_s", "setup_s"})
    assert result["correct"], result["checks"]


def workers_above_one_fit_through_parallel_wrapper():
    """The ``workers`` traffic parameter: data-parallel over the suite's
    virtual CPU devices, still held to the single-program reference."""
    result = bench.run_cell(tiny_cell("bert", workers=2), 3, 1.0, 0, CPU, None)
    check_last_line(result, {"train_samples_per_s", "setup_s"})
    assert result["correct"], result["checks"]


# ------------------------------------------- the control and the faults fail

def _correct(checks, limits):
    return all(checks[name] <= limit for name, limit in limits.items())


def the_fp8_control_comes_out_as_not_correct(seed):
    cell = tiny_cell("bert")
    ctx = tiny_ctx(cell, seed)
    want = cell.runner.reference_readings(ctx)
    control = cell.runner.reference_readings(ctx, precision="fp8")
    assert not _correct(reference_train.compare(control, want), cell.limits)
    assert _correct(reference_train.compare(want, want), cell.limits)


def _break_step(monkeypatch, wrap):
    from deeplearning4j_tpu.models.multi_layer_network import MultiLayerNetwork
    from deeplearning4j_tpu.runtime import compile_cache
    # a broken step can make the AOT path fall back; the counter is the process's, and other
    # tests of the suite read it: put it back when this test ends
    monkeypatch.setattr(compile_cache.STATS, "aot_fallbacks", compile_cache.STATS.aot_fallbacks)
    sound = MultiLayerNetwork._train_step_fn
    monkeypatch.setattr(MultiLayerNetwork, "_train_step_fn", lambda self: wrap(sound(self)))


def _state_unchanged(step):
    def faulty(ts, x, y, rng, fmask, lmask):
        new, loss = step(ts, x, y, rng, fmask, lmask)
        return type(ts)(params=ts.params, model_state=ts.model_state, opt_state=new.opt_state,
                        step=new.step), loss
    return faulty


def _half_batch(step):
    def faulty(ts, x, y, rng, fmask, lmask):
        h = x.shape[0] // 2
        return step(ts, x[:h], y[:h], rng, None if fmask is None else fmask[:h],
                    None if lmask is None else lmask[:h])
    return faulty


def a_run_with_the_timed_path_broken_is_not_correct(monkeypatch, fault):
    _break_step(monkeypatch, fault)
    result = bench.run_cell(tiny_cell("bert"), 9, 0.5, 0, CPU, None)
    assert result["correct"] is False, result["checks"]
    if fault is _state_unchanged:
        assert result["checks"]["delta_norm_gap"][0] == pytest.approx(1.0, abs=1e-6)


def compare_leaves_out_leaves_with_no_gradient():
    ones, tiny = np.ones(4, np.float32), np.full(4, 1e-9, np.float32)
    want = {"losses": [1.0], "grad": [ones, ones, tiny], "delta": ([ones, ones, tiny], [2 * ones])}
    got = {"losses": [1.0], "grad": [ones, np.array([1, 1, 1, 2], np.float32), tiny],
           "delta": ([ones, ones, 0.5 * ones], [2 * ones])}
    checks = reference_train.compare(got, want)
    assert checks["delta_norm_gap"] == 0.0  # the third leaf has no gradient to speak of and is left out
    # the second leaf's gradient differs by 1 in one element of a leaf of norm 2
    assert checks["grad_diff_worst"] == pytest.approx(0.5) and checks["grad_diff_median"] < 1e-6
    assert checks["grad_norm_gap"] == pytest.approx((7 ** 0.5 - 2) / 2)
    # counted in roundings: the reference's own gradient moves by 0.5 in that element when rounded
    want["grad_rounded"] = [ones, np.array([1, 1, 1, 1.5], np.float32), tiny]
    assert reference_train.compare(got, want)["grad_diff_roundings"] == pytest.approx(0.0, abs=1e-3)
    want["grad_rounded"] = [np.array([1, 1, 1, 1.5], np.float32)] * 2 + [tiny]
    got["grad"][0] = np.array([1, 1, 1, 2], np.float32)
    assert reference_train.compare(got, want)["grad_diff_roundings"] == pytest.approx(2.0)
    got["delta"] = (got["delta"][0], [ones])  # a state leaf has no gradient and stays in
    checks = reference_train.compare(got, want)
    assert checks["delta_norm_gap"] == pytest.approx(0.5) and checks["state_diff_median"] == pytest.approx(0.5)


# ------------------------------------------------------------------ the tests
# Five tests, no more, in a directory that is collected last: xdist hands files out by their
# number of tests, largest first, and this file then goes out after every other one, so that
# which worker runs which of the suite's older files stays as it was before this file came.

def test_manifest_and_resolution_from_a_cells_name():
    manifest_keeps_to_the_contract()
    for name in CELLS:
        run_finds_every_file_of_a_cell_by_its_name(name)
    an_unknown_cell_is_an_error()


def test_flops_trace_reduction_and_comparison_against_numbers_worked_by_hand():
    bert_flops_against_a_number_worked_by_hand()
    resnet50_flops_against_a_number_worked_by_hand()
    trace_reduce_reads_busy_idle_and_a_gaps_label()
    compare_leaves_out_leaves_with_no_gradient()


def test_without_a_listed_tpu_a_run_fails_and_prints_no_result(monkeypatch):
    run_fails_on_a_cpu_and_prints_no_result()
    an_unknown_device_kind_is_an_error(monkeypatch)


def test_toy_runs_print_the_contracts_last_line_and_are_correct():
    for family in ("bert", "resnet50"):
        a_toy_run_prints_the_contracts_last_line_and_is_correct(family)
    workers_above_one_fit_through_parallel_wrapper()


def test_the_control_and_each_fault_come_out_as_not_correct(monkeypatch):
    for seed in (5, 6, 7):
        the_fp8_control_comes_out_as_not_correct(seed)
    for fault in (_state_unchanged, _half_batch):
        with monkeypatch.context() as patch:
            a_run_with_the_timed_path_broken_is_not_correct(patch, fault)
