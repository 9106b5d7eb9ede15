"""The benchmark's own tests (CPU, toy sizes passed in by the tests).

What they hold: ``run.py`` finds every file of every cell by name and the
manifest keeps to its contract; the FLOP functions agree with numbers
worked by hand at the published widths; the trace reduction reads busy,
idle and a gap's label off a small synthetic trace; a run without a listed
TPU fails rather than falls back; a toy run prints the contract's last
line with ``correct`` true; the control (the reference in fp8 put in the
program's place) and each fault a training cell can have come out as not
correct; the reference follows its steps in place (four trees of the
parameters' size on the device, no more) and reads what the formulas worked
by hand read; the reduced trace holds every op, every kind and every scope
after the mark, as the program's own scope table has them; and each reader
of those reads a hand-made trace.
"""

import gc
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


# ---------------------------------------------- the reference, in place

def _toy_problem(seed=0, width=256, rows=8):
    """A 2-leaf model whose gradient can be written down: loss = mean((x w + b - y)^2)."""
    rng = np.random.default_rng(seed)
    params = {"b": rng.normal(size=(width,)).astype(np.float32),
              "w": (rng.normal(size=(width, width)) / 16).astype(np.float32)}
    batches = [(rng.normal(size=(rows, width)).astype(np.float32), rng.normal(size=(rows, width)).astype(np.float32))
               for _ in range(3)]

    def loss_fn(p, state, batch, mm, conv):
        x, y = batch
        return ((mm(x, p["w"]) + p["b"] - y) ** 2).mean(), state

    return params, batches, loss_fn


def _by_hand(params, batches, opt):
    """The same steps in float64 numpy: the gradient from the residual, then
    Adam (Kingma & Ba 2014, algorithm 1) or Nesterov momentum written out."""
    p = {k: v.astype(np.float64) for k, v in params.items()}
    moments = [{k: np.zeros_like(v) for k, v in p.items()} for _ in range(2)]
    losses, first = [], None
    for t, (x, y) in enumerate(batches, start=1):
        r = x.astype(np.float64) @ p["w"] + p["b"] - y
        losses.append((r ** 2).mean())
        g = {"w": 2 * x.T.astype(np.float64) @ r / r.size, "b": 2 * r.sum(0) / r.size}
        first = first or g
        for k in p:
            if opt["name"] == "adam":
                m = moments[0][k] = opt["b1"] * moments[0][k] + (1 - opt["b1"]) * g[k]
                v = moments[1][k] = opt["b2"] * moments[1][k] + (1 - opt["b2"]) * g[k] ** 2
                p[k] = p[k] - opt["lr"] * (m / (1 - opt["b1"] ** t)) / (np.sqrt(v / (1 - opt["b2"] ** t)) + opt["eps"])
            else:
                tr = moments[0][k] = g[k] + opt["momentum"] * moments[0][k]
                p[k] = p[k] - opt["lr"] * (g[k] + opt["momentum"] * tr)
    return losses, first, {k: p[k] - params[k] for k in p}


def follow_holds_four_trees_and_reads_what_the_formulas_read(opt):
    import jax
    import jax.numpy as jnp
    params, batches, loss_fn = _toy_problem()
    param_bytes = sum(a.nbytes for a in params.values())
    batch_bytes = sum(a.nbytes for b in batches for a in b)
    gc.collect()
    before = sum(a.nbytes for a in jax.live_arrays())  # whatever earlier tests of this process left
    held = []

    def watch(step):
        def watched(*args):
            held.append(sum(a.nbytes for a in jax.live_arrays()))
            out = step(*args)
            jax.block_until_ready(out)
            held.append(sum(a.nbytes for a in jax.live_arrays()))  # with the step's results, gradient and all
            return out
        return watched

    got = reference_train.follow(loss_fn, jax.tree.map(jnp.asarray, params), {},
                                 [tuple(jnp.asarray(a) for a in b) for b in batches], opt, transform=watch)
    assert len(held) == 6
    # parameters, moments and one gradient: never the start, the first gradient or a second copy beside them
    assert max(held) - before <= 4.5 * param_bytes + batch_bytes, (held, before, param_bytes)
    assert sum(a.nbytes for a in jax.live_arrays()) - before <= 1024  # and nothing stays behind
    assert all(isinstance(a, np.ndarray) for a in jax.tree.leaves((got["grad"], got["delta"])))
    losses, grad, delta = _by_hand(params, batches, opt)
    assert got["losses"] == pytest.approx(losses, rel=1e-5)
    for k in params:
        assert got["grad"][k] == pytest.approx(grad[k], rel=1e-4, abs=1e-7)
        # a float32 parameter of size ~1 holds its change to ~1e-7; Adam's three steps move it by ~3e-3
        assert got["delta"][0][k] == pytest.approx(delta[k], rel=1e-3, abs=5e-7)
    assert got["delta"][1] == {}
    # the comparison takes trees on the host or on the device alike, a leaf at a time
    same = reference_train.compare(got, dict(got, grad=jax.tree.map(jnp.asarray, got["grad"])))
    assert same["grad_diff_worst"] == 0.0 and same["delta_norm_gap"] == 0.0 and same["loss_gap"] == 0.0


# ------------------------------------------------ every op, kind and scope

MS = 1_000_000
STEP_OPS = [("fusion.1", 4), ("fusion.2", 6), ("fused_attention_fwd.3", 2), ("copy.4", 1), ("fusion.5", 3)]
STEP_HLO = """HloModule jit_step, is_scheduled=true
ENTRY %main.9 (p0: f32[8]) -> f32[8] {
  %fusion.1 = f32[8]{0} fusion(%p0), kind=kOutput, calls=%c1, metadata={op_name="jit(step)/jit(main)/jvp(layer_1.Block)/ffn/dot_general" source_file="a.py" source_line=3}
  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kOutput, calls=%c2, metadata={op_name="jit(step)/jit(main)/transpose(jvp(layer_1.Block))/ffn/dot_general"}
  %fused_attention_fwd.3 = f32[8]{0} custom-call(%fusion.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jit(main)/jvp(layer_2.Block)/fused_attention/pallas_call"}
  %copy.4 = f32[8]{0} copy(%fused_attention_fwd.3), metadata={op_name="jit(step)/jit(main)/convert_element_type"}
  ROOT %fusion.5 = f32[8]{0} fusion(%copy.4), kind=kLoop, calls=%c3, metadata={op_name="jit(step)/jit(main)/updater/add"}
}
"""


def _step_planes(mark_ms, devices=1):
    """Three runs of a 20 ms step program (16 ms of ops, back to back from
    each run's start) at 0, 30 and 60 ms on each device, a 1 ms program
    between them, and a ``measure`` mark on the host."""
    ops, modules = [], []
    for start in (0, 30, 60):
        modules.append(("jit_step(1)", start * MS, 20 * MS))
        at = start
        for name, ms in STEP_OPS:
            ops.append((name + " = f32[8]{0} fusion(...)", at * MS, ms * MS))  # the trace gives the whole text
            at += ms
    modules.append(("jit_tiny(2)", 25 * MS, 1 * MS))
    ops.append(("copy.9", 25 * MS, 1 * MS))
    device = [("XLA Ops", ops), ("XLA Modules", modules), ("Steps", [("0", 0, 80 * MS)])]
    return ([(f"/device:TPU:{i}", device) for i in range(devices)]
            + [("/host:CPU", [("python3", [("measure", mark_ms * MS, 0)])])])


def the_reduced_trace_holds_every_op_kind_and_scope_after_the_mark():
    # the mark falls 1 ms into the first run's kernel: that run is no whole run, its ops count by their part
    out = trace_reduce.reduce_planes(_step_planes(mark_ms=11), [STEP_HLO])
    assert out["program"] == "jit_step(1)" and out["program_runs"] == pytest.approx(2 + 9 / 20)
    assert out["op_seconds"] == pytest.approx({"fusion.1": 0.008, "fusion.2": 0.012, "fused_attention_fwd.3": 0.005,
                                               "copy.4": 0.003, "fusion.5": 0.009, "copy.9": 0.001})
    assert out["kind_seconds"]["fusion"] == pytest.approx([0.029, 7])
    assert out["kind_seconds"]["fused_attention_fwd"] == pytest.approx([0.005, 2.5])
    assert out["kind_seconds"]["copy"] == pytest.approx([0.004, 4])
    scopes = out["scopes"]
    assert scopes["runs"] == 2 and scopes["step_s"] == pytest.approx(0.020)
    assert scopes["scopes"] == {"forward": pytest.approx({"Block/ffn": 0.004, "Block/fused_attention": 0.002}),
                                "backward": pytest.approx({"Block/ffn": 0.006}),
                                "optimizer": pytest.approx({"updater": 0.003})}
    assert scopes["unattributed"] == pytest.approx({"copy [convert_element_type]": 0.001})
    assert scopes["phases"] == pytest.approx({"forward": 0.006, "backward": 0.006, "optimizer": 0.003, "other": 0.005})
    assert scopes["attributed_fraction"] == pytest.approx(0.75)
    # scopes first (their seconds in the whole runs), then kinds
    assert out["device_ops"][:4] == [["Block/ffn bwd", pytest.approx(0.012)], ["Block/ffn fwd", pytest.approx(0.008)],
                                     ["updater opt", pytest.approx(0.006)],
                                     ["Block/fused_attention fwd", pytest.approx(0.004)]]
    assert out["device_ops"][4] == ["fusion.*x3", pytest.approx(0.029)] and len(out["device_ops"]) <= 10
    # two devices: everything is per device
    two = trace_reduce.reduce_planes(_step_planes(mark_ms=11, devices=2), [STEP_HLO])
    assert two["op_seconds"] == pytest.approx(out["op_seconds"]) and two["scopes"]["runs"] == 4
    assert two["scopes"]["scopes"]["backward"] == pytest.approx({"Block/ffn": 0.006})
    assert two["kind_seconds"]["fused_attention_fwd"] == pytest.approx([0.005, 2.5])
    # no HLO text from the runner: no scope table, the line falls back to kinds and single ops
    bare = trace_reduce.reduce_planes(_step_planes(mark_ms=11))
    assert bare["scopes"] is None and bare["device_ops"][0] == ["fusion.*x3", pytest.approx(0.029)]
    # a step program none of whose ops carries a scope (a stale compile cache) fails the traced run
    with pytest.raises(RuntimeError, match="carries a scope"):
        trace_reduce.reduce_planes(_step_planes(mark_ms=11),
                                   [re.sub(r'op_name="[^"]*/', 'op_name="jit(step)/jit(main)/', STEP_HLO)])


def the_yardsticks_scope_table_is_the_programs():
    """``trace_reduce`` keeps its own copy of the join (a PR that claims a
    gain cannot edit it); with the mark before everything it reads what
    ``runtime.profiler.scope_times`` reads off the same planes."""
    from deeplearning4j_tpu.runtime import profiler
    planes = _step_planes(mark_ms=0)
    ours = trace_reduce.reduce_planes(planes, [STEP_HLO])["scopes"]
    theirs = profiler.scope_times([p for p in planes if p[0].startswith("/device:")], [STEP_HLO],
                                  depth=2, merge_layers=True)
    assert set(ours) == set(theirs) and ours["runs"] == theirs["runs"] == 3
    for key in ("program", "step_s", "attributed_fraction"):
        assert ours[key] == pytest.approx(theirs[key])
    for key in ("phases", "unattributed"):
        assert ours[key] == pytest.approx(theirs[key])
    for phase in theirs["scopes"]:
        assert ours["scopes"][phase] == pytest.approx(dict(theirs["scopes"][phase]))
    for op_name in ("jit(s)/jit(main)/transpose(jvp(layer_3.Block))/qkv/dot_general", "jit(s)/jit(main)/mul",
                    "jit(s)/loss/reduce_sum", "jit(s)/jit(main)/jvp(jit(gelu))/layer_0.Embed/ln/add"):
        assert trace_reduce.classify_op_name(op_name) == profiler.classify_op_name(op_name)


# ------------------------------------------------------- the new readers

def the_new_readers_read_a_trace_made_by_hand():
    cell = bench.resolve("bert-base-ft-b32-s512")
    peak = bench.load_json(os.path.join(ROOT, "benchmark", "peaks.json"))["TPU v5 lite"]
    # the kernel pair at the cell's shape, by hand: forward K Q^T and V E, 2 x 2 x 512 x 512 x 64 FLOPs a head, 12 heads,
    # 32 rows; backward five such matmuls for the forward's two. Bytes: 25.2 MB an operand in bf16, 4 and 8 of them.
    flops = cell.family.attention_kernel_flops(cell.config, cell.traffic)
    least = cell.family.attention_kernel_bytes(cell.config, cell.traffic)
    assert flops == {"fused_attention_fwd": 32 * 12 * 2 * (2 * 512 * 512 * 64),
                     "fused_attention_bwd": 32 * 12 * 5 * (2 * 512 * 512 * 64)}
    operand = 32 * 512 * 768 * 2
    assert least == {"fused_attention_fwd": 4 * operand + 32 * 512 * 4, "fused_attention_bwd": 8 * operand + 32 * 512 * 4}
    read = {name: bench.load_module("readers", name).read
            for name in ("fused_attention_roofline", "attention_share.train", "ffn_share.train")}
    # twelve runs of each kernel at the times PERF.md section 5 gives (PR 27): both bound by FLOPs,
    # (0.1308 + 0.3270) ms at peak over (0.348 + 0.624) ms taken
    trace = {"kind_seconds": {"fused_attention_fwd": [12 * 0.348e-3, 12], "fused_attention_bwd": [12 * 0.624e-3, 12],
                              "fusion": [2.4, 17930]},
             "scopes": {"step_s": 0.0673, "scopes": {
                 "forward": {"TransformerEncoderBlock/ffn": 0.01058, "TransformerEncoderBlock/fused_attention": 0.0042,
                             "TransformerEncoderBlock/qkv": 0.00412, "loss": 0.0001},
                 "backward": {"TransformerEncoderBlock/ffn": 0.02107, "TransformerEncoderBlock/fused_attention": 0.00749},
                 "optimizer": {"updater": 0.00067}}}}
    by_hand = 100 * (3.5 * 4 * 32 * 512 * 512 * 768 / 197e12) / (0.348e-3 + 0.624e-3)
    assert read["fused_attention_roofline"](None, trace, cell, peak) == pytest.approx(by_hand, rel=1e-9)
    assert by_hand == pytest.approx(47.1, abs=0.05)
    assert read["attention_share.train"](None, trace, cell, peak) == pytest.approx(100 * 0.01169 / 0.0673)
    assert read["ffn_share.train"](None, trace, cell, peak) == pytest.approx(100 * 0.03165 / 0.0673)
    # a bandwidth-starved chip: the bytes bound holds and the reader takes it
    slow = dict(peak, hbm_bytes_per_s=peak["hbm_bytes_per_s"] / 10)
    by_bytes = 100 * (least["fused_attention_fwd"] + least["fused_attention_bwd"]) / slow["hbm_bytes_per_s"] / 0.972e-3
    assert read["fused_attention_roofline"](None, trace, cell, slow) == pytest.approx(by_bytes, rel=1e-9)
    # the XLA form's scopes count as attention too; nothing to read returns nothing, never 0
    xla = {"kind_seconds": {"fusion": [2.4, 17930]}, "scopes": {"step_s": 0.1, "scopes": {
        "forward": {"SelfAttentionLayer/scores": 0.01, "SelfAttentionLayer/softmax": 0.005}, "backward":
        {"SelfAttentionLayer/context": 0.005}, "optimizer": {}}}}
    assert read["fused_attention_roofline"](None, xla, cell, peak) is None
    assert read["attention_share.train"](None, xla, cell, peak) == pytest.approx(20.0)
    assert read["ffn_share.train"](None, xla, cell, peak) is None
    assert read["attention_share.train"](None, {"kind_seconds": {}, "scopes": None}, cell, peak) is None


# ------------------------------------------------------------------ the tests
# Few tests, each of several cases, in a directory that is collected last: xdist hands files out by
# their number of tests, largest first, so the fewer this file has, the fewer of the suite's older
# files change the worker they run on (PERF.md section 7 row 10: the hang that made it matter is cured).

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


def test_the_reference_follows_in_place_and_reads_what_the_formulas_read():
    follow_holds_four_trees_and_reads_what_the_formulas_read(
        {"name": "adam", "lr": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8})
    follow_holds_four_trees_and_reads_what_the_formulas_read({"name": "nesterov", "lr": 0.01, "momentum": 0.9})


def test_the_reduced_trace_holds_every_op_kind_and_scope():
    the_reduced_trace_holds_every_op_kind_and_scope_after_the_mark()
    the_yardsticks_scope_table_is_the_programs()


def test_the_new_readers_read_a_trace_made_by_hand():
    the_new_readers_read_a_trace_made_by_hand()
