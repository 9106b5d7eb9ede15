"""The Kimi Linear family's yardstick (CPU, toy sizes): a toy run through the
runner is ``correct`` and its fp8 control is not; the FLOPs, bytes and
parameter count at the published cut agree with numbers worked by hand; the
five readers the cell brings read a trace made by hand, ``None`` included.
"""

import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import reference_train  # noqa: E402
from benchmark import run as bench  # noqa: E402

CELL = "kimi-linear-pt-b1-s8192"
TINY = {"family": "kimi_linear", "hidden_size": 32, "num_hidden_layers": 5, "num_attention_heads": 2,
        "linear_attn_config": {"full_attn_layers": [4], "kda_layers": [1, 2, 3, 5], "head_dim": 16,
                               "num_heads": 2, "short_conv_kernel_size": 4},
        "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "intermediate_size": 64, "moe_intermediate_size": 24, "first_k_dense_replace": 1,
        "router_width": 16, "held_experts": [4, 4], "held_rows": 512, "num_experts_per_token": 4,
        "num_shared_experts": 1, "routed_scaling_factor": 2.446, "rms_norm_eps": 1e-5, "vocab_size": 96,
        "initializer_range": 0.02, "kda_gate_rank": 8, "recompute": {"set_remat": True},
        "optimizer": {"name": "adam", "lr": 2e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8},
        "precision": {"compute": "float32"}}
TRAFFIC = {"runner": "train_fit", "batch": 2, "seq_len": 128, "count": 4, "check_steps": 3, "workers": 1}
# toy-size limits: the float32 program against the float32 reference on the CPU reads 1e-7 / 1.5e-6
# (measured), the fp8 control 9e-5 / 2.5e-2, the reference with bfloat16 operands 9e-6 / 8e-3
LIMITS = {"loss_gap": 1e-5, "delta_norm_gap": 1e-3}
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


@pytest.fixture(autouse=True)
def remat_put_back():
    """The family's ``build`` turns ``Environment.set_remat`` on for the process."""
    from deeplearning4j_tpu.runtime.environment import get_environment
    env = get_environment()
    was = env.remat_segments
    yield
    env.set_remat(was)


def tiny_cell():
    manifest = bench.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    return types.SimpleNamespace(
        name="tiny", chips=1, config=TINY, traffic=TRAFFIC, limits=LIMITS,
        family=bench.load_module("families", "kimi_linear"), runner=bench.load_module("runners", "train_fit"),
        end_to_end=manifest["end_to_end"], per_layer=[])


def test_a_toy_run_is_correct_with_its_routing_held_to_the_reference():
    result = bench.run_cell(tiny_cell(), 2 ** 31 + 7, 0.2, 0, CPU, None)
    assert result["correct"], result["checks"]
    assert set(result["metrics"]) == {"train_samples_per_s", "setup_s"}
    assert result["attempted"] > 0 and result["failed"] == 0


@pytest.mark.parametrize("precision,correct", [("fp8", False), ("bfloat16", False), ("float32", True)])
def test_the_reference_in_a_lower_precision_is_not_correct(precision, correct):
    cell = tiny_cell()
    ctx = types.SimpleNamespace(config=cell.config, traffic=cell.traffic, family=cell.family, seed=5)
    want = cell.runner.reference_readings(ctx)
    checks = reference_train.compare(cell.runner.reference_readings(ctx, precision=precision), want)
    assert all(checks[name] <= limit for name, limit in LIMITS.items()) == correct, checks
    assert checks["state_diff_median"] <= 0.05  # the counters: a few of ~128 assignments an expert may flip


def test_flops_bytes_and_parameters_at_the_published_cut_against_numbers_worked_by_hand():
    cell = bench.resolve(CELL)
    family, config, traffic = cell.family, cell.config, cell.traffic
    d, inner, t = 2304, 32 * 128, 8192
    kda_params = (4 * d * inner + 2 * (d * 128 + 128 * inner) + inner + d * 32 + 3 * 4 * inner + 32 + inner + 128)
    mla_params = d * 32 * 192 + d * 576 + 512 + 512 * 32 * 256 + 32 * 128 * d
    expert = 3 * d * 1024
    moe_params = d * 256 + 9 * expert  # router, eight held experts and the shared one
    total = (2 * 20480 * d + d + 5 * 2 * d + 4 * kda_params + mla_params + 3 * d * 9216 + 4 * moe_params)
    assert kda_params == pytest.approx(39.52e6, rel=1e-3) and mla_params == pytest.approx(29.11e6, rel=1e-3)
    assert family.n_params(config) == total == 602449792
    # forward FLOPs a token: weights at 2 x in x out, the recurrence 6 x 128 x 128 a head, the causal half
    # of 8192 keys against heads of 192 and 128, 8 x 8 / 256 routed assignments, the head over 20480 rows
    kda = 2 * (4 * d * inner + 2 * (d * 128 + 128 * inner) + d * 32) + 6 * 128 * 128 * 32
    mla = 2 * (d * 32 * 192 + d * 576 + 512 * 32 * 256 + 32 * 128 * d) + 2 * (192 + 128) * 32 * t / 2
    moe = 2 * d * 256 + 2 * expert * (1 + 8 * 8 / 256)
    per_token = 4 * kda + mla + 2 * 3 * d * 9216 + 4 * moe + 2 * d * 20480
    assert per_token == pytest.approx(767.6e6, rel=1e-3)
    got = family.flops_per_step(config, traffic)
    assert got == pytest.approx(3 * per_token * t, rel=1e-12) and got == pytest.approx(18.87e12, rel=1e-3)
    assert family.least_bytes_per_step(config, traffic) == 2 * 12 * total + 2 * 4 * t
    assert 16 * total == pytest.approx(9.64e9, rel=1e-3)  # parameters, moments and gradients: 57% of 16.9e9
    assert family.samples_per_step(traffic) == 1


def test_the_cells_batches_are_next_token_pairs_from_the_slice():
    cell = bench.resolve(CELL)
    first, second = (cell.family.batches(cell.config, dict(cell.traffic, count=2), seed)[0] for seed in (9, 9))
    ids, labels, mask = first
    assert ids.shape == labels.shape == (1, 8192) and ids.dtype == labels.dtype and str(ids.dtype) == "int32"
    assert mask is None and (ids[:, 1:] == labels[:, :-1]).all() and (ids == second[0]).all()
    assert 0 <= ids.min() and ids.max() < 20480 and ids.flags["C_CONTIGUOUS"]


def test_the_five_readers_read_a_trace_made_by_hand():
    cell = bench.resolve(CELL)
    peak = bench.load_json(os.path.join(ROOT, "benchmark", "peaks.json"))["TPU v5 lite"]
    read = {m["name"]: bench.load_module("readers", m["name"]).read for m in cell.per_layer
            if CELL in m.get("workloads", []) and m["workloads"] == [CELL]}
    assert set(read) == {"kda_scan_share.train", "kda_share.train", "moe_share.train", "mlp_share.train",
                         "flash_attention_roofline"}
    # the kernels by hand: 32 heads x 8192^2 / 2 pairs; forward q k^T (192) and p v (128), dq pass 192 + 128 + 192,
    # dk/dv pass 192 + 128 + 128 + 192; operands in bf16, row statistics 8 float32 lanes a row
    pairs = 32 * 8192 ** 2 / 2
    flops = cell.family.flash_kernel_flops(cell.config, cell.traffic)
    assert flops["flash_attention_fwd"] == 2 * pairs * 320 and flops["flash_attention_bwd_dq"] == 2 * pairs * 512
    assert flops["flash_attention_bwd_dkv"] == flops["flash_attention_bwd_dkv_chunked"] == 2 * pairs * 640
    rows = 32 * 8192
    least = cell.family.flash_kernel_bytes(cell.config, cell.traffic)
    assert least["flash_attention_fwd"] == rows * ((192 + 192 + 128 + 128) * 2 + 32)
    assert least["flash_attention_bwd_dq"] == rows * ((3 * 192 + 2 * 128) * 2 + 64)
    assert least["flash_attention_bwd_dkv"] == rows * ((3 * 192 + 3 * 128) * 2 + 64)
    trace = {"kind_seconds": {"flash_attention_fwd": [3 * 7e-3, 3], "flash_attention_bwd_dq": [3 * 12e-3, 3],
                              "flash_attention_bwd_dkv": [3 * 15e-3, 3], "fusion": [2.4, 1000],
                              "ragged-dot-none": [2.5 * 0.006, 2.5 * 44], "ragged-dot-metadata": [2.5 * 0.002, 2.5 * 12]},
             "program_runs": 2.5,
             "scopes": {"step_s": 0.4, "scopes": {
                 "forward": {"DecoderBlock/while": 0.03, "DecoderBlock/kda_scan": 0.01, "DecoderBlock": 0.035,
                             "DecoderBlock/kda_in": 0.02, "DecoderBlock/kda_out": 0.01,
                             "DecoderBlock/router": 0.001, "DecoderBlock/experts": 0.004, "DecoderBlock/flash": 0.007,
                             "DecoderBlock/mlp": 0.01, "loss/lm_head": 0.01},
                 "backward": {"DecoderBlock/while": 0.11, "DecoderBlock/kda_scan": 0.01, "DecoderBlock": 0.115,
                              "DecoderBlock/kda_in": 0.05, "DecoderBlock/kda_out": 0.02,
                              "DecoderBlock/dispatch": 0.002, "DecoderBlock/experts": 0.009,
                              "DecoderBlock/combine": 0.001, "DecoderBlock/shared_expert": 0.003},
                 "optimizer": {"updater": 0.001}}}}
    # the scan's body is read under ``while``; the while ops' own span, booked under the bare layer, is not
    assert read["kda_scan_share.train"](None, trace, cell, peak) == pytest.approx(100 * 0.16 / 0.4)
    assert read["kda_share.train"](None, trace, cell, peak) == pytest.approx(100 * 0.26 / 0.4)
    # the expert layer's scopes, and the grouped matmuls by the compiler's own name for them: they carry no scope
    assert read["moe_share.train"](None, trace, cell, peak) == pytest.approx(100 * (0.020 + 0.008) / 0.4)
    assert read["moe_share.train"](None, dict(trace, kind_seconds={}), cell, peak) == pytest.approx(100 * 0.020 / 0.4)
    assert read["mlp_share.train"](None, trace, cell, peak) == pytest.approx(100 * 0.01 / 0.4)
    by_hand = 100 * (2 * pairs * (320 + 512 + 640) / 197e12) / (7e-3 + 12e-3 + 15e-3)
    assert read["flash_attention_roofline"](None, trace, cell, peak) == pytest.approx(by_hand, rel=1e-9)
    assert by_hand == pytest.approx(47.2, abs=0.1) and by_hand < 100
    # the chunked backward's names are read too; a bandwidth-starved chip is bound by its bytes
    chunked = {"kind_seconds": {"flash_attention_bwd_dq_chunked": [12e-3, 1]}, "scopes": None}
    assert read["flash_attention_roofline"](None, chunked, cell, peak) == pytest.approx(
        100 * 2 * pairs * 512 / 197e12 / 12e-3)
    slow = dict(peak, hbm_bytes_per_s=1e9)
    assert read["flash_attention_roofline"](None, chunked, cell, slow) == pytest.approx(
        100 * least["flash_attention_bwd_dq"] / 1e9 / 12e-3)
    # nothing to read returns nothing, never 0: another model's trace, a trace without scopes, a family without kernels
    bert = {"kind_seconds": {"fused_attention_fwd": [0.1, 12]}, "scopes": {"step_s": 0.07, "scopes": {
        "forward": {"TransformerEncoderBlock/ffn": 0.01}, "backward": {}, "optimizer": {}}}}
    assert all(fn(None, bert, cell, peak) is None for fn in read.values())
    assert all(read[name](None, chunked, cell, peak) is None for name in read if name != "flash_attention_roofline")
    other = types.SimpleNamespace(family=bench.load_module("families", "bert"), config={}, traffic={}, chips=1)
    assert read["flash_attention_roofline"](None, trace, other, peak) is None
