"""The LFM2 family's yardstick (CPU, toy sizes): the harness finds the cell by
its name and its configuration keeps every published number but the three
it cuts; a toy run through the runner is ``correct`` with the routing held
to the reference, and its fp8 control and bfloat16 witness are not; the
parameter count, FLOPs and the causal grouped-head flash kernels' FLOPs and
bytes at the published cut agree with numbers worked by hand; the reader of
attention's T x T part, and the readers the cell does not list yet, read a
trace made by hand with this family's functions, ``None`` where nothing ran:
grouped-query attention's, the experts', the dense SwiGLU's and the flash
kernels' roofline (their yardstick tests pick the readers of the cells that
brought them by a list of one cell); the gated short convolution's scope is
there for ``trace_reduce.scope_share`` to find, though no metric reads it yet.
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
from benchmark.trace_reduce import scope_share  # noqa: E402

CELL = "lfm2-24b-a2b-pt-b1-s8192"
LAYER_TYPES = ["full_attention" if i % 4 == 2 else "conv" for i in range(40)]
# the row's config.json as published (LiquidAI/LFM2-24B-A2B), every number and group of it
PUBLISHED = {"conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 11776,
             "layer_types": LAYER_TYPES, "max_position_embeddings": 128000, "model_type": "lfm2_moe",
             "moe_intermediate_size": 1536, "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
             "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4, "num_hidden_layers": 40,
             "num_key_value_heads": 8, "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
             "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}
TINY = {"family": "lfm2_moe", "hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 1,
        "conv_L_cache": 3, "conv_bias": False, "tie_embedding": True, "use_expert_bias": True,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"}, "intermediate_size": 64,
        "moe_intermediate_size": 24, "num_dense_layers": 2, "layer_types": LAYER_TYPES[:6],
        "layers_here": [0, 2, 3, 4, 5], "router_width": 16, "held_experts": [4, 4], "held_rows": 1024,
        "num_experts_per_tok": 4, "routed_scaling_factor": 1, "expert_bias_rate": 1e-3, "norm_eps": 1e-5, "vocab_size": 96,
        "initializer_range": 0.02, "recompute": {"set_remat": True},
        "optimizer": {"name": "adam", "lr": 2e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8},
        "precision": {"compute": "float32"}}
TRAFFIC = {"runner": "train_fit", "batch": 2, "seq_len": 128, "count": 4, "check_steps": 3, "workers": 1}
# toy-size limits, as the other decoder families' toy runs hold them: the float32 program against the float32
# reference on the CPU reads ~1e-7 / ~1e-6, the fp8 control and the reference with bfloat16 operands far above
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
        family=bench.load_module("families", "lfm2_moe"), runner=bench.load_module("runners", "train_fit"),
        end_to_end=manifest["end_to_end"], per_layer=[])


def test_the_harness_finds_the_cell_and_its_configuration_keeps_the_published_numbers():
    cell = bench.resolve(CELL)
    assert cell.chips == 1 and cell.config["family"] == "lfm2_moe" and cell.traffic["runner"] == "train_fit"
    assert cell.traffic == bench.resolve("kimi-linear-pt-b1-s8192").traffic  # the traffic file the Kimi cell uses
    assert set(cell.limits) <= {"delta_norm_gap", "grad_diff_roundings", "loss_gap", "grad_norm_gap"} and cell.limits
    names = {m["name"] for m in cell.per_layer}
    assert {"mfu.train", "device_idle_share.train", "train_step_roofline", "dispatch_ms.train", "data_wait_share.train",
            "compiles_in_window.train", "fit_unattributed_share.train", "h2d_ms.train", "attention_share.train"} == names
    assert [m["name"] for m in cell.end_to_end] == ["train_samples_per_s", "setup_s"]
    # every published number and group is here unchanged, but depth, the experts held and the vocabulary
    reduced = {"num_hidden_layers": 5, "num_experts": 8, "vocab_size": 8192}
    assert {k: cell.config[k] for k in PUBLISHED} == dict(PUBLISHED, **reduced)
    assert set(cell.config["reduced"]) == set(reduced) == set(cell.config["published"])
    assert cell.config["published"] == {k: PUBLISHED[k] for k in reduced}
    assert cell.config["layers_here"] == [0, 2, 3, 4, 5] and cell.config["held_experts"] == [0, 8]
    assert cell.config["router_width"] == PUBLISHED["num_experts"]
    assert [LAYER_TYPES[i] for i in cell.config["layers_here"]] == ["conv", "full_attention", "conv", "conv", "conv"]
    assert {"tie_embedding", "router_score", "expert_bias", "initializer_range", "optimizer"} <= set(cell.config["assumed"])
    assert cell.config["tie_embedding"] is True and cell.config["expert_bias_rate"] == 1e-3
    assert cell.config["bytes"]["parameters"] == cell.family.n_params(cell.config)


def test_a_toy_run_is_correct_with_the_routing_held_to_the_reference():
    result = bench.run_cell(tiny_cell(), 2 ** 31 + 11, 0.2, 0, CPU, None)
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
    assert checks["state_diff_median"] <= 0.05  # the counters: a few of ~256 assignments may flip


def test_parameters_flops_and_the_flash_kernels_at_the_published_cut_against_numbers_worked_by_hand():
    cell = bench.resolve(CELL)
    family, config, traffic = cell.family, cell.config, cell.traffic
    d, t, vocab = 2048, 8192, 8192
    conv = d * 3 * d + 3 * d + d * d                      # W_in, the taps, W_out
    attention = d * 2048 + 2 * d * 512 + 2048 * d + 2 * 64  # W_q, W_k and W_v at 8 heads of 64, W_o, the head norms
    dense = 3 * d * 11776
    experts = d * 64 + 8 * 3 * d * 1536                   # the router keeps its 64 outputs; 8 experts held
    assert (conv, attention, dense, experts) == (16783360, 10485888, 72351744, 75628544)
    blocks = [2 * d + conv + dense, 2 * d + attention + experts] + 3 * [2 * d + conv + experts]
    assert blocks[:3] == [89139200, 86118528, 92416000]
    total = vocab * d + sum(blocks) + d  # the table once: the head reads it
    assert family.n_params(config) == total == 469284992
    assert 16 * total == pytest.approx(7.509e9, rel=1e-3)  # parameters, moments and a gradient: 44% of 16.9e9
    # forward FLOPs a token: weights at 2 x in x out, the causal half of 8192 keys against 32 query heads of 64
    # (q k^T and p v), 4 x 8 / 64 of the routed assignments, the tied head
    per_token = (4 * 2 * 4 * d * d + 2 * (2 * d * 2048 + 2 * d * 512) + 2 * 2 * 64 * 32 * t / 2
                 + 2 * dense + 4 * (2 * d * 64 + 2 * 3 * d * 1536 * 0.5) + 2 * d * vocab)
    assert per_token == pytest.approx(405.8e6, rel=1e-3)
    got = family.flops_per_step(config, traffic)
    assert got == pytest.approx(3 * per_token * t, rel=1e-12) and got == pytest.approx(9.973e12, rel=1e-3)
    conv_share = 3 * 4 * 2 * 4 * d * d * t / got   # the four convolutions' projections
    assert conv_share == pytest.approx(0.331, abs=1e-3)
    assert family.least_bytes_per_step(config, traffic) == 2 * 12 * total + 2 * 4 * t
    assert family.samples_per_step(traffic) == 1
    # the kernels by hand: 32 query heads x 8192^2 / 2 pairs at heads of 64; forward q k^T and p v, dq pass three
    # matmuls, dk/dv pass four; q, o, dO and dq at 32 heads, k, v, dk and dv at 8, in bf16; row statistics
    # 8 float32 lanes a query row
    pairs, rows = 32 * t ** 2 / 2, 32 * t
    flops = family.flash_kernel_flops(config, traffic)
    assert set(flops) == set(family.FLASH_KERNELS) == set(family.flash_kernel_bytes(config, traffic))
    assert flops["flash_attention_fwd"] == 2 * pairs * 128 and flops["flash_attention_bwd_dq"] == 2 * pairs * 192
    assert flops["flash_attention_bwd_dkv"] == flops["flash_attention_bwd_dkv_chunked"] == 2 * pairs * 256
    least = family.flash_kernel_bytes(config, traffic)
    q_head, kv_head = t * 64 * 2, t * 64 * 2
    assert least["flash_attention_fwd"] == 2 * 32 * q_head + 2 * 8 * kv_head + rows * 32
    assert least["flash_attention_bwd_dq"] == 3 * 32 * q_head + 2 * 8 * kv_head + rows * 64
    assert least["flash_attention_bwd_dkv"] == 2 * 32 * q_head + 4 * 8 * kv_head + rows * 64
    peak = bench.load_json(os.path.join(ROOT, "benchmark", "peaks.json"))["TPU v5 lite"]
    for kernel in flops:  # all bound by FLOPs at this chip's peaks: 1.40 / 2.09 / 2.79 ms against 0.14 / 0.17 / 0.16
        assert flops[kernel] / peak["bf16_flops_per_s"] > 5 * least[kernel] / peak["hbm_bytes_per_s"]


def test_the_cells_batches_are_next_token_pairs_from_the_slice():
    cell = bench.resolve(CELL)
    first, second = (cell.family.batches(cell.config, dict(cell.traffic, count=2), seed)[0] for seed in (2 ** 31 + 9,) * 2)
    ids, labels, mask = first
    assert ids.shape == labels.shape == (1, 8192) and ids.dtype == labels.dtype and str(ids.dtype) == "int32"
    assert mask is None and (ids[:, 1:] == labels[:, :-1]).all() and (ids == second[0]).all()
    assert 0 <= ids.min() and ids.max() < 8192 and ids.flags["C_CONTIGUOUS"]


def test_the_readers_read_a_trace_made_by_hand():
    cell = bench.resolve(CELL)
    peak = bench.load_json(os.path.join(ROOT, "benchmark", "peaks.json"))["TPU v5 lite"]
    mine = ("attention_share.train", "gqa_share.train", "flash_attention_roofline", "expert_share.train",
            "held_assignments.train", "mlp_share.train")
    read = {name: bench.load_module("readers", name).read for name in mine}
    flops = cell.family.flash_kernel_flops(cell.config, cell.traffic)
    at_peak = {k: v / peak["bf16_flops_per_s"] for k, v in flops.items()}
    trace = {"kind_seconds": {"ragged-dot-none.1": [0.02, 40],
                              "flash_attention_fwd": [20 * 2 * at_peak["flash_attention_fwd"], 20],
                              "flash_attention_bwd_dq": [20 * 3 * at_peak["flash_attention_bwd_dq"], 20],
                              "flash_attention_bwd_dkv": [20 * 2 * at_peak["flash_attention_bwd_dkv"], 20],
                              "bd_flash_attention_fwd": [1.0, 20]},
             "program_runs": 2.5,
             "scopes": {"step_s": 0.1, "scopes": {
                 "forward": {"DecoderBlock/short_conv": 0.01, "DecoderBlock/qkv": 0.001, "DecoderBlock/qk_norm": 0.0002,
                             "DecoderBlock/rope": 0.0004, "DecoderBlock/out_proj": 0.0005, "DecoderBlock/flash": 0.002,
                             "DecoderBlock/norm": 0.001, "DecoderBlock/mlp": 0.004, "DecoderBlock/router": 0.0003,
                             "DecoderBlock/dispatch": 0.0001, "DecoderBlock/experts": 0.0001,
                             "DecoderBlock/combine": 0.0002, "loss/lm_head": 0.002, "EmbeddingSequenceLayer": 0.0005},
                 "backward": {"DecoderBlock/short_conv": 0.02, "DecoderBlock/qkv": 0.002, "DecoderBlock/out_proj": 0.001,
                              "DecoderBlock/flash": 0.005, "DecoderBlock/mlp": 0.008, "DecoderBlock/router": 0.0004,
                              "DecoderBlock/dispatch": 0.0003, "DecoderBlock/experts": 0.0002},
                 "optimizer": {"updater": 0.005, "DecoderBlock/short_conv": 1.0}}}}
    run = {"profiler": {"model_state": {"layer_2/mlp/assigned": [500.0, 520.0], "layer_2/mlp/overflow": [0.0],
                                        "layer_3/mlp/assigned": [480.0, 10.0], "layer_3/mlp/select_bias": [0.0] * 4}}}
    # the convolution's scope, forward and backward, not the updater's
    assert scope_share(trace["scopes"], ("short_conv",)) == pytest.approx(100 * 0.03 / 0.1)
    assert read["attention_share.train"](run, trace, cell, peak) == pytest.approx(100 * 0.007 / 0.1)
    assert read["gqa_share.train"](run, trace, cell, peak) == pytest.approx(
        100 * (0.001 + 0.0002 + 0.0004 + 0.0005 + 0.002 + 0.001) / 0.1)
    assert read["expert_share.train"](run, trace, cell, peak) == pytest.approx(
        100 * (0.0003 + 0.0001 + 0.0001 + 0.0002 + 0.0004 + 0.0003 + 0.0002) / 0.1 + 100 * (0.02 / 2.5) / 0.1)
    assert read["held_assignments.train"](run, trace, cell, peak) == 1510.0
    assert read["mlp_share.train"](run, trace, cell, peak) == pytest.approx(100 * 0.012 / 0.1)  # layer 0's SwiGLU
    # the causal kernels' runs at their FLOPs over peak (all FLOPs-bound) over the seconds they took; not the bd kernel's
    least = 20 * sum(at_peak[k] for k in ("flash_attention_fwd", "flash_attention_bwd_dq", "flash_attention_bwd_dkv"))
    took = 20 * (2 * at_peak["flash_attention_fwd"] + 3 * at_peak["flash_attention_bwd_dq"]
                 + 2 * at_peak["flash_attention_bwd_dkv"])
    assert read["flash_attention_roofline"](run, trace, cell, peak) == pytest.approx(100 * least / took)
    assert 30 < 100 * least / took < 50
    # nothing to read returns nothing, never 0 and never an exception: another model's trace, a trace without
    # scopes, the parent's program (no scope of this operator, no model state in its profiler's report)
    other = {"kind_seconds": {"bd_flash_attention_fwd": [1.0, 20]}, "program_runs": 2.0,
             "scopes": {"step_s": 0.3, "scopes": {"forward": {"DecoderBlock/kda_in": 0.02}, "backward": {},
                                                  "optimizer": {}}}}
    for bare in ({"profiler": {"iterations": 3}}, {"profiler": None}, {}):
        assert all(read[name](bare, other, cell, peak) is None for name in mine)
        assert all(read[name](bare, {"kind_seconds": {}, "scopes": None}, cell, peak) is None for name in mine)
        assert all(read[name](bare, {"kind_seconds": {}}, cell, peak) is None for name in mine)
