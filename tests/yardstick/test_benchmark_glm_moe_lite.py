"""The GLM-4.7-Flash family's yardstick (CPU, toy sizes): the harness finds
the cell by its name; a toy run through the runner is ``correct`` with both
loss terms held to the reference, and its fp8 control is not; the
parameter count, FLOPs and the flash kernels' FLOPs and bytes at the
published cut agree with numbers worked by hand; the two readers the cell
brings read a trace made by hand, ``None`` where nothing ran.
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

CELL = "glm-4.7-flash-pt-b1-s8192"
TINY = {"family": "glm_moe_lite", "hidden_size": 32, "num_attention_heads": 2, "q_lora_rank": 12, "kv_lora_rank": 16,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 24, "rope_theta": 1000000,
        "intermediate_size": 64, "moe_intermediate_size": 24, "first_k_dense_replace": 1,
        "num_hidden_layers": 4, "layers_here": [0, 1, 2, 7], "published": {"num_hidden_layers": 7},
        "num_nextn_predict_layers": 1, "mtp_loss_weight": 0.3,
        "router_width": 16, "held_experts": [4, 4], "held_rows": 512, "num_experts_per_tok": 4,
        "n_shared_experts": 1, "routed_scaling_factor": 1.8, "rms_norm_eps": 1e-5, "vocab_size": 96,
        "initializer_range": 0.02, "recompute": {"set_remat": True},
        "optimizer": {"name": "adam", "lr": 2e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8},
        "precision": {"compute": "float32"}}
TRAFFIC = {"runner": "train_fit", "batch": 2, "seq_len": 128, "count": 4, "check_steps": 3, "workers": 1}
# toy-size limits, as the Kimi family's toy run holds them: the float32 program against the float32
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
        family=bench.load_module("families", "glm_moe_lite"), runner=bench.load_module("runners", "train_fit"),
        end_to_end=manifest["end_to_end"], per_layer=[])


def test_the_harness_finds_the_cell_and_its_files_by_name():
    cell = bench.resolve(CELL)
    assert cell.chips == 1 and cell.config["family"] == "glm_moe_lite" and cell.traffic["runner"] == "train_fit"
    assert cell.traffic == bench.resolve("kimi-linear-pt-b1-s8192").traffic  # the traffic file the Kimi cell uses
    assert set(cell.limits) <= {"delta_norm_gap", "grad_diff_roundings", "loss_gap", "grad_norm_gap"} and cell.limits
    names = {m["name"] for m in cell.per_layer}
    assert {"mfu.train", "device_idle_share.train", "train_step_roofline", "dispatch_ms.train", "data_wait_share.train",
            "compiles_in_window.train", "fit_unattributed_share.train", "h2d_ms.train", "attention_share.train",
            "mla_share.train", "mtp_share.train"} == names
    assert [m["name"] for m in cell.end_to_end] == ["train_samples_per_s", "setup_s"]
    # every published width is the catalog's; only depth, the experts held and the vocabulary are cut
    widths = {"hidden_size": 2048, "intermediate_size": 10240, "moe_intermediate_size": 1536, "num_attention_heads": 20,
              "q_lora_rank": 768, "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
              "v_head_dim": 256, "num_experts_per_tok": 4, "n_shared_experts": 1, "routed_scaling_factor": 1.8,
              "rope_theta": 1000000, "first_k_dense_replace": 1, "num_nextn_predict_layers": 1}
    assert {k: cell.config[k] for k in widths} == widths
    assert set(cell.config["reduced"]) == {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert cell.config["published"] == {"num_hidden_layers": 47, "n_routed_experts": 64, "vocab_size": 154880}
    assert (cell.config["num_hidden_layers"], cell.config["n_routed_experts"], cell.config["vocab_size"]) == (5, 8, 19360)
    assert cell.config["layers_here"] == [0, 1, 2, 3, 47] and cell.config["held_experts"] == [0, 8]


def test_a_toy_run_is_correct_with_both_loss_terms_and_the_routing_held_to_the_reference():
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
    assert checks["state_diff_median"] <= 0.05  # counters and the two terms: a few of ~128 assignments may flip


def test_a_run_that_trains_on_the_main_term_alone_is_not_correct():
    """The two recorded terms are state leaves, so ``delta_norm_gap`` holds
    each to the reference's: a program without the second loss reads 1 on
    ``mtp_loss`` (a state left unchanged), whatever the total's gap."""
    cell = tiny_cell()
    ctx = types.SimpleNamespace(config=cell.config, traffic=cell.traffic, family=cell.family, seed=5)
    want = cell.runner.reference_readings(ctx)
    loss_fn = cell.family.reference_loss(cell.config)

    def main_only(params, state, batch, mm, conv):
        _, new = loss_fn(params, state, batch, mm, conv)
        new = dict(new, layer_4=dict(new["layer_4"], mtp_loss=state["layer_4"]["mtp_loss"],
                                     _aux_loss=state["layer_4"]["_aux_loss"]))
        return new["layer_6"]["main_loss"], new

    start = cell.family.init_params(cell.config, 5)
    import jax.numpy as jnp
    batches = [tuple(None if a is None else jnp.asarray(a) for a in b)
               for b in cell.family.batches(cell.config, cell.traffic, 5)[:3]]
    got = reference_train.follow(main_only, start[0], start[1], batches, cell.config["optimizer"])
    checks = reference_train.compare(got, want)
    assert checks["delta_norm_gap"] >= 0.99 and checks["loss_gap"] > 0.1, checks


def test_parameters_flops_and_the_flash_kernels_at_the_published_cut_against_numbers_worked_by_hand():
    cell = bench.resolve(CELL)
    family, config, traffic = cell.family, cell.config, cell.traffic
    d, t, vocab = 2048, 8192, 19360
    attention = 2048 * 768 + 768 + 768 * 20 * 256 + 2048 * 576 + 512 + 512 * 20 * 448 + 20 * 256 * 2048
    assert attention == 1572864 + 768 + 3932160 + 1179648 + 512 + 4587520 + 10485760 == 21759232
    dense_block = attention + 2 * d + 3 * d * 10240
    expert_block = attention + 2 * d + d * 64 + 8 * 3 * d * 1536 + 3 * d * 1536
    mtp_own = 2 * d * d + 3 * d
    assert (dense_block, expert_block, mtp_own) == (84677888, 106829056, 8394752)
    total = dense_block + 4 * expert_block + mtp_own + 2 * vocab * d + d  # embedding and head once each
    assert family.n_params(config) == total == 599689472
    assert 16 * total == pytest.approx(9.595e9, rel=1e-3)  # parameters, moments and a gradient: 56.8% of 16.9e9
    # forward FLOPs a token: weights at 2 x in x out, the causal half of 8192 keys against heads of 256 and 256,
    # 4 x 8 / 64 routed assignments + the shared expert, the head twice (T and T - 1 positions), W_eh
    mla = 2 * (attention - 768 - 512) + 2 * 512 * 20 * t / 2
    moe = 2 * d * 64 + 2 * 3 * d * 1536 * 1.5
    per_token = 5 * mla + 2 * 3 * d * 10240 + 4 * moe + 2 * d * vocab * (2 - 1 / t) + 2 * 2 * d * d
    assert per_token == pytest.approx(1052.5e6, rel=1e-3)
    got = family.flops_per_step(config, traffic)
    assert got == pytest.approx(3 * per_token * t, rel=1e-12) and got == pytest.approx(25.87e12, rel=1e-3)
    flash_share = 3 * 5 * 2 * 512 * 20 * t / 2 * t / got  # the T x T part, as the kernels' own FLOPs count it
    assert flash_share == pytest.approx(0.398, abs=2e-3)
    assert family.least_bytes_per_step(config, traffic) == 2 * 12 * total + 2 * 4 * t
    assert family.samples_per_step(traffic) == 1
    # the kernels by hand: 20 heads x 8192^2 / 2 pairs; forward q k^T (256) and p v (256), dq pass 256 + 256 + 256,
    # dk/dv pass 256 + 256 + 256 + 256; operands in bf16, row statistics 8 float32 lanes a row
    pairs, rows = 20 * t ** 2 / 2, 20 * t
    flops = family.flash_kernel_flops(config, traffic)
    assert set(flops) == set(family.FLASH_KERNELS) == set(family.flash_kernel_bytes(config, traffic))
    assert flops["flash_attention_fwd"] == 2 * pairs * 512 and flops["flash_attention_bwd_dq_chunked"] == 2 * pairs * 768
    assert flops["flash_attention_bwd_dkv"] == flops["flash_attention_bwd_dkv_chunked"] == 2 * pairs * 1024
    least = family.flash_kernel_bytes(config, traffic)
    assert least["flash_attention_fwd"] == rows * (4 * 256 * 2 + 32)
    assert least["flash_attention_bwd_dq_chunked"] == rows * (5 * 256 * 2 + 64)
    assert least["flash_attention_bwd_dkv_chunked"] == rows * (6 * 256 * 2 + 64)
    peak = bench.load_json(os.path.join(ROOT, "benchmark", "peaks.json"))["TPU v5 lite"]
    for kernel in flops:  # all bound by FLOPs at this chip's peaks: 3.49 / 5.23 / 6.98 ms against 0.42 / 0.52 / 0.63
        assert flops[kernel] / peak["bf16_flops_per_s"] > 5 * least[kernel] / peak["hbm_bytes_per_s"]


def test_the_cells_batches_are_next_token_pairs_from_the_slice():
    cell = bench.resolve(CELL)
    first, second = (cell.family.batches(cell.config, dict(cell.traffic, count=2), seed)[0] for seed in (2 ** 31 + 9,) * 2)
    ids, labels, mask = first
    assert ids.shape == labels.shape == (1, 8192) and ids.dtype == labels.dtype and str(ids.dtype) == "int32"
    assert mask is None and (ids[:, 1:] == labels[:, :-1]).all() and (ids == second[0]).all()
    assert 0 <= ids.min() and ids.max() < 19360 and ids.flags["C_CONTIGUOUS"]


def test_the_two_readers_read_a_trace_made_by_hand():
    cell = bench.resolve(CELL)
    peak = bench.load_json(os.path.join(ROOT, "benchmark", "peaks.json"))["TPU v5 lite"]
    read = {m["name"]: bench.load_module("readers", m["name"]).read for m in cell.per_layer
            if m.get("workloads") == [CELL]}
    assert set(read) == {"mla_share.train", "mtp_share.train"}
    trace = {"kind_seconds": {"ragged-dot-none": [0.01, 40]}, "program_runs": 2.5,
             "scopes": {"step_s": 0.5, "scopes": {
                 "forward": {"DecoderBlock/mla_qkv": 0.012, "DecoderBlock/rope": 0.004, "DecoderBlock/mla_out": 0.006,
                             "DecoderBlock/flash": 0.03, "DecoderBlock/norm": 0.002,
                             "MultiTokenPrediction/mtp_in": 0.003, "MultiTokenPrediction/mla_qkv": 0.003,
                             "MultiTokenPrediction/rope": 0.001, "MultiTokenPrediction/flash": 0.0075,
                             "MultiTokenPrediction/lm_head": 0.01, "MultiTokenPrediction": 0.0005,
                             "loss/lm_head": 0.01, "EmbeddingSequenceLayer": 0.001},
                 "backward": {"DecoderBlock/mla_qkv": 0.03, "DecoderBlock/rope": 0.008, "DecoderBlock/mla_out": 0.012,
                              "MultiTokenPrediction/mla_qkv": 0.0075, "MultiTokenPrediction/mla_out": 0.003,
                              "MultiTokenPrediction/lm_head": 0.02, "MultiTokenPrediction/experts": 0.001},
                 "optimizer": {"updater": 0.01, "MultiTokenPrediction/never": 1.0}}}}
    # the projections and rotary of the trunk's blocks and of the prediction layer's, forward and backward; not flash
    assert read["mla_share.train"](None, trace, cell, peak) == pytest.approx(
        100 * (0.012 + 0.004 + 0.006 + 0.003 + 0.001 + 0.03 + 0.008 + 0.012 + 0.0075 + 0.003) / 0.5)
    # everything whose first component is the prediction layer's name, the bare one too; the trunk's head is not
    assert read["mtp_share.train"](None, trace, cell, peak) == pytest.approx(
        100 * (0.003 + 0.003 + 0.001 + 0.0075 + 0.01 + 0.0005 + 0.0075 + 0.003 + 0.02 + 0.001) / 0.5)
    # nothing to read returns nothing, never 0: another model's trace, a trace without scopes (the parent's program)
    kimi = {"kind_seconds": {}, "scopes": {"step_s": 0.3, "scopes": {
        "forward": {"DecoderBlock/kda_in": 0.02, "DecoderBlock/flash": 0.007}, "backward": {}, "optimizer": {}}}}
    assert all(fn(None, kimi, cell, peak) is None for fn in read.values())
    assert all(fn(None, {"kind_seconds": {}, "scopes": None}, cell, peak) is None for fn in read.values())
    assert all(fn(None, {"kind_seconds": {}}, cell, peak) is None for fn in read.values())
