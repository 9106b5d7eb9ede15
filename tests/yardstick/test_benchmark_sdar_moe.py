"""The SDAR family's yardstick (CPU, toy sizes): the harness finds the cell
by its name; a toy run through the runner is ``correct`` with the loss and
the routing held to the reference, and its fp8 control, a program that
trains unweighted and one that trains on all positions are not; the
parameter count, FLOPs and the block-diffusion kernels' FLOPs and bytes at
the published cut agree with numbers worked by hand; the five readers the
cell brings read a trace made by hand, ``None`` where nothing ran.
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

CELL = "sdar-30b-a3b-bd-b1-s4096"
TINY = {"family": "sdar_moe", "hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "rope_theta": 1000000, "moe_intermediate_size": 24, "num_experts": 4, "num_experts_per_tok": 4,
        "num_hidden_layers": 2, "layers_here": [0, 1], "published": {"num_hidden_layers": 4},
        "router_width": 16, "held_experts": [4, 4], "held_rows": 1024, "rms_norm_eps": 1e-6, "vocab_size": 96,
        "block_length": 4, "mask_token_id": 95, "initializer_range": 0.02, "embedding_std": 4.0, "mask_embedding_std": 0.02,
        "qk_norm_gain": 1.5, "recompute": {"set_remat": True},
        "optimizer": {"name": "adam", "lr": 2e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8},
        "precision": {"compute": "float32"}}
TRAFFIC = {"runner": "train_fit", "batch": 2, "seq_len": 128, "count": 4, "check_steps": 3, "workers": 1,
           "block_length": 4, "masked_per_row": 80}
# toy-size limits, as the other decoder families' toy runs hold them: the float32 program against the float32
# reference on the CPU reads ~1e-7 / ~1e-6, the fp8 control and the reference with bfloat16 operands far above
LIMITS = {"loss_gap": 1e-5, "delta_norm_gap": 1e-3}
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
HEAD = "layer_4"


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
        family=bench.load_module("families", "sdar_moe"), runner=bench.load_module("runners", "train_fit"),
        end_to_end=manifest["end_to_end"], per_layer=[])


def test_the_harness_finds_the_cell_and_its_files_by_name():
    cell = bench.resolve(CELL)
    assert cell.chips == 1 and cell.config["family"] == "sdar_moe" and cell.traffic["runner"] == "train_fit"
    assert cell.traffic == {"runner": "train_fit", "batch": 1, "seq_len": 4096, "count": 8, "check_steps": 3,
                            "workers": 1, "block_length": 4, "masked_per_row": 2560}
    assert set(cell.limits) <= {"delta_norm_gap", "grad_diff_roundings", "loss_gap", "grad_norm_gap"} and cell.limits
    names = {m["name"] for m in cell.per_layer}
    assert {"mfu.train", "device_idle_share.train", "train_step_roofline", "dispatch_ms.train", "data_wait_share.train",
            "compiles_in_window.train", "fit_unattributed_share.train", "h2d_ms.train", "attention_share.train",
            "bd_flash_attention_roofline", "gqa_share.train", "expert_share.train", "diffusion_head_share.train",
            "held_assignments.train"} == names
    assert [m["name"] for m in cell.end_to_end] == ["train_samples_per_s", "setup_s"]
    # every published width is the catalog's; only depth, the experts held and the vocabulary are cut
    widths = {"hidden_size": 2048, "head_dim": 128, "num_attention_heads": 32, "num_key_value_heads": 4,
              "moe_intermediate_size": 768, "intermediate_size": 6144, "num_experts_per_tok": 8,
              "norm_topk_prob": True, "rope_theta": 1000000, "rms_norm_eps": 1e-6, "decoder_sparse_step": 1,
              "max_position_embeddings": 32768, "tie_word_embeddings": False}
    assert {k: cell.config[k] for k in widths} == widths
    assert set(cell.config["reduced"]) == {"num_hidden_layers", "num_experts", "vocab_size"}
    assert cell.config["published"] == {"num_hidden_layers": 48, "num_experts": 128, "vocab_size": 151936}
    assert (cell.config["num_hidden_layers"], cell.config["num_experts"], cell.config["vocab_size"]) == (8, 8, 18992)
    assert cell.config["layers_here"] == list(range(8)) and cell.config["held_experts"] == [0, 8]
    assert cell.config["router_width"] == 128 and cell.config["held_rows"] == 12288 == 3 * 8192 * 8 * 8 // 128
    assert cell.config["block_length"] == cell.traffic["block_length"] == 4
    assert cell.config["mask_token_id"] == cell.config["vocab_size"] - 1
    manifest = bench.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry, = (c for c in manifest["configs"] if c["name"] == "sdar-30b-a3b-chat")
    assert entry["source"] == cell.config["source"] and sorted(entry["reduced"]) == sorted(cell.config["reduced"])


def test_a_toy_run_is_correct_with_the_loss_and_the_routing_held_to_the_reference():
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
    assert checks["state_diff_median"] <= 0.05  # counters and the loss: a few of ~256 assignments may flip


def followed(loss_fn, cell, seed=5):
    import jax.numpy as jnp
    start = cell.family.init_params(cell.config, seed)
    batches = [tuple(None if a is None else jnp.asarray(a) for a in b)
               for b in cell.family.batches(cell.config, cell.traffic, seed)[:3]]
    return reference_train.follow(loss_fn, start[0], start[1], batches, cell.config["optimizer"])


@pytest.mark.parametrize("fault", ["unweighted", "all_positions", "half_of_the_labels"])
def test_a_program_that_trains_unweighted_or_on_all_positions_is_not_correct(fault):
    """Three programs a builder could write by mistake, each put in the
    program's place: the masked positions' mean without the blocks' weights
    B / m_b; a loss over every position of the noisy half (the unmasked ones
    predict their own visible token); half of the trained positions left
    out (the calibration's planted fault,
    ``calibrate_half_labels.half_labels``). None stays within the limits."""
    import jax
    import jax.numpy as jnp

    from benchmark import calibrate_half_labels
    cell = tiny_cell()
    ctx = types.SimpleNamespace(config=cell.config, traffic=cell.traffic, family=cell.family, seed=5)
    want = cell.runner.reference_readings(ctx)
    family, s, keys = cell.family, cell.family._sizes(cell.config), cell.family._keys(cell.family._sizes(cell.config))
    sound = family.reference_loss(cell.config)
    if fault == "half_of_the_labels":
        got = cell.runner.reference_readings(ctx, transform=calibrate_half_labels.half_labels)
    else:
        def faulty(params, state, batch, mm, conv):
            ids, labels, _ = batch
            t = labels.shape[1]
            x = params[keys["embed"]]["W"][ids]
            new = {}
            for key in keys["blocks"]:
                x, new[key] = family._block(x, params[key], state[key], s, mm)
            logp = jax.nn.log_softmax(mm(family._rms_norm(x[:, :t], params[keys["norm"]]["w"], s["eps"]),
                                         params[keys["head"]]["W"]), -1)
            if fault == "unweighted":
                nll = -jnp.take_along_axis(logp, jnp.maximum(labels, 0)[..., None], -1)[..., 0]
                loss = jnp.sum(jnp.where(labels >= 0, nll, 0.0)) / jnp.sum(labels >= 0)
            else:
                loss = -jnp.mean(jnp.take_along_axis(logp, ids[:, t:, None], -1))
            new[keys["head"]] = {"diffusion_loss": loss, "masked_positions": jnp.float32(80.0)}
            return loss, new

        got = followed(faulty, cell)
    checks = reference_train.compare(got, want)
    assert not all(checks[name] <= limit for name, limit in LIMITS.items()), checks
    assert checks["delta_norm_gap"] > 20 * LIMITS["delta_norm_gap"] or checks["loss_gap"] > 0.05, checks
    assert reference_train.compare(followed(sound, cell), want)["delta_norm_gap"] <= 1e-6


def test_parameters_flops_and_the_bd_kernels_at_the_published_cut_against_numbers_worked_by_hand():
    cell = bench.resolve(CELL)
    family, config, traffic = cell.family, cell.config, cell.traffic
    d, t, vocab = 2048, 4096, 18992
    attention = 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048
    assert attention == 18874368
    layer = attention + 2 * 128 + 2 * d + d * 128 + 8 * 3 * d * 768
    assert layer == 18874368 + 256 + 4096 + 262144 + 8 * 4718592 == 56889600
    total = 8 * layer + 2 * vocab * d + d
    assert family.n_params(config) == total == 532910080 == config["bytes"]["parameters"]
    assert 16 * total == config["bytes"]["train_state_bytes"] == 8526561280  # 50.5% of 16.9e9
    assert 16 * total / 16.9e9 == pytest.approx(config["bytes"]["share_of_16.9e9"], abs=1e-4)
    # forward FLOPs: the projections and the router over 8192 positions, attention over T^2 + T B allowed entries,
    # 8 x 8 / 128 routed assignments a position, the head over the 4096 noisy positions
    allowed = t * t + t * 4
    projections = 8 * 2 * attention * 2 * t
    flash = 8 * 4 * 128 * 32 * allowed
    router = 8 * 2 * d * 128 * 2 * t
    experts = 8 * 2 * 3 * d * 768 * 2 * t * 8 * 8 / 128
    head = 2 * d * vocab * t
    assert (3 * projections, 3 * flash, 3 * experts, 3 * head) == pytest.approx((7.42e12, 6.60e12, 0.928e12, 0.956e12),
                                                                                 rel=2e-3)
    got = family.flops_per_step(config, traffic)
    assert got == pytest.approx(3 * (projections + flash + router + experts + head), rel=1e-12)
    assert got == pytest.approx(16.0e12, rel=5e-3) and 3 * flash / got == pytest.approx(0.412, abs=3e-3)
    assert family.least_bytes_per_step(config, traffic) == 2 * 12 * total + 3 * 4 * t
    assert family.samples_per_step(traffic) == 1
    # the kernels by hand: 32 heads x (T^2 + T B) pairs; forward q k^T and p v, dq pass three matmuls, dk/dv pass four,
    # all against heads of 128; q, o, do, dq once a query head, k, v, dk, dv once a key/value head, in bf16
    pairs = 32 * allowed
    flops = family.bd_flash_kernel_flops(config, traffic)
    assert set(flops) == set(family.BD_FLASH_KERNELS) == set(family.bd_flash_kernel_bytes(config, traffic))
    assert flops["bd_flash_attention_fwd"] == 2 * pairs * 256 == pytest.approx(275.1e9, rel=1e-3)
    assert flops["bd_flash_attention_bwd_dq"] == 2 * pairs * 384 and flops["bd_flash_attention_bwd_dkv"] == 2 * pairs * 512
    least = family.bd_flash_kernel_bytes(config, traffic)
    q_rows, kv_rows, stat = 32 * 8192 * 128 * 2, 4 * 8192 * 128 * 2, 32 * 8192 * 32
    assert least["bd_flash_attention_fwd"] == 2 * q_rows + 2 * kv_rows + stat
    assert least["bd_flash_attention_bwd_dq"] == 3 * q_rows + 2 * kv_rows + 2 * stat
    assert least["bd_flash_attention_bwd_dkv"] == 2 * q_rows + 4 * kv_rows + 2 * stat
    peak = bench.load_json(os.path.join(ROOT, "benchmark", "peaks.json"))["TPU v5 lite"]
    for kernel in flops:  # all bound by FLOPs at this chip's peaks: 1.40 / 2.09 / 2.79 ms against 0.19 / 0.28 / 0.22
        assert flops[kernel] / peak["bf16_flops_per_s"] > 5 * least[kernel] / peak["hbm_bytes_per_s"]


def test_the_cells_batches_are_a_noisy_and_a_clean_row_with_2560_masked_positions_whatever_the_seed():
    cell = bench.resolve(CELL)
    for seed in (2 ** 31 + 9, 3):
        first, second = (cell.family.batches(cell.config, dict(cell.traffic, count=2), seed) for _ in range(2))
        for (ids, labels, mask), (again, _, _) in zip(first, second):
            assert ids.shape == (1, 8192) and labels.shape == (1, 4096) and mask is None
            assert str(ids.dtype) == str(labels.dtype) == "int32" and (ids == again).all()
            noisy, clean, masked = ids[:, :4096], ids[:, 4096:], labels >= 0
            assert masked.sum() == 2560 and (noisy[masked] == 18991).all() and (noisy[~masked] == clean[~masked]).all()
            assert (labels[masked] == clean[masked]).all() and (labels[~masked] == -1).all()
            assert 0 <= clean.min() and clean.max() <= 18990
            per_block = masked.reshape(1024, 4).sum(-1)
            assert all((per_block == m).sum() == 256 for m in (1, 2, 3, 4))


def test_the_five_readers_read_a_trace_made_by_hand():
    cell = bench.resolve(CELL)
    peak = bench.load_json(os.path.join(ROOT, "benchmark", "peaks.json"))["TPU v5 lite"]
    read = {m["name"]: bench.load_module("readers", m["name"]).read for m in cell.per_layer
            if m.get("workloads") == [CELL]}
    assert set(read) == {"bd_flash_attention_roofline", "gqa_share.train", "expert_share.train",
                         "diffusion_head_share.train", "held_assignments.train"}
    flops = cell.family.bd_flash_kernel_flops(cell.config, cell.traffic)
    at_peak = {k: v / peak["bf16_flops_per_s"] for k, v in flops.items()}
    trace = {"kind_seconds": {"ragged-dot-none.1": [0.03, 40], "ragged-dot-none.2": [0.01, 20],
                              "bd_flash_attention_fwd": [40 * 2 * at_peak["bd_flash_attention_fwd"], 40],
                              "bd_flash_attention_bwd_dq": [20 * 4 * at_peak["bd_flash_attention_bwd_dq"], 20],
                              "bd_flash_attention_bwd_dkv": [20 * 4 * at_peak["bd_flash_attention_bwd_dkv"], 20],
                              "flash_attention_fwd": [1.0, 20]},
             "program_runs": 2.5,
             "scopes": {"step_s": 0.2, "scopes": {
                 "forward": {"DecoderBlock/qkv": 0.01, "DecoderBlock/qk_norm": 0.002, "DecoderBlock/rope": 0.004,
                             "DecoderBlock/out_proj": 0.005, "DecoderBlock/flash": 0.02, "DecoderBlock/norm": 0.002,
                             "DecoderBlock/router": 0.003, "DecoderBlock/dispatch": 0.001, "DecoderBlock/experts": 0.0005,
                             "DecoderBlock/combine": 0.002, "loss/lm_head": 0.004, "loss": 0.001,
                             "EmbeddingSequenceLayer": 0.001},
                 "backward": {"DecoderBlock/qkv": 0.02, "DecoderBlock/qk_norm": 0.004, "DecoderBlock/rope": 0.008,
                              "DecoderBlock/out_proj": 0.01, "DecoderBlock/flash": 0.05, "DecoderBlock/router": 0.004,
                              "DecoderBlock/dispatch": 0.003, "DecoderBlock/experts": 0.001, "loss/lm_head": 0.008,
                              "loss": 0.002},
                 "optimizer": {"updater": 0.01, "loss": 1.0}}}}
    run = {"profiler": {"model_state": {"layer_1/mlp/assigned": [500.0, 520.0], "layer_1/mlp/overflow": [0.0],
                                        "layer_2/mlp/assigned": [480.0, 10.0], "layer_9/diffusion_loss": [9.0]}}}
    # the three kernels' runs at their FLOPs over peak (all FLOPs-bound), over the seconds they took
    least = 40 * at_peak["bd_flash_attention_fwd"] + 20 * (at_peak["bd_flash_attention_bwd_dq"]
                                                           + at_peak["bd_flash_attention_bwd_dkv"])
    took = sum(trace["kind_seconds"][k][0] for k in flops)
    assert read["bd_flash_attention_roofline"](run, trace, cell, peak) == pytest.approx(100 * least / took)
    assert 25 < 100 * least / took < 50  # the causal kernel's second of device time is not in it
    assert read["gqa_share.train"](run, trace, cell, peak) == pytest.approx(
        100 * (0.01 + 0.002 + 0.004 + 0.005 + 0.02 + 0.004 + 0.008 + 0.01) / 0.2)
    # the scopes and the grouped matmuls by kind over the step program's runs
    assert read["expert_share.train"](run, trace, cell, peak) == pytest.approx(
        100 * (0.003 + 0.001 + 0.0005 + 0.002 + 0.004 + 0.003 + 0.001) / 0.2 + 100 * (0.04 / 2.5) / 0.2)
    assert read["diffusion_head_share.train"](run, trace, cell, peak) == pytest.approx(
        100 * (0.004 + 0.001 + 0.008 + 0.002) / 0.2)
    assert read["held_assignments.train"](run, trace, cell, peak) == 1510.0
    # nothing to read returns nothing, never 0 and never an exception: another model's trace, a trace without
    # scopes, the parent's program (no scopes of this mixer, no model state in its profiler's report, no profiler)
    other = {"kind_seconds": {"flash_attention_fwd": [1.0, 20]}, "program_runs": 2.0,
             "scopes": {"step_s": 0.3, "scopes": {"forward": {"DecoderBlock/kda_in": 0.02, "DecoderBlock/flash": 0.007},
                                                  "backward": {}, "optimizer": {}}}}
    for bare in ({"profiler": {"iterations": 3}}, {"profiler": None}, {}):
        assert all(fn(bare, other, cell, peak) is None for fn in read.values())
        assert all(fn(bare, {"kind_seconds": {}, "scopes": None}, cell, peak) is None for fn in read.values())
        assert all(fn(bare, {"kind_seconds": {}}, cell, peak) is None for fn in read.values())
