"""``chip_smoke.py`` off the chip (ISSUE 21).

The script itself has no CPU mode: run here it must refuse, naming what it
found. Its check functions are imported and rehearsed at a tiny preset on
the CPU mesh with interpreted kernels — the rehearsal that keeps a chip
call from being spent on a typo. One process per chip: every launcher's
spawn environment pins its children to the CPU.
"""

import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402

from deeplearning4j_tpu.ops.pallas import flash_attention as fa  # noqa: E402
from deeplearning4j_tpu.runtime import compile_cache  # noqa: E402
from deeplearning4j_tpu.runtime.environment import get_environment  # noqa: E402
from deeplearning4j_tpu.train.updaters import Adam  # noqa: E402


def _run_script(**env):
    # the suite itself runs with interpreted kernels (tests/test_pallas.py
    # exports the variable at collection); the child starts without it
    base = {k: v for k, v in os.environ.items()
            if k != "DL4J_TPU_PALLAS_INTERPRET"}
    return subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")], cwd=str(REPO),
        env=dict(base, **env), capture_output=True, text=True, timeout=240)


def test_script_refuses_the_cpu_naming_the_platform():
    proc = _run_script(JAX_PLATFORMS="cpu")
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr, proc.stderr[-2000:]
    assert '"ok"' not in proc.stdout  # no result line


def test_script_refuses_interpreted_kernels():
    proc = _run_script(JAX_PLATFORMS="cpu", DL4J_TPU_PALLAS_INTERPRET="1")
    assert proc.returncode != 0
    assert "DL4J_TPU_PALLAS_INTERPRET" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_checks_rehearse_at_a_tiny_preset(tmp_path, monkeypatch):
    """Every phase of the smoke, four-device leg included, on the 8-device
    CPU mesh: same code path as the chip run, sizes cut, kernels
    interpreted, no Mosaic call expected."""
    monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
    # the chunked flash backward at a length the interpreter can afford
    monkeypatch.setattr(fa, "BWD_CHUNK_THRESHOLD", 256)
    monkeypatch.setattr(fa, "BWD_CHUNK", 512)
    env = get_environment()
    compute_dtype = env.compute_dtype
    compile_cache.enable(str(tmp_path / "cache"))  # the compile counters
    preset = chip_smoke.Preset(
        platform="cpu", expect_mosaic=False,
        bert=dict(vocab_size=500, d_model=64, n_layers=1, n_heads=2,
                  ffn_size=128, max_len=32, updater=Adam(1e-3)),
        batch=8, seq=16, train_batches=4, train_epochs=2,
        serve_rows=(1,), max_batch_size=1,
        rnn_t=8, rnn_b=8, rnn_h=128, rnn_vocab=20, rnn_steps=3,
        attn_shape=(1, 1, 128, 64), attn_long=(1, 1, 512, 64),
        attn_fused=(2, 128, 2, 64),
        kimi_seq=128)
    report = {"phases": {}}
    # a fallback an earlier test of this process left behind is not this
    # run's: the smoke reads the counter's change over its own run
    compile_cache.STATS.record("aot_fallbacks")
    try:
        chip_smoke.run(preset, report, str(tmp_path))
    finally:
        compile_cache.disable()
        env.set_compute_dtype(compute_dtype)
    assert set(report["phases"]) == {"kernels", "char_rnn", "bert_train",
                                     "bert_serve", "kimi_linear",
                                     "glm_moe_lite",
                                     "four_chips"}
    assert report["kimi_linear"]["last_loss"] < report["kimi_linear"]["first_loss"]
    glm = report["glm_moe_lite"]
    assert glm["last_loss"] < glm["first_loss"] and glm["main_loss"] > 0 and glm["mtp_loss"] > 0
    # the delta rule's kernel pair (interpreted here) against its XLA form
    assert report["kimi_linear"]["chunk_kda"]["bwd_err"] < 0.05
    assert set(report["kernels"]) == {
        "fused_lstm", "fused_lstm_graves", "fused_gru", "flash_padding_mask",
        "flash_causal", "flash_causal_chunked", "fused_attention"}
    assert report["bert_serve"]["replicas"] == 8
    assert all(n > 0 for n in report["bert_serve"]["replica_batches"])
    assert report["compile_cache"]["aot_fallbacks"] == 0


def test_interpreter_is_refused_on_a_tpu_backend(monkeypatch):
    """A compiled run never enters the interpreter: the variable that
    selects it is an error where the backend is a TPU."""
    import jax

    from deeplearning4j_tpu.ops.pallas import common
    monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
    assert common.interpret_mode() and common.kernels_available()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="DL4J_TPU_PALLAS_INTERPRET"):
        common.interpret_mode()
    monkeypatch.delenv("DL4J_TPU_PALLAS_INTERPRET")
    assert not common.interpret_mode() and common.kernels_available()
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert not common.kernels_available()


# ------------------------------------------------------ one process per chip
def test_every_launcher_pins_its_children_to_the_cpu(monkeypatch):
    """A chip belongs to one process. Whatever the parent's environment
    says, the children of DistributedSupervisor, FleetSupervisor and
    RouterSupervisor are CPU processes: none can take the chip from a
    parent that holds it."""
    from deeplearning4j_tpu.serving import control_plane, fleet
    from deeplearning4j_tpu.train import distributed

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    env = distributed.worker_env()
    assert env["JAX_PLATFORMS"] == "cpu" and "XLA_FLAGS" not in env
    assert str(REPO) in env["PYTHONPATH"].split(os.pathsep)
    sup = distributed.DistributedSupervisor(lambda rank, port: [], 2, [])
    assert sup.env is None  # _launch falls through to worker_env()

    spec = fleet.WorkerSpec(worker_id="w0", model_name="m",
                            archive="a.zip", host_device_count=2)
    env = fleet._worker_env(spec)
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env["XLA_FLAGS"] == "--xla_force_host_platform_device_count=2"
    # routers spawn through the same FleetSupervisor path
    assert control_plane.RouterSupervisor._spawn is fleet.FleetSupervisor._spawn
    rspec = control_plane.RouterSpec(router_id="r0", config_path="c.json")
    assert fleet._worker_env(rspec)["JAX_PLATFORMS"] == "cpu"
