"""Fault tolerance x multihost, integrated (VERDICT r2 item 6).

Two localhost processes train data-parallel through
``initialize_multihost`` with periodic checkpoints; the supervisor (this
test) watches per-worker heartbeat files through ``HeartbeatMonitor``.
Mid-training worker 1 is killed (simulated chip/host loss). The SPMD step
is all-or-nothing, so worker 0 stalls in the allreduce and its heartbeat
goes stale -> the monitor raises, the supervisor kills the survivor,
re-forms the mesh on a fresh coordinator port, and the restarted workers
restore the newest checkpoint and finish. The final weights must match an
uninterrupted single-process run exactly (deterministic per-epoch data).

This is the TPU-native analog of the reference's MeshOrganizer
heartbeat + node-remap + restart-round story (SURVEY.md §5.3): membership
change == restart round from checkpoint.
"""

import json
import subprocess
import sys
import time

import numpy as np
import pytest

from deeplearning4j_tpu.train.fault_tolerance import (HeartbeatMonitor,
                                                      TrainingFailure)

_WORKER = r"""
import json, os, sys, tempfile
import numpy as np

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.runtime.mesh import initialize_multihost

pid = int(sys.argv[1]); nproc = int(sys.argv[2]); port = sys.argv[3]
ckpt_dir = sys.argv[4]; total_epochs = int(sys.argv[5])
crash_at = int(sys.argv[6]); hb_file = sys.argv[7]

initialize_multihost(coordinator_address=f"127.0.0.1:{port}",
                     num_processes=nproc, process_id=pid)
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
mesh = Mesh(np.asarray(jax.devices()).reshape(-1), ("dp",))

rng = np.random.default_rng(0)
W0 = rng.normal(0, 0.5, (8, 4)).astype(np.float32)

ckpt = os.path.join(ckpt_dir, "state.npz")
if os.path.exists(ckpt):
    blob = np.load(ckpt)
    W, start_epoch = blob["W"], int(blob["epoch"]) + 1
else:
    W, start_epoch = W0, 0
W = jnp.asarray(W)

def loss(w, x, y):
    p = jax.nn.log_softmax(x @ w)
    return -jnp.mean(jnp.sum(p * y, axis=-1))

step = jax.jit(lambda w, x, y: w - 0.1 * jax.grad(loss)(w, x, y))
xsh = NamedSharding(mesh, P("dp", None))
n_local = 16 // nproc
losses = []
for epoch in range(start_epoch, total_epochs):
    if pid == 1 and epoch == crash_at:
        os._exit(17)  # simulated worker death mid-round
    erng = np.random.default_rng(100 + epoch)  # deterministic per-epoch data
    X = erng.normal(0, 1, (16, 8)).astype(np.float32)
    Y = np.eye(4, dtype=np.float32)[erng.integers(0, 4, 16)]
    lo = pid * n_local
    x_g = jax.make_array_from_process_local_data(xsh, X[lo:lo + n_local])
    y_g = jax.make_array_from_process_local_data(xsh, Y[lo:lo + n_local])
    W = step(W, x_g, y_g)
    losses.append(float(loss(W, x_g, y_g)))   # forces the step to finish
    with open(hb_file, "w") as f:              # heartbeat AFTER real progress
        f.write(str(epoch))
    if pid == 0:  # checkpoint each completed round, atomically
        Wh = np.asarray(jax.device_get(W))
        tmp = ckpt + ".tmp.npz"
        np.savez(tmp, W=Wh, epoch=epoch)
        os.replace(tmp, ckpt)
print("DONE" + json.dumps({"W": np.asarray(jax.device_get(W)).tolist(),
                           "losses": losses}))
"""


def _free_port():
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return str(s.getsockname()[1])


def _launch(wfile, env, port, ckpt_dir, epochs, crash_at, hb_files):
    return [subprocess.Popen(
        [sys.executable, str(wfile), str(pid), "2", port, str(ckpt_dir),
         str(epochs), str(crash_at), str(hb_files[pid])],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env, text=True)
        for pid in range(2)]


@pytest.mark.slow
def test_worker_death_detected_restored_and_completes(tmp_path):
    wfile = tmp_path / "worker.py"
    wfile.write_text(_WORKER)
    ckpt_dir = tmp_path / "ckpts"
    ckpt_dir.mkdir()
    hb_files = [tmp_path / f"hb{i}" for i in range(2)]
    from deeplearning4j_tpu.train.distributed import worker_env
    env = worker_env()

    EPOCHS, CRASH_AT = 6, 3

    # ---- round 1: worker 1 dies at epoch 3; monitor must notice ----
    procs = _launch(wfile, env, _free_port(), ckpt_dir, EPOCHS, CRASH_AT,
                    hb_files)
    monitor = HeartbeatMonitor(timeout_s=25.0)
    seen = {}
    failure = None
    deadline = time.time() + 240
    try:
        while time.time() < deadline:
            for i, hb in enumerate(hb_files):
                if hb.exists():
                    m = hb.stat().st_mtime
                    if seen.get(i) != m:
                        seen[i] = m
                        monitor.beat()  # any worker progressing = alive
            if any(p.poll() not in (None, 0) for p in procs):
                failure = TrainingFailure("worker process died")
                break
            try:
                monitor.check()
            except TrainingFailure as e:  # survivor stalled in allreduce
                failure = e
                break
            if all(p.poll() == 0 for p in procs):
                break
            time.sleep(0.5)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
            p.communicate(timeout=60)
    assert failure is not None, \
        "the killed worker must be detected (exit or stale heartbeat)"
    # progress up to the crash round was checkpointed
    assert (ckpt_dir / "state.npz").exists()
    assert int(np.load(ckpt_dir / "state.npz")["epoch"]) == CRASH_AT - 1

    # ---- round 2: re-form the mesh, restore, finish ----
    procs = _launch(wfile, env, _free_port(), ckpt_dir, EPOCHS, -1, hb_files)
    outs = []
    for p in procs:
        out, err = p.communicate(timeout=240)
        assert p.returncode == 0, f"restarted worker failed:\n{err[-3000:]}"
        line = [l for l in out.splitlines() if l.startswith("DONE")]
        assert line, out
        outs.append(json.loads(line[0][4:]))
    W_final = np.asarray(outs[0]["W"])
    np.testing.assert_array_equal(W_final, np.asarray(outs[1]["W"]))
    # restarted run resumed at the right epoch (3 remaining rounds)
    assert len(outs[0]["losses"]) == EPOCHS - CRASH_AT

    # ---- oracle: uninterrupted single-process run of the same schedule ----
    import jax
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    W = jnp.asarray(rng.normal(0, 0.5, (8, 4)).astype(np.float32))

    def loss(w, x, y):
        p = jax.nn.log_softmax(x @ w)
        return -jnp.mean(jnp.sum(p * y, axis=-1))

    step = jax.jit(lambda w, x, y: w - 0.1 * jax.grad(loss)(w, x, y))
    tail = []
    for epoch in range(EPOCHS):
        erng = np.random.default_rng(100 + epoch)
        X = erng.normal(0, 1, (16, 8)).astype(np.float32)
        Y = np.eye(4, dtype=np.float32)[erng.integers(0, 4, 16)]
        W = step(W, jnp.asarray(X), jnp.asarray(Y))
        tail.append(float(loss(W, jnp.asarray(X), jnp.asarray(Y))))
    np.testing.assert_allclose(W_final, np.asarray(W), rtol=1e-6, atol=1e-6)
    # the restarted run's loss tail matches the uninterrupted run's tail
    np.testing.assert_allclose(outs[0]["losses"][-2:], tail[-2:],
                               rtol=1e-5, atol=1e-6)
