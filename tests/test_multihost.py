"""Multihost smoke test (VERDICT r1 item 7): spawn two localhost processes
that call ``initialize_multihost`` (jax.distributed over a loopback
coordinator), build a global 2-process DP mesh, run ONE data-parallel step
each on its local shard, and assert the allreduced gradients match the
single-process run bit-for-bit.

This is the executable analog of the reference testing its whole Spark/Aeron
wire path on one box with ``local[N]`` (SURVEY.md §4): the same
``jax.distributed`` + GSPMD program later spans real hosts over ICI/DCN.
"""

import json
import subprocess
import sys

import numpy as np
import pytest

_WORKER = r"""
import json, os, sys
import numpy as np

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.runtime.mesh import initialize_multihost

pid = int(sys.argv[1]); nproc = int(sys.argv[2]); port = sys.argv[3]
initialize_multihost(coordinator_address=f"127.0.0.1:{port}",
                     num_processes=nproc, process_id=pid)

assert jax.process_count() == nproc, jax.process_count()
# 2 local CPU devices per process -> 4 global devices
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
devs = np.asarray(jax.devices()).reshape(-1)   # global device list
mesh = Mesh(devs, ("dp",))

rng = np.random.default_rng(0)
W = jnp.asarray(rng.normal(0, 0.5, (8, 4)), jnp.float32)     # replicated
X = rng.normal(0, 1, (16, 8)).astype(np.float32)             # global batch
Y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 16)]

def loss(w, x, y):
    p = jax.nn.log_softmax(x @ w)
    return -jnp.mean(jnp.sum(p * y, axis=-1))

xsh = NamedSharding(mesh, P("dp", None))
# each process hands jax only its LOCAL shard; make_array_from_process_local_data
# assembles the global array (the multi-host data-loading contract)
n_local = 16 // nproc
lo = pid * n_local
x_g = jax.make_array_from_process_local_data(xsh, X[lo:lo + n_local])
y_g = jax.make_array_from_process_local_data(xsh, Y[lo:lo + n_local])

g = jax.jit(jax.grad(loss))(W, x_g, y_g)
out = np.asarray(jax.device_get(g))
print("GRAD" + json.dumps(out.tolist()))
"""


@pytest.mark.slow
def test_two_process_dp_grads_match_single_process(tmp_path):
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = str(s.getsockname()[1])

    wfile = tmp_path / "worker.py"
    wfile.write_text(_WORKER)
    # workers are CPU processes: the backend is pinned, never auto-detected
    from deeplearning4j_tpu.train.distributed import worker_env
    env = worker_env()
    procs = [subprocess.Popen(
        [sys.executable, str(wfile), str(pid), "2", port],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env, text=True)
        for pid in range(2)]
    outs = []
    for p in procs:
        out, err = p.communicate(timeout=240)
        assert p.returncode == 0, f"worker failed:\n{err[-3000:]}"
        grad_lines = [l for l in out.splitlines() if l.startswith("GRAD")]
        assert grad_lines, out
        outs.append(np.asarray(json.loads(grad_lines[0][4:])))

    # both processes see the same (allreduced) gradient
    np.testing.assert_array_equal(outs[0], outs[1])

    # single-process oracle
    import jax
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    W = jnp.asarray(rng.normal(0, 0.5, (8, 4)), jnp.float32)
    X = rng.normal(0, 1, (16, 8)).astype(np.float32)
    Y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 16)]

    def loss(w, x, y):
        p = jax.nn.log_softmax(x @ w)
        return -jnp.mean(jnp.sum(p * y, axis=-1))

    ref = np.asarray(jax.grad(loss)(W, jnp.asarray(X), jnp.asarray(Y)))
    np.testing.assert_allclose(outs[0], ref, rtol=1e-6, atol=1e-6)
