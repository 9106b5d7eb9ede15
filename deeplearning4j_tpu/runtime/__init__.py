"""Runtime substrate: environment/config facade, mesh discovery, RNG, profiling.

TPU-native replacement for the reference's runtime plumbing:
``org.nd4j.config.ND4JSystemProperties`` / ``ND4JEnvironmentVars`` (flag
facade), ``CudaEnvironment`` (device runtime tuning), ``Nd4j.getRandom()``
(global RNG), and ``OpProfiler`` (profiling hooks).
"""

from deeplearning4j_tpu.runtime import chaos
from deeplearning4j_tpu.runtime.chaos import (
    AddLatency,
    ChaosCancelled,
    ChaosController,
    ChaosError,
    ChaosListener,
    CorruptBytes,
    FailNth,
    FailWithProbability,
    HangUntilCancelled,
)
from deeplearning4j_tpu.runtime.environment import Environment, get_environment
from deeplearning4j_tpu.runtime.mesh import (
    MeshSpec,
    create_mesh,
    device_count,
    devices,
    local_mesh,
)
from deeplearning4j_tpu.runtime.rng import RngManager, get_default_rng, set_default_seed
from deeplearning4j_tpu.runtime.profiler import OpProfiler, ProfilerConfig
# the jax device-trace context manager keeps its old spelling as
# runtime.profiler.trace; the package-level name `trace` now names the
# distributed-tracing module (ISSUE 9), re-exported here as device_trace:
# the entry to the scope table (docs/observability.md "Training")
from deeplearning4j_tpu.runtime.profiler import trace as device_trace
from deeplearning4j_tpu.runtime import trace
# the fleet event journal (ISSUE 15): the black box every control seam
# writes to — see docs/observability.md "Black box"
from deeplearning4j_tpu.runtime import journal

__all__ = [
    "trace",
    "journal",
    "device_trace",
    "chaos",
    "ChaosController",
    "ChaosError",
    "ChaosCancelled",
    "ChaosListener",
    "FailNth",
    "FailWithProbability",
    "AddLatency",
    "CorruptBytes",
    "HangUntilCancelled",
    "Environment",
    "get_environment",
    "MeshSpec",
    "create_mesh",
    "device_count",
    "devices",
    "local_mesh",
    "RngManager",
    "get_default_rng",
    "set_default_seed",
    "OpProfiler",
    "ProfilerConfig",
]
