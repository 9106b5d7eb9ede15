"""Runtime configuration facade.

TPU-native equivalent of the reference's flag system (upstream
``org.nd4j.config.ND4JSystemProperties`` / ``ND4JEnvironmentVars`` and the
libnd4j ``Environment`` singleton; see SURVEY.md §5.6): a single process-wide
configuration object, settable programmatically or through ``DL4J_TPU_*``
environment variables, controlling dtype policy, debug modes, and defaults.

Unlike the reference there is no backend switch to manage — JAX/PJRT selects
the platform — but the same knobs (default float dtype, NaN panic, verbose op
logging, workspace-debug analog) are exposed so user code ports cleanly.
"""

from __future__ import annotations

import dataclasses
import os
import threading
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

_ENV_PREFIX = "DL4J_TPU_"

_DTYPES = {
    "float32": jnp.float32,
    "bfloat16": jnp.bfloat16,
    "float16": jnp.float16,
    "float64": jnp.float64,
}


@dataclasses.dataclass
class Environment:
    """Process-wide runtime configuration.

    Attributes mirror the reference's runtime flags where a TPU analog exists:

    - ``default_dtype``: dtype of freshly initialised parameters (reference:
      ``Nd4j.setDefaultDataTypes``). ``float32`` by default.
    - ``compute_dtype``: dtype activations/matmuls are cast to inside the
      jitted step. ``bfloat16`` keeps the MXU fed; params stay
      ``default_dtype`` (mixed precision policy).
    - ``nan_panic``: throw on first NaN/Inf produced by a jitted step
      (reference: OpProfiler ``ANY_PANIC``); implemented via
      ``jax.config.debug_nans`` plus explicit checks in the fit loop.
    - ``verbose`` / ``debug``: op-level logging analogs of libnd4j
      ``Environment::setVerbose/setDebug``.
    - ``cache_compiled``: persistent XLA compilation cache directory.
    """

    default_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.float32
    nan_panic: bool = False
    verbose: bool = False
    debug: bool = False
    cache_compiled: Optional[str] = None
    # Analog of org.nd4j.memory.limit: fraction of HBM jax may pre-allocate.
    memory_fraction: Optional[float] = None
    # Rematerialization (jax.checkpoint) of single-entry DAG segments during
    # training: trades recompute FLOPs for HBM traffic — the winning trade
    # when a model is bandwidth-bound (ResNet-50 measured 87 GB/step vs the
    # v5e's 819 GB/s). The workspace-memory knob of this framework. Layers
    # that set ``remat_in_scopes`` (the decoder block) recompute inside each
    # of their named scopes instead of as a whole (``nn.attention_layers``).
    remat_segments: bool = False
    # Flat-buffer packing of small train-state leaves at the jitted-step
    # boundary (runtime/state_packing.py): bit-identical math, ~4x fewer
    # buffer handles per dispatch. The TPU analog of the reference's
    # flat-params design (MultiLayerNetwork.init() flattening). On by
    # default for the single-process fit path; sharded training keeps
    # per-leaf state.
    packed_state: bool = True
    # Batches grouped per device dispatch in all three fit loops
    # (MultiLayerNetwork.fit, ComputationGraph.fit, SameDiff.fit; >1 =
    # opt-in): K same-shape batches run as ONE unrolled jitted program,
    # so K steps pay one host dispatch. Costs K-fold compile time;
    # losses/listeners still observe every step. Its benefit on this
    # machine is not measured (ROADMAP queue 1 item 6).
    dispatch_unroll: int = 1
    # AOT dispatch fast path (runtime/compile_cache.AotCache): the fit
    # loops and serving replicas call cached lower().compile() executables
    # per (graph, shape, mesh) signature instead of re-entering jit
    # dispatch every step. Bit-identical results (same trace, same
    # executable); any signature drift falls back to the jit path. On by
    # default; DL4J_TPU_AOT_DISPATCH=0 disables.
    aot_dispatch: bool = True

    def set_remat(self, enabled: bool = True) -> "Environment":
        self.remat_segments = bool(enabled)
        return self

    def set_default_dtype(self, dtype) -> "Environment":
        self.default_dtype = _coerce_dtype(dtype)
        return self

    def set_compute_dtype(self, dtype) -> "Environment":
        self.compute_dtype = _coerce_dtype(dtype)
        return self

    def allow_bfloat16(self) -> "Environment":
        """Enable the standard TPU mixed-precision policy (bf16 compute)."""
        self.compute_dtype = jnp.bfloat16
        return self

    def enable_bf16_state(self) -> "Environment":
        """FULL-bf16 training state: parameters AND optimizer moments live
        in bfloat16 (compute already bf16). An HBM-traffic knob for
        bandwidth-bound steps — BERT-base measured 35.8 vs 40.5 GB/step and
        1724 vs 1637 samples/s on v5e. CAVEAT: bf16 has ~3 significant
        digits, so parameter updates smaller than ~param*0.004 round away —
        fine for pre-training-scale learning rates, risky for tiny
        fine-tune LRs (2e-5 on mature weights). Opt-in, never default."""
        self.default_dtype = jnp.bfloat16
        self.compute_dtype = jnp.bfloat16
        return self

    def set_packed_state(self, enabled: bool = True) -> "Environment":
        self.packed_state = bool(enabled)
        return self

    def set_dispatch_unroll(self, k: int) -> "Environment":
        if int(k) < 1:
            raise ValueError("dispatch_unroll must be >= 1")
        self.dispatch_unroll = int(k)
        return self

    def set_aot_dispatch(self, enabled: bool = True) -> "Environment":
        self.aot_dispatch = bool(enabled)
        return self

    def set_compile_cache(self, directory: str) -> "Environment":
        """Enable the persistent executable cache at ``directory`` (tests
        and drills; ignored, with a log line, when
        ``JAX_COMPILATION_CACHE_DIR`` places the cache); see
        :mod:`deeplearning4j_tpu.runtime.compile_cache`."""
        from deeplearning4j_tpu.runtime import compile_cache
        self.cache_compiled = compile_cache.enable(directory)
        return self

    def set_nan_panic(self, enabled: bool) -> "Environment":
        self.nan_panic = enabled
        jax.config.update("jax_debug_nans", bool(enabled))
        return self

    def to_dict(self) -> Dict[str, Any]:
        return {
            "default_dtype": jnp.dtype(self.default_dtype).name,
            "compute_dtype": jnp.dtype(self.compute_dtype).name,
            "nan_panic": self.nan_panic,
            "verbose": self.verbose,
            "debug": self.debug,
            "cache_compiled": self.cache_compiled,
            "memory_fraction": self.memory_fraction,
            "remat_segments": self.remat_segments,
            "packed_state": self.packed_state,
            "dispatch_unroll": self.dispatch_unroll,
            "aot_dispatch": self.aot_dispatch,
        }


def _coerce_dtype(dtype):
    if isinstance(dtype, str):
        if dtype not in _DTYPES:
            raise ValueError(f"Unknown dtype {dtype!r}; expected one of {sorted(_DTYPES)}")
        return _DTYPES[dtype]
    return jnp.dtype(dtype).type


_lock = threading.Lock()  # guards: (_instance singleton construction)
_instance: Optional[Environment] = None


def get_environment() -> Environment:
    """Return the process-wide :class:`Environment` singleton.

    First call reads ``DL4J_TPU_*`` environment variables:
    ``DL4J_TPU_DTYPE``, ``DL4J_TPU_COMPUTE_DTYPE``, ``DL4J_TPU_NAN_PANIC``,
    ``DL4J_TPU_VERBOSE``, ``DL4J_TPU_DEBUG``, ``DL4J_TPU_AOT_DISPATCH``.
    """
    global _instance
    with _lock:
        if _instance is None:
            env = Environment()
            if os.environ.get(_ENV_PREFIX + "DTYPE"):
                env.set_default_dtype(os.environ[_ENV_PREFIX + "DTYPE"])
            if os.environ.get(_ENV_PREFIX + "COMPUTE_DTYPE"):
                env.set_compute_dtype(os.environ[_ENV_PREFIX + "COMPUTE_DTYPE"])
            if os.environ.get(_ENV_PREFIX + "NAN_PANIC", "").lower() in ("1", "true"):
                env.set_nan_panic(True)
            env.verbose = os.environ.get(_ENV_PREFIX + "VERBOSE", "").lower() in ("1", "true")
            env.debug = os.environ.get(_ENV_PREFIX + "DEBUG", "").lower() in ("1", "true")
            env.remat_segments = os.environ.get(
                _ENV_PREFIX + "REMAT", "").lower() in ("1", "true")
            if os.environ.get(_ENV_PREFIX + "PACKED_STATE", "").lower() in ("0", "false"):
                env.packed_state = False
            if os.environ.get(_ENV_PREFIX + "DISPATCH_UNROLL", "").isdigit():
                # "0" from the environment means "disable" — clamp to the
                # no-grouping value instead of tripping the >=1 validation.
                env.set_dispatch_unroll(
                    max(1, int(os.environ[_ENV_PREFIX + "DISPATCH_UNROLL"])))
            if os.environ.get(_ENV_PREFIX + "AOT_DISPATCH", "").lower() in (
                    "0", "false"):
                env.aot_dispatch = False
            _instance = env
        return _instance
