"""Cold-start layer: persistent executable cache + AOT dispatch fast path.

The reference stack splits one-time native-graph construction from cheap
per-call execution (SURVEY §2.1 native graph executor, §2.2 OpExecutioner
SPI). On this runtime the expensive one-time cost is XLA compilation — a
process restart or a registry hot-swap recompiles every bucket×replica
executable from scratch, and compile time gates time-to-ready. This module
closes both ends:

**Persistent executable cache** (:func:`enable`): turns on JAX's persistent
compilation cache, forces every executable to be cached (the default 1 s
minimum-compile-time gate would skip exactly the sub-second serving-bucket
programs cold start is made of), and instruments the load path. One rule
decides where the cache lives (:func:`resolve_dir`):

- ``JAX_COMPILATION_CACHE_DIR`` set: that directory, used as given. JAX
  read it at import; nothing here calls
  ``jax.config.update("jax_compilation_cache_dir", ...)``, and an explicit
  directory argument is ignored with a log line.
- not set: the explicit directory if one was passed (tests, drills), else
  ``<repo>/.jax_cache`` — a fixed path derived from the package location,
  because the path is part of what makes a later process find the entries.

JAX's own cache key already covers the jax/jaxlib version and the backend,
so the directory carries no version suffix. Nothing in the library enables
the cache at import; entry points (``chip_smoke.py``, ``bench.py``) call
:func:`enable`. The instrumentation:

- **hit / miss / corrupt counters + compile seconds**, exposed through
  :func:`stats`, ``runtime.profiler.compile_cache_stats`` and the serving
  ``/metrics`` endpoint (``compile_cache_hits_total`` …).
- **corrupt-entry tolerance**: a truncated or bit-rotten cache entry (or a
  fault injected at the ``runtime.compile_cache.load`` chaos point) is
  counted, logged, and answered with "not cached" — a cold compile is
  always a correct fallback; a bad cache file can never take the process
  down. The entry is rewritten by the post-compile cache write.

**AOT dispatch fast path** (:class:`AotCache`): the fit loops and the
serving replica pool re-dispatch ONE jitted program millions of times at a
fixed shape. ``jax.jit``'s dispatch still pays a python cache probe and
signature re-derivation per call; :class:`AotCache` instead keeps the
``lower().compile()`` executable per (graph, shape, mesh) signature and
calls it directly with the already-device-resident donated buffers. The
executable is compiled from the *same* jitted trace, so results are
bit-identical to the jit path — and any signature drift the caller's cheap
key missed raises ``TypeError`` at argument check (before execution or
donation), which falls back to the jit path, never to a wrong answer.
Disable with ``DL4J_TPU_AOT_DISPATCH=0`` or
``get_environment().set_aot_dispatch(False)``.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Any, Dict, Hashable, Optional

import jax
from jax._src import compilation_cache as _cc
from jax._src import monitoring

from deeplearning4j_tpu.runtime import chaos, trace

logger = logging.getLogger(__name__)

#: JAX's own variable: when set, it alone places the cache.
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def default_dir() -> str:
    """``<repo>/.jax_cache``: fixed, derived from where the package lives
    (never from ``tempfile``, a pid or the clock — a directory that moves
    never hits)."""
    repo_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(repo_root, ".jax_cache")


def resolve_dir(directory: Optional[str] = None) -> str:
    """The one resolution rule (module docstring): environment variable,
    else the explicit directory, else :func:`default_dir`."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        if directory and directory != placed:
            logger.warning(
                "compile cache: %s=%s places the cache; ignoring the "
                "explicit directory %s", ENV_VAR, placed, directory)
        return placed
    if directory:
        return os.path.abspath(os.path.expanduser(directory))
    return default_dir()


class CompileCacheStats:
    """Thread-safe counters for the persistent cache + AOT layer."""

    def __init__(self):
        # guards: hits, misses, corrupt_entries, compiles, compile_seconds, retrieval_seconds, aot_compiles, aot_compile_seconds, aot_fallbacks
        self._lock = threading.Lock()
        self._zero()

    def _zero(self):  # holds: _lock (or pre-sharing, from __init__)
        self.hits = 0               # executables deserialized from the cache
        self.misses = 0             # consulted, absent -> backend compile
        self.corrupt_entries = 0    # unreadable entry -> fallback compile
        self.compiles = 0           # backend compiles observed
        self.compile_seconds = 0.0  # total backend compile wall time
        self.retrieval_seconds = 0.0  # total cache deserialize wall time
        self.aot_compiles = 0       # lower().compile() executables minted
        self.aot_compile_seconds = 0.0
        self.aot_fallbacks = 0      # signature drift -> jit path fallback

    def reset(self):
        with self._lock:
            self._zero()

    def record(self, field: str, dt: float = 0.0):
        with self._lock:
            setattr(self, field, getattr(self, field) + 1)
            if field == "compiles":
                self.compile_seconds += dt
            elif field == "hits":
                self.retrieval_seconds += dt
            elif field == "aot_compiles":
                self.aot_compile_seconds += dt

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "enabled": is_enabled(),
                "cache_dir": _cache_dir,
                "hits": self.hits,
                "misses": self.misses,
                "corrupt_entries": self.corrupt_entries,
                "compiles": self.compiles,
                "compile_seconds": round(self.compile_seconds, 4),
                "retrieval_seconds": round(self.retrieval_seconds, 4),
                "aot_compiles": self.aot_compiles,
                "aot_compile_seconds": round(self.aot_compile_seconds, 4),
                "aot_fallbacks": self.aot_fallbacks,
            }


STATS = CompileCacheStats()

_cache_dir: Optional[str] = None
_hooks_installed = False
_orig_get = None


def stats() -> Dict[str, Any]:
    """Process-wide cache/AOT counters (see also
    ``runtime.profiler.compile_cache_stats`` and serving ``/metrics``).
    Note: hit/miss counts include jax's own small internal jits
    (convert_element_type etc.), not only model programs — they are true
    per-executable counts."""
    return STATS.snapshot()


def reset_stats() -> None:
    STATS.reset()


def is_enabled() -> bool:
    return _cache_dir is not None


def cache_dir() -> Optional[str]:
    return _cache_dir


def _install_hooks() -> None:
    """Patch the cache load path (counters + chaos + corrupt tolerance) and
    subscribe to jax's compile-duration monitoring stream. Idempotent."""
    global _hooks_installed, _orig_get
    if _hooks_installed:
        return
    _orig_get = _cc.get_executable_and_time

    def _guarded_get(cache_key, *args, **kwargs):
        # arguments after the key are forwarded as given: jax owns that
        # signature (0.9 added executable_devices) and a wrapper that
        # names them turns every read into a TypeError jax downgrades to
        # a warning — a cache that silently never hits
        t0 = time.perf_counter()
        try:
            chaos.inject("runtime.compile_cache.load")
            executable, compile_time = _orig_get(cache_key, *args, **kwargs)
        except (KeyboardInterrupt, SystemExit):
            raise  # an abort is not a corrupt entry; let it abort
        except BaseException as e:
            # Corrupt/truncated entry, deserialize failure, or an injected
            # fault: count it, answer "not cached", and let the caller
            # compile — the post-compile write refreshes the bad entry.
            STATS.record("corrupt_entries")
            logger.warning(
                "compile cache: entry %s unreadable (%s: %s); falling back "
                "to a fresh compile", str(cache_key)[:16],
                type(e).__name__, e)
            return None, None
        if executable is None:
            STATS.record("misses")
        else:
            STATS.record("hits", time.perf_counter() - t0)
        return executable, compile_time

    _cc.get_executable_and_time = _guarded_get

    def _on_duration(name: str, dur: float, **kw) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            STATS.record("compiles", dur)

    monitoring.register_event_duration_secs_listener(_on_duration)
    _hooks_installed = True


def enable(directory: Optional[str] = None) -> str:
    """Turn on the persistent executable cache at :func:`resolve_dir`
    ``(directory)`` and return that path. Safe to call repeatedly / with a
    new directory."""
    global _cache_dir
    resolved = resolve_dir(directory)
    _install_hooks()
    if os.environ.get(ENV_VAR):
        # placed from outside: jax took the directory from its own
        # variable at import and this module leaves that setting alone
        if jax.config.jax_compilation_cache_dir != resolved:
            raise RuntimeError(
                f"{ENV_VAR}={resolved} but jax holds "
                f"{jax.config.jax_compilation_cache_dir!r}: the variable "
                f"must be set before jax is imported")
    else:
        os.makedirs(resolved, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", resolved)
        _cc.reset_cache()  # drop an initialized handle on the old dir
    # Cache EVERYTHING: serving cold start is dominated by many sub-second
    # bucket×replica compiles that the default 1s/size floors would skip.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _cache_dir = resolved
    logger.info("compile cache enabled at %s", resolved)
    return resolved


def disable() -> None:
    """Detach the persistent cache (counters and hooks stay; they are
    inert without a configured directory). A cache placed through
    ``JAX_COMPILATION_CACHE_DIR`` is not this module's to detach."""
    global _cache_dir
    if _cache_dir is None:
        return
    if os.environ.get(ENV_VAR):
        logger.warning("compile cache: %s places the cache; disable() "
                       "leaves it on", ENV_VAR)
        return
    jax.config.update("jax_compilation_cache_dir", None)
    _cc.reset_cache()
    _cache_dir = None


# --------------------------------------------------------------------- AOT
def aot_enabled() -> bool:
    from deeplearning4j_tpu.runtime.environment import get_environment
    return bool(get_environment().aot_dispatch)


class AotCache:
    """Cache of AOT ``lower().compile()`` executables for ONE call site.

    ``call(key, jitted, *args)`` runs ``jitted``'s program for ``args``
    through a cached compiled executable — minting it with
    ``jitted.lower(*args).compile()`` on first sight of ``key``. The caller
    owns the key (cheap structural signatures like ``(x.shape, x.dtype)``
    beat re-flattening the whole arg tree every step); a key collision is
    harmless: the executable's own argument check raises ``TypeError``
    BEFORE anything executes or donates, and the call falls back to the
    jit path (same math, one wasted probe).

    Not locked: every current call site dispatches from a single thread
    (fit loop / batcher coalescer); a racing duplicate mint would only
    waste one compile.
    """

    __slots__ = ("name", "_entries")

    def __init__(self, name: str = ""):
        self.name = name
        self._entries: Dict[Hashable, Any] = {}

    def __len__(self) -> int:
        return len(self._entries)

    def clear(self) -> None:
        self._entries.clear()

    def evict(self, pred) -> int:
        """Drop every entry whose key satisfies ``pred`` (ISSUE 10: a
        retired serving replica's executables leave the ledger so
        ``compile_count`` keeps describing the LIVE pool). Returns the
        number evicted. An execution already dispatched through an
        evicted entry is unaffected — eviction only forgets the handle."""
        dead = [k for k in list(self._entries) if pred(k)]
        for k in dead:
            self._entries.pop(k, None)
        return len(dead)

    def hlo_texts(self):
        """The optimized HLO of every cached executable: where
        ``runtime.profiler.scope_times`` finds each instruction's
        ``op_name``."""
        return [entry.as_text() for entry in self._entries.values()]

    def call(self, key: Hashable, jitted, *args):
        if not aot_enabled():
            return jitted(*args)
        entry = self._entries.get(key)
        # the dispatching span (batcher dispatch stage, fit-loop step)
        # gets the executable-cache outcome stamped on it (ISSUE 9)
        trace.annotate_current("aot", "hit" if entry is not None else "miss")
        if entry is None:
            t0 = time.perf_counter()
            entry = jitted.lower(*args).compile()
            STATS.record("aot_compiles", time.perf_counter() - t0)
            self._entries[key] = entry
        try:
            return entry(*args)
        except (TypeError, ValueError):
            # The caller's key was too coarse for these arguments — a shape
            # the structural key missed or a weak-type flip (TypeError), or
            # a sharding/layout change (ValueError: e.g. FSDP state whose
            # bias shardings XLA re-assigns after the first step). Both are
            # raised by the executable's argument check BEFORE anything
            # executes or donates: drop the entry and take the
            # always-correct jit path; the next call re-lowers from the
            # now-stable arguments.
            self._entries.pop(key, None)
            STATS.record("aot_fallbacks")
            logger.warning("AotCache(%s): signature drift at key %r; "
                           "falling back to jit dispatch", self.name, key)
            return jitted(*args)
