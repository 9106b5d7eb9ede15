"""Step-time profiler for the overlapped training pipeline.

A :class:`TrainingProfiler` attached to ``fit(..., profiler=...)``
(MultiLayerNetwork, ComputationGraph, ParallelWrapper) names every part of
the fit loop's wall time. :meth:`TrainingProfiler.stage` is the one place a
fit stage is timed: a ``perf_counter`` pair that feeds the totals, the
histograms and ``trace.stage_event``, under a
``jax.profiler.TraceAnnotation("fit.<stage>")`` - so each stage also lands
on the host plane of whatever ``jax.profiler`` session is open, on the
device trace's clock. The stages that tile the fit thread:

- **next_batch** - the iterator's ``next`` (the queue wait when a
  :class:`~deeplearning4j_tpu.train.prefetch.DevicePrefetcher` runs ahead),
- **h2d** - ``coerce_training_batch``: host arrays to device arrays (on the
  prefetch worker when prefetching, and then off the fit thread),
- **rng** - ``next_key``: one ``jax.random.split`` dispatched per step,
- **dispatch** - ``gd.submit``: issuing the jitted step, compiles included
  (a ``StepTraceAnnotation``, so device ops group by step),
- **drain** - ``gd.flush`` and the delivery flush at an epoch's end,
- **sync** - the dispatcher's ``sync`` (``PackedStepLoop``: the packed
  state unpacked).

**data_wait** is what the fit thread waited for its next batch: next_batch +
h2d when synchronous, the queue wait alone when prefetched. **step** is
submit -> loss ready, observed on the completion thread, off the fit thread.
``fit`` itself is the root (``start`` .. ``stop``); what no stage covers is
``unattributed_s``.

``report()['data_wait_fraction']`` is the fraction of fit wall time the
device spent starved for data; ``unattributed_fraction`` says how much of
the fit call the stages do not explain. Histograms reuse
:class:`~deeplearning4j_tpu.serving.metrics.LatencyHistogram` - one
percentile implementation across training and serving.

Thread-safety: stages are recorded from the fit loop, the prefetch worker
and the completion worker concurrently; all mutation is behind one lock.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, Optional

import jax
import numpy as np

from deeplearning4j_tpu.runtime import compile_cache, trace


class _Stage:
    """One timed stage (see :meth:`TrainingProfiler.stage`). ``seconds`` is
    set on exit. A stage left by an exception (the iterator's
    ``StopIteration``) adds its time to the total and is not counted."""

    __slots__ = ("_profiler", "_name", "_annotation", "_t0", "seconds")

    def __init__(self, profiler, name, annotation, started):
        self._profiler, self._name = profiler, name
        self._annotation, self._t0 = annotation, started
        self.seconds = 0.0

    def __enter__(self) -> "_Stage":
        self._annotation.__enter__()
        if self._t0 is None:
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.seconds = time.perf_counter() - self._t0
        self._annotation.__exit__(exc_type, exc, tb)
        self._profiler._record(self._name, self.seconds,
                               counted=exc_type is None)
        return False


class TrainingProfiler:
    """Per-iteration stage timing for ``fit``. Attach one instance per fit
    call (``net.fit(it, profiler=TrainingProfiler())``); read
    :meth:`report` after fit returns."""

    STAGES = ("data_wait", "dispatch", "step",
              "next_batch", "h2d", "rng", "drain", "sync")
    #: the stages that tile the fit thread (``data_wait`` stands for its
    #: parts ``next_batch`` + ``h2d``; ``step`` runs on the completion thread)
    TILE = ("data_wait", "rng", "dispatch", "drain", "sync")
    #: counters of ``compile_cache.stats()`` whose change over the fit call
    #: is reported
    _CACHE_KEYS = ("aot_compiles", "aot_fallbacks", "aot_compile_seconds")

    def __init__(self):
        from deeplearning4j_tpu.serving.metrics import LatencyHistogram
        # guards: _totals, _counts, _hists, _t_start, _t_stop, _cache_start, _cache_stop
        self._lock = threading.Lock()
        self._hists = {s: LatencyHistogram() for s in self.STAGES}
        self._totals = {s: 0.0 for s in self.STAGES}
        self._counts = {s: 0 for s in self.STAGES}
        self._t_start: Optional[float] = None
        self._t_stop: Optional[float] = None
        self._cache_start: Optional[Dict] = None
        self._cache_stop: Optional[Dict] = None
        self._exchange = None  # ExchangeStats from a DistributedTrainer
        self._model_state: Dict[str, object] = {}  # the small leaves of the last step's model state

    def attach_exchange(self, stats) -> "TrainingProfiler":
        """Attach a :class:`~deeplearning4j_tpu.runtime.profiler.ExchangeStats`
        (the distributed trainer does this when handed a profiler): its
        encode/exchange/decode/apply split and compression counters merge
        into :meth:`report` under ``exchange_*`` keys and onto the
        :meth:`summary` headline."""
        self._exchange = stats
        return self

    #: leaves of the model state up to this many elements are counters
    COUNTER_SIZE = 1024

    def record_model_state(self, model_state) -> None:
        """Keep the small leaves of the model state as ``fit`` leaves it (an
        expert layer's ``assigned`` and ``overflow``, a head's recorded loss
        terms): the arrays themselves, nothing is read until :meth:`report`."""
        leaves = jax.tree_util.tree_flatten_with_path(model_state)[0]
        with self._lock:
            self._model_state = {"/".join(str(getattr(k, "key", k)) for k in path): leaf for path, leaf in leaves
                                 if getattr(leaf, "size", self.COUNTER_SIZE + 1) <= self.COUNTER_SIZE}

    # ------------------------------------------------------------ recording
    def start(self) -> "TrainingProfiler":
        """Mark the window start (``fit`` calls this; explicit calls allow
        profiling a sub-window)."""
        cache = compile_cache.stats()
        with self._lock:
            if self._t_start is None:
                self._t_start = time.perf_counter()
                self._cache_start = cache
        return self

    def stop(self) -> "TrainingProfiler":
        cache = compile_cache.stats()
        with self._lock:
            self._t_stop = time.perf_counter()
            self._cache_stop = cache
        return self

    def stage(self, name: str, step: Optional[int] = None,
              started: Optional[float] = None) -> _Stage:
        """Context manager timing one stage of :attr:`STAGES` under a
        ``jax.profiler.TraceAnnotation("fit.<name>")``; with ``step`` a
        ``StepTraceAnnotation`` carrying ``step_num``. ``started`` is the
        ``perf_counter`` reading of a stage that began on another thread
        (``step``: submitted on the fit thread, ready on the completion
        thread); the annotation then covers the wait alone."""
        if step is None:
            annotation = jax.profiler.TraceAnnotation("fit." + name)
        else:
            annotation = jax.profiler.StepTraceAnnotation(
                "fit." + name, step_num=step)
        return _Stage(self, name, annotation, started)

    def _record(self, stage: str, seconds: float, counted: bool = True) -> None:
        # stage split onto the active span, when one is open in this
        # thread (ISSUE 9) — the trace-tree view of the same numbers
        trace.stage_event(stage, seconds)
        with self._lock:
            if self._t_start is None:
                self._t_start = time.perf_counter() - seconds
            self._totals[stage] += seconds
            if counted:
                self._counts[stage] += 1
                self._hists[stage].observe(seconds)

    def record_data_wait(self, seconds: float, counted: bool = True) -> None:
        self._record("data_wait", seconds, counted)

    def record_dispatch(self, seconds: float) -> None:
        self._record("dispatch", seconds)

    def record_step(self, seconds: float) -> None:
        self._record("step", seconds)

    # ------------------------------------------------------------ reporting
    @property
    def iterations(self) -> int:
        with self._lock:
            return self._counts["dispatch"]

    def elapsed(self) -> float:
        with self._lock:
            if self._t_start is None:
                return 0.0
            end = self._t_stop if self._t_stop is not None else time.perf_counter()
            return max(0.0, end - self._t_start)

    def report(self) -> Dict[str, float]:
        """Aggregate stage report. ``data_wait_fraction`` is data-wait time
        over the profiled wall-clock window; ``steps_per_sec`` counts
        dispatched iterations over the same window."""
        elapsed = self.elapsed()
        cache_now = compile_cache.stats()
        with self._lock:
            out: Dict[str, float] = {
                "iterations": self._counts["dispatch"],
                "elapsed_s": round(elapsed, 4),
            }
            for s in self.STAGES:
                n = self._counts[s]
                out[f"{s}_total_s"] = round(self._totals[s], 4)
                out[f"{s}_mean_ms"] = round(
                    self._totals[s] / n * 1e3, 3) if n else 0.0
                out[f"{s}_p99_ms"] = round(
                    self._hists[s].percentile(99) * 1e3, 3)
            out["data_wait_fraction"] = round(
                self._totals["data_wait"] / elapsed, 4) if elapsed else 0.0
            # the root is the fit call; what no fit-thread stage covers
            out["fit_total_s"] = out["elapsed_s"]
            unattributed = max(
                0.0, elapsed - sum(self._totals[s] for s in self.TILE))
            out["unattributed_s"] = round(unattributed, 4)
            out["unattributed_fraction"] = round(
                unattributed / elapsed, 4) if elapsed else 0.0
            before = self._cache_start or cache_now  # never started: no change
            after = self._cache_stop or cache_now
            delta = {k: after[k] - before[k] for k in self._CACHE_KEYS}
            out["aot_compiles"] = delta["aot_compiles"]
            out["aot_fallbacks"] = delta["aot_fallbacks"]
            # lower + compile of the step executables minted inside the call
            out["compile_in_fit_s"] = round(delta["aot_compile_seconds"], 4)
            out["steps_per_sec"] = round(
                self._counts["dispatch"] / elapsed, 2) if elapsed else 0.0
            # the step stage is observed on the async completion path; a
            # state-reading listener forces synchronous delivery, where it
            # is never recorded — flag that rather than report 0 as "free"
            out["step_measured"] = self._counts["step"] > 0
            counters = dict(self._model_state)
        # the last step's counters, by their path in the model state, read to the host here
        out["model_state"] = {path: np.asarray(leaf, np.float64).ravel().tolist() for path, leaf in counters.items()}
        if self._exchange is not None:
            out["exchange"] = self._exchange.report()
        return out

    def summary(self) -> str:
        r = self.report()
        step = (f"step {r['step_mean_ms']:.2f}ms submit->ready"
                if r["step_measured"] else
                "step unmeasured (synchronous delivery)")
        line = (f"TrainingProfiler: {r['iterations']} iterations in "
                f"{r['elapsed_s']:.2f}s ({r['steps_per_sec']:.1f} steps/s); "
                f"data wait {r['data_wait_total_s']:.2f}s "
                f"({r['data_wait_fraction']:.0%} of wall), dispatch "
                f"{r['dispatch_mean_ms']:.2f}ms/iter, {step}")
        if self._exchange is not None:
            line += "; " + self._exchange.headline()
        return line


def submit_timed(gd, rng, build, profiler: Optional[TrainingProfiler] = None) -> None:
    """``gd.submit(build(rng.next_key()))`` - ``run_fit``'s submit.
    ``build`` splices the step's key into its argument
    tuple; with a profiler the key draw is the ``rng`` stage and the submit
    the ``dispatch`` stage."""
    if profiler is None:
        gd.submit(build(rng.next_key()))
        return
    with profiler.stage("rng"):
        key = rng.next_key()
    with profiler.stage("dispatch", step=profiler.iterations):
        gd.submit(build(key))


def drain_timed(gd, drain, profiler: Optional[TrainingProfiler] = None) -> None:
    """An epoch's end in ``run_fit``: flush the buffered group,
    then the delivery queue (``on_epoch_end`` must observe every
    ``iteration_done``) - the ``drain`` stage."""
    if profiler is None:
        gd.flush()
        drain()
        return
    with profiler.stage("drain"):
        gd.flush()
        drain()


def sync_timed(ploop, profiler: Optional[TrainingProfiler] = None) -> None:
    """The dispatcher's ``sync(release=True)`` when ``fit`` returns - the
    ``sync`` stage (the packed state's final unpack)."""
    if profiler is None:
        ploop.sync(release=True)
        return
    with profiler.stage("sync"):
        ploop.sync(release=True)
