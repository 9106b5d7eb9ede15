"""Profiling and debugging hooks.

TPU-native equivalent of the reference's tracing stack (SURVEY.md §5.1):

- ``OpProfiler`` / ``ProfilerConfig`` (upstream
  ``org.nd4j.linalg.profiler.OpProfiler``): section timing + NaN panic modes.
  Per-op hooks make no sense under XLA (ops are fused into one program), so the
  unit of timing here is a *section* (a jitted step, an epoch, an ETL stage).
- SameDiff ``ProfilingListener`` Chrome-trace output → `jax.profiler` traces
  (viewable in TensorBoard/Perfetto), exposed via :func:`trace`, whose
  :meth:`DeviceTrace.scope_times` reduces the step program's device time to
  the scopes the program opened (``jax.named_scope`` under ``models/`` and
  ``nn/``; docs/observability.md, "Training").
"""

from __future__ import annotations

import bisect
import contextlib
import dataclasses
import glob
import os
import re
import time
from collections import defaultdict
from typing import Dict, Iterable, Iterator, List, Optional, Tuple

import jax

# aliased: this module's own `trace` is the jax device-trace context
# manager; the distributed-tracing module must not shadow (or be
# shadowed by) it
from deeplearning4j_tpu.runtime import trace as _dtrace


@dataclasses.dataclass
class ProfilerConfig:
    """Modes mirror the reference's enum where meaningful on TPU."""

    enabled: bool = False
    check_for_nan: bool = False  # reference NAN_PANIC
    check_for_inf: bool = False  # reference INF_PANIC


class OpProfiler:
    """Section timer with aggregate stats.

    Usage::

        prof = OpProfiler()
        with prof.section("train_step"):
            state = step(state, batch)
        prof.summary()
    """

    def __init__(self, config: Optional[ProfilerConfig] = None):
        from deeplearning4j_tpu.serving.metrics import LatencyHistogram
        self.config = config or ProfilerConfig(enabled=True)
        self._totals: Dict[str, float] = defaultdict(float)
        self._counts: Dict[str, int] = defaultdict(int)
        # serving's SLO histogram doubles as the section-latency histogram:
        # one percentile implementation across training and serving
        self._hists: Dict[str, "LatencyHistogram"] = defaultdict(LatencyHistogram)

    @contextlib.contextmanager
    def section(self, name: str) -> Iterator[None]:
        if not self.config.enabled:
            yield
            return
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            self._totals[name] += dt
            self._counts[name] += 1
            self._hists[name].observe(dt)

    def timings(self) -> Dict[str, Dict[str, float]]:
        return {
            name: {
                "total_s": self._totals[name],
                "count": self._counts[name],
                "mean_s": self._totals[name] / max(1, self._counts[name]),
                "p50_s": self._hists[name].percentile(50),
                "p99_s": self._hists[name].percentile(99),
            }
            for name in self._totals
        }

    def summary(self) -> str:
        lines = ["OpProfiler summary:"]
        for name, t in sorted(self.timings().items(), key=lambda kv: -kv[1]["total_s"]):
            lines.append(
                f"  {name:30s} total={t['total_s'] * 1e3:9.2f}ms "
                f"n={t['count']:6d} mean={t['mean_s'] * 1e3:9.3f}ms"
            )
        cc = compile_cache_stats()
        if cc["compiles"] or cc["hits"] or cc["aot_compiles"]:
            lines.append(
                f"  compile cache: hits={cc['hits']} misses={cc['misses']} "
                f"corrupt={cc['corrupt_entries']} "
                f"compile={cc['compile_seconds']:.2f}s "
                f"aot={cc['aot_compiles']} "
                f"(+{cc['aot_compile_seconds']:.2f}s)")
        return "\n".join(lines)

    def reset(self) -> None:
        self._totals.clear()
        self._counts.clear()
        self._hists.clear()


class ExchangeStats:
    """Per-step stage split + compression counters for the distributed
    trainer's gradient exchange (ISSUE 6): ``encode`` (threshold codec),
    ``exchange`` (the collective), ``decode`` (peer-contribution
    accumulate), ``apply`` (updater step). Reuses the serving
    :class:`~deeplearning4j_tpu.serving.metrics.LatencyHistogram` — one
    percentile implementation across serving, training and distributed
    training. Attach to a
    :class:`~deeplearning4j_tpu.train.profiler.TrainingProfiler` via
    ``profiler.attach_exchange(stats)`` to surface the split and the
    compression ratio on the training headline.

    Thread-safety: recorded from the worker's step loop only, but guarded
    by a lock anyway so a supervisor thread may snapshot mid-run.
    """

    STAGES = ("encode", "exchange", "decode", "apply")

    def __init__(self):
        import threading

        from deeplearning4j_tpu.serving.metrics import LatencyHistogram
        # guards: _totals, _counts, _hists, _wire_bytes, _dense_bytes, _payload_bytes, _steps
        self._lock = threading.Lock()
        self._hists = {s: LatencyHistogram() for s in self.STAGES}
        self._totals = {s: 0.0 for s in self.STAGES}
        self._counts = {s: 0 for s in self.STAGES}
        self._dense_bytes = 0      # what a dense f32 exchange would move
        self._wire_bytes = 0       # what this worker actually put on the wire
        self._payload_bytes = 0    # unpadded encoded payload
        self._steps = 0

    def record(self, stage: str, seconds: float) -> None:
        _dtrace.stage_event(stage, seconds)  # onto the active train.step span
        with self._lock:
            self._totals[stage] += seconds
            self._counts[stage] += 1
            self._hists[stage].observe(seconds)

    def record_bytes(self, dense_bytes: int, wire_bytes: int,
                     payload_bytes: int) -> None:
        with self._lock:
            self._dense_bytes += int(dense_bytes)
            self._wire_bytes += int(wire_bytes)
            self._payload_bytes += int(payload_bytes)
            self._steps += 1

    @property
    def steps(self) -> int:
        with self._lock:
            return self._steps

    def report(self) -> Dict[str, float]:
        with self._lock:
            out: Dict[str, float] = {"steps": self._steps}
            for s in self.STAGES:
                n = self._counts[s]
                out[f"{s}_total_s"] = round(self._totals[s], 4)
                out[f"{s}_mean_ms"] = round(
                    self._totals[s] / n * 1e3, 3) if n else 0.0
                out[f"{s}_p99_ms"] = round(
                    self._hists[s].percentile(99) * 1e3, 3)
            steps = max(1, self._steps)
            out["comms_bytes_per_step"] = round(self._wire_bytes / steps)
            out["dense_bytes_per_step"] = round(self._dense_bytes / steps)
            out["payload_bytes_per_step"] = round(self._payload_bytes / steps)
            out["compression_ratio"] = round(
                self._dense_bytes / self._wire_bytes, 2) \
                if self._wire_bytes else 1.0
        return out

    def headline(self) -> str:
        r = self.report()
        return (f"exchange {r['exchange_mean_ms']:.2f}ms/step "
                f"(encode {r['encode_mean_ms']:.2f} decode "
                f"{r['decode_mean_ms']:.2f} apply {r['apply_mean_ms']:.2f}), "
                f"{r['comms_bytes_per_step']} B/step on the wire "
                f"({r['compression_ratio']}x vs dense)")


#: phases of a training step, as JAX writes them into ``op_name``
PHASES = ("forward", "backward", "optimizer", "other")
_OPS_LINE, _MODULES_LINE = "XLA Ops", "XLA Modules"
_HLO_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?metadata=\{op_name=\"([^\"]*)\"", re.M)
_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")
_LAYER_SCOPE = re.compile(r"^[^/()]+\.[A-Za-z_]\w*$")  # <layer key>.<LayerClass>


def _split_op_name(op_name: str) -> List[str]:
    """``a/jvp(b)/c`` -> ``[a, jvp(b), c]`` (a ``/`` inside parentheses
    does not split)."""
    parts, depth, start = [], 0, 0
    for i, ch in enumerate(op_name):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == "/" and depth == 0:
            parts.append(op_name[start:i])
            start = i + 1
    parts.append(op_name[start:])
    return parts


def classify_op_name(op_name: str) -> Optional[Tuple[str, Tuple[str, ...]]]:
    """``(phase, scope path)`` of one HLO ``op_name``, or ``None`` when it
    carries no scope of the program's.

    JAX writes the name stack as ``jit(step)/jvp(layer_3.Block)/qkv/dot_general``:
    components wrapped in ``transpose(...)`` are the backward pass, ``jvp(...)``
    alone the forward pass; ``jit(...)`` components and the last one (the
    primitive) are no scopes. A path counts as the program's when it starts
    with ``loss``, ``updater`` or a ``<layer key>.<LayerClass>`` scope."""
    path, backward = [], False
    for part in _split_op_name(op_name)[:-1]:
        wrappers = []
        while (m := _WRAPPED.match(part)):
            wrappers.append(m.group(1))
            part = m.group(2)
        if "jit" in wrappers or "pjit" in wrappers:
            continue
        backward = backward or "transpose" in wrappers
        if part:
            path.append(part)
    if not path or not (path[0] in ("loss", "updater")
                        or _LAYER_SCOPE.match(path[0])):
        return None
    phase = ("backward" if backward else
             "optimizer" if path[0] == "updater" else "forward")
    return phase, tuple(path)


def _instruction(event_name: str) -> str:
    """The HLO instruction's name from a device event's name (the trace
    gives the instruction's whole text: ``%fusion.390 = f32[..] fusion(..``)."""
    return event_name.split(" = ")[0].lstrip("%")


def scope_times(planes, hlo_texts: Iterable[str], depth: int = 2,
                merge_layers: bool = False) -> Dict[str, object]:
    """Seconds per run of the step program by phase and scope.

    ``planes``: ``[(plane name, [(line name, [(event name, start_ns,
    duration_ns)])])]`` as :meth:`DeviceTrace.planes` loads them;
    ``hlo_texts``: the optimized HLO (``compiled.as_text()``) of the
    candidate executables. The TPU plane's events carry the instruction's
    text but not its ``op_name`` (looked at by hand on a v5e trace, PR 26;
    ``ProfileData`` does not hand out the event metadata's ``tf_op``), so
    each event's instruction name (``fusion.390``) is joined with the
    ``op_name`` that instruction has in the HLO text. A fusion spanning two
    scopes goes whole to the scope XLA names in the fusion's own metadata:
    its root's, and for a multi-output fusion (a tuple root) the compiler's
    pick among the parts - Adam's update fused into a weight-gradient
    matmul is booked under that layer's backward pass, not ``updater``.
    ``merge_layers`` drops the ``<layer key>.`` of the first scope, so that
    the blocks of one class add up (``TransformerEncoderBlock/qkv``).

    The step program is the module with most device time on the first
    device plane. Returns ``program``, ``runs`` (its whole runs in the
    trace), ``step_s`` (their mean device time), ``phases`` (seconds per
    run for each of :data:`PHASES`; they sum to ``step_s``: ``other`` is
    what no scope covers, ops and gaps alike), ``scopes`` (``{phase:
    {scope path cut to depth: seconds per run}}``), ``unattributed``
    (seconds per run of the ops without a scope of the program's, by XLA
    kind and primitive) and ``attributed_fraction``.

    Raises ``RuntimeError`` when no op of the step program carries a scope:
    the executable then came out of a compile cache written before the
    scopes were (the cache key leaves ``op_name`` out), or the HLO text is
    another program's. A renamed scope needs a cleared cache."""
    device = next((dict(lines) for name, lines in sorted(planes)
                   if name.startswith("/device:") and _OPS_LINE in dict(lines)), None)
    if device is None:
        raise RuntimeError(
            "scope_times: the trace has no device plane with an 'XLA Ops' "
            f"line (planes: {[name for name, _ in planes]}); a CPU trace "
            "puts no op on a device plane")
    module_ns: Dict[str, int] = defaultdict(int)
    for name, _, duration in device.get(_MODULES_LINE, []):
        module_ns[name] += duration
    if not module_ns:
        raise RuntimeError("scope_times: no program ran on the device in this trace")
    program = max(module_ns, key=module_ns.get)
    runs = sorted((s, s + d) for name, s, d in device[_MODULES_LINE] if name == program)
    starts = [s for s, _ in runs]
    op_ns: Dict[str, int] = defaultdict(int)  # by instruction, over all whole runs
    for name, start, duration in device[_OPS_LINE]:
        i = bisect.bisect_right(starts, start) - 1
        if i >= 0 and start + duration <= runs[i][1]:
            op_ns[_instruction(name)] += duration
    # the executable whose instruction names cover most of these events
    op_names = max((dict(_HLO_INSTRUCTION.findall(text)) for text in hlo_texts),
                   key=lambda table: len(op_ns.keys() & table.keys()), default={})
    scopes = {phase: defaultdict(int) for phase in PHASES[:3]}
    unattributed: Dict[str, int] = defaultdict(int)
    for name, duration in op_ns.items():
        op_name = op_names.get(name, "")
        found = classify_op_name(op_name)
        if found is None:
            kind = name.rstrip("0123456789").rstrip(".")
            primitive = _split_op_name(op_name)[-1]
            unattributed[f"{kind} [{primitive}]" if primitive else kind] += duration
        else:
            phase, path = found
            if merge_layers and _LAYER_SCOPE.match(path[0]):
                path = (path[0].rsplit(".", 1)[1],) + path[1:]
            scopes[phase]["/".join(path[:depth])] += duration
    if not any(scopes.values()):
        raise RuntimeError(
            f"scope_times: none of the {len(op_ns)} device ops of {program} "
            "carries a scope of the program's (layer_N.Class, loss, updater). "
            "Either the executable was served from a compile cache written "
            "before the scopes existed - the cache key ignores op_name, so a "
            "new or renamed scope needs a cleared cache "
            "(runtime.compile_cache.cache_dir()) - or the HLO text handed in "
            f"is not this program's ({len(op_ns.keys() & op_names.keys())} of "
            f"{len(op_ns)} instruction names found in it)")
    n = len(runs)
    per_run = lambda table: {k: v / n / 1e9 for k, v in
                             sorted(table.items(), key=lambda kv: -kv[1])}
    step_s = sum(e - s for s, e in runs) / n / 1e9
    phases = {phase: sum(table.values()) / n / 1e9 for phase, table in scopes.items()}
    attributed = sum(phases.values())
    phases["other"] = step_s - attributed
    return {"program": program, "runs": n, "step_s": step_s, "phases": phases,
            "scopes": {phase: per_run(table) for phase, table in scopes.items()},
            "unattributed": per_run(unattributed),
            "attributed_fraction": attributed / step_s}


def format_scope_times(table: Dict[str, object]) -> str:
    """The table of :func:`scope_times` as text: ms per step, one row per
    scope, one column per phase."""
    rows = sorted({scope for per in table["scopes"].values() for scope in per},
                  key=lambda scope: -sum(per.get(scope, 0) for per in table["scopes"].values()))
    cols = PHASES[:3]
    lines = [f"{table['program']}: {table['step_s'] * 1e3:.2f} ms a step over "
             f"{table['runs']} runs, {table['attributed_fraction']:.1%} in named scopes",
             f"{'scope':52s}" + "".join(f"{c:>11s}" for c in cols)]
    for scope in rows:
        lines.append(f"{scope:52s}" + "".join(
            f"{table['scopes'][c].get(scope, 0) * 1e3:11.3f}" for c in cols))
    lines.append(f"{'total':52s}" + "".join(f"{table['phases'][c] * 1e3:11.3f}" for c in cols)
                 + f"   other {table['phases']['other'] * 1e3:.3f}")
    for kind, seconds in table["unattributed"].items():
        lines.append(f"  unattributed {kind:40s}{seconds * 1e3:9.3f}")
    return "\n".join(lines)


class DeviceTrace:
    """What :func:`trace` yields: the directory ``jax.profiler`` wrote to,
    and the reduction of its newest trace to the program's scopes."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir

    def planes(self):
        """The newest ``.xplane.pb`` under ``log_dir`` as :func:`scope_times`
        takes it, read with ``jax.profiler.ProfileData`` alone: the device
        planes' ``XLA Ops`` and ``XLA Modules`` lines."""
        from jax.profiler import ProfileData
        paths = sorted(glob.glob(os.path.join(self.log_dir, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not paths:
            raise RuntimeError(f"no .xplane.pb under {self.log_dir}: nothing was traced")
        return [(plane.name, [(line.name, [(e.name, e.start_ns, e.duration_ns)
                                           for e in line.events])
                              for line in plane.lines
                              if line.name in (_OPS_LINE, _MODULES_LINE)])
                for plane in ProfileData.from_file(paths[-1]).planes
                if plane.name.startswith("/device:")]

    def scope_times(self, model, depth: int = 2,
                    merge_layers: bool = False) -> Dict[str, object]:
        """:func:`scope_times` of this trace for ``model``'s step program
        (a ``MultiLayerNetwork`` / ``ComputationGraph`` that ``fit`` ran
        under the trace): the HLO text comes from the model's AOT
        executables (``env.aot_dispatch``)."""
        texts = [text for cache in model._jit_cache.values()
                 if hasattr(cache, "hlo_texts") for text in cache.hlo_texts()]
        if not texts:
            raise RuntimeError(
                "scope_times: the model holds no AOT executable to read the "
                "step program's HLO from (fit it under the trace with "
                "env.aot_dispatch on)")
        return scope_times(self.planes(), texts, depth, merge_layers)


@contextlib.contextmanager
def trace(log_dir: str) -> Iterator[DeviceTrace]:
    """Capture a device trace (Chrome-trace analog of ``ProfilingListener``)::

        with profiler.trace(log_dir) as t:
            net.fit(iterator, profiler=TrainingProfiler())
        print(profiler.format_scope_times(t.scope_times(net)))

    Host events are annotations only (the ``fit.*`` stages of a
    ``TrainingProfiler`` among them); view the whole with TensorBoard's
    profile plugin or Perfetto."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=options)
    try:
        yield DeviceTrace(log_dir)
    finally:
        jax.profiler.stop_trace()


def compile_cache_stats() -> Dict[str, object]:
    """Persistent-executable-cache and AOT-dispatch counters (hit/miss/
    corrupt, backend compile seconds, AOT executables minted) — the same
    numbers the serving ``/metrics`` endpoint renders; see
    :mod:`deeplearning4j_tpu.runtime.compile_cache`."""
    from deeplearning4j_tpu.runtime import compile_cache
    return compile_cache.stats()


_ROUTER_METRICS = None


def attach_router(metrics) -> None:
    """Register the process's live
    :class:`~deeplearning4j_tpu.serving.router.RouterMetrics` (ISSUE 7)
    so profiling tooling can read the fleet gauges without holding a
    router reference. Called by ``FleetRouter.start``; the newest router
    wins (one routing tier per process)."""
    global _ROUTER_METRICS
    _ROUTER_METRICS = metrics


def router_stats() -> Dict[str, object]:
    """Fleet-router gauges for the process's attached router: forwards,
    hedges launched/won/discarded-duplicates, failovers, shed skips,
    rolling deploys, and request-latency percentiles. Empty dict when no
    router is attached (the single-process serving topology)."""
    if _ROUTER_METRICS is None:
        return {}
    return _ROUTER_METRICS.snapshot()


_QUANT_METRICS: Dict[str, object] = {}


def attach_quant_metrics(name: str, metrics) -> None:
    """Register a model's :class:`~deeplearning4j_tpu.serving.metrics
    .ServingMetrics` under its served name when it carries a serving dtype
    policy (ISSUE 8) so profiling tooling can read the quantized-vs-f32
    latency split without holding a registry reference. Called by
    ``ModelRegistry.register`` for policy-carrying models; a hot-swap
    re-attaches the replacement's metrics (newest wins per name)."""
    _QUANT_METRICS[str(name)] = metrics


def quant_split_stats() -> Dict[str, Dict[str, object]]:
    """Per-model quantized-vs-f32 serving split for every attached
    policy-carrying model: the dtype-policy label, how much traffic rode
    the reduced-precision path, and the latency percentiles of each dtype
    class side by side — the profiler-side view of the
    ``serving_dtype_latency_seconds`` / ``serving_quantized_requests_total``
    series on ``/metrics``. Empty dict when nothing quantized is being
    served."""
    out: Dict[str, Dict[str, object]] = {}
    for name, m in list(_QUANT_METRICS.items()):
        s = m.snapshot()
        out[name] = {
            "dtype_policy": s.get("dtype_policy"),
            "requests_total": s.get("requests_total", 0),
            "quantized_requests_total": s.get("quantized_requests_total", 0),
            "quant_responses": s.get("quant_responses", 0),
            "float_responses": s.get("float_responses", 0),
            "latency_quant_p50_s": s.get("latency_quant_p50_s"),
            "latency_quant_p99_s": s.get("latency_quant_p99_s"),
            "latency_float_p50_s": s.get("latency_float_p50_s"),
            "latency_float_p99_s": s.get("latency_float_p99_s"),
        }
    return out


def detach_quant_metrics(name: str) -> None:
    """Drop a served name's attached quantized metrics (tests and graceful
    undeploy; absent names are a no-op)."""
    _QUANT_METRICS.pop(str(name), None)


_CAPACITY_PROVIDER = None


def attach_capacity(provider) -> None:
    """Register a capacity provider (a zero-arg callable returning the
    ``serving/capacity.py`` registry payload — ISSUE 10) so profiling
    tooling can read per-model resource accounting without holding a
    registry reference. Called by ``ModelServer.start``; the newest
    provider wins (mirrors :func:`attach_router`)."""
    global _CAPACITY_PROVIDER
    _CAPACITY_PROVIDER = provider


def detach_capacity(provider=None) -> None:
    """Drop the attached capacity provider. When ``provider`` is given,
    detach only if it is still the CURRENT one — a stopping server must
    not clobber a newer server's attachment (``ModelServer.stop`` passes
    its own provider)."""
    global _CAPACITY_PROVIDER
    if provider is None or _CAPACITY_PROVIDER is provider:
        _CAPACITY_PROVIDER = None


def capacity_stats() -> Dict[str, object]:
    """The attached registry's capacity ledger (per-model parameter /
    device bytes, replica utilization, queue headroom, compile footprint
    — the same payload ``/v1/capacity`` serves). Empty dict when no
    serving registry is attached."""
    if _CAPACITY_PROVIDER is None:
        return {}
    return _CAPACITY_PROVIDER()


def device_memory_stats() -> Dict[str, Dict[str, int]]:
    """Per-device memory stats — feeds the HBM crash report (§5.5 parity)."""
    out = {}
    for d in jax.devices():
        stats = getattr(d, "memory_stats", lambda: None)()
        if stats:
            out[str(d)] = {k: int(v) for k, v in stats.items()}
    return out
