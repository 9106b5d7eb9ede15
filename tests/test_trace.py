"""Distributed tracing + SLO telemetry (ISSUE 9): the flight recorder.

Layers under test:

- **runtime/trace.py** in isolation: span-tree correctness under
  concurrent requests, tail sampling (flagged traces always kept, healthy
  dropped at rate 0), the disabled no-op fast path (singleton, zero
  allocations attributed to trace.py), ring-buffer memory cap, Chrome
  trace-event (Perfetto) export round-trip.
- **SLOMonitor** burn-rate math against hand-computed windows (injected
  clock — no sleeping).
- **Cross-process propagation over real HTTP**: router -> worker ->
  batcher spans merged into ONE tree via the router's ``/v1/traces``
  aggregation, with bucket/replica/AOT annotations and the winner's
  bit-identity checksum; fleet-wide ``/metrics`` aggregation (summed
  counters, bucket-merged histograms, SLO burn rates).
- **The acceptance drill** over real subprocess workers: a hedged fleet
  request under the straggler-chaos schedule (plus a SIGKILL) yields one
  merged trace showing both worker attempts (loser marked discarded),
  batcher stage spans, and the stamped chaos event.
"""

import json
import os
import threading
import time
import tracemalloc
import urllib.request

import hashlib
import numpy as np
import pytest

from deeplearning4j_tpu.models import MultiLayerNetwork
from deeplearning4j_tpu.nn import (DenseLayer, InputType,
                                   NeuralNetConfiguration, OutputLayer)
from deeplearning4j_tpu.runtime import chaos, trace
from deeplearning4j_tpu.runtime.chaos import AddLatency, ChaosController
from deeplearning4j_tpu.serving import ModelRegistry, ModelServer
from deeplearning4j_tpu.serving.metrics import LatencyHistogram
from deeplearning4j_tpu.serving.router import FleetRouter, StaticFleet
from deeplearning4j_tpu.serving.slo import SLOMonitor, SLOTarget


def _conf(seed=7):
    return (NeuralNetConfiguration.builder().seed(seed).updater(None)
            .list()
            .layer(DenseLayer(n_out=16, activation="tanh"))
            .layer(OutputLayer(n_out=4, activation="softmax"))
            .set_input_type(InputType.feed_forward(8))
            .build())


RNG = np.random.default_rng(0)
X = RNG.normal(size=(16, 8)).astype(np.float32)
BATCHER_KW = dict(max_batch_size=4, buckets=[1, 4], batch_timeout_ms=1.0,
                  pipeline_depth=0)


@pytest.fixture(autouse=True)
def _trace_isolation(request):
    """Every test starts from a known tracing state with an empty
    collector and leaves no tracing state (or env knobs) behind. Tests
    sharing the module-scoped fleet keep tracing ON (the fixture's
    servers were started under it); everything else starts disabled."""
    if "traced_fleet" in request.fixturenames:
        trace.enable(rate=1.0, capacity=512)
    else:
        trace.disable()
        trace.collector().clear()
    yield
    trace.disable()
    trace.collector().clear()
    os.environ.pop("DL4J_TPU_ACCESS_LOG", None)
    os.environ.pop("DL4J_TPU_TRACE", None)


def _post(port, name="m", n=2, timeout_ms=10000, ofs=0):
    body = json.dumps({"inputs": X[ofs:ofs + n].tolist(),
                       "timeout_ms": timeout_ms}).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/models/{name}/predict", data=body)
    resp = urllib.request.urlopen(req, timeout=60)
    return resp.status, dict(resp.getheaders()), json.loads(resp.read())


def _spans_named(record, name):
    return [s for s in record["spans"] if s["name"] == name]


# ==========================================================================
# span trees
def test_span_tree_structure_and_annotations():
    trace.enable(rate=1.0, capacity=16)
    with trace.span("root") as r:
        r.set("model", "m")
        with trace.span("child-a") as a:
            a.event("mark", k=1)
        with trace.span("child-b"):
            pass
    recs = trace.collector().traces()
    assert len(recs) == 1
    rec = recs[0]
    assert all(s["trace_id"] == rec["trace_id"] for s in rec["spans"])
    roots = trace.span_tree(rec)
    assert len(roots) == 1 and roots[0]["name"] == "root"
    assert roots[0]["annotations"] == {"model": "m"}
    kids = [c["name"] for c in roots[0]["children"]]
    assert kids == ["child-a", "child-b"]  # start-time ordered
    assert roots[0]["children"][0]["events"][0]["name"] == "mark"
    for s in rec["spans"]:
        assert s["duration_s"] is not None and s["duration_s"] >= 0.0


def test_span_trees_intact_under_concurrent_requests():
    """8 threads each build their own trace; contextvar isolation must
    keep every tree intact — no span leaks into a foreign trace."""
    trace.enable(rate=1.0, capacity=64)
    n_threads, n_children = 8, 3

    def worker(i):
        with trace.span(f"root-{i}"):
            for j in range(n_children):
                with trace.span(f"child-{i}-{j}"):
                    time.sleep(0.001)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    recs = trace.collector().traces()
    assert len(recs) == n_threads
    seen_roots = set()
    for rec in recs:
        roots = trace.span_tree(rec)
        assert len(roots) == 1, f"trace {rec['trace_id']} has {len(roots)} roots"
        i = int(roots[0]["name"].split("-")[1])
        seen_roots.add(i)
        names = {c["name"] for c in roots[0]["children"]}
        assert names == {f"child-{i}-{j}" for j in range(n_children)}, \
            f"trace {i} contaminated: {names}"
    assert seen_roots == set(range(n_threads))


# ==========================================================================
# tail sampling + ring + no-op path
def test_tail_sampling_keeps_flagged_drops_healthy_at_rate_zero():
    trace.enable(rate=0.0, capacity=16)
    for _ in range(5):
        with trace.span("healthy"):
            pass
    assert trace.collector().traces() == []
    assert trace.collector().dropped == 5
    # a chaos-faulted trace is stamped by the injector and kept
    with ChaosController(seed=1) as c:
        c.on("drill.point", AddLatency(0.0))
        with trace.span("faulted"):
            chaos.inject("drill.point")
    # a hedged trace is kept
    with trace.span("routed") as s:
        s.flag("hedged")
    kept = trace.collector().traces()
    assert [r["spans"][0]["name"] for r in kept] == ["faulted", "routed"]
    assert kept[0]["flags"] == ["chaos"]
    ev = kept[0]["spans"][0]["events"][0]
    assert ev["name"] == "chaos" and ev["point"] == "drill.point"
    assert kept[1]["flags"] == ["hedged"]


def test_latency_threshold_flags_slow_traces():
    trace.enable(rate=0.0, latency_threshold_ms=5.0, capacity=8)
    with trace.span("fast"):
        pass
    with trace.span("slow"):
        time.sleep(0.02)
    kept = trace.collector().traces()
    assert len(kept) == 1 and kept[0]["flags"] == ["slow"]


def test_ring_buffer_caps_memory():
    trace.enable(rate=1.0, capacity=8)
    for i in range(50):
        with trace.span(f"t{i}"):
            pass
    recs = trace.collector().traces()
    assert len(recs) == 8  # bounded regardless of traffic
    assert trace.collector().kept == 50
    # the ring holds the MOST RECENT traces, oldest first even after
    # wraparound (slots carry their insertion sequence)
    assert [r["spans"][0]["name"] for r in recs] == \
        [f"t{i}" for i in range(42, 50)]


def test_disabled_path_is_singleton_and_allocation_free():
    """The rate-0/no-op contract the serving hot path relies on: span()
    returns THE shared no-op object and a dispatch-path-shaped loop
    attributes zero live allocations to trace.py."""
    trace.disable()
    assert trace.span("a") is trace.NOOP
    assert trace.span("b") is trace.NOOP
    assert trace.current_span() is None
    assert trace.current_trace_id() is None
    assert trace.NOOP.child("c") is trace.NOOP

    def hot_loop():
        for _ in range(500):
            with trace.span("batcher.dispatch") as sp:
                sp.set("bucket", 4)
                sp.event("x")
            trace.flag_current("shed")
            trace.annotate_current("aot", "hit")
            trace.stage_event("encode", 0.01)

    hot_loop()  # warm any lazy interpreter state
    tracemalloc.start()
    hot_loop()  # and once traced: specialization/bookkeeping one-offs
    before = tracemalloc.take_snapshot()
    hot_loop()
    after = tracemalloc.take_snapshot()
    tracemalloc.stop()
    # the contract is zero PER-REQUEST allocations: any leak on the
    # dispatch path would show up 500x here; a handful of one-time
    # interpreter-internal allocations (bytecode specialization) do not
    # count against it
    grown = [st for st in after.compare_to(before, "lineno")
             if st.size_diff > 0 and st.count_diff >= 100 and st.traceback
             and any(fr.filename == trace.__file__ for fr in st.traceback)]
    assert not grown, f"per-call allocations attributed to trace.py: {grown}"


# ==========================================================================
# Perfetto / Chrome trace-event export
def test_perfetto_export_round_trips():
    trace.enable(rate=1.0, capacity=8)
    with trace.span("request") as r:
        r.set("bucket", 4)
        with trace.span("dispatch") as d:
            d.event("chaos", point="p", action="latency:0.1")
    recs = trace.collector().traces()
    exported = trace.to_chrome_trace(recs)
    parsed = json.loads(json.dumps(exported))  # the round trip
    events = parsed["traceEvents"]
    complete = [e for e in events if e["ph"] == "X"]
    instants = [e for e in events if e["ph"] == "i"]
    assert {e["name"] for e in complete} == {"request", "dispatch"}
    assert [e["name"] for e in instants] == ["dispatch:chaos"]
    req = next(e for e in complete if e["name"] == "request")
    dis = next(e for e in complete if e["name"] == "dispatch")
    src = {s["name"]: s for s in recs[0]["spans"]}
    for name, ev in (("request", req), ("dispatch", dis)):
        assert ev["ts"] == pytest.approx(src[name]["start_ts"] * 1e6)
        assert ev["dur"] == pytest.approx(src[name]["duration_s"] * 1e6)
    # parentage survives in args; the dispatch nests inside the request
    assert dis["args"]["parent_id"] == req["args"]["span_id"]
    assert req["args"]["bucket"] == 4
    # nesting holds to wall-clock anchor jitter (ts is time.time()-based,
    # dur is monotonic — allow a few ms of skew)
    slack_us = 5000.0
    assert req["ts"] - slack_us <= dis["ts"]
    assert dis["ts"] + dis["dur"] <= req["ts"] + req["dur"] + slack_us


# ==========================================================================
# SLO burn-rate math
def test_slo_burn_rate_matches_hand_computed_windows():
    clock = {"t": 1000.0}
    mon = SLOMonitor(target=SLOTarget(availability=0.99, latency_ms=100.0,
                                      latency_target=0.9),
                     windows_s=(60, 600), now_fn=lambda: clock["t"])
    # hand-built window: 100 requests, 5 unavailable; of the 95 ok, 10
    # breach the 100 ms latency objective
    for i in range(95):
        mon.record("m", ok=True, latency_s=0.2 if i < 10 else 0.05)
    for _ in range(5):
        mon.record("m", ok=False)
    w = mon.report()["m"]["windows"]
    for name in ("60s", "600s"):
        assert w[name]["requests"] == 100
        assert w[name]["availability"] == pytest.approx(0.95)
        # burn = error_rate / budget = 0.05 / 0.01
        assert w[name]["availability_burn_rate"] == pytest.approx(5.0)
        assert w[name]["latency_attainment"] == pytest.approx(1 - 10 / 95,
                                                              abs=1e-6)
        # latency burn = slow_rate / budget = (10/95) / 0.1
        assert w[name]["latency_burn_rate"] == pytest.approx(
            (10 / 95) / 0.1, abs=1e-3)
    # 2 minutes later the fast window has emptied; the slow one has not
    clock["t"] += 120
    w = mon.report()["m"]["windows"]
    assert w["60s"]["requests"] == 0
    assert w["60s"]["availability_burn_rate"] == 0.0
    assert w["600s"]["requests"] == 100
    assert w["600s"]["availability_burn_rate"] == pytest.approx(5.0)
    text = mon.render_prometheus()
    assert 'slo_availability_burn_rate{model="m",window="600s"} 5.0' in text
    assert 'slo_target_availability{model="m"} 0.99' in text


def test_slo_monitor_caps_model_cardinality():
    """Client-sent names must not grow SLO state without bound: past
    ``max_models`` distinct names, new outcomes are dropped."""
    mon = SLOMonitor(now_fn=lambda: 1000.0, max_models=3)
    for i in range(10):
        mon.record(f"m{i}", ok=True, latency_s=0.01)
    rep = mon.report()
    assert sorted(rep) == ["m0", "m1", "m2"]
    # known names keep recording under the cap
    mon.record("m1", ok=False)
    assert mon.report()["m1"]["windows"]["60s"]["requests"] == 2


def test_slo_monitor_create_gate_blocks_never_served_names():
    """The router records with ``create=(status == 200)``: a junk name
    that never served must not occupy a slot, while a tracked model's
    failures count in full."""
    mon = SLOMonitor(now_fn=lambda: 1000.0, max_models=8)
    mon.record("junk", ok=False, create=False)
    assert "junk" not in mon.report()
    mon.record("real", ok=True, latency_s=0.01, create=True)
    mon.record("real", ok=False, create=False)
    w = mon.report()["real"]["windows"]["60s"]
    assert w["requests"] == 2 and w["availability"] == pytest.approx(0.5)


def test_hedge_flag_header_keeps_worker_half_at_rate_zero():
    """Tail sampling decides per process: the router's hedge attempt
    carries ``X-Trace-Flags: hedged`` so the worker's half of the trace
    self-keeps even at rate 0 with nothing locally wrong."""
    trace.enable(rate=0.0, capacity=16)
    reg = ModelRegistry()
    reg.register("m", MultiLayerNetwork(_conf()).init(),
                 warmup_example=X[:1], **BATCHER_KW)
    srv = ModelServer(reg, worker_id="whf")
    try:
        status, _, hdrs = srv._handle_predict(
            "m", json.dumps({"inputs": X[:2].tolist()}).encode(),
            headers={"X-Trace-Id": "t-hedge", "X-Parent-Span-Id": "p1",
                     "X-Trace-Flags": "hedged"})
        assert status == 200 and hdrs["X-Trace-Id"] == "t-hedge"
        # an un-flagged healthy request on the same server is dropped
        status, _, _ = srv._handle_predict(
            "m", json.dumps({"inputs": X[:2].tolist()}).encode())
        assert status == 200
    finally:
        reg.shutdown()
    kept = trace.collector().traces()
    assert len(kept) == 1 and kept[0]["trace_id"] == "t-hedge"
    assert kept[0]["flags"] == ["hedged"]
    assert trace.collector().dropped == 1


def test_latency_histogram_merge_is_bucketwise():
    a, b = LatencyHistogram(), LatencyHistogram()
    for v in (0.001, 0.002, 0.02):
        a.observe(v)
    for v in (0.002, 0.2, 1.5):
        b.observe(v)
    merged = LatencyHistogram.from_wire(a.to_wire()).merge(
        LatencyHistogram.from_wire(b.to_wire()))
    assert merged.count == 6
    assert merged.sum == pytest.approx(a.sum + b.sum)
    assert merged.max == pytest.approx(1.5)
    # bucket merge: percentiles come from combined counts, and a
    # reference histogram fed both streams agrees exactly
    ref = LatencyHistogram()
    for v in (0.001, 0.002, 0.02, 0.002, 0.2, 1.5):
        ref.observe(v)
    for p in (50, 90, 99):
        assert merged.percentile(p) == ref.percentile(p)
    with pytest.raises(ValueError):
        LatencyHistogram(lo=1e-3).merge(LatencyHistogram())


# ==========================================================================
# cross-process propagation over real HTTP (in-process workers)
@pytest.fixture(scope="module")
def traced_fleet():
    """Two real ModelServer workers (identically seeded nets) behind a
    router; tracing at rate 1 so every trace is kept."""
    cfg = trace.enable(rate=1.0, capacity=512)
    servers, endpoints = [], {}
    for i in range(2):
        reg = ModelRegistry()
        reg.register("m", MultiLayerNetwork(_conf()).init(),
                     warmup_example=X[:1], **BATCHER_KW)
        srv = ModelServer(reg, worker_id=f"tw{i}")
        endpoints[f"tw{i}"] = f"127.0.0.1:{srv.start(0)}"
        servers.append(srv)
    router = FleetRouter(StaticFleet(endpoints), probe_interval_s=0.05,
                         hedge_initial_ms=5000.0)  # no hedging here
    port = router.start(0)
    yield router, port
    router.stop()
    for srv in servers:
        srv.stop(shutdown_registry=True)
    trace.disable()
    del cfg


def test_cross_process_propagation_over_real_http(traced_fleet):
    router, port = traced_fleet
    status, headers, _ = _post(port, n=2)
    assert status == 200
    tid = headers["X-Trace-Id"]

    def fetch():
        merged = router.aggregate_traces(tid)
        if merged and len(_spans_named(merged[0], "batcher.complete")) >= 1:
            return merged[0]
        return None

    deadline = time.monotonic() + 10
    rec = fetch()
    while rec is None and time.monotonic() < deadline:
        time.sleep(0.05)
        rec = fetch()
    assert rec is not None, "merged trace never appeared"
    # one connected tree: router.request -> router.attempt ->
    # worker.predict -> batcher stage spans
    roots = trace.span_tree(rec)
    assert len(roots) == 1 and roots[0]["name"] == "router.request"
    (attempt,) = _spans_named(rec, "router.attempt")
    assert attempt["parent_id"] == roots[0]["span_id"]
    assert attempt["annotations"]["winner"] is True
    assert len(attempt["annotations"]["body_crc32"]) == 8
    (predict,) = _spans_named(rec, "worker.predict")
    assert predict["parent_id"] == attempt["span_id"]
    assert predict["annotations"]["bucket"] == 4
    assert predict["annotations"]["replica"] == 0
    (dispatch,) = _spans_named(rec, "batcher.dispatch")
    assert dispatch["parent_id"] == predict["span_id"]
    assert dispatch["annotations"]["bucket"] == 4
    assert dispatch["annotations"]["aot"] in ("hit", "miss")
    (complete,) = _spans_named(rec, "batcher.complete")
    assert complete["annotations"]["replica"] == dispatch["annotations"]["replica"]
    # the same merge is served over HTTP, and exports chrome JSON
    via_http = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{port}/v1/traces?trace_id={tid}",
        timeout=10).read())
    assert via_http["traces"][0]["trace_id"] == tid
    chrome = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{port}/v1/traces?trace_id={tid}&format=chrome",
        timeout=10).read())
    assert any(e["name"] == "worker.predict"
               for e in chrome["traceEvents"])


def test_router_metrics_aggregate_fleet_wide(traced_fleet):
    router, port = traced_fleet
    base = router.slo.report().get("m", {})
    n_before = (base.get("windows", {}).get("3600s", {}) or {}).get(
        "requests", 0)
    for k in range(6):
        assert _post(port, n=1 + k % 4, ofs=k % 8)[0] == 200
    text = urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics",
                                  timeout=10).read().decode()
    # fleet-wide sums: requests recorded across the fleet equal the sum of
    # the per-worker labeled series
    fleet_total = per_worker_total = 0
    for line in text.splitlines():
        if line.startswith('fleet_serving_requests_total{model="m"}'):
            fleet_total = float(line.rsplit(" ", 1)[1])
        elif line.startswith('fleet_serving_requests_total{model="m",'):
            per_worker_total += float(line.rsplit(" ", 1)[1])
    assert fleet_total >= 6
    assert fleet_total == per_worker_total
    # merged-histogram percentiles and the SLO burn rates are rendered
    assert 'fleet_serving_latency_seconds{model="m",quantile="0.99"}' in text
    assert 'slo_availability_burn_rate{model="m",window="60s"} 0.0' in text
    # the router's own (fleet-wide) monitor saw exactly this traffic
    rep = router.slo.report()["m"]["windows"]["3600s"]
    assert rep["requests"] >= n_before + 6
    assert rep["availability"] == 1.0


# ==========================================================================
# access log + crash-report correlation
def test_access_log_line_and_crash_report_carry_trace_id(capfd):
    os.environ["DL4J_TPU_ACCESS_LOG"] = "1"
    trace.enable(rate=1.0, capacity=16)
    reg = ModelRegistry()
    reg.register("m", MultiLayerNetwork(_conf()).init(),
                 warmup_example=X[:1], **BATCHER_KW)
    srv = ModelServer(reg, worker_id="wlog")
    try:
        status, _, _ = srv._handle_predict(
            "m", json.dumps({"inputs": X[:2].tolist()}).encode())
        assert status == 200
    finally:
        reg.shutdown()
    line = next(ln for ln in capfd.readouterr().err.splitlines()
                if '"dl4j_tpu_access"' in ln)
    rec = json.loads(line)
    assert rec["model"] == "m" and rec["outcome"] == 200
    assert rec["worker"] == "wlog"
    assert rec["bucket"] == 4          # stamped by the batcher stage span
    assert rec["latency_ms"] > 0
    assert rec["trace_id"]
    # crash reports join the flight recorder via the active trace id
    from deeplearning4j_tpu.runtime.crash_reporting import CrashReportingUtil
    with trace.span("train.step") as sp:
        report = CrashReportingUtil.memory_report(
            error=RuntimeError("RESOURCE_EXHAUSTED"))
        assert f"trace: {sp.trace_id}" in report
    assert "trace: -" in CrashReportingUtil.memory_report()
    # off by default: no knob, no line
    os.environ.pop("DL4J_TPU_ACCESS_LOG")
    capfd.readouterr()
    trace.emit_access_log({"model": "m"})
    assert '"dl4j_tpu_access"' not in capfd.readouterr().err


# ==========================================================================
# training step spans
def test_train_step_span_carries_exchange_stage_events():
    from deeplearning4j_tpu.train import Sgd
    from deeplearning4j_tpu.train.distributed import (DistributedConfig,
                                                      DistributedTrainer)
    trace.enable(rate=1.0, capacity=16)
    net = MultiLayerNetwork(
        (NeuralNetConfiguration.builder().seed(7).updater(Sgd(0.1))
         .list()
         .layer(DenseLayer(n_out=8, activation="relu"))
         .layer(OutputLayer(n_out=4, activation="softmax"))
         .set_input_type(InputType.feed_forward(8)).build())).init()
    tr = DistributedTrainer(net, DistributedConfig(threshold=1e-3),
                            world=2, rank=None)
    x = X[:8]
    y = np.eye(4, dtype=np.float32)[np.arange(8) % 4]
    tr.step(x, y)
    recs = [r for r in trace.collector().traces()
            if r["spans"] and r["spans"][-1]["name"] == "train.step"]
    assert recs, "no train.step trace kept"
    root = trace.span_tree(recs[-1])[0]
    assert root["annotations"]["world"] == 2
    assert root["annotations"]["rank"] == "loopback"
    stages = [e["stage"] for e in root["events"] if e["name"] == "stage"]
    # the ExchangeStats hooks stamp the full pipeline split on the span
    for stage in ("encode", "exchange", "decode", "apply"):
        assert stage in stages, (stage, stages)


# ==========================================================================
# the acceptance drill: subprocess fleet, hedge + SIGKILL + chaos stamp
def _rendezvous(model, wids):
    def score(wid):
        h = hashlib.blake2b(f"{model}|{wid}".encode(), digest_size=8)
        return int.from_bytes(h.digest(), "big")
    return sorted(wids, key=score, reverse=True)


@pytest.mark.slow  # wall-time tier-2 (ISSUE 19): heaviest tier-1 cases demoted so `not slow` finishes inside the 870 s budget
def test_hedged_sigkill_drill_yields_one_merged_trace(tmp_path):
    """ISSUE 9 acceptance: a hedged fleet request under the chaos drill
    (deterministic straggler schedule on the primary worker; SIGKILL
    after) yields ONE merged trace tree over real subprocess workers:
    router attempt spans, BOTH worker attempts with the loser marked
    discarded (bit-identical body checksum recorded on both), batcher
    stage spans with bucket/replica/AOT annotations, and the chaos event
    stamped inside the straggling worker's span."""
    from deeplearning4j_tpu.runtime.environment import get_environment
    from deeplearning4j_tpu.serving.fleet import FleetSupervisor, WorkerSpec

    a1 = str(tmp_path / "model-v1.zip")
    cache = str(tmp_path / "cache")
    MultiLayerNetwork(_conf()).init().save(a1)
    get_environment().set_compile_cache(cache)
    reg = ModelRegistry()
    reg.load("m", a1, warmup_example=X[:1], **BATCHER_KW)
    reg.shutdown()  # persists the warmup manifest next to a1

    ids = [f"w{i}" for i in range(3)]
    ranked = _rendezvous("m", ids)
    straggler = ranked[0]  # the worker every "m" request is routed to
    sig = {"__single__": {"shape_tail": [8], "dtype": "float32"}}
    os.environ["DL4J_TPU_TRACE"] = "1"  # workers inherit: keep every trace
    specs = [WorkerSpec(
        worker_id=w, model_name="m", archive=a1, version=1,
        batcher_kw=dict(BATCHER_KW), cache_dir=cache, warmup_signature=sig,
        straggle=({"p": 1.0, "ms": 400.0, "seed": 5}
                  if w == straggler else None))
        for w in ids]
    trace.enable(rate=1.0, capacity=256)
    with FleetSupervisor(specs, run_dir=str(tmp_path / "run"),
                         max_restarts=4, heartbeat_timeout_s=60.0) as sup:
        router = FleetRouter(sup, probe_interval_s=0.1,
                             hedge_initial_ms=80.0,
                             hedge_warm_count=10**9)
        port = router.start(0)
        try:
            status, headers, _ = _post(port, n=2, timeout_ms=15000)
            assert status == 200
            tid = headers["X-Trace-Id"]
            assert router.metrics.snapshot()["hedges_total"] >= 1

            def fetch():
                merged = router.aggregate_traces(tid)
                if not merged:
                    return None
                rec = merged[0]
                # wait for the LATE loser: 2 attempts and 2 worker spans
                if (len(_spans_named(rec, "router.attempt")) >= 2
                        and len(_spans_named(rec, "worker.predict")) >= 2):
                    return rec
                return None

            deadline = time.monotonic() + 20
            rec = fetch()
            while rec is None and time.monotonic() < deadline:
                time.sleep(0.1)
                rec = fetch()
            assert rec is not None, "merged hedged trace never completed"

            # ONE tree rooted at the router's request span
            roots = trace.span_tree(rec)
            assert len(roots) == 1 and roots[0]["name"] == "router.request"
            assert "hedged" in rec["flags"] and "chaos" in rec["flags"]

            attempts = _spans_named(rec, "router.attempt")
            assert len(attempts) == 2
            loser = next(a for a in attempts
                         if a["annotations"].get("discarded"))
            winner = next(a for a in attempts
                          if a["annotations"].get("winner"))
            assert loser["annotations"]["worker"] == straggler
            assert winner["annotations"]["worker"] != straggler
            # the discarded duplicate WAS bit-identical to the winner
            assert (loser["annotations"]["body_crc32"]
                    == winner["annotations"]["body_crc32"])

            predicts = _spans_named(rec, "worker.predict")
            assert {p["annotations"]["worker"] for p in predicts} == \
                {straggler, winner["annotations"]["worker"]}
            # the chaos drill stamped the straggling worker's span
            strag_span = next(p for p in predicts
                              if p["annotations"]["worker"] == straggler)
            chaos_evs = [e for e in strag_span["events"]
                         if e["name"] == "chaos"]
            assert chaos_evs and chaos_evs[0]["point"] == \
                "serving.worker.predict"
            assert chaos_evs[0]["action"].startswith("latency:")

            # batcher stage spans with bucket/replica/AOT annotations,
            # parented under each worker's predict span
            dispatches = _spans_named(rec, "batcher.dispatch")
            assert len(dispatches) >= 2
            for d in dispatches:
                assert d["annotations"]["bucket"] == 4
                assert "replica" in d["annotations"]
                assert d["annotations"]["aot"] in ("hit", "miss")
                assert d["parent_id"] in {p["span_id"] for p in predicts}
            assert len(_spans_named(rec, "batcher.complete")) >= 2

            # ---- SIGKILL leg of the drill: kill the straggler under
            # traffic; the request is still served (failover/hedge), the
            # supervisor restarts the victim within budget
            sup.kill_worker(straggler)
            status2, headers2, _ = _post(port, n=1, timeout_ms=15000)
            assert status2 == 200
            merged2 = router.aggregate_traces(headers2["X-Trace-Id"])
            assert merged2 and any(
                a["annotations"].get("winner")
                for a in _spans_named(merged2[0], "router.attempt"))
            deadline = time.monotonic() + 90
            while len(sup.endpoints()) < 3 and time.monotonic() < deadline:
                time.sleep(0.2)
            assert len(sup.endpoints()) == 3
            sup.check()
        finally:
            router.stop()


# ----------------------------------------------------------------- ISSUE 26
# Scopes in the step program and their reduction (runtime/profiler.py). The
# request tracer above is untouched; these sit here because this is the
# tracing file.

def _lowered_names(lowered):
    """Every ``op_name``-like location of a lowered program."""
    return lowered.as_text(debug_info=True)


def _mln_step_text(net, x, y, fm=None):
    import jax
    step = jax.jit(net._train_step_fn())
    return _lowered_names(step.lower(net.train_state, x, y,
                                     net.rng.next_key(), fm, None))


def test_scopes_name_layers_loss_and_updater_in_the_mln_step():
    from deeplearning4j_tpu.train import Sgd
    conf = (NeuralNetConfiguration.builder().seed(3).updater(Sgd(0.1)).list()
            .layer(DenseLayer(n_out=16, activation="tanh"))
            .layer(OutputLayer(n_out=4, activation="softmax"))
            .set_input_type(InputType.feed_forward(8)).build())
    net = MultiLayerNetwork(conf).init()
    y = np.eye(4, dtype=np.float32)[RNG.integers(0, 4, 16)]
    text = _mln_step_text(net, X, y)
    # the output layer's activation is dead code in a training step: its
    # work is the loss's
    for scope in ("jvp(layer_0.DenseLayer)", "transpose(jvp(layer_0.DenseLayer))",
                  "jvp(loss)", "transpose(jvp(loss))", "updater"):
        assert scope in text, scope
    assert net._train_step_fn().__name__ == "mln_train_step"
    assert net._jitted_packed()[0].__name__ == "packed_train_step"


def test_scopes_name_nodes_loss_and_updater_in_the_graph_step():
    import jax
    from deeplearning4j_tpu.models import ComputationGraph
    from deeplearning4j_tpu.nn.graph_vertices import ElementWiseVertex
    from deeplearning4j_tpu.train import Sgd
    g = (NeuralNetConfiguration.builder().seed(3).updater(Sgd(0.1))
         .graph_builder().add_inputs("in"))
    g.add_layer("d1", DenseLayer(n_out=8, activation="relu"), "in")
    g.add_vertex("add", ElementWiseVertex(op="add"), "in", "d1")
    g.add_layer("out", OutputLayer(n_out=4, activation="softmax", loss="mcxent"), "add")
    g.set_outputs("out")
    g.set_input_types(InputType.feed_forward(8))
    net = ComputationGraph(g.build()).init()
    y = np.eye(4, dtype=np.float32)[RNG.integers(0, 4, 16)]
    text = _lowered_names(jax.jit(net._train_step_fn()).lower(
        net.train_state, {"in": X}, [y], net.rng.next_key(), None))
    for scope in ("jvp(d1.DenseLayer)", "jvp(add.ElementWiseVertex)",
                  "transpose(jvp(d1.DenseLayer))", "jvp(loss)", "updater"):
        assert scope in text, scope


def test_attention_sub_scopes_in_a_one_block_transformer():
    from deeplearning4j_tpu.zoo import Bert
    net = Bert(vocab_size=50, d_model=16, n_layers=1, n_heads=2, ffn_size=32,
               max_len=8, dropout_rate=0.0).init()
    ids = RNG.integers(0, 50, (2, 8)).astype(np.int32)
    y = np.eye(2, dtype=np.float32)[[0, 1]]
    text = _mln_step_text(net, ids, y, np.ones((2, 8), np.float32))
    block = "jvp(layer_1.TransformerEncoderBlock)/"
    for sub in ("qkv", "scores", "softmax", "context", "out_proj", "ln1", "ffn", "ln2"):
        assert block + sub + "/" in text, sub
    for sub in ("embed", "ln"):
        assert f"jvp(layer_0.BertEmbeddingLayer)/{sub}/" in text, sub
    assert "transpose(jvp(layer_1.TransformerEncoderBlock))/scores/" in text


@pytest.mark.parametrize("op_name,want", [
    ("jit(s)/jvp(layer_3.Block)/qkv/dot_general", ("forward", ("layer_3.Block", "qkv"))),
    ("jit(s)/transpose(jvp(layer_3.Block))/scores/bhqd,bhkd->bhqk/dot_general",
     ("backward", ("layer_3.Block", "scores", "bhqd,bhkd->bhqk"))),
    ("jit(s)/jvp(layer_0.Embed)/embed/jit(_take)/gather", ("forward", ("layer_0.Embed", "embed"))),
    ("jit(s)/updater/mul", ("optimizer", ("updater",))),
    ("jit(s)/transpose(jvp(loss))/mul", ("backward", ("loss",))),
    ("jit(s)/layer_1.Dense/dot_general", ("forward", ("layer_1.Dense",))),  # inference
    ("jit(s)/jvp()/convert_element_type", None),   # the cast before the layers
    ("jit(s)/slice", None),                        # the packer's unpack
    ("reduce_sum", None),
])
def test_classify_op_name(op_name, want):
    from deeplearning4j_tpu.runtime import profiler
    assert profiler.classify_op_name(op_name) == want


_HLO = """HloModule jit_packed_train_step, is_scheduled=true

%fused_computation.1 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %inner.1 = f32[4]{0} add(%p, %p), metadata={op_name="jit(s)/updater/add"}
}

ENTRY %main.9 (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %slice.1 = f32[2]{0} slice(%a), slice={[0:2]}, metadata={op_name="jit(s)/slice"}
  %fusion.1 = f32[4]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(s)/jvp(layer_0.Embed)/embed/add"}
  %fusion.2 = f32[4]{0} fusion(%a), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(s)/jvp(layer_1.Block)/qkv/dot_general"}
  %fusion.3 = f32[4]{0} fusion(%a), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(s)/transpose(jvp(layer_1.Block))/qkv/dot_general"}
  %fusion.4 = f32[4]{0} fusion(%a), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(s)/transpose(jvp(layer_2.Block))/qkv/dot_general"}
  %copy-done.7 = f32[4]{0} copy-done(%a)
  ROOT %fusion.5 = f32[4]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(s)/updater/add"}
}
"""


def _scope_planes(names=("slice.1", "fusion.1", "fusion.2", "fusion.3", "fusion.4",
                         "copy-done.7", "fusion.5")):
    """Two whole runs of a 100 us step program (ops of 2, 10, 20, 30, 5, 3
    and 8 us, 22 us of gaps), one tiny other program between them, and one
    op of the step program's names outside any run."""
    us = 1000
    durations = (2, 10, 20, 30, 5, 3, 8)
    ops, modules = [], []
    for base in (0, 200 * us):
        modules.append(("jit_packed_train_step(7)", base, 100 * us))
        at = base + 1 * us
        for name, d in zip(names, durations):
            ops.append((f"%{name} = f32[4]{{0}} fusion(f32[4]{{0}} %a), kind=kLoop", at, d * us))
            at += (d + 3) * us
    modules.append(("jit_tiny(2)", 120 * us, 4 * us))
    ops.append(("%fusion.1 = f32[1]{0} fusion()", 120 * us, 4 * us))
    return [("/device:TPU:0", [("XLA Modules", modules), ("XLA Ops", ops), ("Steps", [])]),
            ("/host:CPU", [("python", [("fit.dispatch", 0, 5)])])]


def test_scope_times_by_hand():
    from deeplearning4j_tpu.runtime import profiler
    table = profiler.scope_times(_scope_planes(), ["HloModule other\n", _HLO], depth=2)
    assert table["program"] == "jit_packed_train_step(7)" and table["runs"] == 2
    assert table["step_s"] == pytest.approx(100e-6)
    assert table["scopes"]["forward"] == pytest.approx(
        {"layer_1.Block/qkv": 20e-6, "layer_0.Embed/embed": 10e-6})
    assert table["scopes"]["backward"] == pytest.approx(
        {"layer_1.Block/qkv": 30e-6, "layer_2.Block/qkv": 5e-6})
    assert table["scopes"]["optimizer"] == pytest.approx({"updater": 8e-6})
    assert table["unattributed"] == pytest.approx({"copy-done": 3e-6, "slice [slice]": 2e-6})
    assert table["phases"] == pytest.approx(
        {"forward": 30e-6, "backward": 35e-6, "optimizer": 8e-6, "other": 27e-6})
    assert sum(table["phases"].values()) == pytest.approx(table["step_s"])
    assert table["attributed_fraction"] == pytest.approx(0.73)
    # the depth cut and the layers of one class added up
    assert profiler.scope_times(_scope_planes(), [_HLO], depth=1)["scopes"]["backward"] == \
        pytest.approx({"layer_1.Block": 30e-6, "layer_2.Block": 5e-6})
    merged = profiler.scope_times(_scope_planes(), [_HLO], depth=2, merge_layers=True)
    assert merged["scopes"]["backward"] == pytest.approx({"Block/qkv": 35e-6})
    text = profiler.format_scope_times(merged)
    assert "Block/qkv" in text and "unattributed copy-done" in text


def test_scope_times_raises_on_a_scope_less_step_program():
    """An executable out of a cache written before the scopes existed has
    every instruction and no scope: a raise naming the cause, never an
    empty table. The same for a CPU trace, which has no device plane."""
    import re
    from deeplearning4j_tpu.runtime import profiler
    stale = re.sub(r'op_name="jit\(s\)/[^"]*/(\w+)"', r'op_name="jit(s)/\1"', _HLO)
    with pytest.raises(RuntimeError, match="cleared cache"):
        profiler.scope_times(_scope_planes(), [stale])
    with pytest.raises(RuntimeError, match="0 of 7 instruction names"):
        profiler.scope_times(_scope_planes(), ["HloModule other\n"])
    with pytest.raises(RuntimeError, match="no device plane"):
        profiler.scope_times(_scope_planes()[1:], [_HLO])


def test_device_trace_yields_the_reduction_and_a_cpu_trace_raises(tmp_path):
    """``profiler.trace`` is the operator's entry: it yields the object
    that owns ``scope_times``; the fit stages land on the host plane of its
    session as ``fit.*`` annotations, dispatch with its ``step_num``."""
    from jax.profiler import ProfileData
    from deeplearning4j_tpu.data import NumpyDataSetIterator
    from deeplearning4j_tpu.runtime import profiler
    from deeplearning4j_tpu.train import Sgd, TrainingProfiler
    conf = (NeuralNetConfiguration.builder().seed(3).updater(Sgd(0.1)).list()
            .layer(DenseLayer(n_out=16, activation="tanh"))
            .layer(OutputLayer(n_out=4, activation="softmax"))
            .set_input_type(InputType.feed_forward(8)).build())
    net = MultiLayerNetwork(conf).init()
    y = np.eye(4, dtype=np.float32)[RNG.integers(0, 4, 16)]
    with profiler.trace(str(tmp_path)) as t:
        net.fit(NumpyDataSetIterator(X, y, batch_size=4), profiler=TrainingProfiler())
    assert any("op_name=" in text for text in net._jit_cache["__aot__"].hlo_texts())
    with pytest.raises(RuntimeError, match="no device plane"):
        t.scope_times(net)
    path = next(tmp_path.glob("**/*.xplane.pb"))
    host = {}
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("fit."):
                    host.setdefault(e.name, []).append(dict(e.stats))
    assert {"fit.next_batch", "fit.h2d", "fit.rng", "fit.dispatch", "fit.drain",
            "fit.sync", "fit.step"} <= set(host)
    assert sorted(int(s["step_num"]) for s in host["fit.dispatch"]) == [0, 1, 2, 3]
