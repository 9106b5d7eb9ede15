"""Bench honesty checker (`bench.py --check-tables`): the drill sections
recorded in BENCH_EXTRA.json must be structurally complete and internally
consistent — any drift fails loudly. Pure host logic, no device needed."""

import importlib.util
import json
import pathlib

REPO = pathlib.Path(__file__).resolve().parents[1]

_spec = importlib.util.spec_from_file_location("bench", REPO / "bench.py")
bench = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench)


def test_check_tables_passes_on_repo_state():
    """The committed BENCH_EXTRA.json's drill sections must be consistent
    — this is the same check the driver can run in CI."""
    assert bench.check_tables(log=lambda *a: None) == 0


def test_chaos_smoke_zero_silent_wrong_answers(tmp_path):
    """`bench.py --chaos-smoke` (ISSUE 2 satellite): a small run of the
    sustained-load benchmark under the fixed seeded fault schedule must
    account for every request (exact result or explicit error), trip and
    recover the breaker, and export its counts into BENCH_EXTRA.json."""
    extra_path = tmp_path / "BENCH_EXTRA.json"
    msgs = []
    rc = bench.chaos_smoke(n_threads=4, per_thread=15,
                           bench_extra=str(extra_path), log=msgs.append)
    assert rc == 0, f"chaos smoke failed: {msgs}"
    out = json.loads(extra_path.read_text())["chaos_smoke"]
    assert out["wrong"] == 0
    assert out["hung_clients"] == 0
    assert out["answered"] == out["total_requests"] == 60
    assert out["ok"] > 0
    assert out["breaker_opens_total"] >= 1
    assert out["recovered_after_chaos"] is True


# ----------------------------------------------- ISSUE 6 distributed keys
def _dist_section(steps=40.0, dense_b=1000000, enc_b=62500, eff=0.6):
    return {
        "dense": {"steps_per_sec": 41.0, "comms_bytes_per_step": dense_b,
                  "matches_oracle": True},
        "encoded": {"steps_per_sec": steps, "comms_bytes_per_step": enc_b,
                    "matches_oracle": True},
        "comms_reduction_vs_dense": round(dense_b / enc_b, 2),
        "scaling_curve": {"1": {"steps_per_sec": 66.0},
                          "2": {"steps_per_sec": 50.0},
                          "4": {"steps_per_sec": round(66.0 * eff, 3)}},
        "scaling_efficiency": eff,
        "scaling_efficiency_world": 4,
        "dist_steps_per_sec": steps,
    }


def _extra_with_dist(dist):
    measured = {}
    measured["distributed"] = dist
    measured["dist_steps_per_sec"] = dist.get("dist_steps_per_sec")
    measured["scaling_efficiency"] = dist.get("scaling_efficiency")
    enc = dist.get("encoded") or {}
    measured["comms_bytes_per_step"] = enc.get("comms_bytes_per_step")
    return measured


def test_check_tables_validates_distributed_section(tmp_path):
    """ISSUE 6 satellite: --check-tables covers the distributed keys — a
    self-consistent recorded section passes, and each drift class
    (top-level copy disagreeing, reduction not recomputable from the byte
    rows, efficiency not recomputable from the curve) fails loudly."""
    extra = tmp_path / "BENCH_EXTRA.json"

    extra.write_text(json.dumps(_extra_with_dist(_dist_section())))
    assert bench.check_tables(str(extra), log=lambda *a: None) == 0

    # top-level copy drift
    bad = _extra_with_dist(_dist_section())
    bad["dist_steps_per_sec"] = 999.0
    extra.write_text(json.dumps(bad))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 1
    assert any("dist_steps_per_sec" in m and "top-level" in m for m in msgs)

    # claimed reduction not derivable from the recorded byte rows
    dist = _dist_section()
    dist["comms_reduction_vs_dense"] = 99.0
    extra.write_text(json.dumps(_extra_with_dist(dist)))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 1
    assert any("comms_reduction_vs_dense" in m for m in msgs)

    # claimed scaling efficiency not derivable from the recorded curve
    dist = _dist_section()
    dist["scaling_efficiency"] = 0.95
    extra.write_text(json.dumps(_extra_with_dist(dist)))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 1
    assert any("scaling_efficiency" in m and "curve" in m for m in msgs)

    # missing required key
    dist = _dist_section()
    dist.pop("scaling_curve")
    extra.write_text(json.dumps(_extra_with_dist(dist)))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 1
    assert any("scaling_curve" in m and "missing" in m for m in msgs)

    # a recorded run that diverged from the oracle must never pass
    dist = _dist_section()
    dist["encoded"]["matches_oracle"] = False
    extra.write_text(json.dumps(_extra_with_dist(dist)))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 1
    assert any("matches_oracle" in m for m in msgs)

    # a malformed section is a FAIL line, not a checker crash (empty
    # curve, non-dict arm, non-numeric reduction all land here)
    dist = _dist_section()
    dist["scaling_curve"] = {}
    extra.write_text(json.dumps(_extra_with_dist(dist)))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 1
    assert any("malformed" in m for m in msgs)
    dist = _dist_section()
    dist["dense"] = "not-a-dict"
    extra.write_text(json.dumps(_extra_with_dist(dist)))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 1
    assert any("malformed" in m for m in msgs)


def test_check_tables_distributed_absent_is_warning(tmp_path):
    """No --distributed run recorded yet → warn, don't fail (same
    contract as a skipped BERT import)."""
    measured = {}
    extra = tmp_path / "BENCH_EXTRA.json"
    extra.write_text(json.dumps(measured))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 0
    assert any("distributed" in m and "WARN" in m for m in msgs)


# --------------------------------------------------------------- ISSUE 7
def _fleet_section():
    """A self-consistent BENCH_EXTRA.json["fleet"] section."""
    return {
        "unhedged": {"workers": 1, "hedge": False, "requests": 320,
                     "p50_ms": 8.6, "p99_ms": 131.8, "matches_oracle": True,
                     "straggler_p": 0.04, "straggler_ms": 120.0},
        "hedged": {"workers": 3, "hedge": True, "requests": 320,
                   "p50_ms": 12.5, "p99_ms": 25.4, "matches_oracle": True,
                   "straggler_p": 0.04, "straggler_ms": 120.0,
                   "hedges": 40, "hedge_wins": 12, "hedges_discarded": 35},
        "p99_speedup": 5.19,
        "kill_drill": {"requests": 567, "errors": 0, "victim": "h0",
                       "absorbed_attempts": 27, "supervisor_restarts": 1,
                       "matches_oracle": True},
        "rolling_deploy": {"requests": 2206, "errors": 0,
                           "versions_seen": [1, 2],
                           "on_traffic_compiles": 0, "workers": 3,
                           "ready_s": {"h0": 1.0, "h1": 1.0, "h2": 1.0}},
    }


def _extra_with_fleet(fleet):
    measured = {}
    measured["fleet"] = fleet
    return measured


def test_check_tables_validates_fleet_section(tmp_path):
    """ISSUE 7 satellite: --check-tables covers the fleet keys — a
    self-consistent recorded section passes, and each drift class (drill
    errors, on-traffic compiles, single-version deploy, speedup not
    recomputable or <= 1, divergence from the oracle) fails loudly."""
    extra = tmp_path / "BENCH_EXTRA.json"

    extra.write_text(json.dumps(_extra_with_fleet(_fleet_section())))
    assert bench.check_tables(str(extra), log=lambda *a: None) == 0

    # a kill drill that saw client-visible errors must never pass
    fleet = _fleet_section()
    fleet["kill_drill"]["errors"] = 3
    extra.write_text(json.dumps(_extra_with_fleet(fleet)))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 1
    assert any("kill_drill" in m and "errors" in m for m in msgs)

    # on-traffic compiles after a deploy break the manifest-prewarm claim
    fleet = _fleet_section()
    fleet["rolling_deploy"]["on_traffic_compiles"] = 2
    extra.write_text(json.dumps(_extra_with_fleet(fleet)))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 1
    assert any("on-traffic compile" in m for m in msgs)

    # a deploy that only ever served one version was not zero-downtime
    fleet = _fleet_section()
    fleet["rolling_deploy"]["versions_seen"] = [2]
    extra.write_text(json.dumps(_extra_with_fleet(fleet)))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 1
    assert any("versions_seen" in m for m in msgs)

    # claimed speedup not derivable from the recorded arm rows
    fleet = _fleet_section()
    fleet["p99_speedup"] = 99.0
    extra.write_text(json.dumps(_extra_with_fleet(fleet)))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 1
    assert any("p99_speedup" in m for m in msgs)

    # hedging that did not beat the unhedged arm fails the recorded claim
    fleet = _fleet_section()
    fleet["hedged"]["p99_ms"] = 140.0
    fleet["p99_speedup"] = round(131.8 / 140.0, 2)
    extra.write_text(json.dumps(_extra_with_fleet(fleet)))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 1
    assert any("did not beat" in m for m in msgs)

    # divergence from the oracle must never pass
    fleet = _fleet_section()
    fleet["hedged"]["matches_oracle"] = False
    extra.write_text(json.dumps(_extra_with_fleet(fleet)))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 1
    assert any("matches_oracle" in m for m in msgs)

    # missing required key
    fleet = _fleet_section()
    fleet.pop("kill_drill")
    extra.write_text(json.dumps(_extra_with_fleet(fleet)))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 1
    assert any("kill_drill" in m and "missing" in m for m in msgs)

    # a malformed section is a FAIL line, not a checker crash
    fleet = _fleet_section()
    fleet["hedged"] = "not-a-dict"
    extra.write_text(json.dumps(_extra_with_fleet(fleet)))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 1
    assert any("malformed" in m for m in msgs)


def test_check_tables_fleet_absent_is_warning(tmp_path):
    """No --fleet run recorded yet → warn, don't fail (same contract as
    the distributed section)."""
    measured = {}
    extra = tmp_path / "BENCH_EXTRA.json"
    extra.write_text(json.dumps(measured))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 0
    assert any("fleet" in m and "WARN" in m for m in msgs)


# --------------------------------------------------------------- ISSUE 8
def _quant_section():
    """A self-consistent BENCH_EXTRA.json["quant"] section."""
    return {
        "f32": {"qps": 650.0, "rows_per_sec": 52000, "ok": 640,
                "rejected": 0, "p50_ms": 12.8, "p99_ms": 25.6,
                "request_dtype": "float32",
                "host_bytes_per_request": 5242880,
                "on_traffic_compiles": 0, "bit_identical": True},
        "int8": {"qps": 1365.0, "rows_per_sec": 109200, "ok": 640,
                 "rejected": 0, "p50_ms": 6.4, "p99_ms": 12.8,
                 "request_dtype": "int8",
                 "host_bytes_per_request": 1310720,
                 "on_traffic_compiles": 0, "bit_identical": True},
        "speedup": 2.1,
        "bytes_ratio": 4.0,
        "accuracy_delta": 0.027,
        "gate_max_delta": 0.05,
        "gate_passed": True,
        "gate_n_examples": 256,
    }


def _extra_with_quant(quant):
    measured = {}
    measured["quant"] = quant
    measured["quant_speedup"] = quant.get("speedup")
    measured["quant_accuracy_delta"] = quant.get("accuracy_delta")
    return measured


def test_check_tables_validates_quant_section(tmp_path):
    """ISSUE 8 satellite: --check-tables covers the quant keys — a
    self-consistent recorded section passes, and each drift class
    (speedup not recomputable from the arm rows, speedup below the 1.2x
    acceptance floor, accuracy delta outside the declared gate, a failed
    gate flag, non-bit-identical arms, on-traffic compiles, stale
    top-level copies) fails loudly."""
    extra = tmp_path / "BENCH_EXTRA.json"

    extra.write_text(json.dumps(_extra_with_quant(_quant_section())))
    assert bench.check_tables(str(extra), log=lambda *a: None) == 0

    # claimed speedup not derivable from the recorded arm qps rows
    quant = _quant_section()
    quant["speedup"] = 9.9
    ex = _extra_with_quant(quant)
    extra.write_text(json.dumps(ex))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 1
    assert any("quant.speedup" in m and "recomputable" not in m for m in msgs)

    # a recorded run below the 1.2x floor is a recorded regression
    quant = _quant_section()
    quant["int8"]["qps"] = 700.0
    quant["speedup"] = round(700.0 / 650.0, 3)
    extra.write_text(json.dumps(_extra_with_quant(quant)))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 1
    assert any("1.2x" in m for m in msgs)

    # accuracy delta past the declared gate must never pass
    quant = _quant_section()
    quant["accuracy_delta"] = 0.08
    extra.write_text(json.dumps(_extra_with_quant(quant)))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 1
    assert any("accuracy_delta" in m and "gate" in m for m in msgs)

    # ...and so must a recorded failed-gate flag
    quant = _quant_section()
    quant["gate_passed"] = False
    extra.write_text(json.dumps(_extra_with_quant(quant)))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 1
    assert any("gate_passed" in m for m in msgs)

    # a non-bit-identical arm invalidates the whole comparison
    quant = _quant_section()
    quant["int8"]["bit_identical"] = False
    extra.write_text(json.dumps(_extra_with_quant(quant)))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 1
    assert any("bit_identical" in m for m in msgs)

    # on-traffic compiles break the policy-prewarm claim
    quant = _quant_section()
    quant["int8"]["on_traffic_compiles"] = 3
    extra.write_text(json.dumps(_extra_with_quant(quant)))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 1
    assert any("on-traffic compile" in m for m in msgs)

    # stale top-level copies are doc drift
    ex = _extra_with_quant(_quant_section())
    ex["quant_speedup"] = 1.5
    extra.write_text(json.dumps(ex))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 1
    assert any("quant_speedup" in m and "top-level" in m for m in msgs)

    # a missing required key is reported, not crashed over
    quant = _quant_section()
    del quant["bytes_ratio"]
    extra.write_text(json.dumps(_extra_with_quant(quant)))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 1
    assert any("quant.bytes_ratio" in m and "missing" in m for m in msgs)


def test_check_tables_quant_absent_is_warning(tmp_path):
    """No --quant run recorded yet -> warn, don't fail (same contract as
    the other optional sections)."""
    measured = {}
    extra = tmp_path / "BENCH_EXTRA.json"
    extra.write_text(json.dumps(measured))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 0
    assert any("quant" in m and "WARN" in m for m in msgs)


def _trace_section():
    """A self-consistent BENCH_EXTRA.json["trace"] section."""
    return {
        "off": {"qps": 430.0, "elapsed_s": 1.86, "ok": 800,
                "bit_identical": True},
        "sampled": {"qps": 425.7, "elapsed_s": 1.88, "ok": 800,
                    "bit_identical": True},
        "overhead_pct": 1.0,
        "sample_rate": 0.05,
        "rate0_per_call_allocations": 0,
        "span_cost_us": 12.8,
        "kept_traces": 34,
        "dropped_traces": 766,
    }


def _extra_with_trace(trace):
    measured = {}
    measured["trace"] = trace
    measured["trace_overhead_pct"] = trace.get("overhead_pct")
    return measured


def test_check_tables_validates_trace_section(tmp_path):
    """ISSUE 9 satellite: --check-tables covers the trace keys — a
    self-consistent recorded section passes, and each drift class
    (overhead not recomputable from the arm qps rows, overhead over the
    3% bound, a non-allocation-free rate-0 path, non-bit-identical arms,
    a sampled arm that never traced, stale top-level copies, missing
    keys) fails loudly."""
    extra = tmp_path / "BENCH_EXTRA.json"

    extra.write_text(json.dumps(_extra_with_trace(_trace_section())))
    assert bench.check_tables(str(extra), log=lambda *a: None) == 0

    # claimed overhead not derivable from the recorded arm qps rows
    tr = _trace_section()
    tr["overhead_pct"] = 2.5
    ex = _extra_with_trace(tr)
    extra.write_text(json.dumps(ex))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 1
    assert any("trace.overhead_pct" in m and "give" in m for m in msgs)

    # a recorded run over the 3% bound is a recorded regression
    tr = _trace_section()
    tr["sampled"]["qps"] = 400.0
    tr["overhead_pct"] = round((1 - 400.0 / 430.0) * 100, 2)
    ex = _extra_with_trace(tr)
    extra.write_text(json.dumps(ex))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 1
    assert any("3% acceptance bound" in m for m in msgs)

    # the rate-0 fast path must never have allocated per call
    tr = _trace_section()
    tr["rate0_per_call_allocations"] = 2
    extra.write_text(json.dumps(_extra_with_trace(tr)))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 1
    assert any("rate0_per_call_allocations" in m for m in msgs)

    # a non-bit-identical arm invalidates the whole comparison
    tr = _trace_section()
    tr["sampled"]["bit_identical"] = False
    extra.write_text(json.dumps(_extra_with_trace(tr)))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 1
    assert any("bit_identical" in m for m in msgs)

    # an on arm that completed zero traces was not actually tracing
    tr = _trace_section()
    tr["kept_traces"] = tr["dropped_traces"] = 0
    extra.write_text(json.dumps(_extra_with_trace(tr)))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 1
    assert any("not actually tracing" in m for m in msgs)

    # stale top-level copies are doc drift
    ex = _extra_with_trace(_trace_section())
    ex["trace_overhead_pct"] = 0.1
    extra.write_text(json.dumps(ex))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 1
    assert any("trace_overhead_pct" in m and "top-level" in m for m in msgs)

    # a missing required key is reported, not crashed over
    tr = _trace_section()
    del tr["rate0_per_call_allocations"]
    extra.write_text(json.dumps(_extra_with_trace(tr)))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 1
    assert any("trace.rate0_per_call_allocations" in m and "missing" in m
               for m in msgs)


def test_check_tables_trace_absent_is_warning(tmp_path):
    """No --trace-overhead run recorded yet -> warn, don't fail (same
    contract as the other optional sections)."""
    measured = {}
    extra = tmp_path / "BENCH_EXTRA.json"
    extra.write_text(json.dumps(measured))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 0
    assert any("trace" in m and "WARN" in m for m in msgs)


def _autoscale_section():
    """A self-consistent BENCH_EXTRA.json["autoscale"] section (the
    ISSUE 10 closed-loop drill record)."""
    return {
        "requests_total": 41,
        "errors": 0,
        "bit_identical": True,
        "control_ticks": 19,
        "tick_budget": 100,
        "breach_tick": 1,
        "scale_up_tick": 5,
        "ticks_from_breach": 4,
        "on_traffic_compiles": 0,
        "scale_up": {"burn_fast": 8.0, "burn_slow": 4.4,
                     "replicas_after": 2, "compile_count": 5,
                     "headroom_bytes": None, "replica_cost_bytes": 2720},
        "scale_down": {"burn_fast": 0.0, "replicas_after": 1,
                       "elapsed_since_up_s": 1.52},
        "config": {"up_burn": 2.0, "confirm_burn": 1.0, "down_burn": 0.5,
                   "up_cooldown_s": 0.5, "down_cooldown_s": 1.5,
                   "fast_window_s": 1, "slow_window_s": 2},
    }


def _extra_with_autoscale(section):
    measured = {}
    measured["autoscale"] = section
    measured["autoscale_ticks_to_scale"] = section.get("ticks_from_breach")
    return measured


def test_check_tables_validates_autoscale_section(tmp_path):
    """ISSUE 10 satellite: --check-tables covers the autoscale keys — a
    self-consistent drill record passes; a drill with client errors, a
    non-bit-identical run, a tick count not recomputable from the
    breach/scale-up rows, an over-budget scale-up, on-traffic compiles,
    a cooldown-violating scale-down, wrong replica trajectories, or a
    stale top-level copy fails loudly."""
    extra = tmp_path / "BENCH_EXTRA.json"

    extra.write_text(json.dumps(_extra_with_autoscale(_autoscale_section())))
    assert bench.check_tables(str(extra), log=lambda *a: None) == 0

    cases = [
        (dict(errors=3), "client-invisible"),
        (dict(bit_identical=False), "bit-identical"),
        (dict(ticks_from_breach=2), "tick rows give"),
        (dict(breach_tick=1, scale_up_tick=150, ticks_from_breach=149),
         "over the recorded budget"),
        (dict(on_traffic_compiles=2), "compiled on live traffic"),
    ]
    for patch, needle in cases:
        sec = _autoscale_section()
        sec.update(patch)
        extra.write_text(json.dumps(_extra_with_autoscale(sec)))
        msgs = []
        assert bench.check_tables(str(extra), log=msgs.append) == 1, needle
        assert any(needle in m for m in msgs), (needle, msgs)

    # a scale-down inside the cooldown is a policy violation on record
    sec = _autoscale_section()
    sec["scale_down"]["elapsed_since_up_s"] = 0.8
    extra.write_text(json.dumps(_extra_with_autoscale(sec)))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 1
    assert any("inside the" in m and "cooldown" in m for m in msgs)

    # wrong replica trajectory (never scaled, or never unwound)
    sec = _autoscale_section()
    sec["scale_down"]["replicas_after"] = 2
    extra.write_text(json.dumps(_extra_with_autoscale(sec)))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 1
    assert any("expected 2->1" in m for m in msgs)

    # a recorded breach that never breached cannot justify the scale-up
    sec = _autoscale_section()
    sec["scale_up"]["burn_fast"] = 1.0
    extra.write_text(json.dumps(_extra_with_autoscale(sec)))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 1
    assert any("never breached" in m for m in msgs)

    # stale top-level copy
    ex = _extra_with_autoscale(_autoscale_section())
    ex["autoscale_ticks_to_scale"] = 9
    extra.write_text(json.dumps(ex))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 1
    assert any("autoscale_ticks_to_scale" in m and "top-level" in m
               for m in msgs)

    # absence is a warning (section not run), never a silent pass
    measured = {}
    extra.write_text(json.dumps(measured))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 0
    assert any("autoscale" in m and "WARN" in m for m in msgs)


# --------------------------------------------------------------- ISSUE 11
def _paging_section():
    """A self-consistent BENCH_EXTRA.json["paging"] section (the ISSUE 11
    HBM-budgeted paging drill record)."""
    return {
        "models_registered": 8,
        "hbm_budget_bytes": 2120,
        "per_model_bytes": 848,
        "budget_models": 2,
        "zipf_a": 1.5,
        "requests_total": 300,
        "request_errors": 0,
        "wrong_outputs": 0,
        "zipf_wall_s": 60.0,
        "resident_hits": 192,
        "cold_hits": 108,
        "hit_rate": 0.64,
        "page_ins": 144,
        "evictions": 150,
        "page_in_queue_waits": 30,
        "cold_page_in_p50_ms": 819.2,
        "cold_page_in_p99_ms": 1638.4,
        "cold_p99_bound_ms": 30000.0,
        "hot_qps_baseline": 400.0,
        "hot_qps_paged": 410.0,
        "hot_ratio": 1.025,
        "hot_ratio_floor": 0.95,
        "budget_samples": 31,
        "budget_exceeded_samples": 0,
        "max_resident_bytes": 1696,
        "on_traffic_compiles_after_page_in": 0,
    }


def _extra_with_paging(section):
    measured = {}
    measured["paging"] = section
    measured["paging_hit_rate"] = section.get("hit_rate")
    measured["paging_cold_p99_ms"] = section.get("cold_page_in_p99_ms")
    return measured


def test_check_tables_validates_paging_section(tmp_path):
    """ISSUE 11 satellite: --check-tables covers the paging keys — a
    self-consistent drill record passes; dropped requests, wrong outputs,
    budget-exceeded samples, a max-resident row over the budget, a
    non-recomputable hit rate or hot ratio, a hot ratio under its floor,
    a cold p99 over its recorded bound, a drill that never paged,
    on-traffic compiles after a page-in, or stale top-level copies all
    fail loudly."""
    extra = tmp_path / "BENCH_EXTRA.json"

    extra.write_text(json.dumps(_extra_with_paging(_paging_section())))
    assert bench.check_tables(str(extra), log=lambda *a: None) == 0

    cases = [
        (dict(request_errors=2), "never drop"),
        (dict(wrong_outputs=1), "answered differently"),
        (dict(budget_exceeded_samples=3), "crossed the budget"),
        (dict(max_resident_bytes=99999), "over the recorded budget"),
        (dict(hit_rate=0.9), "recorded hit rows give"),
        (dict(hot_ratio=1.4), "recorded qps rows give"),
        (dict(hot_qps_paged=300.0, hot_ratio=0.75), "under the recorded "
                                                    "floor"),
        (dict(cold_page_in_p99_ms=99999.0), "over the recorded bound"),
        (dict(page_ins=0, evictions=0), "never actually paged"),
        (dict(on_traffic_compiles_after_page_in=3),
         "compiled on live traffic"),
    ]
    for patch, needle in cases:
        sec = _paging_section()
        sec.update(patch)
        ex = _extra_with_paging(sec)
        # keep the top-level copies in sync so only the intended drift
        # class fires (staleness has its own case below)
        ex["paging_hit_rate"] = sec["hit_rate"]
        ex["paging_cold_p99_ms"] = sec["cold_page_in_p99_ms"]
        extra.write_text(json.dumps(ex))
        msgs = []
        assert bench.check_tables(str(extra), log=msgs.append) == 1, needle
        assert any(needle in m for m in msgs), (needle, msgs)

    # a missing required key is its own loud failure
    sec = _paging_section()
    del sec["budget_exceeded_samples"]
    extra.write_text(json.dumps(_extra_with_paging(sec)))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 1
    assert any("budget_exceeded_samples" in m and "missing" in m
               for m in msgs)

    # stale top-level copies
    for key in ("paging_hit_rate", "paging_cold_p99_ms"):
        ex = _extra_with_paging(_paging_section())
        ex[key] = 0.123
        extra.write_text(json.dumps(ex))
        msgs = []
        assert bench.check_tables(str(extra), log=msgs.append) == 1
        assert any(key in m and "top-level" in m for m in msgs), (key, msgs)

    # absence is a warning (section not run), never a silent pass
    measured = {}
    extra.write_text(json.dumps(measured))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 0
    assert any("paging" in m and "WARN" in m for m in msgs)


# --------------------------------------------------------------- ISSUE 12
def _control_plane_section():
    """A self-consistent BENCH_EXTRA.json["control_plane"] section (the
    ISSUE 12 replicated-control-plane drill record)."""
    return {
        "routers": 2,
        "workers": 2,
        "lease_s": 1.5,
        "requests_total": 900,
        "errors": 0,
        "bit_identical": True,
        "router_kill": {"victim": "r1", "errors": 0, "requests": 220,
                        "relaunched_s": 6.2, "client_failovers": 4},
        "traffic_step": {"step_factor": 10, "low_threads": 3,
                         "high_threads": 30, "errors": 0,
                         "requests": 500, "scaled_by": "r0",
                         "predictive_signal": "queue",
                         "burn_fast_at_decision": 0.0, "up_burn": 2.0,
                         "breach_scaleups": 0, "replicas_before": 2,
                         "replicas_after": 3},
        "leader_kill": {"victim": "r0", "new_leader": "r1", "errors": 0,
                        "requests": 180, "takeover_s": 1.9,
                        "takeover_budget_s": 3.0,
                        "elections_recorded": 3},
        "exactly_once": {"applied_scaleups": 1, "replica_growth": 1,
                         "follower_shadow_decisions": 2,
                         "nonleader_applies": 0},
    }


def _extra_with_control_plane(section):
    measured = {}
    measured["control_plane"] = section
    measured["control_plane_takeover_s"] = \
        section["leader_kill"].get("takeover_s")
    return measured


def test_check_tables_validates_control_plane_section(tmp_path):
    """ISSUE 12 satellite: --check-tables covers the control-plane keys —
    a self-consistent drill record passes; client errors in any phase, a
    non-bit-identical run, a single-router "replication" drill, a kill
    absorbed with zero failovers, an at/after-breach "predictive"
    scale-up, breach-triggered scale-ups, a step that never scaled,
    double or non-leader lever applies, a missing follower shadow, an
    over-budget takeover, zero recorded elections, or a stale top-level
    takeover copy all fail loudly."""
    extra = tmp_path / "BENCH_EXTRA.json"

    extra.write_text(json.dumps(
        _extra_with_control_plane(_control_plane_section())))
    assert bench.check_tables(str(extra), log=lambda *a: None) == 0

    def patched(path, value):
        sec = _control_plane_section()
        node = sec
        for k in path[:-1]:
            node = node[k]
        node[path[-1]] = value
        return sec

    cases = [
        (patched(("errors",), 3), "client-invisible"),
        (patched(("bit_identical",), False), "bit-identical"),
        (patched(("routers",), 1), ">= 2 routers"),
        (patched(("router_kill", "errors"), 2), "must be 0"),
        (patched(("traffic_step", "requests"), 0), "no recorded traffic"),
        (patched(("router_kill", "client_failovers"), 0),
         "never absorbed"),
        (patched(("traffic_step", "burn_fast_at_decision"), 2.5),
         "not pre-breach"),
        (patched(("traffic_step", "breach_scaleups"), 2), "must be 0"),
        (patched(("traffic_step", "predictive_signal"), "vibes"),
         "unknown predictive signal"),
        (patched(("traffic_step", "replicas_after"), 2), "never scaled"),
        (patched(("exactly_once", "applied_scaleups"), 2),
         "double (or phantom) lever"),
        (patched(("exactly_once", "nonleader_applies"), 1),
         "non-leader lever"),
        (patched(("exactly_once", "follower_shadow_decisions"), 0),
         "not computing"),
        (patched(("leader_kill", "elections_recorded"), 0),
         "no election events"),
    ]
    for sec, needle in cases:
        extra.write_text(json.dumps(_extra_with_control_plane(sec)))
        msgs = []
        assert bench.check_tables(str(extra), log=msgs.append) == 1, needle
        assert any(needle in m for m in msgs), (needle, msgs)

    # an over-budget takeover fails against its OWN recorded budget
    sec = _control_plane_section()
    sec["leader_kill"]["takeover_s"] = 5.0
    extra.write_text(json.dumps(_extra_with_control_plane(sec)))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 1
    assert any("over the recorded budget" in m for m in msgs)

    # a missing required key is its own loud failure
    sec = _control_plane_section()
    del sec["exactly_once"]
    extra.write_text(json.dumps(_extra_with_control_plane(sec)))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 1
    assert any("control_plane.exactly_once" in m and "missing" in m
               for m in msgs)

    # stale top-level takeover copy
    ex = _extra_with_control_plane(_control_plane_section())
    ex["control_plane_takeover_s"] = 0.1
    extra.write_text(json.dumps(ex))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 1
    assert any("control_plane_takeover_s" in m and "top-level" in m
               for m in msgs)

    # absence is a warning (section not run), never a silent pass
    measured = {}
    extra.write_text(json.dumps(measured))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 0
    assert any("control_plane" in m and "WARN" in m for m in msgs)


def _analysis_section():
    """A self-consistent BENCH_EXTRA.json["analysis"] section (the
    ISSUE 14 lockdep-overhead + lint record)."""
    return {
        "off": {"qps": 4178.0, "bit_identical": True},
        "on": {"qps": 4131.6, "bit_identical": True},
        "overhead_pct": 1.11,
        "bound_pct": 5.0,
        "lint_findings": 0,
        "lockdep_lock_classes": 7,
        "lockdep_edges": 1,
        "lockdep_violations": 0,
    }


def _extra_with_analysis(section):
    measured = {}
    measured["analysis"] = section
    measured["analysis_lockdep_overhead_pct"] = section.get("overhead_pct")
    return measured


def test_check_tables_validates_analysis_section(tmp_path):
    """ISSUE 14 satellite: --check-tables covers the analysis keys — a
    self-consistent recorded section passes, and each drift class
    (overhead not recomputable from the arm qps rows, overhead over the
    recorded bound, non-bit-identical arms, a dirty lint, recorded
    violations, an inert witness, stale top-level copy, missing keys)
    fails loudly."""
    extra = tmp_path / "BENCH_EXTRA.json"

    extra.write_text(json.dumps(_extra_with_analysis(_analysis_section())))
    assert bench.check_tables(str(extra), log=lambda *a: None) == 0

    def failing(mutate, needle):
        s = _analysis_section()
        mutate(s)
        ex = _extra_with_analysis(s)
        extra.write_text(json.dumps(ex))
        msgs = []
        assert bench.check_tables(str(extra), log=msgs.append) == 1, needle
        assert any(needle in m for m in msgs), (needle, msgs)

    failing(lambda s: s.update(overhead_pct=0.3),
            "recorded arm qps rows give")
    failing(lambda s: (s.update(bound_pct=1.0)), "over the recorded")
    failing(lambda s: s["on"].update(bit_identical=False),
            "analysis.on: bit_identical")
    failing(lambda s: s.update(lint_findings=3), "analysis.lint_findings")
    failing(lambda s: s.update(lockdep_violations=1),
            "analysis.lockdep_violations")
    failing(lambda s: s.update(lockdep_lock_classes=0),
            "not actually witnessed")
    failing(lambda s: s.pop("bound_pct"), "missing from the recorded")

    # stale top-level copy
    ex = _extra_with_analysis(_analysis_section())
    ex["analysis_lockdep_overhead_pct"] = 0.5
    # keep the section's own overhead recomputable so ONLY the copy drifts
    extra.write_text(json.dumps(ex))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 1
    assert any("analysis_lockdep_overhead_pct: top-level copy" in m
               for m in msgs)


def test_check_tables_analysis_absent_is_warning(tmp_path):
    """No --analysis run recorded yet -> warn, don't fail (same contract
    as the other optional sections)."""
    measured = {}
    extra = tmp_path / "BENCH_EXTRA.json"
    extra.write_text(json.dumps(measured))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 0
    assert any("analysis" in m and "WARN" in m for m in msgs)


def _sessions_section():
    """A self-consistent BENCH_EXTRA.json["sessions"] section (the
    ISSUE 16 session-tier A/B record)."""
    return {
        "n_sessions": 8,
        "steps_per_session": 30,
        "bucket": 8,
        "serial": {"qps": 250.0, "bit_identical": True},
        "batched": {"qps": 2000.0, "bit_identical": True},
        "speedup": 8.0,
        "on_traffic_compiles": 0,
        "spill_p99_s": 0.0001,
        "rehydrate_p99_s": 0.0005,
        "rehydrate_count": 8,
        "lost": 0,
    }


def _extra_with_sessions(section):
    measured = {}
    measured["sessions"] = section
    measured["sessions_step_speedup"] = section["speedup"]
    return measured


def test_check_tables_validates_sessions_section(tmp_path):
    """ISSUE 16 satellite: --check-tables covers the session-tier keys —
    a self-consistent A/B record passes; a non-bit-identical arm, a
    speedup the recorded qps rows can't reproduce, a batched arm losing
    to the serial rnn_time_step loop, on-traffic compiles, lost
    sessions, a rehydrate cycle that never ran, a negative latency, a
    missing key, or a stale top-level copy all fail loudly."""
    extra = tmp_path / "BENCH_EXTRA.json"

    extra.write_text(json.dumps(_extra_with_sessions(_sessions_section())))
    assert bench.check_tables(str(extra), log=lambda *a: None) == 0

    def failing(mutate, needle):
        sec = _sessions_section()
        mutate(sec)
        extra.write_text(json.dumps(_extra_with_sessions(sec)))
        msgs = []
        assert bench.check_tables(str(extra), log=msgs.append) == 1, needle
        assert any(needle in m for m in msgs), (needle, msgs)

    failing(lambda s: s["serial"].update(bit_identical=False),
            "sessions.serial: bit_identical")
    failing(lambda s: s["batched"].update(bit_identical=False),
            "sessions.batched: bit_identical")
    failing(lambda s: s.update(speedup=4.0), "qps rows give")
    failing(lambda s: (s["batched"].update(qps=200.0),
                       s.update(speedup=0.8)),
            "lost to the serial rnn_time_step loop")
    failing(lambda s: s.update(on_traffic_compiles=2), "must be 0")
    failing(lambda s: s.update(lost=1), "sessions.lost")
    failing(lambda s: s.update(rehydrate_count=0), "never ran")
    failing(lambda s: s.update(spill_p99_s=-1.0),
            "not a non-negative latency")
    failing(lambda s: s.pop("rehydrate_p99_s"), "missing from the recorded")

    # stale top-level copy
    ex = _extra_with_sessions(_sessions_section())
    ex["sessions_step_speedup"] = 2.0
    extra.write_text(json.dumps(ex))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 1
    assert any("sessions_step_speedup: top-level copy" in m for m in msgs)


def test_check_tables_sessions_absent_is_warning(tmp_path):
    """No --sessions run recorded yet -> warn, don't fail (same contract
    as the other optional sections)."""
    measured = {}
    extra = tmp_path / "BENCH_EXTRA.json"
    extra.write_text(json.dumps(measured))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 0
    assert any("sessions" in m and "WARN" in m for m in msgs)


def _delivery_section():
    """A self-consistent BENCH_EXTRA.json["delivery"] section (the
    ISSUE 17 gated-delivery drill record)."""
    return {
        "rounds": 2,
        "canary_cap": 0.25,
        "bad": {
            "verdicts": ["rolled_back", "rolled_back"],
            "causes": ["slo_latency_burn", "slo_latency_burn"],
            "candidate_served": [4, 5],
            "candidate_share": [0.006, 0.0056],
            "max_candidate_share": 0.006,
            "requests": 1391,
            "client_errors": 0,
            "http_errors": 0,
            "incumbent_bit_identical": True,
        },
        "good": {
            "verdicts": ["promoted", "promoted"],
            "requests": 1501,
            "client_errors": 0,
            "http_errors": 0,
            "bit_identical": True,
        },
        "bundle": {
            "stage_histories": {
                "bad-v2": ["gate", "shadow", "canary",
                           "rollback_pending", "rolled_back"],
                "good-v3": ["gate", "shadow", "canary", "canary_ramp",
                            "promote_ready", "promoted"],
                "good-v4": ["gate", "shadow", "canary", "canary_ramp",
                            "promote_ready", "promoted"],
                "bad-v5": ["gate", "shadow", "canary",
                           "rollback_pending", "rolled_back"],
            },
            "seq_gapless": True,
            "rollbacks": 2,
            "promotes": 2,
            "gate_passes": 4,
        },
    }


def _extra_with_delivery(section):
    measured = {}
    measured["delivery"] = section
    measured["delivery_max_bad_share"] = \
        section["bad"]["max_candidate_share"]
    return measured


def test_check_tables_validates_delivery_section(tmp_path):
    """ISSUE 17 satellite: --check-tables covers the gated-delivery
    keys — a self-consistent drill record passes; a bad deploy that did
    not roll back, a candidate share over the canary cap (or a stale
    max), a canary that never served, client errors, broken
    bit-identity, a good deploy that did not promote, a gappy journal,
    a bundle whose rollback/promote counts or stage histories disagree
    with the recorded deploys, a missing key, or a stale top-level copy
    all fail loudly."""
    extra = tmp_path / "BENCH_EXTRA.json"

    extra.write_text(json.dumps(_extra_with_delivery(_delivery_section())))
    assert bench.check_tables(str(extra), log=lambda *a: None) == 0

    def failing(mutate, needle):
        sec = _delivery_section()
        mutate(sec)
        extra.write_text(json.dumps(_extra_with_delivery(sec)))
        msgs = []
        assert bench.check_tables(str(extra), log=msgs.append) == 1, needle
        assert any(needle in m for m in msgs), (needle, msgs)

    failing(lambda s: s["bad"].update(verdicts=["rolled_back",
                                                "promoted"]),
            "every bad deploy must roll back")
    failing(lambda s: s["bad"].update(causes=["slo_latency_burn", ""]),
            "must record its cause")
    failing(lambda s: s["bad"].update(candidate_served=[4, 0]),
            "never exercised")
    failing(lambda s: s["bad"].update(candidate_share=[0.4, 0.0056],
                                      max_candidate_share=0.4),
            "exceeds the 0.25 canary cap")
    failing(lambda s: s["bad"].update(max_candidate_share=0.001),
            "recorded shares give")
    failing(lambda s: s["good"].update(verdicts=["promoted",
                                                 "rolled_back"]),
            "every good deploy must promote")
    failing(lambda s: s["bad"].update(client_errors=3), "must be 0")
    failing(lambda s: s["good"].update(http_errors=1), "must be 0")
    failing(lambda s: s["bad"].update(requests=0), "no recorded traffic")
    failing(lambda s: s["bad"].update(incumbent_bit_identical=False),
            "incumbent_bit_identical")
    failing(lambda s: s["good"].update(bit_identical=False),
            "delivery.good.bit_identical")
    failing(lambda s: s["bundle"].update(seq_gapless=False),
            "seq_gapless")
    failing(lambda s: s["bundle"].update(rollbacks=1),
            "recorded bad deploys")
    failing(lambda s: s["bundle"].update(promotes=3),
            "recorded good deploys")
    failing(lambda s: s["bundle"]["stage_histories"].pop("bad-v2"),
            "histories for")
    failing(lambda s: s["bundle"]["stage_histories"].update(
        {"bad-v2": ["gate", "shadow", "rolled_back"]}),
            "not a complete")
    failing(lambda s: s["bundle"]["stage_histories"].update(
        {"good-v3": ["gate", "shadow", "canary", "canary_ramp",
                     "promote_ready"]}),
            "not a complete")
    failing(lambda s: s.pop("bundle"), "missing from the recorded")

    # stale top-level copy
    ex = _extra_with_delivery(_delivery_section())
    ex["delivery_max_bad_share"] = 0.2
    extra.write_text(json.dumps(ex))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 1
    assert any("delivery_max_bad_share: top-level copy" in m
               for m in msgs)


def test_check_tables_delivery_absent_is_warning(tmp_path):
    """No --delivery run recorded yet -> warn, don't fail (same contract
    as the other optional sections)."""
    measured = {}
    extra = tmp_path / "BENCH_EXTRA.json"
    extra.write_text(json.dumps(measured))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 0
    assert any("delivery" in m and "WARN" in m for m in msgs)


def _wire_section():
    """A self-consistent BENCH_EXTRA.json["wire"] section (the ISSUE 18
    routed transport A/B record)."""
    return {
        "n_threads": 4,
        "per_thread": 20,
        "rows_per_request": 4,
        "features": 4096,
        "json": {"qps": 30.0, "device_idle_fraction": 0.79,
                 "bit_identical": True},
        "json_keepalive": {"qps": 33.0, "device_idle_fraction": 0.78,
                           "bit_identical": True},
        "binary": {"qps": 240.0, "device_idle_fraction": 0.64,
                   "bit_identical": True},
        "speedup": 8.0,
        "keepalive_speedup": 1.1,
        "idle_fraction_delta": 0.15,
        "protocol_errors_clean_arms": 0,
        "shm_hops_total": 168,
        "zero_copy_rows_total": 672,
    }


def _extra_with_wire(section):
    measured = {}
    measured["wire"] = section
    measured["wire_routed_speedup"] = section["speedup"]
    return measured


def test_check_tables_validates_wire_section(tmp_path):
    """ISSUE 18 satellite: --check-tables covers the wire-transport keys
    — a self-consistent A/B record passes; a non-bit-identical arm, a
    speedup the recorded qps rows can't reproduce, a speedup under the
    3x contract, a keepalive speedup that doesn't recompute, an
    idle-fraction delta that disagrees with the arm fractions (or isn't
    a reduction), protocol errors in the clean arms, an out-of-range
    idle fraction, a missing key, or a stale top-level copy all fail
    loudly."""
    extra = tmp_path / "BENCH_EXTRA.json"

    extra.write_text(json.dumps(_extra_with_wire(_wire_section())))
    assert bench.check_tables(str(extra), log=lambda *a: None) == 0

    def failing(mutate, needle):
        sec = _wire_section()
        mutate(sec)
        extra.write_text(json.dumps(_extra_with_wire(sec)))
        msgs = []
        assert bench.check_tables(str(extra), log=msgs.append) == 1, needle
        assert any(needle in m for m in msgs), (needle, msgs)

    failing(lambda s: s["binary"].update(bit_identical=False),
            "wire.binary: bit_identical")
    failing(lambda s: s["json"].update(bit_identical=False),
            "wire.json: bit_identical")
    failing(lambda s: s.update(speedup=5.0), "qps rows give")
    failing(lambda s: (s["binary"].update(qps=60.0), s.update(speedup=2.0)),
            "under the 3x contract")
    failing(lambda s: s.update(keepalive_speedup=3.0),
            "wire.keepalive_speedup")
    failing(lambda s: s.update(idle_fraction_delta=0.4),
            "recorded arm fractions give")
    failing(lambda s: (s["binary"].update(device_idle_fraction=0.85),
                       s.update(idle_fraction_delta=-0.06)),
            "did not reduce device idle time")
    failing(lambda s: s.update(protocol_errors_clean_arms=2),
            "wire.protocol_errors_clean_arms")
    failing(lambda s: s["json"].update(device_idle_fraction=1.4),
            "not a fraction in [0, 1]")
    failing(lambda s: s.pop("idle_fraction_delta"),
            "missing from the recorded section")

    # a malformed section (arm is not a dict) is a failure, not a crash
    failing(lambda s: s.update(json=3.0), "wire")

    # stale top-level copy
    ex = _extra_with_wire(_wire_section())
    ex["wire_routed_speedup"] = 2.0
    extra.write_text(json.dumps(ex))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 1
    assert any("wire_routed_speedup: top-level copy" in m for m in msgs)


def test_check_tables_wire_absent_is_warning(tmp_path):
    """No --wire run recorded yet -> warn, don't fail (same contract as
    the other optional sections)."""
    measured = {}
    extra = tmp_path / "BENCH_EXTRA.json"
    extra.write_text(json.dumps(measured))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 0
    assert any("wire" in m and "WARN" in m for m in msgs)


# ==========================================================================
# ISSUE 19: the scheduler section
def _scheduler_section():
    """A self-consistent BENCH_EXTRA.json["scheduler"] section (the
    ISSUE 19 idle-harvest drill record)."""
    return {
        "tick_s": 0.02,
        "harvest": {
            "baseline": {"requests": 3000, "p99_ms": 15.0,
                         "device_idle_fraction": 0.96,
                         "serving_busy_fraction": 0.04,
                         "harvested_busy_s": 0.0,
                         "bit_identical": True},
            "harvest": {"requests": 3100, "p99_ms": 15.3,
                        "device_idle_fraction": 0.80,
                        "serving_busy_fraction": 0.03,
                        "harvested_busy_s": 2.5,
                        "bit_identical": True},
            "idle_drop": 0.16,
            "p99_ratio": 1.02,
        },
        "preempt": {"ticks_to_preempt": 1, "preempt_join_s": 0.06,
                    "steps_done_at_preempt": 2, "total_steps": 6,
                    "losses_match": True, "params_bit_equal": True},
        "flywheel": {"examples": 16, "epochs": 3, "verdict": "promoted",
                     "deployed": True, "requests": 900,
                     "client_errors": 0,
                     "bundle": {"seq_gapless": True,
                                "scheduler_events": {
                                    "scheduler.submit": 1,
                                    "scheduler.claim": 1,
                                    "scheduler.start": 1,
                                    "scheduler.complete": 1},
                                "stages": ["gate", "shadow", "canary",
                                           "promote_ready",
                                           "promoted"]}},
    }


def _extra_with_scheduler(section):
    measured = {}
    measured["scheduler"] = section
    measured["scheduler_idle_drop"] = section["harvest"]["idle_drop"]
    return measured


def test_check_tables_validates_scheduler_section(tmp_path):
    """ISSUE 19 satellite: --check-tables covers the scheduler keys — a
    self-consistent record passes; a non-bit-identical arm, an idle
    drop the arm fractions can't reproduce (or under the 0.10
    contract), a p99 ratio that doesn't recompute (or over 1.05), a
    baseline arm that somehow harvested, a multi-tick preempt, a
    non-bit-exact resume, a preempt that didn't land mid-run, an
    unpromoted flywheel, a gapped bundle, a job life missing an event,
    a stage history that doesn't end promoted, a missing key, or a
    stale top-level copy all fail loudly."""
    extra = tmp_path / "BENCH_EXTRA.json"

    extra.write_text(json.dumps(_extra_with_scheduler(_scheduler_section())))
    assert bench.check_tables(str(extra), log=lambda *a: None) == 0

    def failing(mutate, needle):
        sec = _scheduler_section()
        mutate(sec)
        extra.write_text(json.dumps(_extra_with_scheduler(sec)))
        msgs = []
        assert bench.check_tables(str(extra), log=msgs.append) == 1, needle
        assert any(needle in m for m in msgs), (needle, msgs)

    failing(lambda s: s["harvest"]["harvest"].update(bit_identical=False),
            "scheduler.harvest.harvest: bit_identical")
    failing(lambda s: s["harvest"].update(idle_drop=0.3),
            "recorded arm fractions give")
    failing(lambda s: (s["harvest"]["harvest"].update(
                           device_idle_fraction=0.88),
                       s["harvest"].update(idle_drop=0.08)),
            "under the 0.10 absolute contract")
    failing(lambda s: s["harvest"].update(p99_ratio=0.9),
            "recorded arm p99s give")
    failing(lambda s: (s["harvest"]["harvest"].update(p99_ms=18.0),
                       s["harvest"].update(p99_ratio=1.2)),
            "more than 5% of routed p99")
    failing(lambda s: s["harvest"]["baseline"].update(
                harvested_busy_s=1.0),
            "must be 0 — no scheduler was attached")
    failing(lambda s: s["harvest"]["harvest"].update(
                harvested_busy_s=0.0),
            "measured no harvested_busy_s")
    failing(lambda s: s["harvest"]["harvest"].update(
                device_idle_fraction=1.4),
            "not a fraction in [0, 1]")
    failing(lambda s: s["preempt"].update(ticks_to_preempt=3),
            "preempt on the next tick")
    failing(lambda s: s["preempt"].update(params_bit_equal=False),
            "resume must be bit-exact")
    failing(lambda s: s["preempt"].update(steps_done_at_preempt=6),
            "not mid-run")
    failing(lambda s: s["flywheel"].update(verdict="rolled_back"),
            "must promote through gated delivery")
    failing(lambda s: s["flywheel"].update(client_errors=2),
            "scheduler.flywheel.client_errors")
    failing(lambda s: s["flywheel"]["bundle"].update(seq_gapless=False),
            "seq_gapless")
    failing(lambda s: s["flywheel"]["bundle"]["scheduler_events"].pop(
                "scheduler.complete"),
            "missing scheduler.complete")
    failing(lambda s: s["flywheel"]["bundle"]["stages"].append(
                "rolled_back"),
            "does not run gate -> promoted")
    failing(lambda s: s.pop("preempt"),
            "missing from the recorded section")

    # a malformed section (arm is not a dict) is a failure, not a crash
    failing(lambda s: s["harvest"].update(baseline=3.0), "scheduler")

    # stale top-level copy
    ex = _extra_with_scheduler(_scheduler_section())
    ex["scheduler_idle_drop"] = 0.5
    extra.write_text(json.dumps(ex))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 1
    assert any("scheduler_idle_drop: top-level copy" in m for m in msgs)


def test_check_tables_scheduler_absent_is_warning(tmp_path):
    """No --scheduler run recorded yet -> warn, don't fail (same
    contract as the other optional sections)."""
    measured = {}
    extra = tmp_path / "BENCH_EXTRA.json"
    extra.write_text(json.dumps(measured))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 0
    assert any("scheduler" in m and "WARN" in m for m in msgs)


# ==========================================================================
# ISSUE 20: the parallel section
def _parallel_section():
    """A self-consistent BENCH_EXTRA.json["parallel"] record (the ISSUE 20
    one-plan parallelism drill)."""
    h = "ab" * 32
    return {
        "steps_timed": 12,
        "batch": 64,
        "devices": 8,
        "single_axis": {"steps_per_sec": 40.0, "phash": h,
                        "bit_identical": True},
        "composed": {"steps_per_sec": 36.0, "phash": h,
                     "bit_identical": True},
        "speedup": 0.9,
        "serve": {
            "model_bytes": 400000,
            "budget_bytes": 240000,
            "flat_rejected": True,
            "requests": 32,
            "bit_identical": True,
            "on_traffic_compiles": 0,
            "budget_samples": 32,
            "budget_held_samples": 32,
            "budget_held": True,
            "per_device_max_bytes": 120000,
        },
    }


def _extra_with_parallel(section):
    measured = {}
    measured["parallel"] = section
    measured["parallel_composed_speedup"] = section["speedup"]
    return measured


def test_check_tables_validates_parallel_section(tmp_path):
    """ISSUE 20 satellite: --check-tables covers the parallel keys — a
    self-consistent record passes; a non-bitwise train arm, a speedup
    the recorded steps/sec rows can't reproduce, an admitted flat
    registration, a diverged or compiling serve drill, a budget that
    isn't actually sub-model-size, a per-device charge over budget, a
    partially-held budget, a missing key, or a stale top-level copy
    all fail loudly."""
    extra = tmp_path / "BENCH_EXTRA.json"

    extra.write_text(json.dumps(_extra_with_parallel(_parallel_section())))
    assert bench.check_tables(str(extra), log=lambda *a: None) == 0

    def failing(mutate, needle):
        sec = _parallel_section()
        mutate(sec)
        extra.write_text(json.dumps(_extra_with_parallel(sec)))
        msgs = []
        assert bench.check_tables(str(extra), log=msgs.append) == 1, needle
        assert any(needle in m for m in msgs), (needle, msgs)

    failing(lambda s: s["composed"].update(bit_identical=False),
            "parallel.composed: bit_identical")
    failing(lambda s: s.update(speedup=2.0), "steps/sec rows give")
    failing(lambda s: s["serve"].update(flat_rejected=False),
            "parallel.serve.flat_rejected")
    failing(lambda s: s["serve"].update(bit_identical=False),
            "parallel.serve.bit_identical")
    failing(lambda s: s["serve"].update(on_traffic_compiles=3),
            "parallel.serve.on_traffic_compiles")
    failing(lambda s: s["serve"].update(budget_bytes=500000),
            "did not constrain anything")
    failing(lambda s: s["serve"].update(per_device_max_bytes=300000),
            "exceeds the")
    failing(lambda s: s["serve"].update(budget_held_samples=30,
                                        budget_held=False),
            "parallel.serve.budget_held")
    failing(lambda s: s.pop("serve"), "missing from the recorded section")

    # a malformed section (arm is not a dict) is a failure, not a crash
    failing(lambda s: s.update(single_axis=3.0), "parallel")

    # stale top-level copy
    ex = _extra_with_parallel(_parallel_section())
    ex["parallel_composed_speedup"] = 2.0
    extra.write_text(json.dumps(ex))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 1
    assert any("parallel_composed_speedup: top-level copy" in m
               for m in msgs)


def test_check_tables_parallel_absent_is_warning(tmp_path):
    """No --parallel run recorded yet -> warn, don't fail (same contract
    as the other optional sections)."""
    measured = {}
    extra = tmp_path / "BENCH_EXTRA.json"
    extra.write_text(json.dumps(measured))
    msgs = []
    assert bench.check_tables(str(extra), log=msgs.append) == 0
    assert any("parallel" in m and "WARN" in m for m in msgs)
