"""Test configuration: run everything on a virtual 8-device CPU mesh.

This is the JAX analog of the reference's ``local[N]`` Spark master trick
(SURVEY.md §4): multi-chip sharding paths are exercised on one host by
multiplying CPU devices.

The platform is pinned twice: ``JAX_PLATFORMS=cpu`` in the environment
(spawned workers inherit it) and ``jax_platforms`` in the config, which
still holds if a pytest plugin imported jax before this file ran.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_ENABLE_X64", "0")

# The persistent compilation cache stays OFF for the suite: the library
# never turns it on at import, and a cache placed from outside
# (JAX_COMPILATION_CACHE_DIR) is dropped here, before jax reads it, so
# every test — and every worker a test spawns — compiles what it runs.
# The cache tests enable a tmp_path cache themselves and detach it.
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import threading  # noqa: E402
import time  # noqa: E402

import pytest  # noqa: E402

import _round_record  # noqa: E402  (sibling module; pytest puts this dir on sys.path)

# Lock-order witness (ISSUE 14): the whole tier-1 suite runs with lockdep
# active (opt out with DL4J_TPU_LOCKDEP=0 when bisecting). The env var
# must be set BEFORE the package import below — the package bootstrap
# patches the threading constructors at import, so module-level locks are
# witnessed too, and spawned fleet/distributed workers inherit the env.
os.environ.setdefault("DL4J_TPU_LOCKDEP", "1")

from deeplearning4j_tpu.analysis import lockdep as _lockdep  # noqa: E402
from deeplearning4j_tpu.analysis.registry import (  # noqa: E402
    PIPELINE_THREAD_NAMES as _PIPELINE_THREAD_NAMES,
)

# Thread names of the training pipeline's background stages (ISSUE 4),
# the trace-collector fan-out fetchers (ISSUE 9), the SLO autoscaler
# control thread (ISSUE 10), and the lease-election heartbeat threads
# (ISSUE 12). Every fit()/close()/aggregate/stop path must join these; a
# survivor after a test means a leaked stage. The tuple is IMPORTED from
# the analysis registry (ISSUE 14) — the lint checks every
# threading.Thread name against the same source, so the leak guard and
# the linter can never drift.


# --------------------------------------------------------------------------
# TESTS_r*.json: per-round test-run artifact (VERDICT r5 weak #3 — "full
# suite green" must be a recorded artifact, not a commit-message claim).
# Every pytest run overwrites the CURRENT round's summary: collected /
# passed / failed / error / skipped counts, whether the slow tier was
# included (markexpr), wall time and exit status. The round number is
# max(BENCH_r*.json) + 1 — the round being built, stamped by the same
# driver convention that records BENCH artifacts at round close.

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_outcomes = {"passed": 0, "failed": 0, "error": 0, "skipped": 0,
             "xfailed": 0, "xpassed": 0}
_collected = {"n": 0, "deselected": 0}
_session_t0 = time.monotonic()


def _current_round() -> int:
    rounds = [int(m.group(1)) for p in
              glob.glob(os.path.join(_REPO_ROOT, "BENCH_r*.json"))
              if (m := re.search(r"BENCH_r(\d+)\.json$", p))]
    return (max(rounds) + 1) if rounds else 1


def pytest_collection_modifyitems(config, items):
    _collected["n"] = len(items)


def pytest_deselected(items):
    _collected["deselected"] += len(items)


def pytest_runtest_logreport(report):
    if report.when == "call":
        if hasattr(report, "wasxfail"):
            _outcomes["xpassed" if report.passed else "xfailed"] += 1
        elif report.passed:
            _outcomes["passed"] += 1
        elif report.failed:
            _outcomes["failed"] += 1
        elif report.skipped:
            _outcomes["skipped"] += 1
    elif report.when in ("setup", "teardown"):
        if report.failed:
            _outcomes["error"] += 1
        elif report.when == "setup" and report.skipped:
            _outcomes["skipped"] += 1


def pytest_sessionfinish(session, exitstatus):
    # only a full-suite run is a round artifact: a single-file, -k, --lf,
    # --deselect, or collect-only run must not overwrite the record with a
    # partial (or empty-but-green) count
    opt = session.config.option
    args = [a for a in session.config.args if not a.startswith("-")]
    if any(not os.path.isdir(a) for a in args):
        return
    if (getattr(opt, "keyword", "") or getattr(opt, "collectonly", False)
            or getattr(opt, "lf", False) or getattr(opt, "failedfirst", False)
            or getattr(opt, "deselect", None)):
        return
    markexpr = getattr(opt, "markexpr", "") or ""
    if markexpr not in ("", "not slow"):
        return  # `-m slow` etc. is a subset run, not a round record
    summary = {
        "round": _current_round(),
        "collected": _collected["n"],
        **_outcomes,
        # counted via pytest_deselected, NOT derived by subtraction (a
        # teardown error double-counts its test against the outcomes sum)
        "deselected": _collected["deselected"],
        "markexpr": markexpr,
        "slow_included": "not slow" not in markexpr,
        "exit_status": int(exitstatus),
        "duration_s": round(time.monotonic() - _session_t0, 1),
        "recorded_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    try:  # the artifact must never be able to fail the suite
        path = os.path.join(_REPO_ROOT,
                            f"TESTS_r{summary['round']:02d}.json")
        if _round_record.record_downgrades_prior(summary, path):
            return
        with open(path, "w") as f:
            json.dump(summary, f, indent=2)
    except OSError:
        pass


@pytest.fixture(autouse=True)
def _no_lockdep_violations():
    """ISSUE 14 guard: the lock-order witness recorded no new violation
    during this test. Cycle formation, blocking-while-holding and
    waits-while-holding all land here, attributed to the test whose
    traffic induced them (background threads may attribute a violation
    one test late — the suite still fails loudly, with both witness
    stacks in the report). Accepted edges live in
    analysis/lockdep_allow.toml with a reason, nowhere else."""
    yield
    if not _lockdep.enabled():
        return
    new = _lockdep.take_new_violations()
    assert not new, "lockdep violations:\n" + _lockdep.render_report(new)


@pytest.fixture(autouse=True)
def _no_stray_pipeline_threads():
    """Tier-1 guard: no prefetch/pipeline thread survives a test."""
    yield

    def stray():
        return [t.name for t in threading.enumerate()
                if t.is_alive() and t.name.startswith(_PIPELINE_THREAD_NAMES)]

    # grace window: a worker that just received its stop/sentinel may still
    # be mid-exit when the test body returns
    deadline = time.monotonic() + 5.0
    names = stray()
    while names and time.monotonic() < deadline:
        time.sleep(0.05)
        names = stray()
    assert not names, f"stray training-pipeline threads leaked: {names}"


@pytest.fixture
def fd_guard():
    """ISSUE 18 guard (opt-in by name): the test must not leak file
    descriptors — keep-alive pools park sockets, and a pool that forgets
    to close them shows up here. Counts ``/proc/self/fd`` before and
    after with a grace window (TIME_WAIT teardown, GC of dropped
    connections) and a small tolerance for allocator noise."""
    fd_dir = "/proc/self/fd"
    if not os.path.isdir(fd_dir):          # non-Linux: nothing to count
        yield
        return
    before = len(os.listdir(fd_dir))
    yield
    deadline = time.monotonic() + 5.0
    after = len(os.listdir(fd_dir))
    while after > before + 4 and time.monotonic() < deadline:
        gc.collect()
        time.sleep(0.05)
        after = len(os.listdir(fd_dir))
    assert after <= before + 4, \
        f"fd leak: {before} open before the test, {after} after"


def _assert_no_orphaned_workers(module_name: str, kind: str,
                                pid_fn: str = "live_worker_pids",
                                kill_fn: str = "kill_stray_workers"):
    """Shared process-leak check: poll ``module_name``'s pid registry
    (``pid_fn`` / ``kill_fn``) with a grace window, kill and fail on
    survivors. Checked only when the module was actually imported
    (importing it here would tax every unrelated test), and stray workers
    are killed so one leak can't cascade into every later test's
    assertion. ``kill_fn`` must kill exactly the population ``pid_fn``
    reports — a guard that only flags orphans must not nuke a managed
    fixture fleet while cleaning one up."""
    import sys as _sys
    mod = _sys.modules.get(module_name)
    if mod is None:
        return
    poll = getattr(mod, pid_fn)
    deadline = time.monotonic() + 5.0
    pids = poll()
    while pids and time.monotonic() < deadline:
        time.sleep(0.05)
        pids = poll()
    if pids:
        killed = getattr(mod, kill_fn)()
        assert False, f"orphaned {kind} worker processes leaked: {killed}"


@pytest.fixture(autouse=True)
def _no_orphaned_distributed_workers():
    """ISSUE 6 guard: no gloo worker subprocess launched through
    ``train.distributed`` survives a test."""
    yield
    _assert_no_orphaned_workers("deeplearning4j_tpu.train.distributed",
                                "distributed")


@pytest.fixture(autouse=True)
def _no_orphaned_fleet_workers():
    """ISSUE 7 guard: no serving fleet worker subprocess launched through
    ``serving.fleet`` outlives its supervisor (a module-scoped fixture
    fleet with a RUNNING FleetSupervisor is managed, not leaked — only
    orphans fail the test)."""
    yield
    _assert_no_orphaned_workers("deeplearning4j_tpu.serving.fleet",
                                "serving fleet",
                                pid_fn="orphaned_worker_pids",
                                kill_fn="kill_orphaned_workers")


@pytest.fixture(autouse=True)
def _no_orphaned_router_processes():
    """ISSUE 12 guard: no router subprocess launched through
    ``serving.control_plane`` outlives its RouterSupervisor — the same
    contract as the fleet-worker guard, one tier up."""
    yield
    _assert_no_orphaned_workers("deeplearning4j_tpu.serving.control_plane",
                                "router",
                                pid_fn="orphaned_router_pids",
                                kill_fn="kill_orphaned_routers")
