"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found from the cell's name in ``BENCHMARK.json``: its
configuration (``configs[].file``), its traffic (``traffic/<traffic>.json``,
which names the runner), the configuration's family
(``families/<family>.py``), the limits of ``correct``
(``limits/<cell>.json``) and, in a traced run, one reader per per-layer
metric (``readers/<metric>.py``). No name of a cell, configuration or
metric appears in this file. It runs on a TPU listed in ``peaks.json`` or
fails; there is no fallback.
"""

import time

PROCESS_START = time.time()

import argparse
import importlib.util
import json
import os
import shutil
import sys
import types

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(kind, name):
    """``benchmark/<kind>/<name>.py`` as a module; names may hold dots."""
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"benchmark_{kind}_{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def applies(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


def resolve(workload, root=ROOT):
    """The cell's files, by name. Raises ``KeyError`` for an unknown cell."""
    manifest = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; it has {sorted(cells)}")
    entry = cells[workload]
    config_entry = {c["name"]: c for c in manifest["configs"]}[entry["config"]]
    config = load_json(os.path.join(root, config_entry["file"]))
    traffic = load_json(os.path.join(BENCH_DIR, "traffic", entry["traffic"] + ".json"))
    return types.SimpleNamespace(
        name=workload, chips=entry["chips"], config=config, traffic=traffic,
        limits=load_json(os.path.join(BENCH_DIR, "limits", workload + ".json")),
        family=load_module("families", config["family"]),
        runner=load_module("runners", traffic["runner"]),
        end_to_end=[m for m in manifest["end_to_end"] if applies(m, workload)],
        per_layer=[m for m in manifest["per_layer"] if applies(m, workload)])


def find_device(chips, peaks):
    """The device block of the result line and this chip's peaks; exits
    non-zero without a TPU that ``peaks.json`` lists or with too few chips."""
    import jax
    devices = jax.devices()
    d = devices[0]
    print(f"jax {jax.__version__} platform {d.platform} device_kind {d.device_kind!r} "
          f"count {len(devices)}", flush=True)
    if d.platform != "tpu":
        sys.exit(f"benchmark: platform is {d.platform!r}, not 'tpu'; no number is taken off the chip")
    if d.device_kind not in peaks:
        sys.exit(f"benchmark: device_kind {d.device_kind!r} is not in peaks.json ({sorted(peaks)})")
    if len(devices) < chips:
        sys.exit(f"benchmark: the cell asks for {chips} chips, jax reports {len(devices)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devices)}, peaks[d.device_kind]


def run_cell(cell, seed, seconds, trace, device, peak, process_start=PROCESS_START):
    """Run the cell once; returns the result line as a dict."""
    trace_dir = os.path.join(ROOT, ".bench_trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = types.SimpleNamespace(
        config=cell.config, traffic=cell.traffic, family=cell.family, chips=cell.chips,
        seed=seed, seconds=seconds, trace=bool(trace), trace_dir=trace_dir,
        process_start=process_start)
    run = cell.runner.run(ctx)
    print(f"comparison with the reference: {json.dumps(run['checks'])}", flush=True)
    checks = {name: [run["checks"][name], limit] for name, limit in cell.limits.items()}
    result = {"correct": all(value <= limit for value, limit in checks.values()),
              "attempted": run["attempted"], "failed": run["failed"], "metrics": {},
              "device": dict(device, memory_peak_bytes=run["memory_peak_bytes"])}
    if trace:
        from benchmark import trace_reduce
        # the step program's HLO text names each op's scope; it is megabytes, and only the reduction reads it
        reduced = trace_reduce.reduce_dir(trace_dir, run.pop("hlo_texts", None))
        shutil.rmtree(trace_dir, ignore_errors=True)
        print(f"trace reduced at {time.time() - process_start:.2f} s", flush=True)
        if reduced is None or not reduced["busy_s"] > 0 or not run["traced_s"]:
            sys.exit("benchmark: the trace holds no device operation after a 'measure' mark")
        for gap in reduced["gap_hosts"]:
            print(f"idle gap {json.dumps(gap)}", flush=True)
        if reduced["scopes"]:
            table = reduced["scopes"]
            print(f"scopes of {table['program']}: {table['step_s'] * 1e3:.3f} ms a step over {table['runs']} whole runs, "
                  f"{100 * table['attributed_fraction']:.2f}% in named scopes; ms by phase and scope: "
                  + json.dumps({phase: {scope: round(s * 1e3, 3) for scope, s in per.items()}
                                for phase, per in table["scopes"].items()}), flush=True)
        for metric in cell.per_layer:
            value = load_module("readers", metric["name"]).read(run, reduced, cell, peak)
            if value is not None:
                result["metrics"][metric["name"]] = {"value": value, "unit": metric["unit"]}
        result["device"].update(busy_s=reduced["busy_s"], window_s=run["traced_s"])
        result["breakdown"] = {"device_ops": reduced["device_ops"], "idle_gaps": reduced["idle_gaps"]}
    else:
        for metric in cell.end_to_end:
            result["metrics"][metric["name"]] = {"value": run["end_to_end"][metric["name"]],
                                                 "unit": metric["unit"]}
    result["checks"] = checks
    print(f"window {run['window_s']:.3f} s, {run['steps']} steps, set-up {run['end_to_end']['setup_s']:.2f} s, "
          f"compiles in window {run['compiles_in_window']}", flush=True)
    return result


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    sys.path.insert(0, ROOT)
    cell = resolve(args.workload)
    device, peak = find_device(cell.chips, load_json(os.path.join(BENCH_DIR, "peaks.json")))
    from deeplearning4j_tpu.runtime import compile_cache
    print(f"compile cache at {compile_cache.enable()}", flush=True)
    result = run_cell(cell, args.seed, args.seconds, args.trace, device, peak)
    print(f"whole run {time.time() - PROCESS_START:.1f} s (the reference runs after the window)", flush=True)
    for name, (value, limit) in result["checks"].items():
        print(f"check {name} {value:.6g} limit {limit:.6g}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
