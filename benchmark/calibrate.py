"""Readings from which a cell's limits of ``correct`` are set (PERF.md §2).

    python3 benchmark/calibrate.py --workload <cell> --seeds 1,2,3 [--program 1] [--control 1] [--faults 1]
                                   [--witness 0] [--leaves 0]

Per seed, in one process: the program's first steps against the float32
reference (the lower readings), the reference in fp8 put in the program's
place (the control), and the reference with a fault planted in it (half of
the batch left out). ``--witness 1`` adds the program computing in float32
and the reference with bfloat16 operands; ``--leaves n`` names the n worst
leaves of each reading and walks through the depth. Prints one JSON line
per reading. Runs on a TPU.
"""

import argparse
import gc
import json
import os
import sys
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import reference_train, run as bench_run  # noqa: E402


def half_batch(step):
    """Fault: the step sees only the first half of the batch's rows."""
    def faulty(params, state, moments, batch, t):
        return step(params, state, moments, tuple(None if a is None else a[:a.shape[0] // 2] for a in batch), t)
    return faulty


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--program", type=int, default=1)
    parser.add_argument("--control", type=int, default=1)
    parser.add_argument("--faults", type=int, default=1)
    parser.add_argument("--witness", type=int, default=0)
    parser.add_argument("--leaves", type=int, default=0, help="print the worst leaves of each reading")
    args = parser.parse_args(argv)
    cell = bench_run.resolve(args.workload)
    bench_run.find_device(cell.chips, bench_run.load_json(os.path.join(bench_run.BENCH_DIR, "peaks.json")))
    import jax
    from deeplearning4j_tpu.runtime import compile_cache
    compile_cache.enable()
    runner = cell.runner
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = types.SimpleNamespace(config=cell.config, traffic=cell.traffic, family=cell.family, seed=seed)
        want = runner.reference(ctx)
        readings = {}
        if args.program:
            net, fitter, datasets = runner.setup(ctx)
            readings["program"] = runner.program_readings(ctx, net, fitter, datasets)
            del net, fitter, datasets
            gc.collect()
        if args.witness:
            # the program computing in float32 at ``highest``, and the reference with bfloat16
            # operands: which side a gap of the bfloat16 program lies on
            f32 = types.SimpleNamespace(**dict(vars(ctx), config=dict(cell.config, precision={"compute": "float32"})))
            with jax.default_matmul_precision("highest"):
                net, fitter, datasets = runner.setup(f32)
                readings["witness_program_float32"] = runner.program_readings(f32, net, fitter, datasets)
            del net, fitter, datasets
            gc.collect()
            readings["witness_reference_bfloat16"] = runner.reference_readings(ctx, precision="bfloat16")
        if args.control:
            readings["control_fp8"] = runner.reference_readings(ctx, precision="fp8")
        if args.faults:
            readings["fault_half_batch"] = runner.reference_readings(ctx, transform=half_batch)
        if args.leaves:
            start = cell.family.init_params(cell.config, seed)
            names = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(start)[0]]
            del start
        for name, got in readings.items():
            print(json.dumps({"workload": cell.name, "seed": seed, "reading": name,
                              "losses": got["losses"], "reference_losses": want["losses"],
                              **reference_train.compare(got, want)}), flush=True)
            if args.leaves:
                table = reference_train.leaf_table(got, want)
                for key in ("grad_gap", "grad_diff", "delta_gap", "delta_diff"):
                    values = table[key]
                    pick = sorted(range(len(values)), key=lambda i: -values[i])[:args.leaves]
                    pick += list(range(0, len(values), max(1, len(values) // 24)))  # and a walk through the depth
                    print(f"  {key}: " + "; ".join(f"{names[i]} {values[i]:.4g}" for i in pick), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
