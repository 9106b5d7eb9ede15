"""What the reference side of a training cell costs on the chip, before a PR asks for the cell.

    python3 benchmark/probe_reference.py --config configs/bert-base-uncased.json --traffic traffic/ft-b32-s512.json \
        [--set '{"hidden_size": 2304, "num_attention_heads": 18, "intermediate_size": 9216, "num_hidden_layers": 8}'] \
        [--set-traffic '{"batch": 2}'] [--seed 1]

Makes the family's weights and batches from the seed, follows the plain
reference through the traffic's ``check_steps`` steps exactly as a run does
after its window (``runners/train_fit.reference_readings`` ->
``reference_train.follow``), and prints each loss and the device's peak:
``peak_bytes_in_use`` (arrays) + ``peak_bytes_reserved`` (the loaded
programs' temporaries), in bytes and per parameter. ``follow`` itself holds
the parameters, the moments and one gradient (16 bytes a parameter under
Adam); what a family's ``loss_fn`` allocates inside a step comes on top and
shows here. Not a cell, not in the manifest; imports nothing of the program.
"""

import argparse
import json
import os
import sys
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import run as bench_run  # noqa: E402


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--config", required=True, help="a file under benchmark/")
    parser.add_argument("--traffic", required=True, help="a file under benchmark/")
    parser.add_argument("--set", default="{}", help="JSON: keys of the configuration to override")
    parser.add_argument("--set-traffic", default="{}", help="JSON: keys of the traffic to override")
    parser.add_argument("--seed", type=int, default=1)
    args = parser.parse_args(argv)
    import jax
    config = dict(bench_run.load_json(os.path.join(bench_run.BENCH_DIR, args.config)), **json.loads(args.set))
    traffic = dict(bench_run.load_json(os.path.join(bench_run.BENCH_DIR, args.traffic)), **json.loads(args.set_traffic))
    ctx = types.SimpleNamespace(config=config, traffic=traffic, seed=args.seed,
                                family=bench_run.load_module("families", config["family"]))
    device = jax.local_devices()[0]
    n_params = ctx.family.n_params(config)
    print(f"{device.platform} {device.device_kind!r}: {n_params / 1e6:.1f} M parameters, "
          f"{16 * n_params / 1e9:.2f} GB at 16 bytes a parameter", flush=True)
    want = bench_run.load_module("runners", traffic["runner"]).reference_readings(ctx)
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use", 0) + stats.get("peak_bytes_reserved", 0)
    print(json.dumps({"losses": want["losses"], "n_params": n_params, "peak_bytes": peak,
                      "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                      "peak_bytes_reserved": stats.get("peak_bytes_reserved"),
                      "bytes_limit": stats.get("bytes_limit"), "peak_bytes_per_param": peak / n_params}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
