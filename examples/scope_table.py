"""Where a training step's device time goes, by the scopes the program opens.

Fits the zoo's BERT-base (b32 x s512, bf16 compute, no dropout: the shape of
the benchmark's cell) under ``runtime.profiler.trace`` with a
``TrainingProfiler`` and prints the step program's milliseconds by phase
and scope, then the fit thread's stages. Needs a device plane in the trace,
so an accelerator: on the CPU it fits a small model and says so.

    python examples/scope_table.py [--batch 32] [--seq-len 512] [--steps 24] [--depth 2]
"""

import argparse
import json
import os
import shutil
import tempfile

import jax
import numpy as np

from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.data.iterators import ListDataSetIterator
from deeplearning4j_tpu.runtime import profiler
from deeplearning4j_tpu.runtime.environment import get_environment
from deeplearning4j_tpu.train import TrainingProfiler
from deeplearning4j_tpu.zoo import Bert


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--batch", type=int, default=32)
    parser.add_argument("--seq-len", type=int, default=512)
    parser.add_argument("--steps", type=int, default=24)
    parser.add_argument("--depth", type=int, default=2)
    parser.add_argument("--by-layer", action="store_true",
                        help="one row per layer, not per layer class")
    # CI runs every example in-process under pytest's own argv
    smoke = os.environ.get("DL4J_TPU_EXAMPLES_SMOKE") == "1"
    args = parser.parse_args([] if smoke else None)

    on_cpu = jax.devices()[0].platform == "cpu"
    get_environment().allow_bfloat16()
    net = (Bert.small(dropout_rate=0.0) if on_cpu else Bert.base(dropout_rate=0.0)).init()
    vocab, batch, seq_len = (1000, 4, 16) if on_cpu else (30522, args.batch, args.seq_len)
    rng = np.random.default_rng(0)
    data = []
    for _ in range(4):
        valid = rng.integers(seq_len // 4, seq_len + 1, (batch,))
        data.append(DataSet(
            rng.integers(0, vocab, (batch, seq_len)).astype(np.int32),
            np.eye(2, dtype=np.float32)[rng.integers(0, 2, batch)],
            features_mask=(np.arange(seq_len)[None, :] < valid[:, None]).astype(np.float32)))
    steps = [data[i % len(data)] for i in range(args.steps)]

    net.fit(ListDataSetIterator(steps[:3], batch_size=batch))  # compiles
    jax.block_until_ready(net.train_state)
    stages = TrainingProfiler()
    log_dir = tempfile.mkdtemp(prefix="scope_table_")
    try:
        with profiler.trace(log_dir) as trace:
            net.fit(ListDataSetIterator(steps, batch_size=batch), profiler=stages)
            jax.block_until_ready(net.train_state)
        if on_cpu:
            print("a CPU trace has no device plane: no scope table here")
        else:
            print(profiler.format_scope_times(trace.scope_times(
                net, depth=args.depth, merge_layers=not args.by_layer)))
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    print(stages.summary())
    print(json.dumps(stages.report()))


if __name__ == "__main__":
    main()
