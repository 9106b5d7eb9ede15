"""ResNet-50 bottleneck-megakernel experiment (round 5, VERDICT r4 item 2).

Measures whether a hand Pallas kernel can beat XLA:TPU's conv emitter on
the anchor op of a whole-bottleneck-block megakernel: the stage-4 1x1
conv (as matmul) with the training-BN sum/sum-of-squares epilogue,
(256*49, 2048) @ (2048, 512) in bf16 with f32 stats.

Result on 1x v5e (2026-07-31): NEGATIVE — the Pallas kernel measures
0.149-0.159 ms across block sizes {224, 448, 896} vs XLA's 0.138 ms for
the identical program (bit-identical conv output); XLA runs at ~97% of
the 197 TF/s bf16 peak. Together with (a) whole-block VMEM residency not
fitting at batch 256 even at stage 4 (two 12.8 MB intra-block
activations + ~9 MB weights > 16 MB VMEM) and (b) training-BN batch
statistics forcing each conv output to be fully materialized before its
normalize, this closes the three-round-old megakernel question: the
~2786 img/s roofline ceiling at current traffic stands. Verdict
recorded per-run in BENCH_EXTRA.json["resnet_megakernel_experiment"].

Run: PYTHONPATH=. python experiments/resnet_megakernel_stage4.py  (on the chip)
Timing protocol: in-jit fori_loop chains of 32 vs 256 dependent
iterations, per-length min over 5 runs, differenced — the constant
dispatch+readback cost of a timing cancels (bench.py mxu_probe
protocol). The chain feeds each iteration's conv OUTPUT back into a
slice of the input so neither variant can dead-code-eliminate the
output write.
"""
import functools
import os
import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

N, K, C = 256 * 49, 2048, 512
BLOCK_N = int(os.environ.get("BN", 448))


def kernel(x_ref, w_ref, y_ref, s1_ref, s2_ref, acc1, acc2):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        acc1[...] = jnp.zeros_like(acc1)
        acc2[...] = jnp.zeros_like(acc2)

    y = jax.lax.dot(x_ref[...], w_ref[...],
                    preferred_element_type=jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)
    acc1[...] += jnp.sum(y, axis=0, keepdims=True)
    acc2[...] += jnp.sum(y * y, axis=0, keepdims=True)

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        s1_ref[...] = acc1[...]
        s2_ref[...] = acc2[...]


@jax.jit
def pallas_conv_stats(x, w):
    return pl.pallas_call(
        kernel,
        grid=(N // BLOCK_N,),
        in_specs=[pl.BlockSpec((BLOCK_N, K), lambda i: (i, 0)),
                  pl.BlockSpec((K, C), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((BLOCK_N, C), lambda i: (i, 0)),
                   pl.BlockSpec((1, C), lambda i: (0, 0)),
                   pl.BlockSpec((1, C), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((N, C), jnp.bfloat16),
                   jax.ShapeDtypeStruct((1, C), jnp.float32),
                   jax.ShapeDtypeStruct((1, C), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((1, C), jnp.float32),
                        pltpu.VMEM((1, C), jnp.float32)],
    )(x, w)


@jax.jit
def xla_conv_stats(x, w):
    y = jax.lax.dot(x, w, preferred_element_type=jnp.float32)
    return y.astype(jnp.bfloat16), \
        jnp.sum(y, axis=0, keepdims=True), \
        jnp.sum(y * y, axis=0, keepdims=True)


def chain(fn, x, w, n):
    def body(i, carry):
        xc, acc = carry
        y, s1, s2 = fn(xc, w)
        xc = xc.at[:, :C].add((y.astype(jnp.float32) * 1e-30).astype(xc.dtype))
        return xc, acc + s2[0, 0]

    return jax.lax.fori_loop(0, n, body, (x, jnp.float32(0.0)))[1]


def main():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (N, K), jnp.bfloat16)
    w = jax.random.normal(key, (K, C), jnp.bfloat16) * 0.02

    yp, s1p, s2p = pallas_conv_stats(x, w)
    yx, s1x, s2x = xla_conv_stats(x, w)
    assert float(jnp.max(jnp.abs(
        yp.astype(jnp.float32) - yx.astype(jnp.float32)))) == 0.0
    print("conv outputs bit-identical; stats rel err:",
          float(jnp.max(jnp.abs(s2p - s2x) / (jnp.abs(s2x) + 1e-3))))

    results = {}
    for name, fn in (("pallas", pallas_conv_stats), ("xla", xla_conv_stats)):
        cf = jax.jit(functools.partial(chain, fn), static_argnums=2)
        lo, hi = 32, 256
        for n in (lo, hi):
            float(cf(x, w, n))

        def timed(n):
            t0 = time.perf_counter()
            float(cf(x, w, n))
            return time.perf_counter() - t0

        t_lo = min(timed(lo) for _ in range(5))
        t_hi = min(timed(hi) for _ in range(5))
        dt = (t_hi - t_lo) / (hi - lo)
        results[name] = dt
        gflop = 2 * N * K * C / 1e9
        print(f"{name:6s} (BN={BLOCK_N}): {dt*1e3:.3f} ms/iter "
              f"(~{gflop/dt/1e3:.1f} TF/s incl. chain-feedback overhead)")
    print(f"pallas vs xla: {results['xla']/results['pallas']:.3f}x "
          f"({'pallas wins' if results['pallas'] < results['xla'] else 'XLA wins'})")


if __name__ == "__main__":
    main()
