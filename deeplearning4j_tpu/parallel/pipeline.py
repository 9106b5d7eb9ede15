"""GPipe-style pipeline parallelism over the ``pipe`` mesh axis.

The reference has NO pipeline parallelism (SURVEY.md §2.3) — parity-plus.
This is the scaling-book shift-register formulation: each pipe-axis device
holds ONE stage's params (leading stage dim sharded by shard_map), and a
``lax.fori_loop`` of ``n_microbatches + n_stages - 1`` ticks streams
microbatches through, passing activations to the next stage with a single
``ppermute`` per tick — all inside one compiled program, collectives on ICI.

Constraint of this formulation: stages must be shape-preserving
(transformer-block-like); the in/out activation shape is the microbatch
shape. Wrap unequal-width networks so the pipelined segment is the uniform
trunk.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.runtime.mesh import PIPE_AXIS


def stack_stage_params(per_stage_params) -> Any:
    """[stage0_tree, stage1_tree, ...] -> one tree with leading stage dim."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_stage_params)


def gpipe(stage_fn: Callable[[Any, jax.Array], jax.Array],
          stacked_params: Any,
          x: jax.Array,
          *,
          mesh: Mesh,
          n_microbatches: int,
          axis_name: str = PIPE_AXIS,
          batch_axes: Optional[tuple] = None) -> jax.Array:
    """Run ``x`` through ``n_stages`` sequential applications of ``stage_fn``,
    pipelined over the mesh's ``axis_name`` dimension.

    stage_fn(params_for_one_stage, microbatch) -> microbatch (same shape).
    stacked_params: every leaf has leading dim n_stages (see
    :func:`stack_stage_params`).
    batch_axes: mesh axes the per-microbatch batch dim additionally shards
    over (a composed pipe x data plan) — each data-coordinate runs the same
    shift-register schedule on its batch slice, so per-row math (and bits)
    are unchanged by the data fan-out.
    """
    S = mesh.shape[axis_name]
    n_stages = jax.tree.leaves(stacked_params)[0].shape[0]
    if n_stages != S:
        # shard_map would hand each device a multi-stage slice and the [0]
        # squeeze would silently drop stages — reject loudly instead
        raise ValueError(f"{n_stages} stages require a {axis_name}-axis of the "
                         f"same size, mesh has {S}")
    B = x.shape[0]
    if B % n_microbatches:
        raise ValueError(f"batch {B} not divisible by {n_microbatches} microbatches")
    mb = B // n_microbatches
    mbs = x.reshape((n_microbatches, mb) + x.shape[1:])
    M, T = n_microbatches, n_microbatches + S - 1

    def per_device(params, mbs_local):
        # shard_map gives each device a (1, ...) slice of the stage dim
        params = jax.tree.map(lambda a: a[0], params)
        idx = lax.axis_index(axis_name)
        shift_perm = [(d, d + 1) for d in range(S - 1)]

        def body(t, carry):
            buf, outputs = carry
            # stage 0 ingests microbatch t (clamped; garbage ticks discarded)
            feed = mbs_local[jnp.minimum(t, M - 1)]
            inp = jnp.where(idx == 0, feed, buf)
            out = stage_fn(params, inp)
            # last stage emits microbatch j = t - (S-1)
            j = t - (S - 1)
            upd = lax.dynamic_update_index_in_dim(
                outputs, out, jnp.maximum(j, 0), axis=0)
            outputs = jnp.where((idx == S - 1) & (j >= 0), upd, outputs)
            buf = lax.ppermute(out, axis_name, shift_perm)
            return buf, outputs

        buf0 = jnp.zeros_like(mbs_local[0])
        out0 = jnp.zeros_like(mbs_local)
        _, outputs = lax.fori_loop(0, T, body, (buf0, out0))
        # only the last device holds real outputs; share them
        return lax.psum(jnp.where(idx == S - 1, outputs, 0.0), axis_name)

    spec_params = jax.tree.map(lambda _: P(axis_name), stacked_params)
    spec_mbs = P(None, tuple(batch_axes)) if batch_axes else P()
    fn = shard_map(per_device, mesh=mesh,
                   in_specs=(spec_params, spec_mbs), out_specs=spec_mbs,
                   check_vma=False)
    out = fn(stacked_params, mbs)
    return out.reshape((B,) + out.shape[2:])


def sequential_reference(stage_fn, stacked_params, x):
    """Unpipelined oracle: apply the stages one after another (for tests and
    single-device fallback)."""
    S = jax.tree.leaves(stacked_params)[0].shape[0]
    for s in range(S):
        params_s = jax.tree.map(lambda a: a[s], stacked_params)
        x = stage_fn(params_s, x)
    return x
