"""Ring attention: sequence/context parallelism over the ICI ring.

The reference has NO long-context mechanism beyond truncated BPTT (SURVEY.md
§5.7) — this is the TPU-first capability that replaces it. Sequences are
sharded over the ``seq`` mesh axis; each device holds its query block and the
key/value blocks rotate around the ring via ``jax.lax.ppermute`` while a
flash-attention-style running softmax (running max + denominator) accumulates
the output. Communication overlaps compute and total memory per device is
O(T/n), so context length scales linearly with the ring size.

Public API:
- :func:`ring_attention` — inside-shard_map building block (needs axis_name)
- :func:`sequence_parallel_attention` — convenience wrapper that shard_maps
  over a mesh's ``seq`` axis.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.runtime.mesh import SEQ_AXIS


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, axis_name: str,
                   causal: bool = False) -> jax.Array:
    """Blockwise ring attention for one sequence shard.

    Args:
      q, k, v: (batch, heads, t_local, d) — the local sequence block; the
        full sequence is ``t_local * axis_size`` long.
      axis_name: mesh axis the sequence is sharded over.
      causal: apply a causal mask using global positions.

    Returns: (batch, heads, t_local, d) attention output for local queries.
    """
    n = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    t_local = q.shape[2]
    d = q.shape[-1]
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))

    q32 = q.astype(jnp.float32)
    o = jnp.zeros(q.shape[:3] + (v.shape[-1],), jnp.float32)
    m = jnp.full(q.shape[:3], -jnp.inf, jnp.float32)  # running max
    l = jnp.zeros(q.shape[:3], jnp.float32)  # running denominator

    q_pos = my_idx * t_local + jnp.arange(t_local)

    def body(step, carry):
        o, m, l, k_blk, v_blk = carry
        # which device's block are we holding? blocks travel "up" the ring
        src = jnp.mod(my_idx - step, n)
        scores = jnp.einsum("bhqd,bhkd->bhqk", q32, k_blk.astype(jnp.float32)) * scale
        if causal:
            k_pos = src * t_local + jnp.arange(t_local)
            cmask = q_pos[:, None] >= k_pos[None, :]
            scores = jnp.where(cmask[None, None], scores, -jnp.inf)
        blk_max = jnp.max(scores, axis=-1)
        new_m = jnp.maximum(m, blk_max)
        # guard fully-masked rows (new_m == -inf)
        safe_m = jnp.where(jnp.isfinite(new_m), new_m, 0.0)
        p = jnp.exp(scores - safe_m[..., None])
        p = jnp.where(jnp.isfinite(scores), p, 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
        l_new = l * corr + jnp.sum(p, axis=-1)
        o_new = o * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_blk.astype(jnp.float32))
        # rotate k/v one step around the ring (overlapped with next compute)
        perm = [(i, (i + 1) % n) for i in range(n)]
        k_nxt = jax.lax.ppermute(k_blk, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_blk, axis_name, perm)
        return (o_new, new_m, l_new, k_nxt, v_nxt)

    o, m, l, _, _ = jax.lax.fori_loop(0, n, body, (o, m, l, k, v))
    out = o / jnp.maximum(l[..., None], 1e-20)
    return out.astype(q.dtype)


def sequence_parallel_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                                mesh: Mesh, causal: bool = False,
                                seq_axis: str = SEQ_AXIS) -> jax.Array:
    """shard_map wrapper: q/k/v are GLOBAL (batch, heads, T, d) arrays; the
    time axis is sharded over ``seq_axis`` and ring attention runs per shard."""
    spec = P(None, None, seq_axis, None)

    body = functools.partial(ring_attention, axis_name=seq_axis, causal=causal)
    fn = shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                   out_specs=spec, check_vma=False)
    return fn(q, k, v)
