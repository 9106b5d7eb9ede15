"""``rows_to_tokens``: the expert layer's sum of buffer rows into tokens
(``combine`` forward, the gradient of ``dispatch``'s gather) by one Pallas
kernel, under the interpreter, against XLA's ``.at[].add``.

The kernel accumulates in float32 and rounds once, so it is held to the
float32 sum of the same rows within one rounding of its output dtype; XLA's
scatter in bf16 rounds at every add, so the layer through either route
agrees within bf16's tolerance, and in float32 to a few float32 roundings.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn import MixtureOfExperts
from deeplearning4j_tpu.nn.base import GlobalConfig
from deeplearning4j_tpu.nn.inputs import InputType
from deeplearning4j_tpu.ops.pallas import rows_to_tokens as rtt
from deeplearning4j_tpu.runtime.environment import get_environment

G = GlobalConfig(dtype=jnp.float32)


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")


def _buffer(n, experts, held, top_k, rows, d, seed, top_idx=None):
    """An expert-sorted buffer as ``_dispatch`` lays it out: (rows (R, d)
    bf16 with NaN past the filled rows, ids with N for an unfilled row, the
    runs' ends)."""
    layer = MixtureOfExperts(n_out=d, n_experts=experts, held=(0, held), top_k=top_k, held_rows=rows)
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    if top_idx is None:
        top_idx = jnp.argsort(jax.random.uniform(keys[0], (n, experts)), axis=1)[:, :top_k]
    _, token, _, filled, sizes, _, _, _ = layer._dispatch(jnp.zeros((n, d)), top_idx)
    ids = jnp.where(filled, token, n)
    values = jnp.where(filled[:, None], jax.random.normal(keys[1], (rows, d), jnp.bfloat16), jnp.nan)
    return values, ids, jnp.cumsum(sizes)


def _middle_tile_routed_elsewhere(n=768, experts=16, top_k=4):
    """Tokens of the middle of three tiles choose only experts not held here."""
    top_idx = jnp.argsort(jax.random.uniform(jax.random.PRNGKey(5), (n, experts)), axis=1)[:, :top_k]
    middle = (jnp.arange(n) >= 256) & (jnp.arange(n) < 512)
    return jnp.where(middle[:, None], 8 + jnp.arange(top_k)[None, :], top_idx)


@pytest.mark.parametrize("case,n,experts,held,top_k,rows,d", [
    ("repeated_ids", 256, 8, 8, 4, 1024, 128),  # every token four times, no row unfilled
    ("sentinel_rows", 256, 16, 4, 4, 384, 128),  # a quarter of the assignments held, the rest unfilled
    ("empty_tile", 768, 16, 8, 4, 1536, 128),  # no row of the middle tile is held
    ("tile_over_a_batch", 256, 2, 2, 2, 512, 128),  # 256 rows of a run in one tile: two batches of 128
    ("d2048", 256, 16, 8, 8, 1024, 2048),
    ("d2304", 256, 32, 8, 8, 512, 2304),
])
def test_the_kernel_sums_rows_as_a_float32_scatter_add(interpreted, case, n, experts, held, top_k, rows, d):
    top_idx = _middle_tile_routed_elsewhere() if case == "empty_tile" else None
    values, ids, ends = _buffer(n, experts, held, top_k, rows, d, seed=len(case), top_idx=top_idx)
    plan = rtt.plan(ids, ends, n)
    got = np.asarray(rtt.rows_to_tokens(values, plan).astype(jnp.float32))
    want = np.asarray(jnp.zeros((n, d), jnp.float32).at[ids].add(values.astype(jnp.float32), mode="drop"))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=2.0 ** -7, atol=0)
    if case == "repeated_ids":
        assert int(jnp.max(jnp.bincount(ids, length=n + 1)[:n])) == top_k
    if case == "sentinel_rows":
        assert 0 < int(ends[-1]) < rows
    if case == "empty_tile":
        assert not np.any(got[256:512]) and np.any(got[:256]) and np.any(got[512:])
    if case == "tile_over_a_batch":
        assert int(ends[0]) == n > rtt.BATCH * rtt.WINDOW


def test_the_two_maps_are_each_others_transpose(interpreted):
    """The gather's gradient is the kernel's sum, the sum's the gather; a
    row that holds nothing gathers zero and passes nothing back."""
    n, d = 256, 128
    values, ids, ends = _buffer(n, 16, 4, 4, 384, d, seed=3)
    values = jnp.where(jnp.isnan(values), 0, values)
    plan = rtt.plan(ids, ends, n)
    tokens = jax.random.normal(jax.random.PRNGKey(4), (n, d), jnp.bfloat16)
    gathered, back = jax.vjp(lambda t: rtt.tokens_to_rows(t, plan), tokens)
    np.testing.assert_array_equal(gathered, jnp.where((ids < n)[:, None], tokens[jnp.minimum(ids, n - 1)], 0))
    np.testing.assert_array_equal(back(values)[0], rtt.rows_to_tokens(values, plan))
    summed, back = jax.vjp(lambda r: rtt.rows_to_tokens(r, plan), values)
    np.testing.assert_array_equal(back(tokens)[0], gathered)


def _layer(kind):
    if kind == "sigmoid_shared":
        return MixtureOfExperts(n_out=128, n_experts=16, top_k=8, router="sigmoid", routed_scale=2.5, gated=True,
                                n_shared=1, hidden_size=64, activation="swish", aux_loss_coef=0.01)
    return MixtureOfExperts(n_out=128, n_experts=128, held=(0, 8), top_k=8, router="softmax", gated=True,
                            hidden_size=64, held_rows=512, activation="swish", aux_loss_coef=0.0)


def _forward_and_grads(layer, params, state, x):
    w = jax.random.normal(jax.random.PRNGKey(3), x.shape[:-1] + (layer.n_out,), jnp.float32)

    def loss(p, x_):  # linear in y: both routes get the same gradient of the output
        y, _ = layer.forward(p, state, x_, training=True)
        return jnp.sum(y.astype(jnp.float32) * w), y
    (_, y), grads = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(params, x)
    return y, grads


@pytest.mark.parametrize("kind,remat", [("sigmoid_shared", False), ("softmax_held", True)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_expert_layer_through_the_kernel_matches_xlas_scatter(monkeypatch, kind, remat, dtype):
    """A sigmoid top-8 layer with a shared expert, and a softmax top-8 layer
    holding 8 of 128 experts in 512 rows: output and the gradients of the
    router, the experts, the shared expert and the input, through the
    kernel and through XLA's scatter-add."""
    env = get_environment()
    was = env.remat_segments
    env.set_remat(remat)
    try:
        layer = _layer(kind)
        layer._g = G
        params, state = layer.init(jax.random.PRNGKey(0), InputType.recurrent(128, 128), G)
        params = jax.tree.map(lambda a: a.astype(dtype), params)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 128, 128), jnp.float32).astype(dtype)
        monkeypatch.delenv("DL4J_TPU_PALLAS_INTERPRET", raising=False)
        want = _forward_and_grads(layer, params, state, x)
        assert "pallas_call" not in str(jax.make_jaxpr(lambda p, x_: _forward_and_grads(layer, p, state, x_))(params, x))
        monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
        got = _forward_and_grads(layer, params, state, x)
        jaxpr = str(jax.make_jaxpr(lambda p, x_: _forward_and_grads(layer, p, state, x_))(params, x))
        assert jaxpr.count("name=rows_to_tokens") == 2  # combine forward, dispatch backward
    finally:
        env.set_remat(was)
    leaves_got, leaves_want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(leaves_got) == len(leaves_want) == 2 + len(jax.tree.leaves(params))
    for a, b in zip(leaves_got, leaves_want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        scale = float(np.max(np.abs(b)))
        assert scale > 0
        np.testing.assert_allclose(a, b, atol=(3e-2 if dtype == "bfloat16" else 2e-5) * scale, rtol=0)


def test_the_scatter_stays_where_the_kernel_cannot_go(interpreted):
    """Expert tables sharded over a mesh of four devices (a ``pallas_call``
    is not partitioned), and a width that is not a multiple of 128: XLA's
    scatter-add, and the same answer as an unsharded call."""
    from deeplearning4j_tpu.parallel import ShardingStrategy
    from deeplearning4j_tpu.runtime.mesh import EXPERT_AXIS, MeshSpec, create_mesh

    layer = MixtureOfExperts(n_out=128, n_experts=8, top_k=2, activation="relu")
    layer._g = G
    params, state = layer.init(jax.random.PRNGKey(0), InputType.feed_forward(128), G)
    x = jax.random.normal(jax.random.PRNGKey(2), (256, 128), jnp.float32)
    forward = lambda p, x_: layer.forward(p, state, x_)[0]  # noqa: E731
    assert "name=rows_to_tokens" in str(jax.make_jaxpr(forward)(params, x))
    mesh = create_mesh(MeshSpec({EXPERT_AXIS: 4}), devices_=jax.devices()[:4])
    sharded = jax.tree.map(jax.device_put, params, ShardingStrategy.expert_parallel(mesh).param_sharding(params))
    assert "pallas_call" not in str(jax.make_jaxpr(forward)(sharded, x))
    assert not rtt.kernel_route(256, 512, (128,), x, sharded["W_e1"])
    np.testing.assert_allclose(forward(sharded, x), forward(params, x), atol=1e-5)

    narrow = MixtureOfExperts(n_out=96, n_experts=8, top_k=2, activation="relu")
    narrow._g = G
    p96, s96 = narrow.init(jax.random.PRNGKey(0), InputType.feed_forward(96), G)
    assert "pallas_call" not in str(jax.make_jaxpr(lambda p, x_: narrow.forward(p, s96, x_)[0])(p96, x[:, :96]))
    assert not rtt.kernel_route(256, 512, (96, 128))
