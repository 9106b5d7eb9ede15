"""SDAR's layers and its block-diffusion objective against the benchmark
family's plain reference (``benchmark/families/sdar_moe.py``, which imports
nothing of the program), and the block-diffusion flash kernels against the
dense path, on seeded weights, at small sizes on the CPU.

Tolerances: both sides compute in float32 on the CPU and differ in the
order of their sums only (a grouped matmul against dense ones, a blockwise
softmax against a whole one), so outputs agree to a few float32 roundings of
their largest element: 2e-5 of it, gradients 1e-4 (sums over up to 512
positions of terms of both signs), losses 1e-6. A wrong mask, position,
weight of a block or routing weight shows at 1e-2 or more.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import reference_train  # noqa: E402
from benchmark import run as bench  # noqa: E402
from benchmark.runners import train_fit  # noqa: E402
from deeplearning4j_tpu.nn import BlockDiffusionLoss, GroupedQueryAttention, MixtureOfExperts  # noqa: E402
from deeplearning4j_tpu.nn.attention_layers import dot_product_attention  # noqa: E402
from deeplearning4j_tpu.nn.base import GlobalConfig, Layer  # noqa: E402
from deeplearning4j_tpu.ops.pallas import flash_attention as fa  # noqa: E402
from deeplearning4j_tpu.runtime.environment import get_environment  # noqa: E402
from deeplearning4j_tpu.zoo import SdarMoe  # noqa: E402

FAMILY = bench.load_module("families", "sdar_moe")
MM = reference_train.contractions("float32")[0]
# two of four layers, four query heads over two key/value heads, experts 4..7 of 16, blocks of 4
CONFIG = {"hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
          "rope_theta": 1000000, "moe_intermediate_size": 24, "num_experts": 4, "num_experts_per_tok": 4,
          "num_hidden_layers": 2, "layers_here": [0, 1], "published": {"num_hidden_layers": 4},
          "router_width": 16, "held_experts": [4, 4], "held_rows": 1024, "rms_norm_eps": 1e-6, "vocab_size": 96,
          "block_length": 4, "mask_token_id": 95, "initializer_range": 0.02, "embedding_std": 4.0, "mask_embedding_std": 0.02,
          "qk_norm_gain": 1.5, "recompute": {"set_remat": True},
          "optimizer": {"name": "adam", "lr": 2e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8},
          "precision": {"compute": "float32"}}
TRAFFIC = {"batch": 2, "seq_len": 128, "count": 3, "check_steps": 3, "block_length": 4, "masked_per_row": 80}
SIZES = FAMILY._sizes(CONFIG)
G = GlobalConfig(dtype=jnp.float32)
HEAD = "layer_4"


@pytest.fixture(autouse=True)
def every_scope_recomputed():
    """As the benchmark's cell runs the layers: ``Environment.set_remat``
    (``FAMILY.build`` turns it on too); put back after the test."""
    env = get_environment()
    was = env.remat_segments
    env.set_remat(True)
    yield
    env.set_remat(was)


def close(got, want, rel):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=0, atol=rel * max(float(np.max(np.abs(want))), 1e-30))


def trees_close(got, want, rel):
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0], jax.tree.leaves(want)):
        try:
            close(g, w, rel)
        except AssertionError as e:
            raise AssertionError(f"{jax.tree_util.keystr(path)}: {e}") from None


def moved(tree, seed=1):
    """Norm scales moved off 1 and matrices scaled up, so that a gradient
    or a broadcast that is wrong there shows."""
    jitter = iter(jax.random.split(jax.random.PRNGKey(seed), 256))
    return jax.tree.map(lambda a: a + 0.1 * jax.random.normal(next(jitter), a.shape) if a.ndim == 1 else 5 * a, tree)


def batch(seed=3):
    ids, labels, _ = FAMILY.batches(CONFIG, TRAFFIC, seed)[0]
    return jnp.asarray(ids), jnp.asarray(labels)


def model(seed=3):
    params, state = FAMILY.init_params(CONFIG, seed)
    return FAMILY.build(CONFIG, seed), moved(params), state


def brute_force_mask(t, block):
    """The definition, entry by entry: a noisy query sees the noisy keys of
    its own block and the clean keys of earlier blocks; a clean query the
    clean keys of its own and earlier blocks."""
    table = np.zeros((2 * t, 2 * t), bool)
    for q in range(2 * t):
        for k in range(2 * t):
            q_blk, k_blk = (q % t) // block, (k % t) // block
            if q < t:
                table[q, k] = k_blk == q_blk if k < t else k_blk < q_blk
            else:
                table[q, k] = k >= t and k_blk <= q_blk
    return table


@pytest.mark.parametrize("t,block", [(8, 1), (8, 2), (12, 4), (12, 3), (16, 16), (24, 8)])
def test_the_mask_is_the_brute_force_table(t, block):
    """For several (T, B), B = 1 and B = T among them: the program's
    definition and the reference's own give the table written out entry by
    entry, which holds T^2 + T B allowed entries."""
    at = jnp.arange(2 * t)
    want = brute_force_mask(t, block)
    np.testing.assert_array_equal(fa.block_diffusion_allowed(at[:, None], at[None, :], t, block), want)
    np.testing.assert_array_equal(FAMILY._may_see(at[:, None], at[None, :], t, block), want)
    assert want.sum() == t * t + t * block


@pytest.mark.parametrize("t,block,tile", [(256, 4, 128), (256, 1, 128), (256, 256, 128), (256, 64, 256), (384, 96, 128)])
def test_a_tiles_mask_and_the_tiles_the_kernels_visit_are_the_tables(t, block, tile):
    """Tile by tile: the mask the kernels work out from a tile's place is
    the table's tile; the ranges of key tiles a query tile visits
    (``_bd_key_tiles``) and of query tiles a key tile is seen by
    (``_bd_query_tiles``) hold every tile with an allowed entry once, the
    ranges called whole hold no forbidden entry, and at T = 4096, B = 4 in
    tiles of 512 that is 80 of 256 tiles, 24 of them masked."""
    table = brute_force_mask(t, block)
    n = 2 * t // tile
    tiles = table.reshape(n, tile, n, tile).transpose(0, 2, 1, 3)
    zero = jnp.zeros((tile, tile), jnp.float32)
    for axis, ranges_of in ((0, fa._bd_key_tiles), (1, fa._bd_query_tiles)):
        for own in range(n):
            seen = {}
            for lo, hi, masked in ranges_of(jnp.int32(own), tile, tile, t, block):
                for other in range(int(lo), int(hi)):
                    assert other not in seen
                    seen[other] = masked
            for other in range(n):
                tile_table = tiles[own, other] if axis == 0 else tiles[other, own]
                assert (other in seen) == bool(tile_table.any()), (axis, own, other)
                if other in seen and not seen[other]:
                    assert tile_table.all()
                if other in seen and axis == 0:
                    got = fa._bd_mask(zero, jnp.int32(own * tile), jnp.int32(other * tile), t=t, block=block) == 0
                    np.testing.assert_array_equal(got, tile_table)
                    by_key = fa._bd_mask(zero, jnp.int32(own * tile), jnp.int32(other * tile), 1, t=t, block=block) == 0
                    np.testing.assert_array_equal(by_key, tile_table.T)
    visited = [(masked, int(hi) - int(lo)) for qi in range(16) for lo, hi, masked in fa._bd_key_tiles(qi, 512, 512, 4096, 4)]
    assert sum(n_ for _, n_ in visited) == 80 and sum(n_ for masked, n_ in visited if masked) == 24


def qkv(t, heads, kv_heads, d=32, dv=32, b=2, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    shape = lambda h, w: (b, h, 2 * t, w)
    return (jax.random.normal(ks[0], shape(heads, d)), jax.random.normal(ks[1], shape(kv_heads, d)),
            jax.random.normal(ks[2], shape(kv_heads, dv)), jax.random.normal(ks[3], shape(heads, dv)))


@pytest.mark.parametrize("t,block,heads,kv_heads,tile", [
    (128, 4, 8, 1, 512), (256, 4, 4, 2, 128), (256, 1, 2, 2, 128), (256, 256, 2, 1, 128), (384, 96, 2, 1, 128)])
def test_the_bd_kernels_match_the_dense_path_forward_and_in_all_three_gradients(t, block, heads, kv_heads, tile,
                                                                                 monkeypatch):
    """Under the interpreter, against ``dot_product_attention``'s XLA form
    with the 2T x 2T mask: 8 query heads a key/value head, one tile a half
    and several, B = 1, B = T and a B that is no power of two."""
    monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(fa, "BLOCK_Q", tile)
    monkeypatch.setattr(fa, "BLOCK_K", tile)
    q, k, v, g = qkv(t, heads, kv_heads, dv=16 if kv_heads == 2 else 32)
    family = (t, block)
    assert fa.flash_attention_compatible(q, k, v, block_diffusion=family)
    calls = []
    real = fa.flash_attention
    monkeypatch.setattr(fa, "flash_attention", lambda *a, **kw: calls.append(1) or real(*a, **kw))
    run = lambda use: jax.value_and_grad(
        lambda q_, k_, v_: jnp.sum(dot_product_attention(q_, k_, v_, use_flash=use, block_diffusion=family) * g),
        argnums=(0, 1, 2))(q, k, v)
    (got, got_grads), (want, want_grads) = run(True), run(False)
    assert len(calls) == 1
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for a, b_ in zip(got_grads, want_grads):
        close(a, b_, 2e-5)
    assert got_grads[1].shape == k.shape and got_grads[2].shape == v.shape


def test_what_the_bd_kernels_cannot_take_is_turned_away_cleanly(monkeypatch):
    """T not a multiple of the tile, blocks that do not divide T, a mask or
    ``causal`` beside the family, query heads no multiple of the key/value
    heads, K and V beyond VMEM: the XLA form runs and gives the mask's
    answer. Fewer key/value heads are the new family's alone."""
    monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
    q, k, v, _ = qkv(192, 4, 2)
    ok = fa.flash_attention_compatible
    assert not ok(q, k, v, block_diffusion=(192, 4))        # 192 is no multiple of 128
    assert not ok(q[:, :, :256], k[:, :, :256], v[:, :, :256], block_diffusion=(128, 3))
    q, k, v, _ = qkv(128, 4, 2)
    assert ok(q, k, v, block_diffusion=(128, 4))
    assert not ok(q, k, v, block_diffusion=(128, 4), causal=True)
    assert not ok(q, k, v, jnp.ones((2, 256), bool), block_diffusion=(128, 4))
    assert not ok(q, k, v, block_diffusion=(64, 4))         # not the two halves of this sequence
    assert not ok(q[:, :3], k, v, block_diffusion=(128, 4))
    assert not ok(q, k, v) and not ok(q, k, v, causal=True)
    big = jax.ShapeDtypeStruct((1, 8, 2 * 16384, 128), jnp.bfloat16)
    assert not ok(big, big, big, block_diffusion=(16384, 4))
    with pytest.raises(ValueError, match="mask family of its own"):
        fa.flash_attention(q, k, v, causal=True, block_diffusion=(128, 4))
    q, k, v, _ = qkv(192, 4, 2)
    got = dot_product_attention(q, k, v, block_diffusion=(192, 4))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, 2, 1)) / jnp.sqrt(32.0)
    want = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(
        jnp.where(brute_force_mask(192, 4)[None, None], scores, -jnp.inf), -1), jnp.repeat(v, 2, 1))
    close(got, want, 2e-5)


def attention_layer(**kw):
    layer = GroupedQueryAttention(n_heads=4, n_kv_heads=2, head_dim=16, rope_theta=1e6, **kw)
    layer._g = G
    return layer


@pytest.mark.parametrize("route", ["flash_interpreted", "xla"])
def test_grouped_query_attention_under_the_mask_matches_the_reference(route, monkeypatch):
    """Through the kernels (interpreted) and through the XLA form: outputs,
    and the gradients with respect to every parameter and the input."""
    if route == "xla":
        monkeypatch.delenv("DL4J_TPU_PALLAS_INTERPRET", raising=False)
    else:
        monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
    params = moved(FAMILY.init_params(CONFIG, 0)[0]["layer_1"]["mixer"])
    layer = attention_layer(block_diffusion=4)
    ours = lambda p, x: layer.forward(p, {}, x, training=True)[0]
    theirs = lambda p, x: FAMILY._attention(x, p, SIZES, MM)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 256, 32), jnp.float32)
    close(ours(params, x), theirs(params, x), 2e-5)
    scalar = lambda f: (lambda p, x_: jnp.sum(jnp.sin(3 * f(p, x_))))
    trees_close(jax.grad(scalar(ours), (0, 1))(params, x), jax.grad(scalar(theirs), (0, 1))(params, x), 1e-4)
    assert set(params) == {"W_q", "W_k", "W_v", "W_o", "q_norm", "k_norm"}


def test_the_clean_half_is_a_block_causal_pass_and_a_noisy_block_sees_its_own_past_only():
    """The clean half's output is what a pass over x0 alone gives under the
    block-causal mask (positions 0..T-1). A noisy block's output does not
    move when later clean blocks or any other noisy block change, and moves
    with its own noisy block and with an earlier clean one."""
    layer = attention_layer(block_diffusion=4)
    params = moved(layer.init(jax.random.PRNGKey(0), None or type("I", (), {"size": 32})(), G)[0])
    t = 32
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 2 * t, 32), jnp.float32)
    y = layer.forward(params, {}, x)[0]

    # x0 alone, block-causal: the same projections, norms and rotary by hand
    from deeplearning4j_tpu.nn.attention_layers import rms_norm, rotary
    clean = x[:, t:]
    heads = lambda w, n: (clean @ params[w]).reshape(1, t, n, 16)
    q = rotary(rms_norm(heads("W_q", 4), params["q_norm"], 1e-6), jnp.arange(t), 1e6).transpose(0, 2, 1, 3)
    k = rotary(rms_norm(heads("W_k", 2), params["k_norm"], 1e-6), jnp.arange(t), 1e6).transpose(0, 2, 1, 3)
    v = heads("W_v", 2).transpose(0, 2, 1, 3)
    blk = jnp.arange(t) // 4
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, 2, 1)) / 4.0
    weights = jax.nn.softmax(jnp.where((blk[None, :] <= blk[:, None])[None, None], scores, -jnp.inf), -1)
    alone = jnp.einsum("bhqk,bhkd->bhqd", weights, jnp.repeat(v, 2, 1)).transpose(0, 2, 1, 3).reshape(1, t, 64)
    close(y[:, t:], alone @ params["W_o"], 2e-5)

    block = slice(12, 16)  # noisy block 3
    bump = lambda rows: layer.forward(params, {}, x.at[:, rows].add(1.0))[0][:, block]
    same = lambda rows: np.testing.assert_array_equal(bump(rows), y[:, block])
    same(slice(t + 12, 2 * t))      # its own and later clean blocks
    same(slice(0, 12))              # earlier noisy blocks
    same(slice(16, t))              # later noisy blocks
    assert float(jnp.max(jnp.abs(bump(slice(t + 8, t + 12)) - y[:, block]))) > 1e-3   # an earlier clean block
    assert float(jnp.max(jnp.abs(bump(slice(13, 14)) - y[:, block]))) > 1e-3          # its own noisy block


def test_without_a_block_length_the_layer_is_causal_over_one_sequence():
    layer = attention_layer()
    params = moved(layer.init(jax.random.PRNGKey(0), type("I", (), {"size": 32})(), G)[0])
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 24, 32), jnp.float32)
    y = layer.forward(params, {}, x)[0]
    np.testing.assert_array_equal(layer.forward(params, {}, x.at[:, 10:].add(1.0))[0][:, :10], y[:, :10])


def written_out_router(tokens, w_router, top_k):
    """softmax over all experts, the top k by probability, their
    probabilities over the sum of the k: in numpy, float64."""
    logits = np.asarray(tokens, np.float64) @ np.asarray(w_router, np.float64)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    chosen = np.argsort(-p, -1, kind="stable")[:, :top_k]
    gates = np.take_along_axis(p, chosen, -1)
    return p, chosen, gates / gates.sum(-1, keepdims=True)


def test_softmax_top_8_with_norm_topk_prob_against_a_written_out_router():
    """``router="softmax"``: probabilities over all 128, the top 8, weights
    that sum to 1 over the 8 chosen (``norm_topk_prob``), no scale, no
    selection bias, no shared expert in parameters or state."""
    layer = MixtureOfExperts(n_out=32, hidden_size=24, n_experts=128, held=(0, 8), top_k=8, router="softmax",
                             gated=True, activation="swish", aux_loss_coef=0.0)
    layer._g = G
    params, state = layer.init(jax.random.PRNGKey(1), type("I", (), {"size": 32})(), G)
    assert set(params) == {"W_router", "W_e1", "W_e2", "W_e3"} and set(state) == {"assigned", "overflow"}
    tokens = jax.random.normal(jax.random.PRNGKey(2), (200, 32), jnp.float32)
    w_router = 5 * params["W_router"]
    scores, chosen, gates = layer._route(w_router, 0.0, tokens, None)
    p, want_chosen, want_gates = written_out_router(tokens, w_router, 8)
    np.testing.assert_allclose(scores, p, atol=1e-6)
    np.testing.assert_array_equal(np.sort(chosen, -1), np.sort(want_chosen, -1))
    np.testing.assert_allclose(np.sort(gates, -1), np.sort(want_gates, -1), atol=1e-6)
    np.testing.assert_allclose(np.sum(gates, -1), 1.0, atol=1e-6)


@pytest.mark.parametrize("router,shares,top_k", [(16, 4, 4), (128, 16, 8)])
def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer(router, shares, top_k):
    """Top-4 of 16 in 4 shares, and top-8 of 128 in 16 shares of 8 as the
    cell cuts it: what the shares compute for their own experts is what the
    uncut reference layer gives (no shared expert: nothing is counted
    twice); every assignment is counted once."""
    held = router // shares
    whole = dict(CONFIG, router_width=router, held_experts=[0, router], num_experts_per_tok=top_k)
    params, state = FAMILY.init_params(whole, 7)
    params, state = jax.tree.map(lambda a: 5 * a, params["layer_1"]["mlp"]), state["layer_1"]["mlp"]
    x = jax.random.normal(jax.random.PRNGKey(11), (2, 128, 32), jnp.float32)
    want, counted = FAMILY._moe(x, params, state, FAMILY._sizes(whole), MM)
    total, assigned = jnp.zeros_like(x), []
    for first in range(0, router, held):
        share = {k: v[first:first + held] if k.startswith("W_e") else v for k, v in params.items()}
        layer = MixtureOfExperts(n_out=32, hidden_size=24, n_experts=router, held=(first, held), top_k=top_k,
                                 router="softmax", gated=True, activation="swish", aux_loss_coef=0.0)
        layer._g = G
        y, new = layer.forward(share, dict(state, assigned=jnp.zeros((held,))), x, training=True)
        total = total + y
        assigned.append(new["assigned"])
    close(total, want, 2e-5)
    np.testing.assert_array_equal(jnp.concatenate(assigned), counted["assigned"])
    assert float(sum(jnp.sum(a) for a in assigned)) == x.shape[0] * x.shape[1] * top_k


def test_the_loss_weighs_a_block_by_its_masked_share_and_trains_the_masked_positions_only():
    """By hand: over the first T hidden states, the masked positions'
    negative log-likelihoods times B / m_b, summed, over T, the mean over
    rows. A position that is not masked, and the whole clean half, get no
    gradient; the state keeps the loss and the masked positions a row."""
    head = BlockDiffusionLoss(n_out=96, has_bias=False, activation="softmax", block_length=4)
    head._g = G
    params, state = head.init(jax.random.PRNGKey(0), type("I", (), {"size": 32, "kind": "recurrent"})(), G)
    assert set(state) == {"diffusion_loss", "masked_positions"}
    _, labels = batch()
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 256, 32), jnp.float32)
    loss, g = jax.value_and_grad(lambda x_: head.compute_loss(params, x_, labels))(x)
    logp = np.asarray(jax.nn.log_softmax(x[:, :128] @ params["W"], -1), np.float64)
    want = 0.0
    for row in range(2):
        for first in range(0, 128, 4):
            here = [i for i in range(first, first + 4) if labels[row, i] >= 0]
            want += sum(-logp[row, i, labels[row, i]] for i in here) * 4 / len(here)
    assert float(loss) == pytest.approx(want / (2 * 128), rel=1e-6)
    moved_rows = np.asarray(jnp.max(jnp.abs(g), -1) > 0)
    np.testing.assert_array_equal(moved_rows[:, :128], np.asarray(labels >= 0))
    assert not moved_rows[:, 128:].any()
    new = head.loss_state(state, loss, labels)
    assert float(new["diffusion_loss"]) == float(loss) and float(new["masked_positions"]) == 80.0
    assert head.activate(params, x).shape == (2, 128, 96)


def test_the_whole_model_matches_the_reference_in_loss_state_and_every_gradient():
    """Embedding, two blocks, the final norm, the head: the program's
    training loss, the recorded loss and masked positions, the counters and
    the gradient of every leaf against ``reference_loss`` from the same
    weights."""
    net, params, state = model()
    ids, labels = batch()

    def ours(p):
        loss, (new_state, _) = net._loss(p, state, ids, labels, None)
        return loss, new_state

    reference = FAMILY.reference_loss(CONFIG)
    theirs = lambda p: reference(p, state, (ids, labels, None), MM, None)
    (loss, new_state), grads = jax.value_and_grad(ours, has_aux=True)(params)
    (want, want_state), want_grads = jax.value_and_grad(theirs, has_aux=True)(params)
    assert float(loss) == pytest.approx(float(want), rel=1e-6) and float(loss) > 1
    trees_close(grads, want_grads, 1e-4)
    trees_close(new_state, want_state, 1e-6)
    assert float(new_state[HEAD]["masked_positions"]) == 80.0
    assert sum(jax.tree.leaves(jax.tree.map(jnp.size, params))) == FAMILY.n_params(CONFIG)


@pytest.mark.parametrize("route", ["flash_interpreted", "xla"])
def test_three_fit_steps_follow_the_reference_leaf_by_leaf(route, monkeypatch):
    """The benchmark's own comparison at a small size: three single steps
    through the public ``fit`` against ``reference_train.follow`` from the
    same weights and batches: each step's loss, the first gradient (read off
    Adam's first moment), the change of every parameter and state leaf."""
    if route == "xla":
        monkeypatch.delenv("DL4J_TPU_PALLAS_INTERPRET", raising=False)
    else:
        monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
    import types
    dtype = get_environment().compute_dtype
    try:
        ctx = types.SimpleNamespace(config=CONFIG, traffic=TRAFFIC, family=FAMILY, seed=5)
        net, fitter, datasets = train_fit.setup(ctx)
        got = train_fit.program_readings(ctx, net, fitter, datasets)
        state = net.train_state.model_state
    finally:
        get_environment().set_compute_dtype(dtype)
    want = train_fit.reference_readings(ctx)
    for ours, theirs in zip(got["losses"], want["losses"]):
        assert ours == pytest.approx(theirs, rel=1e-6)
    trees_close(got["grad"], want["grad"], 1e-4)
    # Adam's step is lr x sign-like, so an entry whose gradient is round-off may step either way:
    # a leaf's change is held by the norm of the difference, a hundredth of the change's own
    for (path, ours), theirs in zip(jax.tree_util.tree_flatten_with_path(got["delta"])[0],
                                    jax.tree.leaves(want["delta"])):
        gap = float(np.linalg.norm(np.asarray(ours, np.float64) - np.asarray(theirs, np.float64)))
        assert gap <= 1e-2 * max(float(np.linalg.norm(theirs)), 1e-12), jax.tree_util.keystr(path)
    checks = reference_train.compare(got, want)
    assert checks["delta_norm_gap"] < 1e-3 and checks["loss_gap"] < 1e-6 and checks["grad_diff_worst"] < 1e-4
    assert all(float(state[f"layer_{i}"]["mlp"]["overflow"]) == 0.0 for i in (1, 2))
    assert float(state[HEAD]["masked_positions"]) == 80.0


def test_every_row_of_every_seed_masks_the_same_number_of_positions():
    """The traffic's draw: every block masks 1..B positions, equally many
    blocks each count, so every row masks ``masked_per_row``; the noisy half
    holds the MASK id exactly there and the labels the clean token; ids stay
    below the MASK's row; the same seed gives the same batches."""
    for seed in (0, 7, 2 ** 31 + 5):
        made = FAMILY.batches(CONFIG, TRAFFIC, seed)
        assert len(made) == 3
        for ids, labels, mask in made:
            assert ids.shape == (2, 256) and labels.shape == (2, 128) and mask is None
            assert ids.dtype == np.int32 and labels.dtype == np.int32
            noisy, clean = ids[:, :128], ids[:, 128:]
            masked = labels >= 0
            per_block = masked.reshape(2, 32, 4).sum(-1)
            assert (np.sort(per_block, -1) == np.repeat([1, 2, 3, 4], 8)).all() and (masked.sum(-1) == 80).all()
            np.testing.assert_array_equal(noisy, np.where(masked, 95, clean))
            np.testing.assert_array_equal(labels, np.where(masked, clean, -1))
            assert clean.max() < 95
        again = FAMILY.batches(CONFIG, TRAFFIC, seed)
        assert all((a[0] == b[0]).all() and (a[1] == b[1]).all() for a, b in zip(made, again))
    assert not (FAMILY.batches(CONFIG, TRAFFIC, 0)[0][0] == FAMILY.batches(CONFIG, TRAFFIC, 1)[0][0]).all()
    with pytest.raises(ValueError, match="does not mask"):
        FAMILY.batches(CONFIG, dict(TRAFFIC, masked_per_row=64), 0)


def test_the_seeded_weights_have_the_scales_the_configuration_states():
    """Matrices N(0, initializer_range); the embedding's vocabulary rows N(0,
    embedding_std) and its MASK row N(0, mask_embedding_std); the per-head
    norms' gains ``qk_norm_gain``, the other norms 1; the same seed gives the
    same weights, another seed others."""
    params, state = FAMILY.init_params(CONFIG, 11)
    table = np.asarray(params["layer_0"]["W"])
    assert np.std(table[:95]) == pytest.approx(4.0, rel=0.05) and np.std(table[95]) == pytest.approx(0.02, rel=0.4)
    mixer = params["layer_1"]["mixer"]
    np.testing.assert_array_equal(mixer["q_norm"], np.full(16, 1.5, np.float32))
    np.testing.assert_array_equal(mixer["k_norm"], np.full(16, 1.5, np.float32))
    np.testing.assert_array_equal(params["layer_1"]["norm1"], np.ones(32, np.float32))
    np.testing.assert_array_equal(params["layer_3"]["w"], np.ones(32, np.float32))
    for name in ("W_q", "W_k", "W_v", "W_o"):
        assert np.std(np.asarray(mixer[name])) == pytest.approx(0.02, rel=0.1)
    assert np.std(np.asarray(params["layer_4"]["W"])) == pytest.approx(0.02, rel=0.1)
    again, _ = FAMILY.init_params(CONFIG, 11)
    other, _ = FAMILY.init_params(CONFIG, 12)
    np.testing.assert_array_equal(again["layer_0"]["W"], table)
    assert not np.array_equal(np.asarray(other["layer_0"]["W"]), table)
    assert all(float(jnp.sum(jnp.abs(leaf))) == 0.0 for leaf in jax.tree.leaves(state))


def test_tiny_trains_through_fit_and_its_state_is_read_on_the_host():
    net = SdarMoe.tiny(held_experts=(0, 4), held_rows=1024).init()
    ids, labels, _ = FAMILY.batches(dict(CONFIG, vocab_size=96), TRAFFIC, 0)[0]
    net.fit(ids, labels)
    first = float(net.score())
    net.fit(ids, labels, epochs=8)
    state = net.train_state.model_state
    assert float(net.score()) < first
    assert float(state[HEAD]["diffusion_loss"]) == pytest.approx(float(net.score()), rel=1e-5)
    assert float(state[HEAD]["masked_positions"]) == 80.0
    for key in ("layer_1", "layer_2"):
        counters = state[key]["mlp"]
        assert counters["assigned"].shape == (4,) and float(counters["overflow"]) == 0.0
        assert 0 < float(jnp.sum(counters["assigned"])) <= 2 * 256 * 2
    assert net.output(ids).shape == (2, 128, 96)  # inference: the noisy half's distributions


def test_the_scopes_sit_directly_under_the_blocks_name_and_no_scores_op_is_left_under_the_kernels(monkeypatch):
    """The yardstick cuts a scope path at two components: the mixer's
    scopes, the experts' and the loss layer's must read ``<layer>/<scope>``
    forward and backward under ``Environment.set_remat``; through the
    kernels no ``scores`` / ``softmax`` op is in the step."""
    import re
    monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
    net = SdarMoe.tiny(held_experts=(0, 4), held_rows=1024).init()
    x, y = jnp.zeros((2, 256), jnp.int32), jnp.zeros((2, 128), jnp.int32)
    step, packer = net._jitted_packed()
    text = step.lower(packer.pack_device(net.train_state), x, y, jax.random.PRNGKey(0), None, None).as_text(
        debug_info=True)
    names = set(re.findall(r'"(jit\(packed_train_step[^"]*)"', text))
    under = {m.group(2) for m in (re.search(r"(jvp|transpose\(jvp)\(layer_1\.DecoderBlock\)+/(\w+)/", n)
                                  for n in names) if m}
    assert {"norm", "qkv", "qk_norm", "rope", "flash", "out_proj", "router", "dispatch", "experts", "combine"} <= under
    assert not {"scores", "softmax", "context"} & under
    assert any(re.search(r"jvp\(loss\)+/lm_head/", n) for n in names)
    assert "bd_flash_attention_fwd" in text and "bd_flash_attention_bwd_dkv" in text


def test_the_new_layers_survive_their_configurations_round_trip():
    conf = SdarMoe.tiny().conf()
    block, head = conf.layers[1], conf.layers[-1]
    again = Layer.from_dict(block.to_dict())
    assert type(again.mixer) is GroupedQueryAttention and again.mixer.block_diffusion == 4
    assert again.mixer.n_kv_heads == 2 and again.to_dict() == block.to_dict()
    assert again.mlp.router == "softmax" and again.mlp.n_shared == 0
    head_again = Layer.from_dict(head.to_dict())
    assert type(head_again) is BlockDiffusionLoss and head_again.block_length == 4


def test_the_chip_smokes_check_rehearses_at_a_tiny_preset(tmp_path, monkeypatch):
    """``chip_smoke.check_sdar_moe`` on the CPU with interpreted kernels:
    the same code path as the chip run, sizes cut, no Mosaic call expected."""
    import chip_smoke
    from deeplearning4j_tpu.runtime import compile_cache
    monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
    compile_cache.enable(str(tmp_path / "cache"))  # the compile counters
    try:
        got = chip_smoke.check_sdar_moe(chip_smoke.Preset(
            platform="cpu", expect_mosaic=False, sdar_seq=128,
            sdar=dict(vocab_size=96, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2, head_dim=16, expert_size=24,
                      n_experts=8, top_k=2, held_experts=(0, 4), held_rows=1024)))
    finally:
        compile_cache.disable()
    assert got["last_loss"] < got["first_loss"] and got["masked_positions"] == 80.0 and len(got["assigned"]) == 2
