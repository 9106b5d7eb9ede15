"""GLM-4.7-Flash's layers and its two-term loss against the benchmark
family's plain reference (``benchmark/families/glm_moe_lite.py``, which
imports nothing of the program), on seeded weights, at small sizes on the CPU.

Tolerances: both sides compute in float32 on the CPU and differ in the
order of their sums only (a grouped matmul against dense ones, a blockwise
softmax against a whole one, a masked mean over T positions against a mean
over T - 1), so outputs agree to a few float32 roundings of their largest
element: 2e-5 of it, gradients 1e-4 (sums over up to 256 tokens of terms of
both signs), losses 1e-6. A wrong rotation, mask, shift of the labels or
routing weight shows at 1e-2 or more.
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
from deeplearning4j_tpu.nn import InputType, LatentAttention, MixtureOfExperts, MultiTokenPrediction  # noqa: E402
from deeplearning4j_tpu.nn.attention_layers import rms_norm, rotary, scoped  # noqa: E402
from deeplearning4j_tpu.nn.base import GlobalConfig, Layer  # noqa: E402
from deeplearning4j_tpu.runtime.environment import get_environment  # noqa: E402
from deeplearning4j_tpu.zoo import GlmMoeLite, KimiLinear  # noqa: E402

FAMILY = bench.load_module("families", "glm_moe_lite")
MM = reference_train.contractions("float32")[0]
# three trunk blocks of seven (dense, two with experts) and the prediction layer, stored as layer 7
CONFIG = {"hidden_size": 32, "num_attention_heads": 2, "q_lora_rank": 12, "kv_lora_rank": 16,
          "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 24, "rope_theta": 1000000,
          "intermediate_size": 64, "moe_intermediate_size": 24, "first_k_dense_replace": 1,
          "num_hidden_layers": 4, "layers_here": [0, 1, 2, 7], "published": {"num_hidden_layers": 7},
          "num_nextn_predict_layers": 1, "mtp_loss_weight": 0.3,
          "router_width": 16, "held_experts": [4, 4], "held_rows": 512, "num_experts_per_tok": 4,
          "n_shared_experts": 1, "routed_scaling_factor": 1.8, "rms_norm_eps": 1e-5, "vocab_size": 96,
          "initializer_range": 0.02, "recompute": {"set_remat": True},
          "optimizer": {"name": "adam", "lr": 2e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8},
          "precision": {"compute": "float32"}}
SIZES = FAMILY._sizes(CONFIG)
G = GlobalConfig(dtype=jnp.float32)
EMBED, MTP, HEAD = "layer_0", "layer_4", "layer_6"


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


def inputs(t, seed=3, b=2):
    return jax.random.normal(jax.random.PRNGKey(seed), (b, t, CONFIG["hidden_size"]), jnp.float32)


def batch(t=128, b=2, seed=3):
    (ids, labels, _), = FAMILY.batches(CONFIG, {"batch": b, "seq_len": t, "count": 1}, seed)
    return jnp.asarray(ids), jnp.asarray(labels)


def model(seed=3):
    params, state = FAMILY.init_params(CONFIG, seed)
    return FAMILY.build(CONFIG, seed), moved(params), state


def test_rotary_is_a_multiplication_by_unit_complex_numbers():
    """Channel i and channel i + d/2 of a position make one complex number,
    which rotary multiplies by exp(i t theta^(-2i/d)); a (b, t, h, d) array
    and a (b, t, d) array turn alike; position 0 is left as it is."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 3, 16), jnp.float32)
    got = np.asarray(rotary(x, jnp.arange(40), 1e6))
    z = np.asarray(x[..., :8], np.float64) + 1j * np.asarray(x[..., 8:], np.float64)
    turn = np.exp(1j * np.arange(40)[:, None] * 1e6 ** (-np.arange(8) / 8.0))[None, :, None, :]
    want = z * turn
    np.testing.assert_allclose(got, np.concatenate([want.real, want.imag], -1), atol=2e-5)
    np.testing.assert_array_equal(got[:, 0], np.asarray(x[:, 0]))
    np.testing.assert_array_equal(np.asarray(rotary(x[:, :, 1], jnp.arange(40), 1e6)), got[:, :, 1])
    assert rotary(x.astype(jnp.bfloat16), jnp.arange(40), 1e6).dtype == jnp.bfloat16


@pytest.mark.parametrize("route", ["flash_interpreted", "xla"])
def test_rotary_latent_attention_with_a_low_rank_query_matches_the_reference(route, monkeypatch):
    """Through the flash kernel (interpreted) and through the XLA form:
    outputs, and the gradients with respect to every parameter and the input."""
    from deeplearning4j_tpu.ops.pallas import flash_attention as fa
    calls = []
    real = fa.flash_attention
    monkeypatch.setattr(fa, "flash_attention", lambda *a, **k: calls.append(1) or real(*a, **k))
    if route == "xla":
        monkeypatch.delenv("DL4J_TPU_PALLAS_INTERPRET", raising=False)
    else:
        monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
    params = moved(FAMILY.init_params(CONFIG, 0)[0]["layer_2"]["mixer"])
    layer = LatentAttention(n_heads=2, q_rank=12, kv_rank=16, qk_nope_dim=16, qk_shared_dim=8, v_dim=24, rope_theta=1e6)
    layer._g = G
    ours = lambda p, x: layer.forward(p, {}, x, training=True)[0]
    theirs = lambda p, x: FAMILY._mla(x, p, SIZES, 1e-5, MM)
    x = inputs(256)
    close(ours(params, x), theirs(params, x), 2e-5)
    scalar = lambda f: (lambda p, x_: jnp.sum(jnp.sin(3 * f(p, x_))))
    trees_close(jax.grad(scalar(ours), (0, 1))(params, x), jax.grad(scalar(theirs), (0, 1))(params, x), 1e-4)
    assert bool(calls) == (route == "flash_interpreted")
    assert set(params) == {"W_qa", "q_norm", "W_qb", "W_kva", "kv_norm", "W_kvb", "W_o"}


def test_the_whole_model_matches_the_reference_in_loss_both_terms_and_every_gradient():
    """Embedding, a dense and two expert blocks, the prediction layer on the
    tied table and head, the final norm, the head: the program's training
    loss, its two recorded terms, the counters and the gradient of every
    leaf against ``reference_loss`` from the same weights."""
    net, params, state = model()
    ids, labels = batch()

    def ours(p):
        loss, (new_state, _) = net._loss(p, state, ids, labels, None)
        return loss, new_state

    reference = FAMILY.reference_loss(CONFIG)
    theirs = lambda p: reference(p, state, (ids, labels, None), MM, None)
    (loss, new_state), grads = jax.value_and_grad(ours, has_aux=True)(params)
    (want, want_state), want_grads = jax.value_and_grad(theirs, has_aux=True)(params)
    assert float(loss) == pytest.approx(float(want), rel=1e-6)
    trees_close(grads, want_grads, 1e-4)
    trees_close(new_state, want_state, 1e-6)
    main, mtp = float(new_state[HEAD]["main_loss"]), float(new_state[MTP]["mtp_loss"])
    assert main == pytest.approx(float(want_state[HEAD]["main_loss"]), rel=1e-6)
    assert mtp == pytest.approx(float(want_state[MTP]["mtp_loss"]), rel=1e-6)
    assert float(loss) == pytest.approx(main + 0.3 * mtp, rel=1e-6) and main > 1 and mtp > 1
    assert sorted(jax.tree.leaves(jax.tree.map(jnp.size, params))) == sorted(
        jax.tree.leaves(jax.tree.map(jnp.size, net.train_state.params)))
    assert sum(jax.tree.leaves(jax.tree.map(jnp.size, params))) == FAMILY.n_params(CONFIG)


def mtp_layer():
    net, params, state = model()
    layer = net.layers[4]
    assert isinstance(layer, MultiTokenPrediction) and layer.tied == {"embed": EMBED, "head": HEAD}
    p = dict(params[MTP], embed=params[EMBED], head=params[HEAD])
    return layer, p, state[MTP]


def test_the_second_loss_leaves_out_exactly_the_last_position():
    """Position i is scored on the label at i + 1: the last has none. Its
    hidden state reaches only its own prediction (attention is causal), so
    the loss does not move with it, and moves with the one before; the mean
    is over T - 1 positions; the input goes on unchanged; without labels the
    layer does nothing."""
    layer, p, state = mtp_layer()
    x, (_, labels) = inputs(128), batch()

    def term(x_):
        y, new = layer.forward(p, state, x_, training=True, labels=labels)
        return new["mtp_loss"], (y, new)

    (loss, (y, new)), g = jax.value_and_grad(term, has_aux=True)(x)
    np.testing.assert_array_equal(y, x)
    assert float(jnp.max(jnp.abs(g[:, -1]))) == 0.0 and float(jnp.min(jnp.max(jnp.abs(g[:, -2]), -1))) > 0.0
    assert float(new["_aux_loss"]) == pytest.approx(0.3 * float(loss), rel=1e-6)
    # by hand from the layer's own pieces, over the first T - 1 positions
    e = jnp.take(p["embed"]["W"], labels, axis=0)
    u = jnp.concatenate([rms_norm(e, p["enorm"]), rms_norm(x, p["hnorm"])], -1) @ p["W_eh"]
    z, _ = layer.block.forward(p["block"], state["block"], u, training=True)
    logp = jax.nn.log_softmax(rms_norm(z, p["norm"])[:, :-1] @ p["head"]["W"], -1)
    want = -jnp.mean(jnp.take_along_axis(logp, labels[:, 1:, None], -1))
    assert float(loss) == pytest.approx(float(want), rel=1e-6)
    same, untouched = layer.forward(p, state, x, training=False)
    assert same is x and untouched is state


def test_a_mask_leaves_out_the_positions_whose_next_is_not_valid():
    layer, p, state = mtp_layer()
    x, (_, labels) = inputs(128), batch()
    mask = jnp.ones((2, 128)).at[1, 100:].set(0)
    _, new = layer.forward(p, state, x, training=True, labels=labels, mask=mask)
    _, short = layer.forward(p, state, x[1:, :100], training=True, labels=labels[1:, :100])
    _, whole = layer.forward(p, state, x[:1], training=True, labels=labels[:1])
    want = (127 * float(whole["mtp_loss"]) + 99 * float(short["mtp_loss"])) / (127 + 99)
    assert float(new["mtp_loss"]) == pytest.approx(want, rel=1e-5)


def test_a_tied_leafs_gradient_is_the_sum_of_its_two_uses_and_adam_moves_it_once():
    """The table and the head are one leaf each in parameters and moments.
    Handing the prediction layer copies of them under other names splits the
    gradient into the trunk's use and the layer's; the tied gradient is
    their sum. After one step through ``fit`` Adam's first moment of each is
    (1 - b1) x that sum, and no entry moved by more than one learning rate."""
    net, params, state = model()
    ids, labels = batch()
    loss = lambda p: net._loss(p, state, ids, labels, None)[0]
    tied = jax.grad(loss)(params)
    layer = net.layers[4]
    try:
        layer.tied = {"embed": "table_copy", "head": "head_copy"}
        split = jax.grad(loss)({**params, "table_copy": params[EMBED], "head_copy": params[HEAD]})
    finally:
        layer.tied = {"embed": EMBED, "head": HEAD}
    for owner, copy in ((EMBED, "table_copy"), (HEAD, "head_copy")):
        one, other = split[owner]["W"], split[copy]["W"]
        assert float(jnp.max(jnp.abs(one))) > 0 and float(jnp.max(jnp.abs(other))) > 0
        close(tied[owner]["W"], one + other, 1e-6)
    for key in set(params) - {EMBED, HEAD}:
        trees_close(tied[key], split[key], 1e-6)

    net.set_params(jax.tree.map(jnp.copy, params))
    net.fit(np.asarray(ids), np.asarray(labels))
    ts = net.train_state
    assert jax.tree.structure(ts.params) == jax.tree.structure(params)
    moments = [leaf for leaf in jax.tree.leaves(ts.opt_state) if getattr(leaf, "ndim", 0) == 2
               and leaf.shape in ((96, 32), (32, 96))]
    assert len(moments) == 4  # mu and nu of the table and of the head, once each
    for owner in (EMBED, HEAD):
        change = ts.params[owner]["W"] - params[owner]["W"]
        assert 0 < float(jnp.max(jnp.abs(change))) <= 2e-4 * (1 + 1e-4)
        mu = next(m for m in moments if m.shape == change.shape and
                  float(jnp.max(jnp.abs(m - 0.1 * tied[owner]["W"]))) <= 1e-5 * float(jnp.max(jnp.abs(m))))
        assert float(jnp.max(jnp.abs(mu))) > 0


def test_a_tie_that_names_nothing_fails_at_init():
    zoo = GlmMoeLite.tiny()
    conf = zoo.conf()
    conf.layers[4].tied = {"embed": "layer_0", "head": "layer_9"}
    from deeplearning4j_tpu.models import MultiLayerNetwork
    with pytest.raises(KeyError, match="layer_9"):
        MultiLayerNetwork(conf).init()


def test_without_the_prediction_layer_the_trunk_alone_is_trained():
    """``mtp=False``: no prediction layer, no recorded terms, and the loss is
    the main term of the model that has one, from the same trunk weights."""
    with_mtp, params, state = model()
    ids, labels = batch()
    _, (new_state, _) = with_mtp._loss(params, state, ids, labels, None)
    alone = FAMILY.build(dict(CONFIG, num_nextn_predict_layers=0), 3)
    assert not any(isinstance(layer, MultiTokenPrediction) for layer in alone.layers)
    trunk = {"layer_4": params["layer_5"], "layer_5": params[HEAD], **{f"layer_{i}": params[f"layer_{i}"] for i in range(4)}}
    assert jax.tree.structure(trunk) == jax.tree.structure(alone.train_state.params)
    trunk_state = {k: v for k, v in state.items() if k in ("layer_2", "layer_3")}
    assert jax.tree.structure(trunk_state) == jax.tree.structure(alone.train_state.model_state)
    loss, _ = alone._loss(trunk, trunk_state, ids, labels, None)
    assert float(loss) == pytest.approx(float(new_state[HEAD]["main_loss"]), rel=1e-6)
    reference = FAMILY.reference_loss(dict(CONFIG, num_nextn_predict_layers=0))
    assert float(reference(trunk, trunk_state, (ids, labels, None), MM, None)[0]) == pytest.approx(float(loss), rel=1e-6)


@pytest.mark.parametrize("router,shares,top_k", [(16, 4, 4), (64, 8, 4)])
def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer(router, shares, top_k):
    """Top-4 of 16 in 4 shares, and of 64 in 8 shares of 8 as the cell cuts
    it: what the shares compute for their own experts, with the shared expert
    (which every share computes alike) counted once, is what the uncut
    reference layer gives; every assignment is counted once."""
    held = router // shares
    whole = dict(CONFIG, router_width=router, held_experts=[0, router], num_experts_per_tok=top_k)
    params, state = FAMILY.init_params(whole, 7)
    params, state = jax.tree.map(lambda a: 5 * a, params["layer_2"]["mlp"]), state["layer_2"]["mlp"]
    x = inputs(128, seed=11)
    want, counted = FAMILY._moe(x, params, state, FAMILY._sizes(whole), whole, MM)
    shared = FAMILY._swiglu(x, params["shared"], MM)
    total, assigned = shared, []
    for first in range(0, router, held):
        share = {k: v[first:first + held] if k.startswith("W_e") else v for k, v in params.items()}
        layer = MixtureOfExperts(n_out=32, hidden_size=24, n_experts=router, held=(first, held), top_k=top_k,
                                 n_shared=1, routed_scale=1.8, router="sigmoid", gated=True, activation="swish",
                                 aux_loss_coef=0.0)
        layer._g = G
        y, new = layer.forward(share, dict(state, assigned=jnp.zeros((held,))), x, training=True)
        total = total + (y - shared)
        assigned.append(new["assigned"])
    close(total, want, 2e-5)
    np.testing.assert_array_equal(jnp.concatenate(assigned), counted["assigned"])
    assert float(sum(jnp.sum(a) for a in assigned)) == x.shape[0] * x.shape[1] * top_k


def test_tiny_trains_through_fit_and_its_terms_and_counters_are_read_on_the_host():
    net = GlmMoeLite.tiny(held_experts=(0, 4), held_rows=512).init()
    ids = np.random.default_rng(0).integers(0, 96, (2, 129), dtype=np.int32)
    x, y = np.ascontiguousarray(ids[:, :-1]), np.ascontiguousarray(ids[:, 1:])
    net.fit(x, y)
    first = float(net.score())
    net.fit(x, y, epochs=8)
    state = net.train_state.model_state
    main, mtp = float(state["layer_6"]["main_loss"]), float(state["layer_4"]["mtp_loss"])
    assert float(net.score()) < first and float(net.score()) == pytest.approx(main + 0.3 * mtp, rel=1e-5)
    for counters in (state["layer_2"]["mlp"], state["layer_4"]["block"]["mlp"]):
        assert counters["assigned"].shape == (4,) and float(counters["overflow"]) == 0.0
        assert 0 < float(jnp.sum(counters["assigned"])) <= 2 * 128 * 2
    assert net.output(x).shape == (2, 128, 96)  # inference: the trunk's head alone


def test_the_prediction_layers_scopes_sit_directly_under_its_name():
    """The yardstick cuts a scope path at two components: the prediction
    layer's input projection, its block's scopes and its head must read
    ``<layer>.MultiTokenPrediction/<scope>``, forward and backward, under
    ``Environment.set_remat`` too; rotary has a scope of its own."""
    import re
    net = GlmMoeLite.tiny(held_experts=(0, 4), held_rows=512).init()
    x = jnp.zeros((2, 128), jnp.int32)
    step, packer = net._jitted_packed()
    text = step.lower(packer.pack_device(net.train_state), x, x, jax.random.PRNGKey(0), None, None).as_text(
        debug_info=True)
    names = set(re.findall(r'"(jit\(packed_train_step[^"]*)"', text))
    under = {m.group(2) for m in (re.search(r"(jvp|transpose\(jvp)\(layer_4\.MultiTokenPrediction\)+/(\w+)/", n)
                                  for n in names) if m}
    assert {"mtp_in", "norm", "mla_qkv", "rope", "flash", "scores", "mla_out", "router", "dispatch", "experts",
            "combine", "shared_expert", "lm_head"} - under <= {"flash", "scores"}, under
    assert not [n for n in names if "MultiTokenPrediction)/jvp(layer_4" in n]  # no checkpoint around the layer
    assert any("DecoderBlock)/rope/checkpoint/" in n for n in names)


def test_a_prediction_layer_survives_its_configurations_round_trip():
    layer = GlmMoeLite.tiny().conf().layers[4]
    again = Layer.from_dict(layer.to_dict())
    assert isinstance(again, MultiTokenPrediction) and again.tied == layer.tied
    assert again.to_dict() == layer.to_dict()
    assert type(again.block.mixer) is LatentAttention and again.block.mixer.rope_theta == 1e6
    assert type(again.head).__name__ == "RnnOutputLayer" and again.head.record_loss


def old_latent_attention_forward(layer, params, x):
    """``LatentAttention.forward`` as it stood before it learned a low-rank
    query and rotary (PR 30's), verbatim."""
    from deeplearning4j_tpu.nn.attention_layers import dot_product_attention

    def _qkv(p, x):
        b, t, _ = x.shape
        h, dn, dr = layer.n_heads, layer.qk_nope_dim, layer.qk_shared_dim
        q = (x @ p["W_q"]).reshape(b, t, h, dn + dr)
        latent = x @ p["W_kva"]
        c, k_shared = latent[..., :layer.kv_rank], latent[..., layer.kv_rank:]
        kv = (rms_norm(c, p["kv_norm"], layer.eps) @ p["W_kvb"]).reshape(b, t, h, dn + layer.v_dim)
        k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_shared[:, :, None, :], (b, t, h, dr))], -1)
        return tuple(a.transpose(0, 2, 1, 3) for a in (q, k, kv[..., dn:]))

    q, k, v = scoped("mla_qkv", _qkv, params, x)
    y = dot_product_attention(q, k, v, None, causal=True)

    def out(p, y):
        b, h, t, dv = y.shape
        return y.transpose(0, 2, 1, 3).reshape(b, t, h * dv) @ p["W_o"]

    return scoped("mla_out", out, params, y)


def test_the_kimi_models_latent_attention_is_the_program_it_was():
    """Without ``q_rank`` and ``rope_theta`` the layer has the parameters it
    had and traces, forward and backward, to the jaxpr of its old form; the
    Kimi tiny model builds it so."""
    mixers = [layer.mixer for layer in KimiLinear.tiny().conf().layers if hasattr(layer, "mixer")]
    layer = next(m for m in mixers if isinstance(m, LatentAttention))
    assert layer.q_rank is None and layer.rope_theta is None and "q_rank" not in layer.to_dict()
    layer._g = G
    params, state = layer.init(jax.random.PRNGKey(0), InputType.recurrent(32, 128), G)
    assert set(params) == {"W_q", "W_kva", "W_kvb", "W_o", "kv_norm"} and state == {}
    x = inputs(128)
    new = lambda p, x_: jnp.sum(layer.forward(p, {}, x_, training=True)[0])
    old = lambda p, x_: jnp.sum(old_latent_attention_forward(layer, p, x_))
    text = lambda f: str(jax.make_jaxpr(jax.value_and_grad(f, (0, 1)))(params, x))
    assert text(new) == text(old)
