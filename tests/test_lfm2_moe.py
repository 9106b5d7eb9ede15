"""LFM2-24B-A2B's layers and its loss against the benchmark family's plain
reference (``benchmark/families/lfm2_moe.py``, which imports nothing of the
program), on seeded weights, at small sizes on the CPU.

Tolerances: both sides compute in float32 on the CPU and differ in the
order of their sums only (a grouped matmul against dense ones, a blockwise
softmax in the flash kernel against a whole one, a convolution as shifted
adds against ``conv_general_dilated``), so outputs agree to a few float32
roundings of their largest element: 2e-5 of it, gradients 1e-4 (sums over up
to 256 tokens of terms of both signs), losses 1e-6. The same reference with
its matmul operands rounded to bfloat16 misses the gradients' tolerance by
more than tenfold (checked below), and a wrong gate, tap, mask or routing
weight shows at 1e-2 or more.
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
from deeplearning4j_tpu.nn import (DecoderBlock, GatedMLP, GatedShortConv, GroupedQueryAttention,  # noqa: E402
                                   InputType, MixtureOfExperts, TiedRnnOutputLayer)
from deeplearning4j_tpu.nn.base import GlobalConfig, Layer  # noqa: E402
from deeplearning4j_tpu.runtime.environment import get_environment  # noqa: E402
from deeplearning4j_tpu.zoo import Lfm2Moe  # noqa: E402
from deeplearning4j_tpu.zoo.lfm2_moe import LAYER_TYPES  # noqa: E402

FAMILY = bench.load_module("families", "lfm2_moe")
TRAIN_FIT = bench.load_module("runners", "train_fit")
MM = reference_train.contractions("float32")[0]
# published layers 0 (conv + dense) and 2-5 (attention, conv, conv, conv, each with experts), as the cell cuts them
CONFIG = {"hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 1, "conv_L_cache": 3,
          "conv_bias": False, "tie_embedding": True, "use_expert_bias": True,
          "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
          "intermediate_size": 64, "moe_intermediate_size": 24, "num_dense_layers": 2,
          "layer_types": list(LAYER_TYPES[:6]), "layers_here": [0, 2, 3, 4, 5],
          "router_width": 16, "held_experts": [4, 4], "held_rows": 512, "num_experts_per_tok": 4,
          "routed_scaling_factor": 1, "expert_bias_rate": 1e-3, "norm_eps": 1e-5, "vocab_size": 96, "initializer_range": 0.02,
          "recompute": {"set_remat": True},
          "optimizer": {"name": "adam", "lr": 2e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8},
          "precision": {"compute": "float32"}}
SIZES = FAMILY._sizes(CONFIG)
G = GlobalConfig(dtype=jnp.float32)


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


def worst(got, want):
    """The largest leaf-wise gap over the leaf's largest element."""
    return max(float(np.max(np.abs(np.asarray(g) - np.asarray(w))) / max(float(np.max(np.abs(np.asarray(w)))), 1e-30))
               for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True))


def trees_close(got, want, rel):
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0], jax.tree.leaves(want)):
        try:
            close(g, w, rel)
        except AssertionError as e:
            raise AssertionError(f"{jax.tree_util.keystr(path)}: {e}") from None


def moved(tree, seed=1):
    """Norm scales moved off 1 and matrices and taps scaled up, so that a
    gradient or a broadcast that is wrong there shows."""
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


def test_the_gated_short_conv_is_its_equations_through_a_grouped_convolution():
    """``(C * conv(B * x)) W_out`` with ``[B | C | x] = h W_in``, the
    depthwise causal convolution written as ``lax.conv_general_dilated``
    with one group a channel: output and the gradients of the input and of
    every parameter; the taps run oldest first, the last on the current
    token; the layer reaches no token ahead of its own."""
    layer = GatedShortConv(kernel_size=3)
    layer._g = G
    d = 32
    params, state = layer.init(jax.random.PRNGKey(0), InputType.recurrent(d, 64), G)
    assert {k: v.shape for k, v in params.items()} == {"W_in": (d, 3 * d), "conv": (3, d), "W_out": (d, d)}
    assert state == {}
    params = moved(params)

    def equations(p, x):
        bcx = jnp.matmul(x, p["W_in"], precision="highest")
        b, c, u = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
        z = jax.lax.conv_general_dilated(b * u, p["conv"][:, None, :], (1,), [(2, 0)],
                                         dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=d,
                                         precision="highest")
        return jnp.matmul(c * z, p["W_out"], precision="highest")

    ours = lambda p, x: layer.forward(p, {}, x, training=True)[0]
    x = inputs(64)
    with jax.default_matmul_precision("highest"):
        close(ours(params, x), equations(params, x), 2e-5)
        scalar = lambda f: (lambda p, x_: jnp.sum(jnp.sin(3 * f(p, x_))))
        trees_close(jax.grad(scalar(ours), (0, 1))(params, x), jax.grad(scalar(equations), (0, 1))(params, x), 1e-4)
        close(ours(params, x), FAMILY._short_conv(x, params, MM), 2e-5)
        # the convolution is causal: what stands after position 40 reaches no output up to it
        later = x.at[:, 41:].set(jax.random.normal(jax.random.PRNGKey(9), x[:, 41:].shape))
        np.testing.assert_array_equal(ours(params, later)[:, :41], ours(params, x)[:, :41])
        # the last tap weighs the current token, the first the token two before
        one_hot = jnp.zeros_like(x).at[:, 20].set(1.0)
        gated = lambda p, x_: GatedShortConv.gated_conv(p["conv"], jnp.matmul(x_, p["W_in"]))
        reach = jnp.abs(jax.jacfwd(lambda x_: jnp.sum(gated(params, x_)[0, 22]))(one_hot))
        assert float(jnp.max(reach[0, 20:23])) > 0 and float(jnp.max(reach[0, :20])) == 0.0
    assert layer.to_dict() == {"@type": "GatedShortConv"} and Layer.from_dict(layer.to_dict()) == layer


@pytest.mark.parametrize("route", ["flash_interpreted", "xla"])
def test_grouped_query_attention_matches_the_reference(route, monkeypatch):
    """Four query heads over one key/value head of 8, causal, through the
    flash kernels (interpreted: the grouped heads' causal forms) and through
    the XLA form, which repeats K and V: outputs, and the gradients of every
    parameter and of the input."""
    from deeplearning4j_tpu.ops.pallas import flash_attention as fa
    calls = []
    real = fa.flash_attention
    monkeypatch.setattr(fa, "flash_attention", lambda *a, **k: calls.append(a[1].shape) or real(*a, **k))
    if route == "xla":
        monkeypatch.delenv("DL4J_TPU_PALLAS_INTERPRET", raising=False)
    else:
        monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
    params = moved(FAMILY.init_params(CONFIG, 0)[0]["layer_2"]["mixer"])
    layer = GroupedQueryAttention(n_heads=4, n_kv_heads=1, head_dim=8, rope_theta=1e6, eps=1e-5)
    layer._g = G
    ours = lambda p, x: layer.forward(p, {}, x, training=True)[0]
    theirs = lambda p, x: FAMILY._attention(x, p, SIZES, MM)
    x = inputs(256)
    close(ours(params, x), theirs(params, x), 2e-5)
    scalar = lambda f: (lambda p, x_: jnp.sum(jnp.sin(3 * f(p, x_))))
    trees_close(jax.grad(scalar(ours), (0, 1))(params, x), jax.grad(scalar(theirs), (0, 1))(params, x), 1e-4)
    # K and V reach the kernel at their own single head
    assert bool(calls and set(calls) == {(2, 1, 256, 8)}) == (route == "flash_interpreted")


def reference_logits(params, ids):
    """The reference's logits, from its own pieces: the blocks, the final
    norm, the table transposed."""
    keys = FAMILY._keys(SIZES)
    x = params[keys["embed"]]["W"][ids]
    for key, (kind, dense) in zip(keys["blocks"], SIZES["blocks"]):
        state = {} if dense else {"mlp": {"select_bias": jnp.zeros((16,))}}
        x, _ = FAMILY._block(x, params[key], state, kind, dense, SIZES, MM)
    return MM(FAMILY._rms_norm(x, params[keys["norm"]]["w"], 1e-5), params[keys["embed"]]["W"].T)


def test_the_whole_model_matches_the_reference_in_logits_loss_and_every_gradient(monkeypatch):
    """Embedding, a conv block with the dense FFN, attention and three conv
    blocks with experts, the final norm, the head tied to the table, the
    grouped heads through the flash kernels (interpreted): the program's
    distributions, loss, counters and the gradient of every leaf against
    the reference from the same weights. The bfloat16 reference misses the
    gradients' tolerance: the comparison can tell the stated precision."""
    monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
    net, params, state = model()
    ids, labels = batch()

    def ours(p):
        loss, (new_state, _) = net._loss(p, state, ids, labels, None)
        return loss, new_state

    reference = FAMILY.reference_loss(CONFIG)
    theirs = lambda p, mm=MM: reference(p, state, (ids, labels, None), mm, None)
    (loss, new_state), grads = jax.value_and_grad(ours, has_aux=True)(params)
    (want, want_state), want_grads = jax.value_and_grad(theirs, has_aux=True)(params)
    assert float(loss) == pytest.approx(float(want), rel=1e-6) and float(loss) > 1
    trees_close(grads, want_grads, 1e-4)
    trees_close(new_state, want_state, 1e-6)
    net.set_params(params)
    close(net.output(np.asarray(ids)), jax.nn.softmax(reference_logits(params, ids), -1), 2e-5)
    assert sum(jax.tree.leaves(jax.tree.map(jnp.size, params))) == FAMILY.n_params(CONFIG)
    bf16 = reference_train.contractions("bfloat16")[0]
    _, rounded = jax.value_and_grad(lambda p: theirs(p, bf16), has_aux=True)(params)
    assert worst(rounded, want_grads) > 10 * 1e-4, worst(rounded, want_grads)


def test_one_step_through_fit_moves_adams_first_moment_by_the_reference_gradient():
    """One step of the public ``fit`` from the benchmark's weights: Adam's
    first moment of every leaf is (1 - b1) x the reference's gradient, the
    table's the sum of the lookup's and the head's (it is one leaf, moved
    once), and the counters are the reference's."""
    net, params, state = model()
    ids, labels = batch()
    reference = FAMILY.reference_loss(CONFIG)
    (_, want_state), want = jax.value_and_grad(lambda p: reference(p, state, (ids, labels, None), MM, None),
                                               has_aux=True)(params)
    net.set_params(jax.tree.map(jnp.copy, params))
    net.fit(np.asarray(ids), np.asarray(labels))
    ts = net.train_state
    mu = [leaf for leaf in jax.tree.leaves(ts.opt_state) if getattr(leaf, "shape", None) == (96, 32)]
    assert len(mu) == 2  # the table's two moments, once each: no head of its own
    first_moment = TRAIN_FIT._first_moment(ts.opt_state, ts.params, "mu")  # the runner's own reading
    trees_close(jax.tree.map(lambda m: m / 0.1, first_moment), want, 1e-4)
    trees_close(ts.model_state, want_state, 1e-6)


def test_the_head_reads_the_table_and_its_gradient_sums_both_uses():
    """The tied head owns no weight; pointing it at a copy of the table
    splits the table's gradient into the lookup's and the head's, and the
    tied gradient is their sum."""
    net, params, state = model()
    ids, labels = batch()
    head = net.layers[-1]
    assert isinstance(head, TiedRnnOutputLayer) and head.tied == {"W": "layer_0/W"}
    assert "layer_7" not in net.train_state.params
    loss = lambda p: net._loss(p, state, ids, labels, None)[0]
    tied = jax.grad(loss)(params)
    try:
        head.tied = {"W": "table_copy/W"}
        split = jax.grad(loss)({**params, "table_copy": params["layer_0"]})
    finally:
        head.tied = {"W": "layer_0/W"}
    lookup, scoring = split["layer_0"]["W"], split["table_copy"]["W"]
    assert float(jnp.max(jnp.abs(lookup))) > 0 and float(jnp.max(jnp.abs(scoring))) > 0
    close(tied["layer_0"]["W"], lookup + scoring, 1e-6)


@pytest.mark.parametrize("router,shares", [(16, 4), (64, 8)])
def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer(router, shares):
    """Top-4 of 16 in 4 shares, and of 64 in 8 shares of 8 as the cell cuts
    it: what the shares compute for their own experts adds up to what the
    uncut reference layer gives (no shared expert: nothing is counted
    twice), and every assignment is counted once."""
    held = router // shares
    whole = dict(CONFIG, router_width=router, held_experts=[0, router])
    params, state = FAMILY.init_params(whole, 7)
    params, state = jax.tree.map(lambda a: 5 * a, params["layer_2"]["mlp"]), state["layer_2"]["mlp"]
    x = inputs(128, seed=11)
    want, counted = FAMILY._moe(x, params, state, FAMILY._sizes(whole), MM)
    total, assigned = jnp.zeros_like(x), []
    for first in range(0, router, held):
        share = {k: v[first:first + held] if k.startswith("W_e") else v for k, v in params.items()}
        layer = MixtureOfExperts(n_out=32, hidden_size=24, n_experts=router, held=(first, held), top_k=4,
                                 routed_scale=1.0, bias_rate=1e-3, router="sigmoid", gated=True, activation="swish",
                                 aux_loss_coef=0.0)
        layer._g = G
        y, new = layer.forward(share, dict(state, assigned=jnp.zeros((held,))), x, training=True)
        total = total + y
        assigned.append(new["assigned"])
        # every share counts every expert's load, so every share moves the bias as the uncut layer does
        np.testing.assert_array_equal(new["select_bias"], counted["select_bias"])
    close(total, want, 2e-5)
    np.testing.assert_array_equal(jnp.concatenate(assigned), counted["assigned"])
    assert float(sum(jnp.sum(a) for a in assigned)) == x.shape[0] * x.shape[1] * 4


def test_the_selection_bias_moves_against_each_experts_load_in_training_only():
    """After a training step the bias of each of the 16 experts moves by the
    rate against its load: down where more tokens chose it than the mean,
    up where fewer, not at all where as many; padding is not counted, and
    inference leaves the bias where it was. The reference moves it alike."""
    whole = dict(CONFIG, held_experts=[0, 16])
    params, state = FAMILY.init_params(whole, 5)
    params, state = jax.tree.map(lambda a: 5 * a, params["layer_2"]["mlp"]), state["layer_2"]["mlp"]
    state = dict(state, select_bias=0.01 * jnp.arange(16.0))
    layer = MixtureOfExperts(n_out=32, hidden_size=24, n_experts=16, top_k=4, bias_rate=1e-3, router="sigmoid",
                             gated=True, activation="swish", aux_loss_coef=0.0)
    layer._g = G
    x = inputs(64, seed=13)
    scores = jax.nn.sigmoid(x.reshape(-1, 32) @ params["W_router"])
    _, chosen = jax.lax.top_k(scores + state["select_bias"], 4)
    load = np.bincount(np.asarray(chosen).ravel(), minlength=16)
    assert load.sum() == 2 * 64 * 4 and len(set(load.tolist())) > 2
    _, new = layer.forward(params, state, x, training=True)
    moved_by = lambda counts: state["select_bias"] + 1e-3 * jnp.sign(jnp.float32(counts.mean()) - counts)
    np.testing.assert_array_equal(new["select_bias"], moved_by(load))
    np.testing.assert_array_equal(FAMILY._moe(x, params, state, FAMILY._sizes(whole), MM)[1]["select_bias"], new["select_bias"])
    _, kept = layer.forward(params, state, x, training=False)
    np.testing.assert_array_equal(kept["select_bias"], state["select_bias"])
    # the second sequence as padding: only the first one's choices count
    mask = jnp.stack([jnp.ones(64), jnp.zeros(64)])
    _, masked = layer.forward(params, state, x, training=True, mask=mask)
    first = np.bincount(np.asarray(chosen)[:64].ravel(), minlength=16)
    np.testing.assert_array_equal(masked["select_bias"], moved_by(first))


def test_the_layer_pattern_is_built_from_layer_types():
    """The published 40 layers: attention at 2, 6, .., 38 (the configuration
    file's list); the cell's layers 0 and 2-5 are conv + dense, then
    attention, conv, conv, conv with experts; a kind the family lacks is
    refused."""
    config = bench.load_json(os.path.join(ROOT, "benchmark", "configs", "lfm2-24b-a2b.json"))
    assert tuple(config["layer_types"]) == LAYER_TYPES and LAYER_TYPES.count("full_attention") == 10
    zoo = Lfm2Moe(vocab_size=96, d_model=32, layers_here=config["layers_here"], n_heads=4, n_kv_heads=1,
                  dense_size=64, n_dense=config["num_dense_layers"], expert_size=24, n_experts=8, top_k=2)
    blocks = [layer for layer in zoo.conf().layers if isinstance(layer, DecoderBlock)]
    assert [(type(b.mixer).__name__, type(b.mlp).__name__) for b in blocks] == [
        ("GatedShortConv", "GatedMLP"), ("GroupedQueryAttention", "MixtureOfExperts")] + 3 * [
        ("GatedShortConv", "MixtureOfExperts")]
    assert isinstance(blocks[0].mlp, GatedMLP) and blocks[0].mlp.hidden_size == 64
    assert blocks[1].mixer.head_dim == 8 and blocks[1].mixer.block_diffusion is None
    assert blocks[2].mlp.bias_rate == 1e-3 and blocks[2].mlp.n_shared == 0 and blocks[2].mlp.router == "sigmoid"
    with pytest.raises(ValueError, match="sliding"):
        Lfm2Moe(layer_types=["conv", "sliding_attention"])
    with pytest.raises(ValueError, match="layers_here"):
        Lfm2Moe(layers_here=[0, 40])


def test_tiny_trains_through_fit_and_counts_its_held_assignments():
    net = Lfm2Moe.tiny(held_experts=(0, 4), held_rows=512).init()
    ids = np.random.default_rng(0).integers(0, 96, (2, 129), dtype=np.int32)
    x, y = np.ascontiguousarray(ids[:, :-1]), np.ascontiguousarray(ids[:, 1:])
    net.fit(x, y)
    first = float(net.score())
    net.fit(x, y, epochs=6)
    assert float(net.score()) < first
    for key in ("layer_2", "layer_6"):
        counters = net.train_state.model_state[key]["mlp"]
        assert counters["assigned"].shape == (4,) and float(counters["overflow"]) == 0.0
        assert 0 < float(jnp.sum(counters["assigned"])) <= 2 * 128 * 2
        # seven steps of the bias, each of 1e-3 or none
        assert 0 < float(jnp.max(jnp.abs(counters["select_bias"]))) <= 7e-3 + 1e-9
    assert net.output(x).shape == (2, 128, 96)


def test_the_operators_scopes_sit_directly_under_their_block():
    """The yardstick cuts a scope path at two components: the convolution's
    three scopes must read ``<layer>.DecoderBlock/short_conv``, forward and
    backward, under ``Environment.set_remat``; attention's keep their names."""
    import re
    net = Lfm2Moe.tiny(held_experts=(0, 4), held_rows=512).init()
    x = jnp.zeros((2, 128), jnp.int32)
    step, packer = net._jitted_packed()
    text = step.lower(packer.pack_device(net.train_state), x, x, jax.random.PRNGKey(0), None, None).as_text(
        debug_info=True)
    names = set(re.findall(r'"(jit\(packed_train_step[^"]*)"', text))
    conv = {m.group(1) for m in (re.search(r"layer_1\.DecoderBlock\)?/short_conv/(\w+)", n) for n in names) if m}
    assert conv == {"in_proj", "conv", "out_proj"}
    backward = [n for n in names if n.startswith("jit(packed_train_step)/transpose(jvp(layer_4.DecoderBlock))/short_conv/")]
    assert backward
    gqa = {m.group(1) for m in (re.search(r"layer_3\.DecoderBlock\)?/(\w+)/", n) for n in names) if m}
    assert {"qkv", "qk_norm", "rope", "out_proj", "router", "dispatch", "experts", "combine"} <= gqa
    assert "short_conv" not in gqa
