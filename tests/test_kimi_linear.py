"""Kimi Linear's layers against the benchmark family's plain reference
(``benchmark/families/kimi_linear.py``, which imports nothing of the
program), on seeded weights, forward and gradients, at small sizes on the CPU.

Tolerances: both sides compute in float32 on the CPU and differ in the
order of their sums only (a chunk's triangular solve against a token loop,
a grouped matmul against eight dense ones, a blockwise softmax against a
whole one), so outputs agree to a few float32 roundings of their largest
element: 2e-5 of it, gradients 1e-4 (sums over up to 192 tokens of terms of
both signs). A wrong decay, mask, gate or routing weight shows at 1e-2 or
more.
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
from deeplearning4j_tpu.nn import (DecoderBlock, GatedMLP, InputType, KimiDeltaAttention,  # noqa: E402
                                   LatentAttention, MixtureOfExperts)
from deeplearning4j_tpu.nn.base import GlobalConfig, Layer  # noqa: E402
from deeplearning4j_tpu.nn import linear_attention_layers  # noqa: E402
from deeplearning4j_tpu.nn.linear_attention_layers import chunk_kda, chunk_kda_xla  # noqa: E402
from deeplearning4j_tpu.ops.pallas.chunk_kda import CHUNK, chunk_kda_compatible  # noqa: E402
from deeplearning4j_tpu.runtime.environment import get_environment  # noqa: E402
from deeplearning4j_tpu.zoo import KimiLinear  # noqa: E402

FAMILY = bench.load_module("families", "kimi_linear")
MM = reference_train.contractions("float32")[0]
CONFIG = {"hidden_size": 32, "num_hidden_layers": 5, "num_attention_heads": 2,
          "linear_attn_config": {"full_attn_layers": [4], "kda_layers": [1, 2, 3, 5], "head_dim": 16,
                                 "num_heads": 2, "short_conv_kernel_size": 4},
          "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
          "intermediate_size": 64, "moe_intermediate_size": 24, "first_k_dense_replace": 1,
          "router_width": 16, "held_experts": [4, 4], "held_rows": 512, "num_experts_per_token": 4,
          "num_shared_experts": 1, "routed_scaling_factor": 2.446, "rms_norm_eps": 1e-5, "vocab_size": 96,
          "initializer_range": 0.02, "kda_gate_rank": 8, "recompute": {"set_remat": True},
          "optimizer": {"name": "adam", "lr": 2e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8},
          "precision": {"compute": "float32"}}
SIZES = FAMILY._sizes(CONFIG)
G = GlobalConfig(dtype=jnp.float32)


@pytest.fixture(autouse=True)
def every_scope_recomputed():
    """As the benchmark's cell runs the layers: ``Environment.set_remat``, under
    which each named scope is a ``jax.checkpoint`` (``FAMILY.build`` turns it
    on too); put back after the test."""
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


def layer_params(layer, seed=0):
    """The benchmark's weights of layer 2 (KDA + experts) or 4 (MLA), with
    the norms and the gate's bias moved off their starting values so that a
    gradient or a broadcast that is wrong there shows."""
    params, state = FAMILY.init_params(CONFIG, seed)
    jitter = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))
    params = jax.tree.map(lambda a: a + 0.1 * jax.random.normal(next(jitter), a.shape) if a.ndim == 1 else 5 * a,
                          params[f"layer_{layer}"])
    return params, state.get(f"layer_{layer}", {})


def inputs(t, seed=3, b=2):
    return jax.random.normal(jax.random.PRNGKey(seed), (b, t, CONFIG["hidden_size"]), jnp.float32)


def program(layer, **kw):
    layer._g = G
    return lambda p, x: layer.forward(p, kw.get("state", {}), x, training=True)[0]


def agree(ours, theirs, params, x, rel=2e-5):
    """Outputs, and the gradients of a scalar of them with respect to the
    parameters and the input."""
    close(ours(params, x), theirs(params, x), rel)
    scalar = lambda f: (lambda p, x_: jnp.sum(jnp.sin(3 * f(p, x_))))
    trees_close(jax.grad(scalar(ours), (0, 1))(params, x), jax.grad(scalar(theirs), (0, 1))(params, x), 5 * rel)


@pytest.mark.parametrize("tokens,decay", [(64, 0.05), (64, 3.0), (192, 0.05), (192, 3.0)])
def test_chunked_delta_rule_matches_the_token_recurrence(tokens, decay):
    """One chunk and three, with a decay weak enough that the carried state
    matters (0.05 a token: a state survives a chunk at e^-3) and one so
    strong (3 a token, e^-192 over a chunk) that a decay factored through
    the chunk's first token would overflow."""
    args = delta_rule_inputs(tokens, decay)
    want = FAMILY.delta_rule(*args)
    close(chunk_kda(*args), want, 2e-5)
    if tokens > 64 and decay < 1:  # the state carried over a chunk's edge is a visible part of the output
        first = FAMILY.delta_rule(*(a[:, 128:] for a in args))
        assert float(jnp.max(jnp.abs(first - want[:, 128:]))) > 1e-3
    grads = lambda f: jax.grad(lambda *a: jnp.sum(jnp.sin(3 * f(*a))), argnums=(0, 1, 2, 3, 4))(*args)
    trees_close(grads(chunk_kda), grads(FAMILY.delta_rule), 1e-4)


def delta_rule_inputs(tokens, decay, b=2, h=3, d=16, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(tokens), 5)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (b, tokens, h, d))) * d ** -0.5
    k = unit(jax.random.normal(ks[1], (b, tokens, h, d)))
    v = jax.random.normal(ks[2], (b, tokens, h, d))
    g = -jax.random.uniform(ks[3], (b, tokens, h, d), minval=0.0, maxval=decay)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, tokens, h)))
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


def kernel_calls(monkeypatch):
    """The list that grows by one with every call ``chunk_kda`` routes to the kernel pair."""
    calls = []
    real = linear_attention_layers.chunk_kda_pallas
    monkeypatch.setattr(linear_attention_layers, "chunk_kda_pallas", lambda *a: calls.append(1) or real(*a))
    return calls


@pytest.mark.parametrize("tokens,decay", [(384, 0.05), (512, 3.0)])
def test_the_delta_rule_kernel_matches_the_xla_form_and_the_token_recurrence(tokens, decay, monkeypatch):
    """The Pallas pair under the interpreter at the published head size (128)
    against the XLA form and against the family's token recurrence, outputs
    and all five gradients: three blocks of two chunks with a weak decay (the
    state carried from block to block in VMEM is a visible part of the
    output; time is on lanes since PR 34, so a block is whole lane tiles of
    128 tokens), two blocks of four chunks with a strong one (3 a token).
    One chunk and one block: ``tests/test_pallas.py``."""
    monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
    calls = kernel_calls(monkeypatch)
    args = delta_rule_inputs(tokens, decay, b=2, h=2, d=128)
    assert chunk_kda_compatible(args[0], args[2])
    got, xla, want = chunk_kda(*args), chunk_kda_xla(*args), FAMILY.delta_rule(*args)
    assert calls
    close(got, xla, 2e-5)
    close(got, want, 2e-5)
    if tokens == 384:
        later = FAMILY.delta_rule(*(a[:, 128:] for a in args))
        assert float(jnp.max(jnp.abs(later - want[:, 128:]))) > 1e-3
    grads = lambda f: jax.grad(lambda *a: jnp.sum(jnp.sin(3 * f(*a))), argnums=(0, 1, 2, 3, 4))(*args)
    ours = grads(chunk_kda)
    trees_close(ours, grads(chunk_kda_xla), 1e-4)
    trees_close(ours, grads(FAMILY.delta_rule), 1e-4)


def test_the_delta_rule_kernels_take_and_give_time_minor_arrays(monkeypatch):
    """The layout the pair is handed in HBM is the layer's to choose, and it
    chooses what XLA holds on the chip: the two ``pallas_call``s in the
    gradient through ``chunk_kda`` take q, k, v, g, dO and give o, dq, dk,
    dv, dg as (b, h * d, t), features before time. Row-major (b, t, h * d)
    operands cost 36 re-layout copies a step in the Kimi Linear cell
    (``tests/test_pallas.py`` holds the compiled step to none)."""
    monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
    b, t, h, d = 2, 128, 2, 128
    args = delta_rule_inputs(t, 0.05, b=b, h=h, d=d)
    jaxpr = jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(chunk_kda(*a)), argnums=(0, 1, 2, 3, 4)))(*args)

    def eqns(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from eqns(sub)

    calls = {eqn.params["name"]: eqn for eqn in eqns(jaxpr.jaxpr) if eqn.primitive.name == "pallas_call"}
    assert sorted(calls) == ["chunk_kda_bwd", "chunk_kda_fwd"]
    shapes = lambda vs: [tuple(v.aval.shape) for v in vs]
    per_token, per_head = (b, h * d, t), (b, t, h)
    fwd, bwd = calls["chunk_kda_fwd"], calls["chunk_kda_bwd"]
    assert shapes(fwd.invars) == [per_token] * 4 + [per_head]
    assert shapes(fwd.outvars)[0] == per_token
    assert shapes(bwd.invars)[:5] == [per_token] * 4 + [per_head] and shapes(bwd.invars)[-1] == per_token
    assert shapes(bwd.outvars) == [per_token] * 4 + [per_head]


@pytest.mark.parametrize("why,d,tokens,chunk,interpreter,takes", [
    ("the cell's head size, whole chunks", 128, 128, CHUNK, True, True),
    ("a head that is no lane tile", 16, 128, CHUNK, True, False),
    ("tokens that are no whole chunks", 128, 96, CHUNK, True, False),
    ("three chunks: a block of one would be half a lane tile of the sequence", 128, 192, CHUNK, True, False),
    ("another chunk than the kernel's", 128, 128, 32, True, False),
    ("no kernels on this platform", 128, 128, CHUNK, False, False)])
def test_chunk_kda_takes_the_kernel_by_shape_and_platform_alone(why, d, tokens, chunk, interpreter, takes, monkeypatch):
    if interpreter:
        monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("DL4J_TPU_PALLAS_INTERPRET", raising=False)
    args = delta_rule_inputs(tokens, 0.05, b=1, h=1, d=d)
    assert chunk_kda_compatible(args[0], args[2], chunk) is takes, why
    entered = kernel_calls(monkeypatch)
    if tokens % chunk:
        with pytest.raises(ValueError, match="no multiple of the chunk"):
            chunk_kda(*args, chunk=chunk)
    else:
        got = chunk_kda(*args, chunk=chunk)
        assert bool(entered) is takes, why
        if not takes:  # the XLA form itself, to the bit
            np.testing.assert_array_equal(got, chunk_kda_xla(*args, chunk=chunk))


def test_kda_layer_matches_the_reference():
    params, _ = layer_params(2)
    layer = KimiDeltaAttention(n_heads=2, head_dim=16, gate_rank=8)
    agree(program(layer), lambda p, x: FAMILY._kda(x, p, SIZES, 1e-5, MM), params["mixer"], inputs(192))


@pytest.mark.parametrize("route", ["flash_interpreted", "xla"])
def test_latent_attention_matches_the_reference(route, monkeypatch):
    """Through the flash kernel (interpreted: q.k head 24 against a v head
    of 16, two sizes in one call) and through the XLA form."""
    from deeplearning4j_tpu.ops.pallas import flash_attention as fa
    calls = []
    real = fa.flash_attention
    monkeypatch.setattr(fa, "flash_attention", lambda *a, **k: calls.append(1) or real(*a, **k))
    if route == "xla":
        monkeypatch.delenv("DL4J_TPU_PALLAS_INTERPRET", raising=False)
    else:
        monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
    params, _ = layer_params(4)
    layer = LatentAttention(n_heads=2, kv_rank=16, qk_nope_dim=16, qk_shared_dim=8, v_dim=16)
    agree(program(layer), lambda p, x: FAMILY._mla(x, p, SIZES, 1e-5, MM), params["mixer"], inputs(256))
    assert bool(calls) == (route == "flash_interpreted")


def moe_layer(held, held_rows=None, n_shared=1):
    return MixtureOfExperts(n_out=32, hidden_size=24, n_experts=16, held=held, held_rows=held_rows, top_k=4,
                            n_shared=n_shared, routed_scale=2.446, router="sigmoid", gated=True,
                            activation="swish", aux_loss_coef=0.0)


def test_expert_layer_matches_the_reference_and_counts_its_assignments():
    params, state = layer_params(2)
    x = inputs(128)
    layer = moe_layer((4, 4), 512)
    reference = lambda p, x_: FAMILY._moe(x_, p, state["mlp"], SIZES, CONFIG, MM)[0]
    agree(program(layer, state=state["mlp"]), reference, params["mlp"], x)
    layer._g = G
    _, new = layer.forward(params["mlp"], state["mlp"], x, training=True)
    _, want = FAMILY._moe(x, params["mlp"], state["mlp"], SIZES, CONFIG, MM)
    assert set(new) == set(want) == {"assigned", "overflow", "select_bias"}
    np.testing.assert_array_equal(new["assigned"], want["assigned"])
    assert float(new["overflow"]) == 0.0 and 0 < float(jnp.sum(new["assigned"])) < x.shape[0] * x.shape[1] * 4


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """16 experts in 4 shares of 4: what the shares compute for their own
    experts, with the shared expert (which every share computes alike)
    counted once, is what the uncut reference layer gives."""
    whole = dict(CONFIG, held_experts=[0, 16])
    params, state = FAMILY.init_params(whole, 7)
    params, state = jax.tree.map(lambda a: 5 * a, params["layer_2"]["mlp"]), state["layer_2"]["mlp"]
    x = inputs(128, seed=11)
    want, counted = FAMILY._moe(x, params, state, FAMILY._sizes(whole), whole, MM)
    shared = FAMILY._swiglu(x, params["shared"], MM)
    total, assigned = shared, []
    for first in range(0, 16, 4):
        share = {k: v[first:first + 4] if k.startswith("W_e") else v for k, v in params.items()}
        layer = moe_layer((first, 4))
        layer._g = G
        y, new = layer.forward(share, dict(state, assigned=jnp.zeros((4,))), x, training=True)
        total = total + (y - shared)
        assigned.append(new["assigned"])
    close(total, want, 2e-5)
    np.testing.assert_array_equal(jnp.concatenate(assigned), counted["assigned"])
    assert float(sum(jnp.sum(a) for a in assigned)) == x.shape[0] * x.shape[1] * 4  # every assignment, once


def test_an_overflowing_buffer_is_counted_and_differs_from_the_reference():
    params, state = layer_params(2)
    x = inputs(128)
    layer = moe_layer((4, 4), 64)
    layer._g = G
    y, new = layer.forward(params["mlp"], state["mlp"], x, training=True)
    want, counted = FAMILY._moe(x, params["mlp"], state["mlp"], SIZES, CONFIG, MM)
    np.testing.assert_array_equal(new["assigned"], counted["assigned"])  # routed, whether they fitted or not
    assert float(new["overflow"]) == float(jnp.sum(counted["assigned"])) - 64 > 0
    assert float(jnp.max(jnp.abs(y - want))) > 1e-2 * float(jnp.max(jnp.abs(want)))


def test_the_whole_model_matches_the_reference_loss_and_gradients():
    """Embedding, five blocks of every kind, the final norm, the untied head
    and the next-token loss: the program's training loss and its gradient
    against ``reference_loss`` from the same weights."""
    params, state = FAMILY.init_params(CONFIG, 3)
    net = FAMILY.build(CONFIG, 3)
    (ids, labels, _), = FAMILY.batches(CONFIG, {"batch": 2, "seq_len": 128, "count": 1}, 3)

    def ours(p):
        loss, (new_state, _) = net._loss(p, state, jnp.asarray(ids), jnp.asarray(labels), None)
        return loss, new_state

    reference = FAMILY.reference_loss(CONFIG)
    theirs = lambda p: reference(p, state, (jnp.asarray(ids), jnp.asarray(labels), None), MM, None)
    (loss, new_state), grads = jax.value_and_grad(ours, has_aux=True)(params)
    (want, want_state), want_grads = jax.value_and_grad(theirs, has_aux=True)(params)
    assert float(loss) == pytest.approx(float(want), rel=1e-6)
    trees_close(grads, want_grads, 1e-4)
    trees_close(new_state, want_state, 1e-6)


def test_tiny_trains_through_fit_and_its_counters_are_read_on_the_host():
    net = KimiLinear.tiny(held_experts=(0, 4), held_rows=512).init()
    ids = np.random.default_rng(0).integers(0, 96, (2, 129), dtype=np.int32)
    x, y = np.ascontiguousarray(ids[:, :-1]), np.ascontiguousarray(ids[:, 1:])
    net.fit(x, y)
    first = float(net.score())
    net.fit(x, y, epochs=8)
    assert float(net.score()) < first
    counters = net.train_state.model_state["layer_2"]["mlp"]
    assert counters["assigned"].shape == (4,) and float(counters["overflow"]) == 0.0
    assert 0 < float(jnp.sum(counters["assigned"])) <= 2 * 128 * 2


def test_set_remat_recomputes_a_decoder_block_inside_its_scopes_and_not_around_it():
    """``Environment.set_remat`` is the one switch: under it every scope of a
    decoder block is its own checkpoint, the network's checkpoint around a
    layer is left out for the blocks (it would name their backward pass
    ``<layer>/<layer>/checkpoint`` and hide the scopes) and kept for the
    embedding; without it no scope is a checkpoint."""
    import re
    x = jnp.zeros((2, 128), jnp.int32)

    def op_names():
        net = KimiLinear.tiny(held_experts=(0, 4), held_rows=512).init()
        step, packer = net._jitted_packed()
        text = step.lower(packer.pack_device(net.train_state), x, x, jax.random.PRNGKey(0), None, None).as_text(
            debug_info=True)
        return set(re.findall(r'"(jit\(packed_train_step[^"]*)"', text))

    names = op_names()
    around = re.compile(r"jvp\((layer_\d+\.\w+)\)\)/jvp\(\1\)/checkpoint")
    assert {m.group(1).split(".")[1] for m in map(around.search, names) if m} == {"EmbeddingSequenceLayer"}
    for scope in ("kda_in", "kda_out", "mla_qkv", "mla_out", "mlp", "router", "dispatch", "experts", "combine",
                  "shared_expert", "norm"):
        assert any(f"/{scope}/checkpoint/" in n for n in names), scope
    get_environment().set_remat(False)
    assert not [n for n in op_names() if "/checkpoint/" in n]


def test_a_decoder_block_hands_its_expert_layers_aux_loss_to_the_network():
    moe = MixtureOfExperts(n_out=32, hidden_size=24, n_experts=4, top_k=2, aux_loss_coef=0.01)
    block = DecoderBlock(mixer=KimiDeltaAttention(n_heads=2, head_dim=16, gate_rank=8), mlp=moe)
    block._g = G
    params, state = block.init(jax.random.PRNGKey(0), InputType.recurrent(32, 64), G)
    assert float(state["_aux_loss"]) == 0.0 and "_aux_loss" in state["mlp"]
    _, new = block.forward(params, state, inputs(64), training=True)
    assert float(new["_aux_loss"]) == float(new["mlp"]["_aux_loss"]) > 0.0
    assert jax.tree.structure(new) == jax.tree.structure(state)


def test_a_decoder_block_survives_its_configurations_round_trip():
    block = DecoderBlock(mixer=KimiDeltaAttention(n_heads=2, head_dim=16), mlp=GatedMLP(hidden_size=64))
    again = Layer.from_dict(block.to_dict())
    assert isinstance(again.mixer, KimiDeltaAttention) and isinstance(again.mlp, GatedMLP)
    assert again.to_dict() == block.to_dict()
    x = inputs(64)
    block._g = again._g = G
    params, state = block.init(jax.random.PRNGKey(0), InputType.recurrent(32, 64), G)
    np.testing.assert_array_equal(block.forward(params, state, x)[0], again.forward(params, state, x)[0])
