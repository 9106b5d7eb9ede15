"""BERT encoder with a sequence-classification head (Devlin et al. 2018).

``build`` hands the configuration to the program's zoo model; everything
else is the benchmark's own: the weights (made here from the seed, in the
layout the program's parameter tree has, so that program and reference
start from the same numbers without either making them for the other),
the batches, the FLOPs and bytes of a step, and the plain reference.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np


def build(config: dict, seed: int):
    from deeplearning4j_tpu.train.updaters import Adam
    from deeplearning4j_tpu.zoo.bert import Bert
    opt = config["optimizer"]
    return Bert(vocab_size=config["vocab_size"], d_model=config["hidden_size"],
                n_layers=config["num_hidden_layers"], n_heads=config["num_attention_heads"],
                ffn_size=config["intermediate_size"], max_len=config["max_position_embeddings"],
                num_classes=config["num_labels"], dropout_rate=config["hidden_dropout_prob"],
                seed=seed % (2 ** 31),
                updater=Adam(opt["lr"], beta1=opt["b1"], beta2=opt["b2"], epsilon=opt["eps"])).init()


def init_params(config: dict, seed: int):
    """(params, model_state) in float32 on the device, one jitted call.
    Matrices are N(0, initializer_range); norms start at (1, 0); biases at 0."""
    d, f, n = config["hidden_size"], config["intermediate_size"], config["num_hidden_layers"]
    std = config["initializer_range"]

    def make(key):
        keys = iter(jax.random.split(key, 5 + 6 * n))

        def w(*shape):
            return std * jax.random.normal(next(keys), shape, jnp.float32)

        ones, zeros = (lambda k: jnp.ones((k,), jnp.float32)), (lambda k: jnp.zeros((k,), jnp.float32))
        params = {"layer_0": {"tok": w(config["vocab_size"], d),
                              "pos": w(config["max_position_embeddings"], d),
                              "seg": w(config["type_vocab_size"], d),
                              "ln_gamma": ones(d), "ln_beta": zeros(d)}}
        for i in range(1, n + 1):
            params[f"layer_{i}"] = {
                "attn": {"W_q": w(d, d), "W_k": w(d, d), "W_v": w(d, d), "W_o": w(d, d),
                         "b_q": zeros(d), "b_k": zeros(d), "b_v": zeros(d), "b_o": zeros(d)},
                "ln1_gamma": ones(d), "ln1_beta": zeros(d),
                "W_ff1": w(d, f), "b_ff1": zeros(f), "W_ff2": w(f, d), "b_ff2": zeros(d),
                "ln2_gamma": ones(d), "ln2_beta": zeros(d)}
        # layer n+1 is the [CLS] pick and holds nothing
        params[f"layer_{n + 2}"] = {"W": w(d, d), "b": zeros(d)}
        params[f"layer_{n + 3}"] = {"W": w(d, config["num_labels"]), "b": zeros(config["num_labels"])}
        return params

    return jax.jit(make)(jax.random.fold_in(jax.random.PRNGKey(0), seed % (2 ** 32))), {}


def batches(config: dict, traffic: dict, seed: int):
    """``count`` host batches of (features, labels, features_mask): token
    ids, one-hot labels, and a padding mask whose valid length per row is
    drawn in ``valid_min..seq_len``. Every row differs."""
    rng = np.random.default_rng(seed)
    b, t = traffic["batch"], traffic["seq_len"]
    out = []
    for _ in range(traffic["count"]):
        ids = rng.integers(0, config["vocab_size"], (b, t), dtype=np.int32)
        valid = rng.integers(traffic["valid_min"], t + 1, (b,))
        mask = (np.arange(t)[None, :] < valid[:, None]).astype(np.float32)
        labels = np.eye(config["num_labels"], dtype=np.float32)[
            rng.integers(0, config["num_labels"], (b,))]
        out.append((ids, labels, mask))
    return out


def samples_per_step(traffic: dict) -> int:
    return traffic["batch"]


def flops_per_step(config: dict, traffic: dict) -> float:
    """Forward + backward = 3 x the forward's matmul FLOPs at the padded
    shape the step computes (2 per multiply-add; nothing recomputed; the
    embedding gather, norms, softmax and Adam count nothing)."""
    b, t = traffic["batch"], traffic["seq_len"]
    d, f, n = config["hidden_size"], config["intermediate_size"], config["num_hidden_layers"]
    per_layer = 2 * b * t * (4 * d * d + 2 * d * f) + 2 * 2 * b * t * t * d
    head = 2 * b * (d * d + d * config["num_labels"])
    return 3.0 * (n * per_layer + head)


def n_params(config: dict) -> int:
    d, f, n = config["hidden_size"], config["intermediate_size"], config["num_hidden_layers"]
    emb = (config["vocab_size"] + config["max_position_embeddings"] + config["type_vocab_size"] + 2) * d
    layer = 4 * (d * d + d) + 2 * d * f + f + d + 4 * d
    return emb + n * layer + d * d + d + d * config["num_labels"] + config["num_labels"]


def least_bytes_per_step(config: dict, traffic: dict) -> float:
    """Train state read once and written once (float32 parameters and two
    Adam moments) plus the batch in."""
    state = 3 * 4 * n_params(config)
    batch = traffic["batch"] * (traffic["seq_len"] * 8 + 4 * config["num_labels"])
    return 2.0 * state + batch


def attention_kernel_flops(config: dict, traffic: dict) -> dict:
    """FLOPs of one run of each kernel of the program's resident fused
    attention pair (``ops/pallas/fused_attention.py``: one run a block and a
    pass), by the kernel's name, from the cell's shapes. Forward: K Q^T and
    V E, two (T, T, d) matmuls a head, 4 b T^2 D. Backward: the scores once
    more, dP, dQ, dK and dV, five such matmuls: 2.5 times the forward.
    Softmax, scaling and masking count nothing."""
    forward = 4.0 * traffic["batch"] * traffic["seq_len"] ** 2 * config["hidden_size"]
    return {"fused_attention_fwd": forward, "fused_attention_bwd": 2.5 * forward}


def attention_kernel_bytes(config: dict, traffic: dict) -> dict:
    """Least HBM bytes of one run of each kernel: every (b, T, D) operand
    read once and every result written once in the compute type (forward q,
    k, v in and o out; backward q, k, v, o, dO in and dq, dk, dv out), and
    the key mask once as a float32 (b, T) column. No (T, T) tensor leaves
    the chip's fast memory."""
    b, t = traffic["batch"], traffic["seq_len"]
    operand = b * t * config["hidden_size"] * jnp.dtype(config["precision"]["compute"]).itemsize
    mask = 4.0 * b * t
    return {"fused_attention_fwd": 4.0 * operand + mask, "fused_attention_bwd": 8.0 * operand + mask}


def _layer_norm(x, gamma, beta, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * gamma + beta


def _block(x, p, key_mask, heads, eps, mm):
    b, t, d = x.shape
    a = p["attn"]

    def split(y):
        return y.reshape(b, t, heads, d // heads).transpose(0, 2, 1, 3)

    q, k, v = (split(mm(x, a[f"W_{n}"]) + a[f"b_{n}"]) for n in "qkv")
    scores = mm(q, k.transpose(0, 1, 3, 2)) / math.sqrt(d // heads)
    scores = jnp.where(key_mask[:, None, None, :], scores, -1e9)
    ctx = mm(jax.nn.softmax(scores, -1), v).transpose(0, 2, 1, 3).reshape(b, t, d)
    x = _layer_norm(x + mm(ctx, a["W_o"]) + a["b_o"], p["ln1_gamma"], p["ln1_beta"], eps)
    h = jax.nn.gelu(mm(x, p["W_ff1"]) + p["b_ff1"], approximate=False)
    return _layer_norm(x + mm(h, p["W_ff2"]) + p["b_ff2"], p["ln2_gamma"], p["ln2_beta"], eps)


def reference_loss(config: dict):
    """``loss_fn(params, state, batch, mm, conv)``: the published forward
    pass and the mean cross-entropy over the batch, float32. Encoder blocks
    run under ``jax.checkpoint`` in a scan so that the backward pass at the
    timed size fits beside nothing else on the chip."""
    n, heads, eps = config["num_hidden_layers"], config["num_attention_heads"], config["layer_norm_eps"]

    def loss_fn(params, state, batch, mm, conv):
        ids, labels, mask = batch
        e = params["layer_0"]
        x = e["tok"][ids] + e["pos"][None, :ids.shape[1]] + e["seg"][0]
        x = _layer_norm(x, e["ln_gamma"], e["ln_beta"], eps)
        key_mask = mask > 0
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *[params[f"layer_{i}"] for i in range(1, n + 1)])
        body = jax.checkpoint(lambda x_, p: (_block(x_, p, key_mask, heads, eps, mm), None))
        x, _ = jax.lax.scan(body, x, stacked)
        pool, out = params[f"layer_{n + 2}"], params[f"layer_{n + 3}"]
        pooled = jnp.tanh(mm(x[:, 0], pool["W"]) + pool["b"])
        logp = jax.nn.log_softmax(mm(pooled, out["W"]) + out["b"], -1)
        return -jnp.mean(jnp.sum(labels * logp, -1)), state

    return loss_fn
