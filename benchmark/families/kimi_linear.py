"""Kimi Linear as a causal language model (Kimi Team 2025, arXiv:2510.26692;
the published implementation is ``fla``'s ``KimiDeltaAttention`` and the
model's ``modeling_kimi.py``): pre-norm blocks whose mixer is Kimi Delta
Attention (KDA) or latent attention without rotary (MLA, NoPE), one leading
dense SwiGLU layer, then sigmoid-routed experts with a shared expert.

``build`` hands the configuration to the program's zoo model; the rest is
the benchmark's own: weights in the program's layout, batches, FLOPs and
bytes, and the plain reference, which follows these equations::

    block:  h = x + Mixer(RMSNorm(x)),  y = h + MLP(RMSNorm(h))
    KDA:    q~, k~, v = SiLU(conv4(x W)) (causal, depthwise); q = q~/|q~| d^-1/2, k = k~/|k~| per head
            g_t = -exp(A_log) softplus(x W_fa W_fb + dt_bias) per channel, beta_t = sigmoid(x W_b) per head
            S' = diag(exp(g_t)) S_(t-1);  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T;  o_t = S_t^T q_t
            out = W_o [RMSNorm_head(o_t) * sigmoid(x W_ga W_gb + b_g)]
    MLA:    [q_n | q_r] = x W_q per head; [c | k_r] = x W_kva; [k_n | v] = RMSNorm(c) W_kvb per head;
            k = [k_n | k_r] (k_r shared by the heads); softmax(q k^T (d_n + d_r)^-1/2 + causal) v -> W_o
    MoE:    s = sigmoid(x W_r); sel = top8(s + bias); w = s[sel] / (sum s[sel] + 1e-20) * scale
            y = sum over e in sel and held: w_e SwiGLU_e(x)  +  SwiGLU_shared(x)

Departures from the published implementation, in program and reference
alike: the L2 norm adds 1e-6 under the root (``fla``'s ``l2norm``); this
chip holds experts ``held_experts`` of ``router_width`` and what the others
would add is left out; ids, logits and the loss are over the vocabulary's
slice; the selection bias is fixed at 0.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

FLASH_KERNELS = {  # (T x T matmuls against the q.k head, against the v head) of one run, by the kernel's name
    "flash_attention_fwd": (1, 1), "flash_attention_bwd_dq": (2, 1), "flash_attention_bwd_dkv": (2, 2),
    "flash_attention_bwd_dq_chunked": (2, 1), "flash_attention_bwd_dkv_chunked": (2, 2)}


def _sizes(config: dict) -> dict:
    lin = config["linear_attn_config"]
    return dict(d=config["hidden_size"], vocab=config["vocab_size"], n=config["num_hidden_layers"],
                h=config["num_attention_heads"], kh=lin["num_heads"], kd=lin["head_dim"],
                conv=lin["short_conv_kernel_size"], rank=config["kda_gate_rank"],
                kv=config["kv_lora_rank"], dn=config["qk_nope_head_dim"], dr=config["qk_rope_head_dim"],
                dv=config["v_head_dim"], dense=config["intermediate_size"], expert=config["moe_intermediate_size"],
                held=tuple(config["held_experts"]), router=config["router_width"],
                top_k=config["num_experts_per_token"], shared=config["num_shared_experts"],
                kda_layers=set(lin["kda_layers"]), first_dense=config["first_k_dense_replace"])


def build(config: dict, seed: int):
    from deeplearning4j_tpu.runtime.environment import get_environment
    from deeplearning4j_tpu.train.updaters import Adam
    from deeplearning4j_tpu.zoo.kimi_linear import KimiLinear
    s, opt, lin = _sizes(config), config["optimizer"], config["linear_attn_config"]
    get_environment().set_remat(config["recompute"]["set_remat"])  # the documented switch; read when the step is traced
    return KimiLinear(
        vocab_size=s["vocab"], d_model=s["d"], n_layers=s["n"], kda_layers=lin["kda_layers"],
        full_attn_layers=lin["full_attn_layers"], n_heads=s["h"], kda_head_dim=s["kd"], conv_size=s["conv"],
        kda_gate_rank=s["rank"], kv_rank=s["kv"], qk_nope_dim=s["dn"], qk_shared_dim=s["dr"], v_dim=s["dv"],
        dense_size=s["dense"], first_k_dense=s["first_dense"], expert_size=s["expert"], n_experts=s["router"],
        held_experts=s["held"], held_rows=config["held_rows"], top_k=s["top_k"], n_shared=s["shared"],
        routed_scale=config["routed_scaling_factor"], eps=config["rms_norm_eps"],
        seed=seed % (2 ** 31),
        updater=Adam(opt["lr"], beta1=opt["b1"], beta2=opt["b2"], epsilon=opt["eps"])).init()


def init_params(config: dict, seed: int):
    """(params, model_state) in float32 on the device, one jitted call.
    Matrices and convolution taps are N(0, initializer_range); norms 1;
    ``A_log`` = log U(1, 16); ``dt_bias`` = softplus^-1 of U(0.001, 0.1); the
    gate's bias, the selection bias and the counters 0."""
    s, std = _sizes(config), config["initializer_range"]
    d, held = s["d"], s["held"][1]

    def make(key):
        count = [0]

        def w(*shape):
            count[0] += 1
            return std * jax.random.normal(jax.random.fold_in(key, count[0]), shape, jnp.float32)

        def uniform(shape, lo, hi):
            count[0] += 1
            return jax.random.uniform(jax.random.fold_in(key, count[0]), shape, jnp.float32, lo, hi)

        ones = lambda k: jnp.ones((k,), jnp.float32)

        def kda():
            inner = s["kh"] * s["kd"]
            dt = uniform((inner,), 0.001, 0.1)
            return {"W_q": w(d, inner), "W_k": w(d, inner), "W_v": w(d, inner), "W_o": w(inner, d),
                    "W_fa": w(d, s["rank"]), "W_fb": w(s["rank"], inner),
                    "W_ga": w(d, s["rank"]), "W_gb": w(s["rank"], inner), "b_g": jnp.zeros((inner,), jnp.float32),
                    "W_b": w(d, s["kh"]), "conv_q": w(s["conv"], inner), "conv_k": w(s["conv"], inner),
                    "conv_v": w(s["conv"], inner), "A_log": jnp.log(uniform((s["kh"],), 1.0, 16.0)),
                    "dt_bias": dt + jnp.log(-jnp.expm1(-dt)), "norm_w": ones(s["kd"])}

        def mla():
            return {"W_q": w(d, s["h"] * (s["dn"] + s["dr"])), "W_kva": w(d, s["kv"] + s["dr"]),
                    "kv_norm": ones(s["kv"]), "W_kvb": w(s["kv"], s["h"] * (s["dn"] + s["dv"])),
                    "W_o": w(s["h"] * s["dv"], d)}

        def swiglu(f):
            return {"W_g": w(d, f), "W_u": w(d, f), "W_d": w(f, d)}

        def moe():
            f = s["expert"]
            return {"W_router": w(d, s["router"]), "W_e1": w(held, d, f), "W_e3": w(held, d, f),
                    "W_e2": w(held, f, d), "shared": swiglu(s["shared"] * f)}

        params = {"layer_0": {"W": w(s["vocab"], d)}}
        state = {}
        for i in range(1, s["n"] + 1):
            dense = i <= s["first_dense"]
            params[f"layer_{i}"] = {"norm1": ones(d), "mixer": kda() if i in s["kda_layers"] else mla(),
                                    "norm2": ones(d), "mlp": swiglu(s["dense"]) if dense else moe()}
            if not dense:
                state[f"layer_{i}"] = {"mlp": {"assigned": jnp.zeros((held,), jnp.float32),
                                               "overflow": jnp.zeros((), jnp.float32),
                                               "select_bias": jnp.zeros((s["router"],), jnp.float32)}}
        params[f"layer_{s['n'] + 1}"] = {"w": ones(d)}
        params[f"layer_{s['n'] + 2}"] = {"W": w(d, s["vocab"])}
        return params, state

    return jax.jit(make)(jax.random.fold_in(jax.random.PRNGKey(0), seed % (2 ** 32)))


def batches(config: dict, traffic: dict, seed: int):
    """``count`` host batches of (ids, next ids, no mask): ``seq_len + 1``
    ids a row from the vocabulary's slice, every position trained."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(traffic["count"]):
        ids = rng.integers(0, config["vocab_size"], (traffic["batch"], traffic["seq_len"] + 1), dtype=np.int32)
        out.append((np.ascontiguousarray(ids[:, :-1]), np.ascontiguousarray(ids[:, 1:]), None))
    return out


def samples_per_step(traffic: dict) -> int:
    return traffic["batch"]


def _mixer_params(s: dict, kda: bool) -> int:
    d = s["d"]
    if kda:
        inner = s["kh"] * s["kd"]
        return (4 * d * inner + 2 * (d * s["rank"] + s["rank"] * inner) + inner + d * s["kh"]
                + 3 * s["conv"] * inner + s["kh"] + inner + s["kd"])
    return (d * s["h"] * (s["dn"] + s["dr"]) + d * (s["kv"] + s["dr"]) + s["kv"]
            + s["kv"] * s["h"] * (s["dn"] + s["dv"]) + s["h"] * s["dv"] * d)


def n_params(config: dict) -> int:
    s = _sizes(config)
    d = s["d"]
    total = 2 * s["vocab"] * d + d
    for i in range(1, s["n"] + 1):
        total += 2 * d + _mixer_params(s, i in s["kda_layers"])
        if i <= s["first_dense"]:
            total += 3 * d * s["dense"]
        else:
            total += d * s["router"] + 3 * d * s["expert"] * (s["held"][1] + s["shared"])
    return total


def flops_per_step(config: dict, traffic: dict) -> float:
    """Forward + backward = 3 x the forward's FLOPs from the shapes alone (2
    per multiply-add; nothing recomputed): every weight matmul at 2 x in x
    out a token; KDA's recurrence at 6 d_k d_v a token and head (``S'^T k``,
    the rank-1 update, ``S^T q``); causal attention at the lower half of T x
    T against a q.k head of d_n + d_r and a v head of d_v; the routed
    experts at the expected top_k x held / router_width assignments a token.
    The gather, the convolution taps, norms, gates, softmax, routing and
    Adam count nothing."""
    s = _sizes(config)
    d, t = s["d"], traffic["seq_len"]
    inner = s["kh"] * s["kd"]
    kda = (2 * (4 * d * inner + 2 * (d * s["rank"] + s["rank"] * inner) + d * s["kh"])
           + 6 * s["kd"] * s["kd"] * s["kh"])
    mla = (2 * (d * s["h"] * (s["dn"] + s["dr"]) + d * (s["kv"] + s["dr"])
                + s["kv"] * s["h"] * (s["dn"] + s["dv"]) + s["h"] * s["dv"] * d)
           + 2 * (s["dn"] + s["dr"] + s["dv"]) * s["h"] * t / 2)
    expert = 2 * 3 * d * s["expert"]
    moe = 2 * d * s["router"] + expert * (s["shared"] + s["top_k"] * s["held"][1] / s["router"])
    per_token = 2 * d * s["vocab"]
    for i in range(1, s["n"] + 1):
        per_token += kda if i in s["kda_layers"] else mla
        per_token += 2 * 3 * d * s["dense"] if i <= s["first_dense"] else moe
    return 3.0 * per_token * traffic["batch"] * t


def least_bytes_per_step(config: dict, traffic: dict) -> float:
    """Train state read once and written once (float32 parameters and two
    Adam moments) plus the batch in (ids and next ids, int32)."""
    return 2.0 * 3 * 4 * n_params(config) + 2 * 4 * traffic["batch"] * traffic["seq_len"]


def flash_kernel_flops(config: dict, traffic: dict) -> dict:
    """FLOPs of one run of each flash-attention kernel (``ops/pallas/
    flash_attention.py``; one run covers every head), by the kernel's name:
    the causal half of T x T, a q.k head of d_n + d_r, the v head unpadded.
    Forward q k^T and p v; dq pass scores, dp = do v^T, dq = ds k; dk/dv
    pass scores, dv = p^T do, dp, dk = ds^T q. The blocks on the diagonal
    that the kernels compute in full, softmax and masking count nothing."""
    s = _sizes(config)
    pairs = traffic["batch"] * s["h"] * traffic["seq_len"] ** 2 / 2
    return {name: 2.0 * pairs * (qk * (s["dn"] + s["dr"]) + v * s["dv"]) for name, (qk, v) in FLASH_KERNELS.items()}


def flash_kernel_bytes(config: dict, traffic: dict) -> dict:
    """Least HBM bytes of one run: q, k, v (and in the backward o's
    cotangent) read once and each result written once in the compute type,
    the float32 row statistics as the kernels lay them out (8 lanes a row)."""
    s = _sizes(config)
    rows = traffic["batch"] * s["h"] * traffic["seq_len"]
    item = jnp.dtype(config["precision"]["compute"]).itemsize
    qk, v, stat = rows * (s["dn"] + s["dr"]) * item, rows * s["dv"] * item, rows * 8 * 4
    back = 2 * qk + 2 * v + 2 * stat
    return {"flash_attention_fwd": 2 * qk + 2 * v + stat,
            "flash_attention_bwd_dq": back + qk, "flash_attention_bwd_dq_chunked": back + qk,
            "flash_attention_bwd_dkv": back + qk + v, "flash_attention_bwd_dkv_chunked": back + qk + v}


# ------------------------------------------------------------ the reference

CHUNK = 64        # tokens between the states the recurrence keeps for its backward pass
QUERY_BLOCK = 256  # queries whose scores exist at once


def _rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


def _swiglu(x, p, mm):
    return mm(jax.nn.silu(mm(x, p["W_g"])) * mm(x, p["W_u"]), p["W_d"])


def _conv(x, w):
    t = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (w.shape[0] - 1, 0), (0, 0)))
    return sum(padded[:, i:i + t] * w[i] for i in range(w.shape[0]))


def delta_rule(q, k, v, g, beta):
    """The recurrence token by token: (b, t, h, d) inputs, ``g`` the
    log-decay per channel, ``beta`` (b, t, h). Elementwise products and sums
    only, so it is float32 on any device. Two levels of ``lax.scan``: the
    outer one keeps the state every ``CHUNK`` tokens, the inner one is
    recomputed in the backward pass."""
    b, t, h, dk = q.shape
    chunk = CHUNK if t % CHUNK == 0 else 1

    def token(S, x):
        q_t, k_t, v_t, g_t, beta_t = x                       # (b, h, d) and (b, h)
        S = jnp.exp(g_t)[..., None] * S
        err = v_t - jnp.sum(S * k_t[..., None], -2)
        S = S + (beta_t[..., None] * k_t)[..., None] * err[..., None, :]
        return S, jnp.sum(S * q_t[..., None], -2)

    def steps(a):  # (b, t, ...) -> (t / chunk, chunk, b, ...)
        return jnp.moveaxis(a, 1, 0).reshape(t // chunk, chunk, *a.shape[:1], *a.shape[2:])

    inner = jax.checkpoint(lambda S, xs: jax.lax.scan(token, S, xs))
    S0 = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(inner, S0, tuple(steps(a) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape(t, b, h, -1), 0, 1)


def _kda(x, p, s, eps, mm):
    b, t, _ = x.shape
    heads = lambda a: a.reshape(b, t, s["kh"], s["kd"])
    q, k, v = (heads(jax.nn.silu(_conv(mm(x, p[f"W_{n}"]), p[f"conv_{n}"]))) for n in "qkv")
    unit = lambda a: a / jnp.sqrt(jnp.sum(jnp.square(a), -1, keepdims=True) + 1e-6)
    q, k = unit(q) * s["kd"] ** -0.5, unit(k)
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
        heads(mm(mm(x, p["W_fa"]), p["W_fb"])) + p["dt_bias"].reshape(s["kh"], s["kd"]))
    beta = jax.nn.sigmoid(mm(x, p["W_b"]))
    o = delta_rule(q, k, v, g, beta)
    gate = jax.nn.sigmoid(heads(mm(mm(x, p["W_ga"]), p["W_gb"]) + p["b_g"]))
    return mm((_rms_norm(o, p["norm_w"], eps) * gate).reshape(b, t, -1), p["W_o"])


def _mla(x, p, s, eps, mm):
    b, t, _ = x.shape
    h, dn, dr, dv = s["h"], s["dn"], s["dr"], s["dv"]
    q = mm(x, p["W_q"]).reshape(b, t, h, dn + dr).transpose(0, 2, 1, 3)
    latent = mm(x, p["W_kva"])
    kv = mm(_rms_norm(latent[..., :s["kv"]], p["kv_norm"], eps), p["W_kvb"]).reshape(b, t, h, dn + dv)
    shared = jnp.broadcast_to(latent[:, :, None, s["kv"]:], (b, t, h, dr))
    k_t = jnp.concatenate([kv[..., :dn], shared], -1).transpose(0, 2, 3, 1)   # (b, h, d, t)
    v = kv[..., dn:].transpose(0, 2, 1, 3)
    block = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t

    @jax.checkpoint
    def queries(args):  # one block of queries against every key: the scores of all of T x T never exist
        q_blk, first = args
        scores = mm(q_blk, k_t) * (dn + dr) ** -0.5
        rows = first + jnp.arange(block)[:, None]
        scores = jnp.where(jnp.arange(t)[None, :] <= rows, scores, -jnp.inf)
        return mm(jax.nn.softmax(scores, -1), v)

    q_blocks = jnp.moveaxis(q.reshape(b, h, t // block, block, dn + dr), 2, 0)
    ctx = jax.lax.map(queries, (q_blocks, jnp.arange(0, t, block)))           # (blocks, b, h, block, dv)
    ctx = jnp.moveaxis(ctx, 0, 2).reshape(b, h, t, dv).transpose(0, 2, 1, 3).reshape(b, t, h * dv)
    return mm(ctx, p["W_o"])


def _moe(x, p, state, s, config, mm):
    """Every assignment to a held expert is computed, none dropped: each held
    expert runs on all tokens and is weighted by its gate (0 where the token
    did not choose it). Returns (y, the layer's new state)."""
    first, held = s["held"]
    tokens = x.reshape(-1, x.shape[-1])
    scores = jax.nn.sigmoid(mm(tokens, p["W_router"]))
    _, chosen = jax.lax.top_k(scores + state["select_bias"], s["top_k"])
    gates = jnp.take_along_axis(scores, chosen, -1)
    gates = gates / (jnp.sum(gates, -1, keepdims=True) + 1e-20) * config["routed_scaling_factor"]
    # (held, N): each held expert's gate for each token, 0 where the token did not choose it
    mine = chosen[None] == first + jnp.arange(held)[:, None, None]
    weight = jnp.sum(jnp.where(mine, gates[None], 0.0), -1)
    experts = {"W_g": p["W_e1"], "W_u": p["W_e3"], "W_d": p["W_e2"]}          # a leading expert axis: batched matmuls
    y = _swiglu(tokens, p["shared"], mm) + jnp.sum(weight[..., None] * _swiglu(tokens, experts, mm), 0)
    new_state = dict(state, assigned=jnp.sum(mine, (1, 2)).astype(jnp.float32), overflow=jnp.zeros((), jnp.float32))
    return y.reshape(x.shape), new_state


def reference_loss(config: dict):
    """``loss_fn(params, state, batch, mm, conv)``: the forward pass above
    and the mean cross-entropy of the next token over all positions, float32.
    A Python loop over ``jax.checkpoint``ed blocks (PERF.md section 2: a
    stacked scan would cost two more trees of the blocks)."""
    s, eps = _sizes(config), config["rms_norm_eps"]

    def block(x, p, st, i, mm):
        mixer = _kda if i in s["kda_layers"] else _mla
        x = x + mixer(_rms_norm(x, p["norm1"], eps), p["mixer"], s, eps, mm)
        normed = _rms_norm(x, p["norm2"], eps)
        if i <= s["first_dense"]:
            return x + _swiglu(normed, p["mlp"], mm), st
        y, mlp_state = _moe(normed, p["mlp"], st["mlp"], s, config, mm)
        return x + y, {"mlp": mlp_state}

    def loss_fn(params, state, batch, mm, conv):
        ids, labels, _ = batch
        x = params["layer_0"]["W"][ids]
        new_state = {}
        for i in range(1, s["n"] + 1):
            x, st = jax.checkpoint(functools.partial(block, i=i, mm=mm))(
                x, params[f"layer_{i}"], state.get(f"layer_{i}", {}))
            if st:
                new_state[f"layer_{i}"] = st
        x = _rms_norm(x, params[f"layer_{s['n'] + 1}"]["w"], eps)
        logp = jax.nn.log_softmax(mm(x, params[f"layer_{s['n'] + 2}"]["W"]), -1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1)), new_state

    return loss_fn
