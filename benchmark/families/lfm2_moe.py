"""LFM2-24B-A2B (``lfm2_moe``; LiquidAI/LFM2-24B-A2B ``config.json``) as a
causal language model: pre-norm blocks whose operator is, by the published
``layer_types``, a gated short convolution or grouped-query attention; a
dense SwiGLU in the ``num_dense_layers`` leading layers and sigmoid-routed
experts with a selection bias in the others; a final norm and a head tied
to the embedding.

``build`` hands the configuration to the program's zoo model; the rest is
the benchmark's own: weights in the program's layout (the tied table once),
batches, FLOPs and bytes, and the plain reference, which follows these
equations (every size from the configuration)::

    block:  h = x + op(RMSNorm(x));  y = h + FFN(RMSNorm(h))         FFN: dense SwiGLU (layer < num_dense_layers), else MoE
    conv:   [B | C | u] = x W_in  (d -> 3d, no bias, split in this order)
            z_t = sum over j = 0 .. L-1 of w[L-1-j] * (B u)_(t-j)   (depthwise, L = conv_L_cache, zeros before t = 0)
            out = (C z) W_out  (no bias, no activation)
    attn:   q = RoPE(RMSNorm_head(x W_q));  k = RoPE(RMSNorm_head(x W_k));  v = x W_v   (heads of hd = d / heads)
            query head i reads key/value head i // (heads / kv heads)
            o = softmax(q k^T hd^-1/2 + causal) v;  out = o W_o
    MoE:    s = sigmoid(x W_r) over all router_width experts;  sel = top_k(s + bias)
            w = s[sel] / (sum s[sel] + 1e-6) * routed_scaling_factor
            y = sum over e in sel and held: w_e SwiGLU_e(x)
            after the step: bias_e += expert_bias_rate * sign(mean load - load_e)   (load_e: tokens that chose e)
    head:   logits_i = RMSNorm_f(h^L_i) E^T  (E the embedding's table);  L = mean CE(logits_i, t_(i+1))

Features are ``ids[:, :-1]`` and labels ``ids[:, 1:]``.

Departures from the published model, in program and reference alike (the
configuration file lists them under ``assumed``): this chip holds experts
``held_experts`` of ``router_width`` and what the others would add is left
out; ids, logits and the loss are over the vocabulary's slice; the selection
bias is model state, starts at 0 and moves outside the gradient by the sign
rule above (DeepSeek-V3's auxiliary-loss-free balancing, at its rate: the
family names the bias but publishes no rule or rate), with the load counted
over this chip's tokens; the seeded weights are N(0, initializer_range), the
convolution's taps too. The program keeps the chosen scores' sum off zero
where the family adds 1e-6 to it: with four scores of ~0.5 the two agree to
float32 rounding.
The head is tied to the embedding by the family's ``tie_embedding``, which
this row's ``config.json`` does not state. Rotary pairs channel ``i`` with
``i + hd/2`` (the published ``rope_type`` "default").
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

FLASH_KERNELS = {  # (T x T matmuls against the q.k head, against the v head) of one run, by the kernel's name
    "flash_attention_fwd": (1, 1), "flash_attention_bwd_dq": (2, 1), "flash_attention_bwd_dkv": (2, 2),
    "flash_attention_bwd_dq_chunked": (2, 1), "flash_attention_bwd_dkv_chunked": (2, 2)}


def _sizes(config: dict) -> dict:
    here = sorted(config["layers_here"])
    h = config["num_attention_heads"]
    return dict(d=config["hidden_size"], vocab=config["vocab_size"], h=h, kv=config["num_key_value_heads"],
                hd=config["hidden_size"] // h, theta=float(config["rope_parameters"]["rope_theta"]),
                conv=config["conv_L_cache"], dense=config["intermediate_size"],
                expert=config["moe_intermediate_size"], held=tuple(config["held_experts"]),
                router=config["router_width"], top_k=config["num_experts_per_tok"],
                scale=float(config["routed_scaling_factor"]), bias_rate=float(config["expert_bias_rate"]),
                eps=config["norm_eps"],
                # the blocks that are here: (operator, whether the FFN is dense)
                blocks=[(config["layer_types"][i], i < config["num_dense_layers"]) for i in here])


def build(config: dict, seed: int):
    from deeplearning4j_tpu.runtime.environment import get_environment
    from deeplearning4j_tpu.train.updaters import Adam
    from deeplearning4j_tpu.zoo.lfm2_moe import Lfm2Moe
    s, opt = _sizes(config), config["optimizer"]
    if config["conv_bias"] or not config["tie_embedding"] or not config["use_expert_bias"]:
        raise ValueError("the family's model has no convolution bias, ties its head and has a selection bias")
    get_environment().set_remat(config["recompute"]["set_remat"])  # the documented switch; read when the step is traced
    return Lfm2Moe(
        vocab_size=s["vocab"], d_model=s["d"], layer_types=config["layer_types"], layers_here=config["layers_here"],
        n_heads=s["h"], n_kv_heads=s["kv"], rope_theta=s["theta"], conv_size=s["conv"], dense_size=s["dense"],
        n_dense=config["num_dense_layers"], expert_size=s["expert"], n_experts=s["router"], held_experts=s["held"],
        held_rows=config["held_rows"], top_k=s["top_k"], routed_scale=s["scale"], bias_rate=s["bias_rate"],
        eps=s["eps"],
        seed=seed % (2 ** 31),
        updater=Adam(opt["lr"], beta1=opt["b1"], beta2=opt["b2"], epsilon=opt["eps"])).init()


def _keys(s: dict) -> dict:
    """The program's layer keys: embedding, the blocks, the final norm (the
    tied head owns no parameter)."""
    n = len(s["blocks"])
    return dict(embed="layer_0", blocks=[f"layer_{i}" for i in range(1, n + 1)], norm=f"layer_{n + 1}")


def init_params(config: dict, seed: int):
    """(params, model_state) in float32 on the device, one jitted call, in
    the program's layout: the embedding's table is one leaf, which the head
    reads too. Matrices and the convolution's taps are N(0,
    initializer_range); norms 1; the selection bias and the counters 0."""
    s, std = _sizes(config), config["initializer_range"]
    d, held, keys = s["d"], s["held"][1], _keys(s)

    def make(key):
        count = [0]

        def w(*shape):
            count[0] += 1
            return std * jax.random.normal(jax.random.fold_in(key, count[0]), shape, jnp.float32)

        ones = lambda k: jnp.ones((k,), jnp.float32)

        def operator(kind):
            if kind == "conv":
                return {"W_in": w(d, 3 * d), "conv": w(s["conv"], d), "W_out": w(d, d)}
            return {"W_q": w(d, s["h"] * s["hd"]), "W_k": w(d, s["kv"] * s["hd"]), "W_v": w(d, s["kv"] * s["hd"]),
                    "W_o": w(s["h"] * s["hd"], d), "q_norm": ones(s["hd"]), "k_norm": ones(s["hd"])}

        def ffn(dense):
            if dense:
                return {"W_g": w(d, s["dense"]), "W_u": w(d, s["dense"]), "W_d": w(s["dense"], d)}
            return {"W_router": w(d, s["router"]), "W_e1": w(held, d, s["expert"]), "W_e3": w(held, d, s["expert"]),
                    "W_e2": w(held, s["expert"], d)}

        params, state = {keys["embed"]: {"W": w(s["vocab"], d)}}, {}
        for key_, (kind, dense) in zip(keys["blocks"], s["blocks"]):
            params[key_] = {"norm1": ones(d), "mixer": operator(kind), "norm2": ones(d), "mlp": ffn(dense)}
            if not dense:
                state[key_] = {"mlp": {"assigned": jnp.zeros((held,), jnp.float32), "overflow": jnp.zeros((), jnp.float32),
                                       "select_bias": jnp.zeros((s["router"],), jnp.float32)}}
        params[keys["norm"]] = {"w": ones(d)}
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


def _operator_matrices(s: dict, kind: str) -> int:
    """Weights of an operator's projections (the per-head norms and the taps
    are not matrices)."""
    d = s["d"]
    return 4 * d * d if kind == "conv" else 2 * d * s["hd"] * (s["h"] + s["kv"])


def _ffn_matrices(s: dict, dense: bool) -> int:
    d = s["d"]
    return 3 * d * s["dense"] if dense else d * s["router"] + 3 * d * s["expert"] * s["held"][1]


def n_params(config: dict) -> int:
    """Every leaf once: the table counts once though the head reads it too."""
    s = _sizes(config)
    d = s["d"]
    own = {"conv": s["conv"] * d, "full_attention": 2 * s["hd"]}  # the taps; the per-head norms
    blocks = sum(2 * d + _operator_matrices(s, kind) + own[kind] + _ffn_matrices(s, dense) for kind, dense in s["blocks"])
    return s["vocab"] * d + blocks + d


def flops_per_step(config: dict, traffic: dict) -> float:
    """Forward + backward = 3 x the forward's FLOPs from the shapes alone (2
    per multiply-add; nothing recomputed): every weight matmul at 2 x in x
    out a token; causal attention at the lower half of T x T, q.k and p.v
    over every query head at the head size; the routed experts at the
    expected top_k x held / router_width assignments a token; the tied head
    over T positions. The convolution's taps and gates, the gathers, rotary,
    norms, softmax, routing and Adam count nothing."""
    s = _sizes(config)
    t = traffic["seq_len"]
    attention = 2 * 2 * s["hd"] * s["h"] * t / 2
    per_token = 2 * s["d"] * s["vocab"]
    for kind, dense in s["blocks"]:
        per_token += 2 * _operator_matrices(s, kind) + (attention if kind == "full_attention" else 0)
        per_token += 2 * (3 * s["d"] * s["dense"] if dense else
                          s["d"] * s["router"] + 3 * s["d"] * s["expert"] * s["top_k"] * s["held"][1] / s["router"])
    return 3.0 * per_token * traffic["batch"] * t


def least_bytes_per_step(config: dict, traffic: dict) -> float:
    """Train state read once and written once (float32 parameters and two
    Adam moments) plus the batch in (ids and next ids, int32)."""
    return 2.0 * 3 * 4 * n_params(config) + 2 * 4 * traffic["batch"] * traffic["seq_len"]


def flash_kernel_flops(config: dict, traffic: dict) -> dict:
    """FLOPs of one run of each flash-attention kernel (``ops/pallas/
    flash_attention.py``; one run covers every head of one attention layer),
    by the kernel's name: the causal half of T x T, every query head, heads
    of hd. Forward q k^T and p v; dq pass scores, dp = do v^T, dq = ds k;
    dk/dv pass scores, dv = p^T do, dp, dk = ds^T q. The blocks on the
    diagonal that the kernels compute in full, softmax and masking count
    nothing."""
    s = _sizes(config)
    pairs = traffic["batch"] * s["h"] * traffic["seq_len"] ** 2 / 2
    return {name: 2.0 * pairs * (qk + v) * s["hd"] for name, (qk, v) in FLASH_KERNELS.items()}


def flash_kernel_bytes(config: dict, traffic: dict) -> dict:
    """Least HBM bytes of one run: q (and in the backward o's cotangent)
    read and each result written once a query head, k and v read (dk and dv
    written) once a key/value head, in the compute type; the float32 row
    statistics as the kernels lay them out (8 lanes a row)."""
    s = _sizes(config)
    item = jnp.dtype(config["precision"]["compute"]).itemsize
    rows = traffic["batch"] * traffic["seq_len"]
    q, kv, stat = s["h"] * rows * s["hd"] * item, s["kv"] * rows * s["hd"] * item, s["h"] * rows * 8 * 4
    dq, dkv = 3 * q + 2 * kv + 2 * stat, 2 * q + 4 * kv + 2 * stat
    return {"flash_attention_fwd": 2 * q + 2 * kv + stat,
            "flash_attention_bwd_dq": dq, "flash_attention_bwd_dq_chunked": dq,
            "flash_attention_bwd_dkv": dkv, "flash_attention_bwd_dkv_chunked": dkv}


# ------------------------------------------------------------ the reference

QUERY_BLOCK = 256  # queries whose scores exist at once


def _rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


def _swiglu(x, p, mm):
    return mm(jax.nn.silu(mm(x, p["W_g"])) * mm(x, p["W_u"]), p["W_d"])


def _rope(x, theta):
    """``x`` (b, t, heads, d) turned by its position on axis 1: ``x cos + rotate_half(x) sin`` with
    the d/2 frequencies ``theta^(-2j/d)`` repeated over both halves."""
    t, d = x.shape[1], x.shape[-1]
    inverse = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inverse[None, :]
    angles = jnp.concatenate([angles, angles], -1)[None, :, None, :]
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * jnp.cos(angles) + turned * jnp.sin(angles)


def _delayed(x, j):
    """``x`` (b, t, c) moved ``j`` positions later along time, zeros in front."""
    return x if j == 0 else jnp.concatenate([jnp.zeros_like(x[:, :j]), x[:, :-j]], 1)


def _short_conv(x, p, mm):
    d, taps = x.shape[-1], p["conv"]
    bcx = mm(x, p["W_in"])
    b, c, u = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
    gated = b * u
    z = sum(taps[taps.shape[0] - 1 - j] * _delayed(gated, j) for j in range(taps.shape[0]))
    return mm(c * z, p["W_out"])


def _attention(x, p, s, mm):
    b, t, _ = x.shape
    h, kv, hd, eps = s["h"], s["kv"], s["hd"], s["eps"]
    q = _rope(_rms_norm(mm(x, p["W_q"]).reshape(b, t, h, hd), p["q_norm"], eps), s["theta"]).transpose(0, 2, 1, 3)
    k = _rope(_rms_norm(mm(x, p["W_k"]).reshape(b, t, kv, hd), p["k_norm"], eps), s["theta"])
    v = mm(x, p["W_v"]).reshape(b, t, kv, hd)
    # query head i reads key/value head i // group
    k_t = jnp.repeat(k, h // kv, axis=2).transpose(0, 2, 3, 1)     # (b, h, hd, t)
    v = jnp.repeat(v, h // kv, axis=2).transpose(0, 2, 1, 3)       # (b, h, t, hd)
    block = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t

    @jax.checkpoint
    def queries(args):  # one block of queries against every key: the scores of all of T x T never exist
        q_blk, first = args
        scores = mm(q_blk, k_t) * hd ** -0.5
        rows = first + jnp.arange(block)[:, None]
        return mm(jax.nn.softmax(jnp.where(jnp.arange(t)[None, :] <= rows, scores, -jnp.inf), -1), v)

    q_blocks = jnp.moveaxis(q.reshape(b, h, t // block, block, hd), 2, 0)
    ctx = jax.lax.map(queries, (q_blocks, jnp.arange(0, t, block)))             # (blocks, b, h, block, hd)
    ctx = jnp.moveaxis(ctx, 0, 2).reshape(b, h, t, hd).transpose(0, 2, 1, 3).reshape(b, t, h * hd)
    return mm(ctx, p["W_o"])


def _moe(x, p, state, s, mm):
    """Every assignment to a held expert is computed, none dropped: each held
    expert runs on all tokens and is weighted by its gate (0 where the token
    did not choose it). Returns (y, the layer's new state: the counters and
    the selection bias moved against every expert's load)."""
    first, held = s["held"]
    tokens = x.reshape(-1, x.shape[-1])
    scores = jax.nn.sigmoid(mm(tokens, p["W_router"]))
    _, chosen = jax.lax.top_k(scores + state["select_bias"], s["top_k"])
    gates = jnp.take_along_axis(scores, chosen, -1)
    gates = gates / (jnp.sum(gates, -1, keepdims=True) + 1e-6) * s["scale"]
    # (held, N): each held expert's gate for each token, 0 where the token did not choose it
    mine = chosen[None] == first + jnp.arange(held)[:, None, None]
    weight = jnp.sum(jnp.where(mine, gates[None], 0.0), -1)
    # one held expert at a time, so that one expert's hidden rows exist at once
    one = jax.checkpoint(lambda y_, w_, p_: y_ + w_[:, None] * _swiglu(tokens, p_, mm))
    y, _ = jax.lax.scan(lambda y_, each: (one(y_, *each), None), jnp.zeros_like(tokens),
                        (weight, {"W_g": p["W_e1"], "W_u": p["W_e3"], "W_d": p["W_e2"]}))
    load = jnp.sum(chosen[..., None] == jnp.arange(s["router"]), (0, 1)).astype(jnp.float32)
    new_state = dict(state, assigned=jnp.sum(mine, (1, 2)).astype(jnp.float32), overflow=jnp.zeros((), jnp.float32),
                     select_bias=state["select_bias"] + s["bias_rate"] * jnp.sign(jnp.mean(load) - load))
    return y.reshape(x.shape), new_state


def _block(x, p, st, kind, dense, s, mm):
    normed = _rms_norm(x, p["norm1"], s["eps"])
    x = x + (_short_conv(normed, p["mixer"], mm) if kind == "conv" else _attention(normed, p["mixer"], s, mm))
    normed = _rms_norm(x, p["norm2"], s["eps"])
    if dense:
        return x + _swiglu(normed, p["mlp"], mm), st
    y, mlp_state = _moe(normed, p["mlp"], st["mlp"], s, mm)
    return x + y, {"mlp": mlp_state}


def _cross_entropy(x, w, table, targets, eps, mm):
    """Mean over positions of -log softmax(RMSNorm(x) E^T)[target]."""
    logp = jax.nn.log_softmax(mm(_rms_norm(x, w, eps), table.T), -1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))


def reference_loss(config: dict):
    """``loss_fn(params, state, batch, mm, conv)``: the forward pass above
    and the next-token loss, float32. A Python loop over
    ``jax.checkpoint``ed blocks (PERF.md section 2: a stacked scan would
    cost two more trees of the blocks). The state it returns holds the
    experts' counters, as the program's does."""
    s = _sizes(config)
    keys = _keys(s)

    def loss_fn(params, state, batch, mm, conv):
        ids, labels, _ = batch
        table = params[keys["embed"]]["W"]
        x = table[ids]
        new_state = {}
        for key, (kind, dense) in zip(keys["blocks"], s["blocks"]):
            x, st = jax.checkpoint(functools.partial(_block, kind=kind, dense=dense, s=s, mm=mm))(
                x, params[key], state.get(key, {}))
            if st:
                new_state[key] = st
        loss = jax.checkpoint(functools.partial(_cross_entropy, eps=s["eps"], mm=mm))(
            x, params[keys["norm"]]["w"], table, labels)
        return loss, new_state

    return loss_fn
