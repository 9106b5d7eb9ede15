"""GLM-4.7-Flash (``glm4_moe_lite``; zai-org/GLM-4.7-Flash ``config.json``)
as a causal language model with its multi-token-prediction loss: pre-norm
blocks of rotary latent attention with a low-rank query, one leading dense
SwiGLU layer, then sigmoid-routed experts with a shared expert, and one
prediction layer after the trunk that shares the embedding and the head.

``build`` hands the configuration to the program's zoo model; the rest is
the benchmark's own: weights in the program's layout (the tied leaves
once), batches, FLOPs and bytes, and the plain reference, which follows
these equations (every size from the configuration)::

    block:  h = x + MLA(RMSNorm(x));  y = h + MLP(RMSNorm(h))          MLP: dense SwiGLU (layer 0), else MoE
    MLA:    c_q = RMSNorm(x W_qa);   [q_n | q_r] = c_q W_qb  per head
            [c | k_r] = x W_kva;  [k_n | v] = RMSNorm(c) W_kvb  per head
            q_r, k_r <- RoPE(theta, all d_r dims, position t);  k = [k_n | k_r]  (k_r shared by the heads)
            o = softmax(q k^T (d_n + d_r)^-1/2 + causal) v;   out = o W_o
    MoE:    s = sigmoid(x W_r);  sel = top_k(s + bias);  w = s[sel] / (sum s[sel] + 1e-20) * scale
            y = sum over e in sel and held: w_e SwiGLU_e(x)  +  SwiGLU_shared(x)
    trunk:  h^0 = Emb(t_i);  h^L after the last trunk block;  logits_i = RMSNorm_f(h^L_i) W_out
            L_main = CE(logits_i, t_(i+1))
    MTP:    u_i = W_eh [RMSNorm_e(Emb(t_(i+1))) ; RMSNorm_h(h^L_i)]   (Emb is the trunk's table)
            z = block(u)  (MLA + MoE, causal over i);  logits'_i = RMSNorm_s(z_i) W_out   (W_out is the trunk's head)
            L_mtp = CE(logits'_i, t_(i+2)) over the T - 1 positions that have such a token
    L = L_main + lambda L_mtp

Features are ``ids[:, :-1]`` and labels ``ids[:, 1:]``: ``Emb(t_(i+1))`` is
the embedding of the label at ``i`` and the MTP label is the label at ``i
+ 1``.

Departures from the published implementation, in program and reference
alike, to be checked when the model's files are in the repository:
``config.json`` gives of the prediction layer only
``num_nextn_predict_layers`` 1; its form is DeepSeek-V3's (arXiv:2412.19437
§2.2, eq. 21-25), which this family's checkpoints bear out by their tensor
names (``enorm``, ``hnorm``, ``eh_proj``, ``shared_head.norm``); ``lambda``
and the order of the halves under ``W_eh`` (embedding first) are assumed;
rotary pairs channel ``i`` with ``i + d_r/2``; this chip holds experts
``held_experts`` of ``router_width`` and what the others would add is left
out; ids, logits and both losses are over the vocabulary's slice; the
selection bias is fixed at 0.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

FLASH_KERNELS = {  # (T x T matmuls against the q.k head, against the v head) of one run, by the kernel's name
    "flash_attention_fwd": (1, 1), "flash_attention_bwd_dq": (2, 1), "flash_attention_bwd_dkv": (2, 2),
    "flash_attention_bwd_dq_chunked": (2, 1), "flash_attention_bwd_dkv_chunked": (2, 2)}


def _sizes(config: dict) -> dict:
    trunk = config["published"]["num_hidden_layers"]  # the prediction layer is stored as layer ``trunk``
    here = sorted(config["layers_here"])
    return dict(d=config["hidden_size"], vocab=config["vocab_size"], h=config["num_attention_heads"],
                qr=config["q_lora_rank"], kv=config["kv_lora_rank"], dn=config["qk_nope_head_dim"],
                dr=config["qk_rope_head_dim"], dv=config["v_head_dim"], theta=float(config["rope_theta"]),
                dense=config["intermediate_size"], expert=config["moe_intermediate_size"],
                held=tuple(config["held_experts"]), router=config["router_width"],
                top_k=config["num_experts_per_tok"], shared=config["n_shared_experts"],
                # the trunk's blocks that are here, True where the MLP is dense; whether the prediction layer is
                blocks=[i < config["first_k_dense_replace"] for i in here if i < trunk],
                mtp=trunk in here and config["num_nextn_predict_layers"] > 0, trunk=trunk)


def build(config: dict, seed: int):
    from deeplearning4j_tpu.runtime.environment import get_environment
    from deeplearning4j_tpu.train.updaters import Adam
    from deeplearning4j_tpu.zoo.glm_moe_lite import GlmMoeLite
    s, opt = _sizes(config), config["optimizer"]
    get_environment().set_remat(config["recompute"]["set_remat"])  # the documented switch; read when the step is traced
    return GlmMoeLite(
        vocab_size=s["vocab"], d_model=s["d"], n_layers=s["trunk"], layers_here=config["layers_here"],
        mtp=s["mtp"], mtp_weight=config["mtp_loss_weight"], n_heads=s["h"], q_rank=s["qr"], kv_rank=s["kv"],
        qk_nope_dim=s["dn"], qk_shared_dim=s["dr"], v_dim=s["dv"], rope_theta=s["theta"],
        dense_size=s["dense"], first_k_dense=config["first_k_dense_replace"], expert_size=s["expert"],
        n_experts=s["router"], held_experts=s["held"], held_rows=config["held_rows"], top_k=s["top_k"],
        n_shared=s["shared"], routed_scale=config["routed_scaling_factor"], eps=config["rms_norm_eps"],
        seed=seed % (2 ** 31),
        updater=Adam(opt["lr"], beta1=opt["b1"], beta2=opt["b2"], epsilon=opt["eps"])).init()


def _keys(s: dict) -> dict:
    """The program's layer keys: embedding, the trunk's blocks, the
    prediction layer (where it is here), the final norm, the head."""
    n = len(s["blocks"])
    after = n + 1 + s["mtp"]
    return dict(embed="layer_0", blocks=[f"layer_{i}" for i in range(1, n + 1)],
                mtp=f"layer_{n + 1}" if s["mtp"] else None, norm=f"layer_{after}", head=f"layer_{after + 1}")


def init_params(config: dict, seed: int):
    """(params, model_state) in float32 on the device, one jitted call, in
    the program's layout: the embedding table and the head are one leaf
    each, which the prediction layer reads too. Matrices are N(0,
    initializer_range); norms 1; the selection bias, the counters and the
    two recorded loss terms 0."""
    s, std = _sizes(config), config["initializer_range"]
    d, held, keys = s["d"], s["held"][1], _keys(s)

    def make(key):
        count = [0]

        def w(*shape):
            count[0] += 1
            return std * jax.random.normal(jax.random.fold_in(key, count[0]), shape, jnp.float32)

        ones = lambda k: jnp.ones((k,), jnp.float32)
        zero = lambda: jnp.zeros((), jnp.float32)

        def mla():
            return {"W_qa": w(d, s["qr"]), "q_norm": ones(s["qr"]), "W_qb": w(s["qr"], s["h"] * (s["dn"] + s["dr"])),
                    "W_kva": w(d, s["kv"] + s["dr"]), "kv_norm": ones(s["kv"]),
                    "W_kvb": w(s["kv"], s["h"] * (s["dn"] + s["dv"])), "W_o": w(s["h"] * s["dv"], d)}

        def swiglu(f):
            return {"W_g": w(d, f), "W_u": w(d, f), "W_d": w(f, d)}

        def moe():
            f = s["expert"]
            return {"W_router": w(d, s["router"]), "W_e1": w(held, d, f), "W_e3": w(held, d, f),
                    "W_e2": w(held, f, d), "shared": swiglu(s["shared"] * f)}

        def block(dense):
            return {"norm1": ones(d), "mixer": mla(), "norm2": ones(d), "mlp": swiglu(s["dense"]) if dense else moe()}

        def counters():
            return {"mlp": {"assigned": jnp.zeros((held,), jnp.float32), "overflow": zero(),
                            "select_bias": jnp.zeros((s["router"],), jnp.float32)}}

        params, state = {keys["embed"]: {"W": w(s["vocab"], d)}}, {}
        for key_, dense in zip(keys["blocks"], s["blocks"]):
            params[key_] = block(dense)
            if not dense:
                state[key_] = counters()
        if s["mtp"]:
            params[keys["mtp"]] = {"enorm": ones(d), "hnorm": ones(d), "W_eh": w(2 * d, d), "norm": ones(d),
                                   "block": block(False)}
            state[keys["mtp"]] = {"_aux_loss": zero(), "mtp_loss": zero(), "block": counters()}
            state[keys["head"]] = {"main_loss": zero()}
        params[keys["norm"]] = {"w": ones(d)}
        params[keys["head"]] = {"W": w(d, s["vocab"])}
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


def _mla_matrices(s: dict) -> int:
    return (s["d"] * s["qr"] + s["qr"] * s["h"] * (s["dn"] + s["dr"]) + s["d"] * (s["kv"] + s["dr"])
            + s["kv"] * s["h"] * (s["dn"] + s["dv"]) + s["h"] * s["dv"] * s["d"])


def _block_params(s: dict, dense: bool) -> int:
    d = s["d"]
    mlp = 3 * d * s["dense"] if dense else d * s["router"] + 3 * d * s["expert"] * (s["held"][1] + s["shared"])
    return 2 * d + _mla_matrices(s) + s["qr"] + s["kv"] + mlp


def n_params(config: dict) -> int:
    """Every leaf once: the embedding and the head count once though the
    prediction layer reads them too."""
    s = _sizes(config)
    d = s["d"]
    total = 2 * s["vocab"] * d + d + sum(_block_params(s, dense) for dense in s["blocks"])
    if s["mtp"]:
        total += 2 * d * d + 3 * d + _block_params(s, False)
    return total


def flops_per_step(config: dict, traffic: dict) -> float:
    """Forward + backward = 3 x the forward's FLOPs from the shapes alone (2
    per multiply-add; nothing recomputed): every weight matmul at 2 x in x
    out a token; causal attention at the lower half of T x T against a q.k
    head of d_n + d_r and a v head of d_v; the routed experts at the
    expected top_k x held / router_width assignments a token; the head once
    for the trunk over T positions and once for the prediction layer over T
    - 1, with its projection of the concatenation. The gathers, rotary,
    norms, softmax, routing and Adam count nothing."""
    s = _sizes(config)
    d, t = s["d"], traffic["seq_len"]
    mla = 2 * _mla_matrices(s) + 2 * (s["dn"] + s["dr"] + s["dv"]) * s["h"] * t / 2
    expert = 2 * 3 * d * s["expert"]
    moe = 2 * d * s["router"] + expert * (s["shared"] + s["top_k"] * s["held"][1] / s["router"])
    head = 2 * d * s["vocab"]
    per_token = head + sum(mla + (2 * 3 * d * s["dense"] if dense else moe) for dense in s["blocks"])
    if s["mtp"]:
        per_token += 2 * 2 * d * d + mla + moe + head * (t - 1) / t
    return 3.0 * per_token * traffic["batch"] * t


def least_bytes_per_step(config: dict, traffic: dict) -> float:
    """Train state read once and written once (float32 parameters and two
    Adam moments) plus the batch in (ids and next ids, int32)."""
    return 2.0 * 3 * 4 * n_params(config) + 2 * 4 * traffic["batch"] * traffic["seq_len"]


def flash_kernel_flops(config: dict, traffic: dict) -> dict:
    """FLOPs of one run of each flash-attention kernel (``ops/pallas/
    flash_attention.py``; one run covers every head of one block), by the
    kernel's name: the causal half of T x T, a q.k head of d_n + d_r, the v
    head of d_v. Forward q k^T and p v; dq pass scores, dp = do v^T, dq = ds
    k; dk/dv pass scores, dv = p^T do, dp, dk = ds^T q. The blocks on the
    diagonal that the kernels compute in full, softmax and masking count
    nothing."""
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

QUERY_BLOCK = 256  # queries whose scores exist at once


def _rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


def _swiglu(x, p, mm):
    return mm(jax.nn.silu(mm(x, p["W_g"])) * mm(x, p["W_u"]), p["W_d"])


def _rope(x, theta):
    """``x`` (b, t, ..., d) turned by its position on axis 1: ``x cos + rotate_half(x) sin`` with
    the d/2 frequencies ``theta^(-2j/d)`` repeated over both halves."""
    t, d = x.shape[1], x.shape[-1]
    inverse = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inverse[None, :]
    angles = jnp.concatenate([angles, angles], -1).reshape((1, t) + (1,) * (x.ndim - 3) + (d,))
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * jnp.cos(angles) + turned * jnp.sin(angles)


def _mla(x, p, s, eps, mm):
    b, t, _ = x.shape
    h, dn, dr, dv = s["h"], s["dn"], s["dr"], s["dv"]
    q = mm(_rms_norm(mm(x, p["W_qa"]), p["q_norm"], eps), p["W_qb"]).reshape(b, t, h, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], s["theta"])], -1).transpose(0, 2, 1, 3)
    latent = mm(x, p["W_kva"])
    kv = mm(_rms_norm(latent[..., :s["kv"]], p["kv_norm"], eps), p["W_kvb"]).reshape(b, t, h, dn + dv)
    shared = jnp.broadcast_to(_rope(latent[..., s["kv"]:], s["theta"])[:, :, None, :], (b, t, h, dr))
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
    # one held expert at a time, so that one expert's hidden rows exist at once (a scan over the
    # leading expert axis: its backward pass stacks the experts' gradients as the weights are stacked)
    one = jax.checkpoint(lambda y_, w_, p_: y_ + w_[:, None] * _swiglu(tokens, p_, mm))
    y, _ = jax.lax.scan(lambda y_, each: (one(y_, *each), None), _swiglu(tokens, p["shared"], mm),
                        (weight, {"W_g": p["W_e1"], "W_u": p["W_e3"], "W_d": p["W_e2"]}))
    new_state = dict(state, assigned=jnp.sum(mine, (1, 2)).astype(jnp.float32), overflow=jnp.zeros((), jnp.float32))
    return y.reshape(x.shape), new_state


def _block(x, p, st, dense, s, config, mm):
    eps = config["rms_norm_eps"]
    x = x + _mla(_rms_norm(x, p["norm1"], eps), p["mixer"], s, eps, mm)
    normed = _rms_norm(x, p["norm2"], eps)
    if dense:
        return x + _swiglu(normed, p["mlp"], mm), st
    y, mlp_state = _moe(normed, p["mlp"], st["mlp"], s, config, mm)
    return x + y, {"mlp": mlp_state}


def _cross_entropy(x, head, targets, mm):
    """Per position: -log softmax(x W_out)[target]. Called under
    ``jax.checkpoint`` so that the two heads' logits never exist at once."""
    logp = jax.nn.log_softmax(mm(x, head), -1)
    return -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]


def reference_loss(config: dict):
    """``loss_fn(params, state, batch, mm, conv)``: the forward pass above
    and ``L_main + lambda L_mtp``, float32. A Python loop over
    ``jax.checkpoint``ed blocks (PERF.md section 2: a stacked scan would
    cost two more trees of the blocks). The state it returns holds the
    experts' counters and the two terms, as the program's does."""
    s, eps, keys = _sizes(config), config["rms_norm_eps"], _keys(_sizes(config))
    weight = config["mtp_loss_weight"]

    def loss_fn(params, state, batch, mm, conv):
        ids, labels, _ = batch
        table, head = params[keys["embed"]]["W"], params[keys["head"]]["W"]
        score = jax.checkpoint(functools.partial(_cross_entropy, mm=mm))
        x = table[ids]
        new_state = {}
        for key, dense in zip(keys["blocks"], s["blocks"]):
            x, st = jax.checkpoint(functools.partial(_block, dense=dense, s=s, config=config, mm=mm))(
                x, params[key], state.get(key, {}))
            if st:
                new_state[key] = st
        main = jnp.mean(score(_rms_norm(x, params[keys["norm"]]["w"], eps), head, labels))
        if not s["mtp"]:
            return main, new_state
        p = params[keys["mtp"]]
        both = jnp.concatenate([_rms_norm(table[labels], p["enorm"], eps), _rms_norm(x, p["hnorm"], eps)], -1)
        z, st = jax.checkpoint(functools.partial(_block, dense=False, s=s, config=config, mm=mm))(
            mm(both, p["W_eh"]), p["block"], state[keys["mtp"]]["block"])
        # position i against the label at i + 1: the last position has none
        mtp = jnp.mean(score(_rms_norm(z[:, :-1], p["norm"], eps), head, labels[:, 1:]))
        new_state[keys["mtp"]] = {"_aux_loss": weight * mtp, "mtp_loss": mtp, "block": st}
        new_state[keys["head"]] = {"main_loss": main}
        return main + weight * mtp, new_state

    return loss_fn
