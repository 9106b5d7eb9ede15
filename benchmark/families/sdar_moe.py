"""SDAR-30B-A3B-Chat (``sdar_moe``; JetLM/SDAR-30B-A3B-Chat ``config.json``)
trained by diffusion over blocks: pre-norm blocks of grouped-query attention
(per-head RMSNorm on queries and keys, rotary positions) and softmax-routed
experts with no shared one, a final norm, an untied head.

``build`` hands the configuration to the program's zoo model; the rest is
the benchmark's own: weights in the program's layout, batches, FLOPs and
bytes, and the plain reference, which follows these equations (every size
from the configuration; ``T`` clean tokens a row, blocks of ``B``)::

    input:  x0 the clean row; xt = x0 with m_b positions of block b replaced by MASK; the model runs on [xt ; x0],
            2T positions p = (half, i) with position i and block i // B in either half
    block:  a = h + Attn(RMSNorm(h));  h' = a + MoE(RMSNorm(a))
    Attn:   q = x W_q -> (heads, hd);  k = x W_k, v = x W_v -> (kv heads, hd)
            q = RoPE(RMSNorm_head(q), i);  k = RoPE(RMSNorm_head(k), i);  query head j reads key/value head j // group
            M: a noisy query sees noisy keys of its own block and clean keys of earlier blocks (blk_k < blk_q);
               a clean query sees clean keys with blk_k <= blk_q and no noisy key
            o = softmax(q k^T hd^-1/2 + M) v;  out = o W_o
    MoE:    p = softmax(x W_r) over all experts;  sel = top_k(p);  w = p[sel] / sum p[sel]
            y = sum over e in sel and held: w_e W_down(silu(W_gate x) * W_up x)
    loss:   logits_i = RMSNorm_f(h^L_(noisy, i)) W_out over the noisy half only, unshifted;
            L = 1/T sum over masked i of (B / m_blk(i)) CE(logits_i, x0_i), the mean over rows

Features are ``[xt ; x0]`` (batch, 2T) and labels (batch, T): ``x0_i`` where
``xt_i`` is MASK, -1 elsewhere; ``m_b`` is counted from the labels.

Departures and assumptions, in program and reference alike (the
configuration file lists them under ``assumed``): the per-head norms are
Qwen3-MoE's, from which ``sdar_moe`` derives; rotary pairs channel ``i`` with
``i + hd/2``; the seeded weights' scales (``init_params`` says why the
embedding and the per-head norms' gains have their own); ``B`` and the noise (``m_b`` uniform on 1..B, positions at
random, weight ``B / m_b``) are the family's convention, ``config.json``
gives neither; this chip holds experts ``held_experts`` of ``router_width``
and what the others would add is left out; ids, logits and the loss are
over the vocabulary's slice, whose last row is the MASK.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

BD_FLASH_KERNELS = {  # (matmuls against the q.k head, against the v head) over the allowed entries, by the kernel's name
    "bd_flash_attention_fwd": (1, 1), "bd_flash_attention_bwd_dq": (2, 1), "bd_flash_attention_bwd_dkv": (2, 2)}


def _sizes(config: dict) -> dict:
    return dict(d=config["hidden_size"], vocab=config["vocab_size"], h=config["num_attention_heads"],
                kv=config["num_key_value_heads"], hd=config["head_dim"], theta=float(config["rope_theta"]),
                expert=config["moe_intermediate_size"], held=tuple(config["held_experts"]),
                router=config["router_width"], top_k=config["num_experts_per_tok"],
                layers=len(config["layers_here"]), block=config["block_length"], eps=config["rms_norm_eps"])


def build(config: dict, seed: int):
    from deeplearning4j_tpu.runtime.environment import get_environment
    from deeplearning4j_tpu.train.updaters import Adam
    from deeplearning4j_tpu.zoo.sdar_moe import SdarMoe
    s, opt = _sizes(config), config["optimizer"]
    get_environment().set_remat(config["recompute"]["set_remat"])  # the documented switch; read when the step is traced
    return SdarMoe(
        vocab_size=s["vocab"], d_model=s["d"], n_layers=config["published"]["num_hidden_layers"],
        layers_here=config["layers_here"], n_heads=s["h"], n_kv_heads=s["kv"], head_dim=s["hd"],
        rope_theta=s["theta"], expert_size=s["expert"], n_experts=s["router"], held_experts=s["held"],
        held_rows=config["held_rows"], top_k=s["top_k"], block_length=s["block"], eps=s["eps"],
        seed=seed % (2 ** 31),
        updater=Adam(opt["lr"], beta1=opt["b1"], beta2=opt["b2"], epsilon=opt["eps"])).init()


def _keys(s: dict) -> dict:
    """The program's layer keys: embedding, the blocks, the final norm, the head."""
    n = s["layers"]
    return dict(embed="layer_0", blocks=[f"layer_{i}" for i in range(1, n + 1)], norm=f"layer_{n + 1}",
                head=f"layer_{n + 2}")


def init_params(config: dict, seed: int):
    """(params, model_state) in float32 on the device, one jitted call, in
    the program's layout. Matrices are N(0, initializer_range); the
    embedding's vocabulary rows N(0, embedding_std), its MASK row N(0,
    mask_embedding_std); the per-head norms' gains ``qk_norm_gain``, the
    other norms 1; the counters and the recorded loss 0.

    Why the embedding and the two gains have scales of their own (measured
    at the cell's widths, PERF.md section 6, PR 38): of a position's 8
    experts this cut holds 8 / 128, so the expert layers give most positions
    nothing, and an attention-only residual stream under N(0, 0.02) weights
    collapses onto one direction by the third layer (mean cosine between
    positions 0.81-0.88): every position then routes alike, a held expert
    takes 0 or ~8000 assignments, and the top-8 boundary flips for thousands
    of positions between bfloat16 and float32. Vocabulary rows far above the
    attention's gain keep a token's identity in its hidden state; the MASK
    row far below it leaves the 2560 masked positions to their context; and
    gains of 1.5 on the normed queries and keys (scores of standard deviation
    2.25) make that context differ from one position to the next."""
    s, std = _sizes(config), config["initializer_range"]
    d, held, keys = s["d"], s["held"][1], _keys(s)

    def make(key):
        count = [0]

        def w(*shape):
            count[0] += 1
            return std * jax.random.normal(jax.random.fold_in(key, count[0]), shape, jnp.float32)

        ones = lambda k: jnp.ones((k,), jnp.float32)
        zero = lambda: jnp.zeros((), jnp.float32)
        rows = jnp.full((s["vocab"], 1), config["embedding_std"] / std).at[config["mask_token_id"]].set(
            config["mask_embedding_std"] / std)
        params, state = {keys["embed"]: {"W": rows * w(s["vocab"], d)}}, {}
        for key_ in keys["blocks"]:
            params[key_] = {
                "norm1": ones(d), "norm2": ones(d),
                "mixer": {"W_q": w(d, s["h"] * s["hd"]), "W_k": w(d, s["kv"] * s["hd"]), "W_v": w(d, s["kv"] * s["hd"]),
                          "W_o": w(s["h"] * s["hd"], d), "q_norm": config["qk_norm_gain"] * ones(s["hd"]),
                          "k_norm": config["qk_norm_gain"] * ones(s["hd"])},
                "mlp": {"W_router": w(d, s["router"]), "W_e1": w(held, d, s["expert"]), "W_e3": w(held, d, s["expert"]),
                        "W_e2": w(held, s["expert"], d)}}
            state[key_] = {"mlp": {"assigned": jnp.zeros((held,), jnp.float32), "overflow": zero()}}
        params[keys["norm"]] = {"w": ones(d)}
        params[keys["head"]] = {"W": w(d, s["vocab"])}
        state[keys["head"]] = {"diffusion_loss": zero(), "masked_positions": zero()}
        return params, state

    return jax.jit(make)(jax.random.fold_in(jax.random.PRNGKey(0), seed % (2 ** 32)))


def batches(config: dict, traffic: dict, seed: int):
    """``count`` host batches of ([xt ; x0], labels, no mask). The clean ids
    are uniform on the slice without its last row, which is the MASK. Every
    row masks the same number of positions, so that the head's and the
    routing's work does not ride on the draw: the blocks' ``m_b`` are a
    seeded permutation of equally many of 1, 2, .., B (``masked_per_row`` =
    blocks x (B + 1) / 2), the positions within a block are drawn at random."""
    rng = np.random.default_rng(seed)
    t, block, mask_id = traffic["seq_len"], traffic["block_length"], config["mask_token_id"]
    blocks = t // block
    if block != config["block_length"] or t % block or blocks % block \
            or traffic["masked_per_row"] * 2 != blocks * (block + 1):
        raise ValueError(f"traffic {traffic} does not mask equally many blocks by 1..{config['block_length']}")
    out = []
    for _ in range(traffic["count"]):
        clean = rng.integers(0, mask_id, (traffic["batch"], t), dtype=np.int32)
        per_block = np.stack([rng.permutation(np.repeat(np.arange(1, block + 1), blocks // block))
                              for _ in range(traffic["batch"])])
        rank = rng.random((traffic["batch"], blocks, block)).argsort(-1).argsort(-1)
        masked = (rank < per_block[..., None]).reshape(traffic["batch"], t)
        noisy = np.where(masked, mask_id, clean).astype(np.int32)
        out.append((np.concatenate([noisy, clean], 1), np.where(masked, clean, -1).astype(np.int32), None))
    return out


def samples_per_step(traffic: dict) -> int:
    return traffic["batch"]


def _attention_matrices(s: dict) -> int:
    return 2 * s["d"] * s["hd"] * (s["h"] + s["kv"])


def n_params(config: dict) -> int:
    s = _sizes(config)
    d = s["d"]
    block = 2 * d + _attention_matrices(s) + 2 * s["hd"] + d * s["router"] + 3 * d * s["expert"] * s["held"][1]
    return 2 * s["vocab"] * d + d + s["layers"] * block


def _allowed(s: dict, traffic: dict) -> int:
    """Entries of the (2T)^2 score matrix that the block-diffusion mask allows: T^2 + T B."""
    t = traffic["seq_len"]
    return t * t + t * s["block"]


def flops_per_step(config: dict, traffic: dict) -> float:
    """Forward + backward = 3 x the forward's FLOPs from the shapes alone (2
    per multiply-add; nothing recomputed): the projections and the router at
    2 x in x out over the 2T positions the model runs on; attention over the
    allowed entries of the mask (not the tiles a kernel visits); the routed
    experts at the expected top_k x held / router_width assignments a
    position; the head over the T noisy positions. The gathers, rotary,
    norms, softmax, routing and Adam count nothing."""
    s = _sizes(config)
    d, t = s["d"], traffic["seq_len"]
    per_position = 2 * _attention_matrices(s) + 2 * d * s["router"] \
        + 2 * 3 * d * s["expert"] * s["top_k"] * s["held"][1] / s["router"]
    attention = 2 * 2 * s["hd"] * s["h"] * _allowed(s, traffic)
    return 3.0 * traffic["batch"] * (s["layers"] * (per_position * 2 * t + attention) + 2 * d * s["vocab"] * t)


def least_bytes_per_step(config: dict, traffic: dict) -> float:
    """Train state read once and written once (float32 parameters and two
    Adam moments) plus the batch in ([xt ; x0] and the labels, int32)."""
    return 2.0 * 3 * 4 * n_params(config) + 3 * 4 * traffic["batch"] * traffic["seq_len"]


def bd_flash_kernel_flops(config: dict, traffic: dict) -> dict:
    """Useful FLOPs of one run of each block-diffusion flash kernel
    (``ops/pallas/flash_attention.py``; one run covers every head of one
    block), by the kernel's name: the mask's allowed entries, every query
    head. Forward q k^T and p v; dq pass scores, dp = do v^T, dq = ds k;
    dk/dv pass scores, dv = p^T do, dp, dk = ds^T q. What the kernels
    compute in the masked tiles beyond the allowed entries counts nothing."""
    s = _sizes(config)
    pairs = traffic["batch"] * s["h"] * _allowed(s, traffic)
    return {name: 2.0 * pairs * (qk + v) * s["hd"] for name, (qk, v) in BD_FLASH_KERNELS.items()}


def bd_flash_kernel_bytes(config: dict, traffic: dict) -> dict:
    """Least HBM bytes of one run: q (and in the backward o's cotangent)
    read and each result written once a query head, k and v read (dk and dv
    written) once a key/value head, in the compute type; the float32 row
    statistics as the kernels lay them out (8 lanes a row)."""
    s = _sizes(config)
    item = jnp.dtype(config["precision"]["compute"]).itemsize
    rows = traffic["batch"] * 2 * traffic["seq_len"] * s["hd"] * item
    q, kv, stat = s["h"] * rows, s["kv"] * rows, traffic["batch"] * s["h"] * 2 * traffic["seq_len"] * 8 * 4
    return {"bd_flash_attention_fwd": 2 * q + 2 * kv + stat,
            "bd_flash_attention_bwd_dq": 3 * q + 2 * kv + 2 * stat,
            "bd_flash_attention_bwd_dkv": 2 * q + 4 * kv + 2 * stat}


# ------------------------------------------------------------ the reference

QUERY_BLOCK = 256  # queries whose scores exist at once


def _rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


def _rope(x, positions, theta):
    """``x`` (b, n, heads, d) turned by ``positions`` (n,) on axis 1: ``x cos + rotate_half(x) sin``
    with the d/2 frequencies ``theta^(-2j/d)`` repeated over both halves."""
    d = x.shape[-1]
    inverse = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions.astype(jnp.float32)[:, None] * inverse[None, :]
    angles = jnp.concatenate([angles, angles], -1)[None, :, None, :]
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * jnp.cos(angles) + turned * jnp.sin(angles)


def _may_see(q_at, k_at, t, block):
    """The block-diffusion mask from its definition: ``q_at`` (rows, 1) and
    ``k_at`` (1, cols) index ``[noisy ; clean]``."""
    q_noisy, k_noisy = q_at < t, k_at < t
    q_blk, k_blk = (q_at % t) // block, (k_at % t) // block
    return ((q_noisy & k_noisy & (k_blk == q_blk)) | (q_noisy & ~k_noisy & (k_blk < q_blk))
            | (~q_noisy & ~k_noisy & (k_blk <= q_blk)))


def _attention(x, p, s, mm):
    b, n, _ = x.shape
    t, h, kv, hd, eps = n // 2, s["h"], s["kv"], s["hd"], s["eps"]
    at = jnp.concatenate([jnp.arange(t), jnp.arange(t)])  # the two halves carry the same positions
    q = _rope(_rms_norm(mm(x, p["W_q"]).reshape(b, n, h, hd), p["q_norm"], eps), at, s["theta"]).transpose(0, 2, 1, 3)
    k = _rope(_rms_norm(mm(x, p["W_k"]).reshape(b, n, kv, hd), p["k_norm"], eps), at, s["theta"])
    v = mm(x, p["W_v"]).reshape(b, n, kv, hd)
    # query head j reads key/value head j // group
    k_t = jnp.repeat(k, h // kv, axis=2).transpose(0, 2, 3, 1)     # (b, h, hd, n)
    v = jnp.repeat(v, h // kv, axis=2).transpose(0, 2, 1, 3)       # (b, h, n, hd)
    block = QUERY_BLOCK if n % QUERY_BLOCK == 0 else n

    @jax.checkpoint
    def queries(args):  # one block of queries against every key: the scores of all of 2T x 2T never exist
        q_blk, first = args
        scores = mm(q_blk, k_t) * hd ** -0.5
        seen = _may_see(first + jnp.arange(block)[:, None], jnp.arange(n)[None, :], t, s["block"])
        return mm(jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1), v)

    q_blocks = jnp.moveaxis(q.reshape(b, h, n // block, block, hd), 2, 0)
    ctx = jax.lax.map(queries, (q_blocks, jnp.arange(0, n, block)))            # (blocks, b, h, block, hd)
    ctx = jnp.moveaxis(ctx, 0, 2).reshape(b, h, n, hd).transpose(0, 2, 1, 3).reshape(b, n, h * hd)
    return mm(ctx, p["W_o"])


def _moe(x, p, state, s, mm):
    """Every assignment to a held expert is computed, none dropped: each held
    expert runs on all positions and is weighted by its gate (0 where the
    position did not choose it). Returns (y, the layer's new state)."""
    first, held = s["held"]
    tokens = x.reshape(-1, x.shape[-1])
    probs = jax.nn.softmax(mm(tokens, p["W_router"]), -1)
    gates, chosen = jax.lax.top_k(probs, s["top_k"])
    gates = gates / jnp.sum(gates, -1, keepdims=True)   # norm_topk_prob: over the chosen, held here or not
    # (held, N): each held expert's gate for each position, 0 where the position did not choose it
    mine = chosen[None] == first + jnp.arange(held)[:, None, None]
    weight = jnp.sum(jnp.where(mine, gates[None], 0.0), -1)

    # one held expert at a time, so that one expert's hidden rows exist at once
    @jax.checkpoint
    def one(y, w, e):
        return y + w[:, None] * mm(jax.nn.silu(mm(tokens, e["W_g"])) * mm(tokens, e["W_u"]), e["W_d"])

    y, _ = jax.lax.scan(lambda y_, each: (one(y_, *each), None), jnp.zeros_like(tokens),
                        (weight, {"W_g": p["W_e1"], "W_u": p["W_e3"], "W_d": p["W_e2"]}))
    new_state = dict(state, assigned=jnp.sum(mine, (1, 2)).astype(jnp.float32), overflow=jnp.zeros((), jnp.float32))
    return y.reshape(x.shape), new_state


def _block(x, p, st, s, mm):
    x = x + _attention(_rms_norm(x, p["norm1"], s["eps"]), p["mixer"], s, mm)
    y, mlp_state = _moe(_rms_norm(x, p["norm2"], s["eps"]), p["mlp"], st["mlp"], s, mm)
    return x + y, {"mlp": mlp_state}


def _diffusion_loss(x, w, head, labels, block, eps, mm):
    """Over the noisy half's hidden states ``x`` (b, T, d): the masked
    positions' negative log-likelihoods, each over its block's masked share
    ``m_b / B``, summed, over T; the mean over rows."""
    b, t = labels.shape
    masked = labels >= 0
    per_block = jnp.sum(masked.reshape(b, t // block, block), -1, keepdims=True)
    share = jnp.broadcast_to(per_block / block, (b, t // block, block)).reshape(b, t)
    logp = jax.nn.log_softmax(mm(_rms_norm(x, w, eps), head), -1)
    nll = -jnp.take_along_axis(logp, jnp.maximum(labels, 0)[..., None], -1)[..., 0]
    return jnp.sum(jnp.where(masked, nll / jnp.where(masked, share, 1.0), 0.0)) / (b * t)


def reference_loss(config: dict):
    """``loss_fn(params, state, batch, mm, conv)``: the forward pass above
    and the block-diffusion loss, float32. A Python loop over
    ``jax.checkpoint``ed blocks (it changes no arithmetic; the step then
    fits beside the 16 bytes a parameter of its own state). The state it
    returns holds the experts' counters, the loss and the number of masked
    positions a row, as the program's does."""
    s, keys = _sizes(config), _keys(_sizes(config))

    def loss_fn(params, state, batch, mm, conv):
        ids, labels, _ = batch
        t = labels.shape[1]
        x = params[keys["embed"]]["W"][ids]
        new_state = {}
        for key in keys["blocks"]:
            x, new_state[key] = jax.checkpoint(functools.partial(_block, s=s, mm=mm))(x, params[key], state[key])
        loss = jax.checkpoint(functools.partial(_diffusion_loss, block=s["block"], eps=s["eps"], mm=mm))(
            x[:, :t], params[keys["norm"]]["w"], params[keys["head"]]["W"], labels)
        new_state[keys["head"]] = {"diffusion_loss": loss,
                                   "masked_positions": jnp.sum(labels >= 0).astype(jnp.float32) / labels.shape[0]}
        return loss, new_state

    return loss_fn
