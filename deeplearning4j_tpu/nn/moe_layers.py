"""Mixture-of-Experts layer with expert parallelism.

The reference has NO MoE (SURVEY.md §2.3: expert parallelism absent) — this
is parity-plus, built because EP is a first-class axis of the TPU design.
A linear router over all ``n_experts`` (softmax as in Switch/GShard, or
sigmoid scores with a selection bias, renormalised and scaled, as in the
DeepSeek-V3 / Kimi family), top-k gating, and an optional load-balancing
auxiliary loss. Dispatch is GROUPED: the (token, expert) assignments are
sorted by expert into a static buffer of rows, each expert's rows go
through its own weights as one grouped matmul over the group sizes
(``jax.lax.ragged_dot``), and the results are weighted and added back per
token. No token is dropped inside the buffer; what does not fit is counted.

A layer may be ONE SHARE of an expert-parallel layer: ``held = (first,
count)`` says which of the ``n_experts`` its weights are. It routes over all
of them, computes its own experts' part for the tokens routed to them (plus
the shared experts, which every share computes alike), and leaves the rest
out: on one chip there is no exchange, and no code stands in for one. With
every expert held, the leading expert dimension of ``W_e*`` can instead be
sharded over the ``expert`` mesh axis (``ShardingStrategy.expert_parallel``).

The aux loss rides the model-state channel: forward returns it under
``_aux_loss`` and ``MultiLayerNetwork._loss`` adds every such entry to the
training loss (in-trace, so gradients flow to the router). The state also
holds the last step's counters: ``assigned`` (assignments routed to each
held expert) and ``overflow`` (assignments beyond ``held_rows``), and under
the sigmoid router the selection bias, which ``bias_rate`` moves after every
training step against the load the step gave each expert.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.attention_layers import GatedMLP, scoped
from deeplearning4j_tpu.nn.base import GlobalConfig, Layer, register_layer
from deeplearning4j_tpu.nn.inputs import InputType
from deeplearning4j_tpu.ops.activations import get_activation
from deeplearning4j_tpu.ops.initializers import init_weights
from deeplearning4j_tpu.ops.pallas import rows_to_tokens


@register_layer
@dataclasses.dataclass
class MixtureOfExperts(Layer):
    """Top-k routed MoE FFN block: ``y = Σ_e gate_e(x) · FFN_e(x)`` over the
    experts held here, plus ``n_shared`` always-on experts.

    Parameters carry a leading expert dimension — ``W_e1 (E_held, nIn,
    hidden)``, ``W_e2 (E_held, hidden, nOut)``, with ``gated`` also ``W_e3``
    (SwiGLU: ``W_e2 (act(x W_e1) * x W_e3)``) — which
    :meth:`ShardingStrategy.expert_parallel
    <deeplearning4j_tpu.parallel.sharding.ShardingStrategy.expert_parallel>`
    shards over the ``expert`` mesh axis."""

    n_out: int = 0
    n_experts: int = 4
    hidden_size: Optional[int] = None  # default 4 * n_out
    top_k: int = 2
    aux_loss_coef: float = 0.01
    router_noise: float = 0.0  # stddev of train-time router logit jitter
    router: str = "softmax"  # or "sigmoid": scores + selection bias pick, unbiased scores weigh
    routed_scale: float = 1.0
    # sigmoid router: each training step moves the selection bias by this towards an even load (down for
    # experts chosen more often than the mean, up for the others: DeepSeek-V3's auxiliary-loss-free balancing)
    bias_rate: float = 0.0
    gated: bool = False  # SwiGLU experts without biases
    n_shared: int = 0  # shared experts of the same width, run on every token
    held: Optional[Tuple[int, int]] = None  # (first, count) of n_experts; default all
    held_rows: Optional[int] = None  # rows of the dispatch buffer; default every assignment

    def output_type(self, input_type: InputType) -> InputType:
        if input_type.kind == "recurrent":
            return InputType.recurrent(self.n_out, input_type.timesteps)
        return InputType.feed_forward(self.n_out)

    def _held(self) -> Tuple[int, int]:
        return tuple(self.held) if self.held is not None else (0, self.n_experts)

    def init(self, key, input_type, g: GlobalConfig):
        n_in = input_type.size
        h = self.hidden_size or 4 * self.n_out
        held = self._held()[1]
        kr, k1, k2, k3, ks = jax.random.split(key, 5)
        winit = self._winit(g)
        params = {
            "W_router": init_weights(kr, (n_in, self.n_experts), winit, fan=(n_in, self.n_experts), dtype=g.dtype),
            "W_e1": init_weights(k1, (held, n_in, h), winit, fan=(n_in, h), dtype=g.dtype),
            "W_e2": init_weights(k2, (held, h, self.n_out), winit, fan=(h, self.n_out),
                                 dtype=g.dtype),
        }
        if self.gated:
            params["W_e3"] = init_weights(k3, (held, n_in, h), winit, fan=(n_in, h), dtype=g.dtype)
        else:
            params["b_e1"] = jnp.zeros((held, h), dtype=g.dtype)
            params["b_e2"] = jnp.zeros((held, self.n_out), dtype=g.dtype)
        if self.n_shared:
            shared = GatedMLP(hidden_size=self.n_shared * h, weight_init=winit)
            params["shared"], _ = shared.init(ks, input_type, g)
        state = {"assigned": jnp.zeros((held,), jnp.float32), "overflow": jnp.zeros((), jnp.float32)}
        if self.router == "sigmoid":
            state["select_bias"] = jnp.zeros((self.n_experts,), jnp.float32)
        if self.aux_loss_coef:
            state["_aux_loss"] = jnp.zeros((), jnp.float32)
        return params, state

    def regularizable_params(self):
        return ("W_router", "W_e1", "W_e2", "W_e3", "W_g", "W_u", "W_d")

    def _route(self, W_router, select_bias, tokens, noise_key):
        """(scores (N, E) float32, top_idx (N, k), gates (N, k) float32)."""
        logits = (tokens @ W_router).astype(jnp.float32)
        if noise_key is not None:
            logits = logits + self.router_noise * jax.random.normal(noise_key, logits.shape, logits.dtype)
        k = min(self.top_k, self.n_experts)
        if self.router == "sigmoid":
            scores = jax.nn.sigmoid(logits)
            _, top_idx = jax.lax.top_k(scores + select_bias, k)
            top_vals = jnp.take_along_axis(scores, top_idx, -1)
            floor = 1e-20
        else:
            scores = jax.nn.softmax(logits, axis=-1)
            top_vals, top_idx = jax.lax.top_k(scores, k)
            floor = 1e-9
        top_vals = top_vals / jnp.maximum(top_vals.sum(-1, keepdims=True), floor)  # gates sum to 1 over the selected
        return scores, top_idx, top_vals * self.routed_scale

    def _buffer_rows(self, n_assign: int) -> int:
        return min(self.held_rows or n_assign, n_assign)

    def _dispatch(self, tokens, top_idx, sum_by_kernel=False):
        """Sort the assignments to held experts by expert into the buffer.
        Returns (rows (R, nIn), the token and the slot of each row, whether a
        row holds an assignment, each held expert's rows in the buffer,
        assignments routed to each held expert, assignments left out, and
        with ``sum_by_kernel`` the plan by which ``rows_to_tokens`` sums rows
        into tokens, else None)."""
        first, held = self._held()
        rows = self._buffer_rows(top_idx.size)
        local = top_idx.reshape(-1) - first
        expert = jnp.where((local >= 0) & (local < held), local, held)  # elsewhere: sorted to the end
        order = jnp.argsort(expert, stable=True)[:rows]
        assigned = jnp.sum(jax.nn.one_hot(expert, held, dtype=jnp.int32), 0)
        ends = jnp.minimum(jnp.cumsum(assigned), rows)
        sizes = jnp.diff(ends, prepend=0)
        filled = jnp.arange(rows) < ends[-1]
        token = order // top_idx.shape[1]
        plan = None
        if sum_by_kernel:
            # each expert's rows ascend by token (the sort is stable): the runs the kernel reads;
            # a row that holds nothing gets the id past the last token, and the gather's zero
            plan = rows_to_tokens.plan(jnp.where(filled, token, tokens.shape[0]), ends, tokens.shape[0])
            rows_in = rows_to_tokens.tokens_to_rows(tokens, plan)
        else:
            # rows past the last group are left as they are by the grouped matmul, forward and backward:
            # select them away here, so that nothing of them reaches a token's gradient
            rows_in = jnp.where(filled[:, None], tokens[token], 0)
        return (rows_in, token, order, filled, sizes, assigned.astype(jnp.float32),
                (jnp.sum(assigned) - ends[-1]).astype(jnp.float32), plan)

    def _experts(self, p, rows, sizes):
        dot = lambda x, w: jax.lax.ragged_dot(x, w, sizes)
        act = get_activation(self._act(self._g) if self._act(self._g) is not None
                             else "relu")
        if self.gated:
            return dot(act(dot(rows, p["W_e1"])) * dot(rows, p["W_e3"]), p["W_e2"])
        expert = jnp.repeat(jnp.arange(sizes.shape[0]), sizes, total_repeat_length=rows.shape[0])
        return dot(act(dot(rows, p["W_e1"]) + p["b_e1"][expert]), p["W_e2"]) + p["b_e2"][expert]

    def forward(self, params, state, x, *, training=False, rng=None, mask=None):
        x = self._apply_input_dropout(x, self._g, training, rng)
        shape = x.shape
        tokens = x.reshape(-1, shape[-1])  # (N, nIn)
        E = self.n_experts
        # distinct subkey: rng was already consumed by input dropout
        noise_key = (jax.random.fold_in(rng, 1) if training and self.router_noise > 0.0 and rng is not None
                     else None)
        scores, top_idx, gates = scoped(
            "router", self._route, params["W_router"], state.get("select_bias", 0.0), tokens, noise_key)
        expert_p = {k: v for k, v in params.items() if k.startswith(("W_e", "b_e"))}
        # the sums of rows into tokens (combine, and the gradient of dispatch's gather) by the kernel
        # where it is compiled for this call; a call over several devices keeps XLA's scatter-add
        by_kernel = rows_to_tokens.kernel_route(
            tokens.shape[0], self._buffer_rows(top_idx.size), (shape[-1], self.n_out),
            tokens, *expert_p.values())
        rows, token, slot, filled, sizes, assigned, overflow, plan = scoped(
            "dispatch", functools.partial(self._dispatch, sum_by_kernel=by_kernel), tokens, top_idx)
        out_rows = scoped("experts", self._experts, expert_p, rows, sizes)

        def combine(out_rows, gates):
            weight = jnp.where(filled, gates.reshape(-1)[slot], 0.0)
            weighted = jnp.where(filled[:, None], out_rows, 0.0) * weight[:, None].astype(out_rows.dtype)
            if plan is not None:
                return rows_to_tokens.rows_to_tokens(weighted, plan)
            return jnp.zeros((tokens.shape[0], self.n_out), out_rows.dtype).at[token].add(weighted)

        y = scoped("combine", combine, out_rows, gates)
        if self.n_shared:
            y = y + scoped("shared_expert", GatedMLP.apply, params["shared"], tokens)

        new_state = dict(state)
        new_state["assigned"], new_state["overflow"] = assigned, overflow
        if training and self.bias_rate and self.router == "sigmoid":
            # every expert's load over this step's tokens (padding left out), held here or not
            chosen = jax.nn.one_hot(top_idx, E, dtype=jnp.float32).sum(1)
            if mask is not None and len(shape) == 3:
                chosen = chosen * mask.reshape(-1, 1).astype(jnp.float32)
            load = chosen.sum(0)
            new_state["select_bias"] = state["select_bias"] + self.bias_rate * jnp.sign(jnp.mean(load) - load)
        if self.aux_loss_coef:
            # Switch-style load balancing: fraction routed (top-1) x mean prob.
            # Masked (padding) tokens are excluded — balancing garbage tokens
            # would bias the router against real ones.
            top1 = jax.nn.one_hot(top_idx[:, 0], E, dtype=scores.dtype)
            if mask is not None and len(shape) == 3:
                w = mask.reshape(-1, 1).astype(scores.dtype)
                denom = jnp.maximum(w.sum(), 1.0)
                frac = jnp.sum(top1 * w, axis=0) / denom
                mean_prob = jnp.sum(scores * w, axis=0) / denom
            else:
                frac = jnp.mean(top1, axis=0)
                mean_prob = jnp.mean(scores, axis=0)
            new_state["_aux_loss"] = (self.aux_loss_coef * E * jnp.sum(frac * mean_prob)).astype(jnp.float32)
        return y.reshape(*shape[:-1], self.n_out), new_state

    def expert_load(self, params, x) -> jnp.ndarray:
        """Fraction of tokens whose top-1 expert is e (diagnostic)."""
        tokens = jnp.asarray(x).reshape(-1, x.shape[-1])
        top1 = jnp.argmax(tokens @ params["W_router"], axis=-1)
        return jnp.mean(jax.nn.one_hot(top1, self.n_experts), axis=0)
