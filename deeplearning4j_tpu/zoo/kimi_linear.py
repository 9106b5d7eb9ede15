"""Kimi Linear (Kimi Team 2025, arXiv:2510.26692): a pre-norm decoder whose
mixers are Kimi Delta Attention (a gated delta rule with a per-channel
decay) and, every fourth layer, latent attention without rotary; one
leading dense SwiGLU layer, then sigmoid-routed experts with a shared one;
an untied head trained on the next token.

The model is built from the registered layers with the builder DSL and is a
causal language model through ``fit``: features are (batch, time) int32
ids, labels the (batch, time) int32 ids of the next tokens.

It may be ONE CHIP'S SHARE of an expert-parallel deployment: ``held_experts
= (first, count)`` of ``n_experts`` (the router keeps its width), a slice of
the vocabulary, some of the layers. ``kda_layers`` and ``full_attn_layers``
are the published 1-based layer numbers; layers up to ``first_k_dense``
have the dense MLP.
"""

from typing import Optional, Sequence, Tuple

from deeplearning4j_tpu.nn import (InputType, NeuralNetConfiguration, RnnOutputLayer)
from deeplearning4j_tpu.nn.attention_layers import DecoderBlock, GatedMLP, LatentAttention, RMSNormLayer
from deeplearning4j_tpu.nn.core_layers import EmbeddingSequenceLayer
from deeplearning4j_tpu.nn.linear_attention_layers import KimiDeltaAttention
from deeplearning4j_tpu.nn.moe_layers import MixtureOfExperts
from deeplearning4j_tpu.train.updaters import Adam
from deeplearning4j_tpu.zoo.base import ZooModel


class KimiLinear(ZooModel):
    def __init__(self, vocab_size: int = 163840, d_model: int = 2304, n_layers: int = 27,
                 kda_layers: Optional[Sequence[int]] = None, full_attn_layers: Optional[Sequence[int]] = None,
                 n_heads: int = 32, kda_head_dim: int = 128, conv_size: int = 4, kda_gate_rank: int = 128,
                 kv_rank: int = 512, qk_nope_dim: int = 128, qk_shared_dim: int = 64, v_dim: int = 128,
                 dense_size: int = 9216, first_k_dense: int = 1,
                 expert_size: int = 1024, n_experts: int = 256, held_experts: Optional[Tuple[int, int]] = None,
                 held_rows: Optional[int] = None, top_k: int = 8, n_shared: int = 1,
                 routed_scale: float = 2.446, eps: float = 1e-5,
                 seed: int = 123, updater=None):
        super().__init__(num_classes=vocab_size, seed=seed)
        if full_attn_layers is None:  # the published pattern: every fourth layer, and the last
            full_attn_layers = sorted(set(range(4, n_layers + 1, 4)) | {n_layers})
        if kda_layers is None:
            kda_layers = [i for i in range(1, n_layers + 1) if i not in full_attn_layers]
        if sorted([*kda_layers, *full_attn_layers]) != list(range(1, n_layers + 1)):
            raise ValueError(f"the delta-rule layers={list(kda_layers)} and the full-attention "
                             f"layers={list(full_attn_layers)} do not make up layers 1..{n_layers}")
        self.vocab_size, self.d_model, self.n_layers = vocab_size, d_model, n_layers
        self.kda_layers, self.first_k_dense = set(kda_layers), first_k_dense
        self.eps = eps
        self.updater = updater or Adam(2e-4, beta2=0.95)
        self.kda = dict(n_heads=n_heads, head_dim=kda_head_dim, conv_size=conv_size, gate_rank=kda_gate_rank)
        self.mla = dict(n_heads=n_heads, kv_rank=kv_rank, qk_nope_dim=qk_nope_dim,
                        qk_shared_dim=qk_shared_dim, v_dim=v_dim)
        self.dense_size = dense_size
        self.moe = dict(n_out=d_model, hidden_size=expert_size, n_experts=n_experts, held=held_experts,
                        held_rows=held_rows, top_k=top_k, n_shared=n_shared, routed_scale=routed_scale,
                        router="sigmoid", gated=True, activation="swish", aux_loss_coef=0.0)

    @staticmethod
    def tiny(**kw) -> "KimiLinear":
        """A few thousand parameters with every kind of layer, for tests:
        KDA + dense, KDA + MoE, KDA + MoE, MLA + MoE."""
        cfg = dict(vocab_size=96, d_model=32, n_layers=4, n_heads=2, kda_head_dim=16, kda_gate_rank=8,
                   kv_rank=16, qk_nope_dim=16, qk_shared_dim=8, v_dim=16, dense_size=64, expert_size=24,
                   n_experts=8, top_k=2)
        cfg.update(kw)
        return KimiLinear(**cfg)

    def conf(self):
        b = (NeuralNetConfiguration.builder()
             .seed(self.seed)
             .updater(self.updater)
             .weight_init("normal")
             .list()
             .layer(EmbeddingSequenceLayer(n_in=self.vocab_size, n_out=self.d_model)))
        for i in range(1, self.n_layers + 1):
            mixer = (KimiDeltaAttention(eps=self.eps, **self.kda) if i in self.kda_layers
                     else LatentAttention(eps=self.eps, **self.mla))
            mlp = (GatedMLP(hidden_size=self.dense_size) if i <= self.first_k_dense
                   else MixtureOfExperts(**self.moe))
            b.layer(DecoderBlock(mixer=mixer, mlp=mlp, eps=self.eps))
        return (b.layer(RMSNormLayer(eps=self.eps))
                .layer(RnnOutputLayer(n_out=self.vocab_size, has_bias=False, activation="softmax",
                                      loss="sparse_mcxent"))
                .set_input_type(InputType.recurrent(1))  # int token ids (b, t)
                .build())
