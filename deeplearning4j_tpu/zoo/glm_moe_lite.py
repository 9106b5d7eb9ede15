"""GLM-4.7-Flash (``glm4_moe_lite``; zai-org/GLM-4.7-Flash ``config.json``):
a pre-norm decoder whose every block has rotary latent attention with a
low-rank query; one leading dense SwiGLU layer, then sigmoid-routed experts
with a shared one; an untied head trained on the next token, and one
multi-token-prediction layer (DeepSeek-V3's form, arXiv:2412.19437 §2.2)
that shares the embedding and the head and is trained on the token after
next: the training loss is ``L_main + mtp_weight * L_mtp``.

Built from the registered layers with the builder DSL; a causal language
model through ``fit``: features are (batch, time) int32 ids, labels the
(batch, time) int32 ids of the next tokens.

It may be ONE CHIP'S SHARE of an expert-parallel, pipelined deployment:
``held_experts = (first, count)`` of ``n_experts`` (the router keeps its
width), a slice of the vocabulary, and ``layers_here``, the published
0-based numbers of the layers this chip holds: the trunk's layers count
from 0 (those below ``first_k_dense`` have the dense MLP) and the number
after the last of them, ``n_layers``, is the prediction layer, where this
family's checkpoints store it. Embedding, final norm and head are always
here.
"""

from typing import Optional, Sequence, Tuple

from deeplearning4j_tpu.nn import (InputType, NeuralNetConfiguration, RnnOutputLayer)
from deeplearning4j_tpu.nn.attention_layers import (DecoderBlock, GatedMLP, LatentAttention,
                                                    MultiTokenPrediction, RMSNormLayer)
from deeplearning4j_tpu.nn.core_layers import EmbeddingSequenceLayer
from deeplearning4j_tpu.nn.moe_layers import MixtureOfExperts
from deeplearning4j_tpu.train.updaters import Adam
from deeplearning4j_tpu.zoo.base import ZooModel


class GlmMoeLite(ZooModel):
    def __init__(self, vocab_size: int = 154880, d_model: int = 2048, n_layers: int = 47,
                 layers_here: Optional[Sequence[int]] = None, mtp: bool = True, mtp_weight: float = 0.3,
                 n_heads: int = 20, q_rank: int = 768, kv_rank: int = 512, qk_nope_dim: int = 192,
                 qk_shared_dim: int = 64, v_dim: int = 256, rope_theta: float = 1e6,
                 dense_size: int = 10240, first_k_dense: int = 1,
                 expert_size: int = 1536, n_experts: int = 64, held_experts: Optional[Tuple[int, int]] = None,
                 held_rows: Optional[int] = None, top_k: int = 4, n_shared: int = 1,
                 routed_scale: float = 1.8, eps: float = 1e-5,
                 seed: int = 123, updater=None):
        super().__init__(num_classes=vocab_size, seed=seed)
        if layers_here is None:
            layers_here = range(n_layers + 1)
        layers_here = sorted(layers_here)
        if len(set(layers_here)) != len(layers_here) or not all(0 <= i <= n_layers for i in layers_here):
            raise ValueError(f"layers_here={layers_here} are not distinct layers of 0..{n_layers} "
                             f"({n_layers} is the prediction layer)")
        self.vocab_size, self.d_model, self.eps = vocab_size, d_model, eps
        self.trunk_layers = [i for i in layers_here if i < n_layers]
        self.mtp = bool(mtp) and n_layers in layers_here
        self.mtp_weight, self.first_k_dense, self.dense_size = mtp_weight, first_k_dense, dense_size
        self.updater = updater or Adam(2e-4, beta2=0.95)
        self.mla = dict(n_heads=n_heads, q_rank=q_rank, kv_rank=kv_rank, qk_nope_dim=qk_nope_dim,
                        qk_shared_dim=qk_shared_dim, v_dim=v_dim, rope_theta=rope_theta)
        self.moe = dict(n_out=d_model, hidden_size=expert_size, n_experts=n_experts, held=held_experts,
                        held_rows=held_rows, top_k=top_k, n_shared=n_shared, routed_scale=routed_scale,
                        router="sigmoid", gated=True, activation="swish", aux_loss_coef=0.0)

    @staticmethod
    def tiny(**kw) -> "GlmMoeLite":
        """A few thousand parameters with every kind of layer, for tests: a
        dense block, two expert blocks, the prediction layer."""
        cfg = dict(vocab_size=96, d_model=32, n_layers=3, n_heads=2, q_rank=12, kv_rank=16, qk_nope_dim=16,
                   qk_shared_dim=8, v_dim=24, dense_size=64, expert_size=24, n_experts=8, top_k=2)
        cfg.update(kw)
        return GlmMoeLite(**cfg)

    def _block(self, dense: bool) -> DecoderBlock:
        mlp = GatedMLP(hidden_size=self.dense_size) if dense else MixtureOfExperts(**self.moe)
        return DecoderBlock(mixer=LatentAttention(eps=self.eps, **self.mla), mlp=mlp, eps=self.eps)

    def conf(self):
        def head():
            return RnnOutputLayer(n_out=self.vocab_size, has_bias=False, activation="softmax",
                                  loss="sparse_mcxent", record_loss=self.mtp)

        b = (NeuralNetConfiguration.builder()
             .seed(self.seed)
             .updater(self.updater)
             .weight_init("normal")
             .list()
             .layer(EmbeddingSequenceLayer(n_in=self.vocab_size, n_out=self.d_model)))
        for i in self.trunk_layers:
            b.layer(self._block(dense=i < self.first_k_dense))
        if self.mtp:
            # layer keys are ``layer_<position>``: the embedding is first, the head comes two after this one
            here = len(self.trunk_layers) + 1
            b.layer(MultiTokenPrediction(block=self._block(dense=False), head=head(), weight=self.mtp_weight,
                                         eps=self.eps, tied={"embed": "layer_0", "head": f"layer_{here + 2}"}))
        return (b.layer(RMSNormLayer(eps=self.eps))
                .layer(head())
                .set_input_type(InputType.recurrent(1))  # int token ids (b, t)
                .build())
