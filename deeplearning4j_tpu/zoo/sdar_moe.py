"""SDAR-30B-A3B-Chat (``sdar_moe``; JetLM/SDAR-30B-A3B-Chat ``config.json``):
a pre-norm decoder whose every block has grouped-query attention (per-head
RMSNorm on queries and keys, rotary positions) and a mixture of experts
routed by softmax (top-k probabilities renormalised, no shared expert), an
untied head, trained by **diffusion over blocks** (BD3-LM's training form,
arXiv:2503.09573; SDAR, arXiv:2510.06303): a row of ``T`` clean tokens is cut
into blocks of ``block_length``, some positions of every block are replaced
by the MASK id, and the network runs once on ``[noisy ; clean]`` under the
block-diffusion mask; the loss is over the masked positions of the noisy
half, each weighted by its block's ``block_length / masked``.

Built from the registered layers with the builder DSL, through ``fit``:
features are (batch, 2 T) int32 ids, the noisy row then the clean one;
labels (batch, T) int32, the clean token at the masked positions and -1
elsewhere. Positions and the mask follow from ``T`` and ``block_length``.

It may be ONE CHIP'S SHARE of an expert-parallel, pipelined deployment:
``held_experts = (first, count)`` of ``n_experts`` (the router keeps its
width), a slice of the vocabulary, and ``layers_here``, the published
0-based numbers of the layers this chip holds. Embedding, final norm and
head are always here.
"""

from typing import Optional, Sequence, Tuple

from deeplearning4j_tpu.nn import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.attention_layers import DecoderBlock, GroupedQueryAttention, RMSNormLayer
from deeplearning4j_tpu.nn.core_layers import EmbeddingSequenceLayer
from deeplearning4j_tpu.nn.moe_layers import MixtureOfExperts
from deeplearning4j_tpu.nn.recurrent_layers import BlockDiffusionLoss
from deeplearning4j_tpu.train.updaters import Adam
from deeplearning4j_tpu.zoo.base import ZooModel


class SdarMoe(ZooModel):
    def __init__(self, vocab_size: int = 151936, d_model: int = 2048, n_layers: int = 48,
                 layers_here: Optional[Sequence[int]] = None, n_heads: int = 32, n_kv_heads: int = 4,
                 head_dim: int = 128, rope_theta: float = 1e6, expert_size: int = 768, n_experts: int = 128,
                 held_experts: Optional[Tuple[int, int]] = None, held_rows: Optional[int] = None, top_k: int = 8,
                 block_length: int = 4, eps: float = 1e-6, seed: int = 123, updater=None):
        super().__init__(num_classes=vocab_size, seed=seed)
        layers_here = sorted(range(n_layers) if layers_here is None else layers_here)
        if len(set(layers_here)) != len(layers_here) or not all(0 <= i < n_layers for i in layers_here):
            raise ValueError(f"layers_here={layers_here} are not distinct layers of 0..{n_layers - 1}")
        self.vocab_size, self.d_model, self.eps, self.block_length = vocab_size, d_model, eps, block_length
        self.layers_here = layers_here
        self.updater = updater or Adam(2e-4, beta2=0.95)
        self.attention = dict(n_heads=n_heads, n_kv_heads=n_kv_heads, head_dim=head_dim, rope_theta=rope_theta,
                              eps=eps, block_diffusion=block_length)
        self.moe = dict(n_out=d_model, hidden_size=expert_size, n_experts=n_experts, held=held_experts,
                        held_rows=held_rows, top_k=top_k, router="softmax", gated=True, activation="swish",
                        aux_loss_coef=0.0)

    @staticmethod
    def tiny(**kw) -> "SdarMoe":
        """A few thousand parameters, for tests: two blocks, two query heads a key/value head."""
        cfg = dict(vocab_size=96, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2, head_dim=16, expert_size=24,
                   n_experts=8, top_k=2)
        cfg.update(kw)
        return SdarMoe(**cfg)

    def conf(self):
        b = (NeuralNetConfiguration.builder()
             .seed(self.seed)
             .updater(self.updater)
             .weight_init("normal")
             .list()
             .layer(EmbeddingSequenceLayer(n_in=self.vocab_size, n_out=self.d_model)))
        for _ in self.layers_here:
            b.layer(DecoderBlock(mixer=GroupedQueryAttention(**self.attention), mlp=MixtureOfExperts(**self.moe),
                                 eps=self.eps))
        return (b.layer(RMSNormLayer(eps=self.eps))
                .layer(BlockDiffusionLoss(n_out=self.vocab_size, has_bias=False, activation="softmax",
                                          block_length=self.block_length))
                .set_input_type(InputType.recurrent(1))  # int token ids (b, 2 t)
                .build())
