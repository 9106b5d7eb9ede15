"""LFM2-24B-A2B (``lfm2_moe``; LiquidAI/LFM2-24B-A2B ``config.json``): a
pre-norm decoder whose mixers are, by the published ``layer_types``, gated
short convolutions (``conv``) or causal grouped-query attention with per-head
RMSNorm on queries and keys and rotary positions (``full_attention``); the
``n_dense`` leading layers have a dense SwiGLU, the rest sigmoid-routed
experts with a selection bias and no shared expert (the bias moves by
``bias_rate`` a training step towards an even load: the family publishes no
rate, 1e-3 is DeepSeek-V3's); a final RMSNorm and a
head tied to the embedding, trained on the next token.

Built from the registered layers with the builder DSL; a causal language
model through ``fit``: features are (batch, time) int32 ids, labels the
(batch, time) int32 ids of the next tokens.

It may be ONE CHIP'S SHARE of an expert-parallel, pipelined deployment:
``held_experts = (first, count)`` of ``n_experts`` (the router keeps its
width), a slice of the vocabulary, and ``layers_here``, the published
0-based numbers of the layers this chip holds. Embedding, final norm and
the tied head are always here.
"""

from typing import Optional, Sequence, Tuple

from deeplearning4j_tpu.nn import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.attention_layers import DecoderBlock, GatedMLP, GroupedQueryAttention, RMSNormLayer
from deeplearning4j_tpu.nn.core_layers import EmbeddingSequenceLayer
from deeplearning4j_tpu.nn.linear_attention_layers import GatedShortConv
from deeplearning4j_tpu.nn.moe_layers import MixtureOfExperts
from deeplearning4j_tpu.nn.recurrent_layers import TiedRnnOutputLayer
from deeplearning4j_tpu.train.updaters import Adam
from deeplearning4j_tpu.zoo.base import ZooModel

# the published model's 40 layers: full attention at 2, 6, .., 38, the gated short convolution elsewhere
LAYER_TYPES = tuple("full_attention" if i % 4 == 2 else "conv" for i in range(40))


class Lfm2Moe(ZooModel):
    def __init__(self, vocab_size: int = 65536, d_model: int = 2048, layer_types: Sequence[str] = LAYER_TYPES,
                 layers_here: Optional[Sequence[int]] = None, n_heads: int = 32, n_kv_heads: int = 8,
                 rope_theta: float = 1e6, conv_size: int = 3, dense_size: int = 11776, n_dense: int = 2,
                 expert_size: int = 1536, n_experts: int = 64, held_experts: Optional[Tuple[int, int]] = None,
                 held_rows: Optional[int] = None, top_k: int = 4, routed_scale: float = 1.0, bias_rate: float = 1e-3,
                 eps: float = 1e-5, seed: int = 123, updater=None):
        super().__init__(num_classes=vocab_size, seed=seed)
        if set(layer_types) - {"conv", "full_attention"}:
            raise ValueError(f"layer_types={list(layer_types)} name a mixer other than conv and full_attention")
        layers_here = sorted(range(len(layer_types)) if layers_here is None else layers_here)
        if len(set(layers_here)) != len(layers_here) or not all(0 <= i < len(layer_types) for i in layers_here):
            raise ValueError(f"layers_here={layers_here} are not distinct layers of 0..{len(layer_types) - 1}")
        self.vocab_size, self.d_model, self.eps = vocab_size, d_model, eps
        self.layer_types, self.layers_here, self.n_dense, self.dense_size = tuple(layer_types), layers_here, n_dense, dense_size
        self.updater = updater or Adam(2e-4, beta2=0.95)
        self.conv_size = conv_size
        self.attention = dict(n_heads=n_heads, n_kv_heads=n_kv_heads, head_dim=d_model // n_heads,
                              rope_theta=rope_theta, eps=eps)
        self.moe = dict(n_out=d_model, hidden_size=expert_size, n_experts=n_experts, held=held_experts,
                        held_rows=held_rows, top_k=top_k, routed_scale=routed_scale, bias_rate=bias_rate,
                        router="sigmoid", gated=True, activation="swish", aux_loss_coef=0.0)

    @staticmethod
    def tiny(**kw) -> "Lfm2Moe":
        """A few thousand parameters with every kind of layer, for tests:
        conv + dense, then one period (attention, conv, conv, conv) with
        experts; four query heads a key/value head."""
        cfg = dict(vocab_size=96, d_model=32, layer_types=LAYER_TYPES[:6], n_heads=4, n_kv_heads=1,
                   dense_size=64, n_dense=1, expert_size=24, n_experts=8, top_k=2)
        cfg.update(kw)
        return Lfm2Moe(**cfg)

    def _block(self, i: int) -> DecoderBlock:
        mixer = (GatedShortConv(kernel_size=self.conv_size) if self.layer_types[i] == "conv"
                 else GroupedQueryAttention(**self.attention))
        mlp = GatedMLP(hidden_size=self.dense_size) if i < self.n_dense else MixtureOfExperts(**self.moe)
        return DecoderBlock(mixer=mixer, mlp=mlp, eps=self.eps)

    def conf(self):
        b = (NeuralNetConfiguration.builder()
             .seed(self.seed)
             .updater(self.updater)
             .weight_init("normal")
             .list()
             .layer(EmbeddingSequenceLayer(n_in=self.vocab_size, n_out=self.d_model)))
        for i in self.layers_here:
            b.layer(self._block(i))
        return (b.layer(RMSNormLayer(eps=self.eps))
                .layer(TiedRnnOutputLayer(n_out=self.vocab_size, has_bias=False, activation="softmax",
                                          loss="sparse_mcxent", tied={"W": "layer_0/W"}))
                .set_input_type(InputType.recurrent(1))  # int token ids (b, t)
                .build())
