"""Neural-network configuration DSL: config-as-data layers + shape inference.

TPU-native rebuild of the reference's ``org.deeplearning4j.nn.conf`` package:
builder-style, JSON-round-trippable layer configs with an ``InputType`` shape
inference system and automatic ``InputPreProcessor`` insertion. Unlike the
reference there are no separate conf/impl class pairs — a layer config *is*
the implementation (pure ``init``/``forward`` functions), and the whole
network forward composes into one XLA program.
"""

from deeplearning4j_tpu.nn.base import GlobalConfig, Layer, get_layer_class, register_layer
from deeplearning4j_tpu.nn.constraints import (
    DropConnect,
    MaxNormConstraint,
    MinMaxNormConstraint,
    NonNegativeConstraint,
    UnitNormConstraint,
    WeightNoise,
)
from deeplearning4j_tpu.nn.inputs import InputType
from deeplearning4j_tpu.nn.config import (
    ListBuilder,
    MultiLayerConfiguration,
    NeuralNetConfiguration,
)
from deeplearning4j_tpu.nn.core_layers import (
    ActivationLayer,
    DenseLayer,
    DropoutLayer,
    EmbeddingLayer,
    EmbeddingSequenceLayer,
    LossLayer,
    OutputLayer,
)
from deeplearning4j_tpu.nn.conv_layers import (
    BatchNormalization,
    Convolution1DLayer,
    ConvolutionLayer,
    Deconvolution2D,
    GlobalPoolingLayer,
    LocalResponseNormalization,
    PoolingType,
    SeparableConvolution2D,
    SpaceToDepthLayer,
    SubsamplingLayer,
    Upsampling2D,
    ZeroPaddingLayer,
)
from deeplearning4j_tpu.nn.recurrent_layers import (
    GRU,
    LSTM,
    Bidirectional,
    GravesLSTM,
    LastTimeStep,
    RnnOutputLayer,
    SimpleRnn,
)
from deeplearning4j_tpu.nn.attention_layers import (
    BertEmbeddingLayer,
    ClsPoolingLayer,
    LearnedPositionalEmbeddingLayer,
    SelfAttentionLayer,
    TransformerEncoderBlock,
    DecoderBlock,
    GatedMLP,
    LatentAttention,
    MultiTokenPrediction,
    RMSNormLayer,
)
from deeplearning4j_tpu.nn.linear_attention_layers import KimiDeltaAttention
from deeplearning4j_tpu.nn.extra_layers import (
    CenterLossOutputLayer,
    Convolution3D,
    Cropping2D,
    ConvLSTM2D,
    LocallyConnected1D,
    LocallyConnected2D,
    PermuteLayer,
    SeparableConvolution1D,
    Subsampling1DLayer,
    Subsampling3DLayer,
    Upsampling1D,
    Upsampling3D,
    Yolo2OutputLayer,
)
from deeplearning4j_tpu.nn.autoencoder_layers import (
    AutoEncoder,
    VariationalAutoencoder,
)
from deeplearning4j_tpu.nn.moe_layers import MixtureOfExperts
from deeplearning4j_tpu.nn.misc_layers import (
    Cropping1D,
    FlattenLayer,
    ElementWiseMultiplicationLayer,
    MaskZeroLayer,
    PReLULayer,
    RepeatVector,
    TimeDistributed,
    ZeroPadding1DLayer,
)

__all__ = [
    "GlobalConfig",
    "Layer",
    "register_layer",
    "get_layer_class",
    "InputType",
    "NeuralNetConfiguration",
    "MultiLayerConfiguration",
    "ListBuilder",
    "DenseLayer",
    "OutputLayer",
    "LossLayer",
    "ActivationLayer",
    "DropoutLayer",
    "EmbeddingLayer",
    "EmbeddingSequenceLayer",
    "ConvolutionLayer",
    "Convolution1DLayer",
    "SubsamplingLayer",
    "PoolingType",
    "BatchNormalization",
    "LocalResponseNormalization",
    "Upsampling2D",
    "ZeroPaddingLayer",
    "SeparableConvolution2D",
    "Deconvolution2D",
    "SpaceToDepthLayer",
    "GlobalPoolingLayer",
    "LSTM",
    "GravesLSTM",
    "GRU",
    "SimpleRnn",
    "Bidirectional",
    "LastTimeStep",
    "RnnOutputLayer",
    "SelfAttentionLayer",
    "TransformerEncoderBlock",
    "DecoderBlock",
    "GatedMLP",
    "LatentAttention",
    "MultiTokenPrediction",
    "RMSNormLayer",
    "KimiDeltaAttention",
    "LearnedPositionalEmbeddingLayer",
    "BertEmbeddingLayer",
    "ClsPoolingLayer",
    "Convolution3D",
    "Subsampling3DLayer",
    "Upsampling1D",
    "Upsampling3D",
    "Cropping2D",
    "ConvLSTM2D",
    "LocallyConnected1D",
    "LocallyConnected2D",
    "FlattenLayer",
    "MaxNormConstraint",
    "MinMaxNormConstraint",
    "UnitNormConstraint",
    "NonNegativeConstraint",
    "DropConnect",
    "WeightNoise",
    "PermuteLayer",
    "SeparableConvolution1D",
    "Subsampling1DLayer",
    "CenterLossOutputLayer",
    "Yolo2OutputLayer",
    "AutoEncoder",
    "MixtureOfExperts",
    "VariationalAutoencoder",
    "PReLULayer",
    "ElementWiseMultiplicationLayer",
    "RepeatVector",
    "MaskZeroLayer",
    "TimeDistributed",
    "Cropping1D",
    "ZeroPadding1DLayer",
]
