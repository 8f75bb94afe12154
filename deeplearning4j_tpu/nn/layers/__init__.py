"""Layer zoo. Config+impl unified dataclasses (see `nn/conf/base.py`)."""
from .feedforward import (
    DenseLayer, OutputLayer, LossLayer, ActivationLayer, DropoutLayer,
    EmbeddingLayer, BaseOutputLayerConf,
)
from .convolution import (
    ConvolutionLayer, Convolution1DLayer, SubsamplingLayer,
    Subsampling1DLayer, ZeroPaddingLayer, ConvolutionMode, PoolingType,
)
from .normalization import BatchNormalization, LocalResponseNormalization
from .pooling import GlobalPoolingLayer
from .recurrent import (GravesLSTM, GravesBidirectionalLSTM, RnnOutputLayer,
                        BaseRecurrentLayer, LastTimeStep)
from .generative import (AutoEncoder, RBM, VariationalAutoencoder,
                         CenterLossOutputLayer,
                         GaussianReconstructionDistribution,
                         BernoulliReconstructionDistribution,
                         CompositeReconstructionDistribution,
                         LossFunctionWrapper)
from .moe import MixtureOfExpertsLayer
from .transformer import EmbeddingSequenceLayer, TransformerBlock
from .shortcut_moe import RMSNormLayer, ShortcutMoEBlock, SparseExpertsLayer
from .hybrid_ssm import HybridSSMBlock
from .sambay import CrossDecoderBlock, LayerNormLayer, SambaYBlock
from .nemotron_h import NemotronHBlock

__all__ = [
    "DenseLayer", "OutputLayer", "LossLayer", "ActivationLayer",
    "DropoutLayer", "EmbeddingLayer", "BaseOutputLayerConf",
    "ConvolutionLayer", "Convolution1DLayer", "SubsamplingLayer",
    "Subsampling1DLayer", "ZeroPaddingLayer", "ConvolutionMode",
    "PoolingType", "BatchNormalization", "LocalResponseNormalization",
    "GlobalPoolingLayer",
    "GravesLSTM", "GravesBidirectionalLSTM", "RnnOutputLayer",
    "BaseRecurrentLayer", "LastTimeStep",
    "AutoEncoder", "RBM", "VariationalAutoencoder", "CenterLossOutputLayer",
    "GaussianReconstructionDistribution", "BernoulliReconstructionDistribution",
    "CompositeReconstructionDistribution", "LossFunctionWrapper",
    "MixtureOfExpertsLayer",
    "EmbeddingSequenceLayer", "TransformerBlock",
    "RMSNormLayer", "ShortcutMoEBlock", "SparseExpertsLayer",
    "HybridSSMBlock", "SambaYBlock", "CrossDecoderBlock", "LayerNormLayer",
    "NemotronHBlock",
]
