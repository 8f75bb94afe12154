"""deeplearning4j_tpu — a TPU-native deep learning framework.

A ground-up JAX/XLA/Pallas/pjit re-design with the capabilities of
Deeplearning4j (reference: /root/reference, DL4J 0.8.1-SNAPSHOT): builder
config DSL with JSON round-trip, Sequential + DAG models over a full layer
zoo, fit/evaluate with listeners, early stopping, transfer learning,
checkpoint/resume, gradient-check-first testing, NLP embeddings, DeepWalk,
t-SNE, Keras import, stats/observability — plus TPU-first capabilities the
reference lacked: tensor/pipeline/sequence parallelism over device meshes
with XLA collectives.
"""

__version__ = "0.1.0"

from .nn import (BackpropType, GradientNormalization, InputType,
                 MultiLayerConfiguration, MultiLayerNetwork,
                 NeuralNetConfiguration, NeuralNetConfigurationBuilder,
                 OptimizationAlgorithm)
from .nn.layers import (ActivationLayer, AutoEncoder, BatchNormalization,
                        BernoulliReconstructionDistribution,
                        CenterLossOutputLayer,
                        CompositeReconstructionDistribution,
                        Convolution1DLayer, ConvolutionLayer, ConvolutionMode,
                        DenseLayer, DropoutLayer, EmbeddingLayer,
                        EmbeddingSequenceLayer, TransformerBlock,
                        GaussianReconstructionDistribution,
                        GlobalPoolingLayer, GravesBidirectionalLSTM,
                        GravesLSTM, HybridSSMBlock,
                        LocalResponseNormalization,
                        LossFunctionWrapper, LossLayer, NemotronHBlock,
                        OutputLayer, PoolingType, RBM, RMSNormLayer,
                        RnnOutputLayer,
                        ShortcutMoEBlock, SparseExpertsLayer,
                        SambaYBlock, CrossDecoderBlock, LayerNormLayer,
                        Subsampling1DLayer, SubsamplingLayer,
                        VariationalAutoencoder, ZeroPaddingLayer)
from .nn.updaters import (AdaDelta, AdaGrad, Adam, AdaMax, Nesterovs, NoOp,
                          RmsProp, Sgd)
from .nn.weights import Distribution, WeightInit
from .nn.graph import ComputationGraph
from .nn.conf.graph import (ComputationGraphConfiguration,
                            DuplicateToTimeSeriesVertex, ElementWiseVertex,
                            GraphVertex, L2NormalizeVertex, L2Vertex,
                            LastTimeStepVertex, MergeVertex,
                            PreprocessorVertex, ScaleVertex, ShiftVertex,
                            StackVertex, SubsetVertex, UnstackVertex)
from .nn.transferlearning import (FineTuneConfiguration, TransferLearning,
                                  TransferLearningHelper)
from .datasets import (ArrayDataSetIterator, DataSet, DataSetIterator,
                       DevicePrefetchIterator, MultiDataSet,
                       PadToBatchIterator)
from .eval import (Evaluation, ROC, ROCMultiClass, RegressionEvaluation)
from .util import GradientCheckUtil, ModelSerializer
from . import telemetry
from .telemetry import TelemetryListener, TelemetrySession

__all__ = [
    "BackpropType", "GradientNormalization", "InputType",
    "MultiLayerConfiguration", "MultiLayerNetwork", "NeuralNetConfiguration",
    "NeuralNetConfigurationBuilder", "OptimizationAlgorithm",
    "ActivationLayer", "AutoEncoder", "BatchNormalization",
    "BernoulliReconstructionDistribution", "CenterLossOutputLayer",
    "CompositeReconstructionDistribution", "Convolution1DLayer",
    "ConvolutionLayer", "ConvolutionMode", "DenseLayer", "DropoutLayer",
    "EmbeddingLayer", "EmbeddingSequenceLayer", "TransformerBlock",
    "GaussianReconstructionDistribution",
    "GlobalPoolingLayer", "GravesBidirectionalLSTM", "GravesLSTM",
    "HybridSSMBlock", "LocalResponseNormalization", "LossFunctionWrapper", "LossLayer",
    "NemotronHBlock", "OutputLayer", "PoolingType", "RBM", "RMSNormLayer",
    "RnnOutputLayer",
    "ShortcutMoEBlock", "SparseExpertsLayer",
    "SambaYBlock", "CrossDecoderBlock", "LayerNormLayer",
    "Subsampling1DLayer", "SubsamplingLayer", "VariationalAutoencoder",
    "ZeroPaddingLayer",
    "AdaDelta", "AdaGrad", "Adam", "AdaMax", "Nesterovs", "NoOp", "RmsProp",
    "Sgd", "Distribution", "WeightInit",
    "ComputationGraph", "ComputationGraphConfiguration",
    "DuplicateToTimeSeriesVertex", "ElementWiseVertex", "GraphVertex",
    "L2NormalizeVertex", "L2Vertex", "LastTimeStepVertex", "MergeVertex",
    "PreprocessorVertex", "ScaleVertex", "ShiftVertex", "StackVertex",
    "SubsetVertex", "UnstackVertex",
    "FineTuneConfiguration", "TransferLearning", "TransferLearningHelper",
    "ArrayDataSetIterator", "DataSet", "DataSetIterator",
    "DevicePrefetchIterator", "MultiDataSet", "PadToBatchIterator",
    "Evaluation", "ROC", "ROCMultiClass", "RegressionEvaluation",
    "GradientCheckUtil", "ModelSerializer",
    "telemetry", "TelemetryListener", "TelemetrySession",
]
