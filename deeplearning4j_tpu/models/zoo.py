"""Model zoo — the configurations an earlier installation's capture
(BASELINE.md) was taken on.

LeNet-MNIST mirrors the reference's canonical MNIST CNN example topology
(Conv 5x5x20 → maxpool → Conv 5x5x50 → maxpool → Dense 500 → softmax 10),
the config DL4J ships in its examples and the first BASELINE config.
"""
from __future__ import annotations

import numpy as np

from ..nn.conf import InputType, NeuralNetConfiguration
from ..nn.layers import (ConvolutionLayer, ConvolutionMode, DenseLayer,
                         OutputLayer, PoolingType, SubsamplingLayer)
from ..nn.multilayer import MultiLayerNetwork
from ..nn.updaters import Adam, Nesterovs

__all__ = ["lenet_mnist", "mlp_mnist", "char_rnn", "resnet50",
           "vgg16", "vgg19", "alexnet", "googlenet", "sample_characters"]


def lenet_mnist(seed: int = 42, updater=None) -> MultiLayerNetwork:
    conf = (NeuralNetConfiguration.builder()
            .seed(seed)
            .updater(updater or Nesterovs(learning_rate=0.01, momentum=0.9))
            .l2(5e-4)
            .list()
            .layer(ConvolutionLayer(n_out=20, kernel_size=(5, 5),
                                    stride=(1, 1), activation="identity",
                                    convolution_mode=ConvolutionMode.TRUNCATE))
            .layer(SubsamplingLayer(pooling_type=PoolingType.MAX,
                                    kernel_size=(2, 2), stride=(2, 2)))
            .layer(ConvolutionLayer(n_out=50, kernel_size=(5, 5),
                                    stride=(1, 1), activation="identity"))
            .layer(SubsamplingLayer(pooling_type=PoolingType.MAX,
                                    kernel_size=(2, 2), stride=(2, 2)))
            .layer(DenseLayer(n_out=500, activation="relu"))
            .layer(OutputLayer(n_out=10, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.convolutional_flat(28, 28, 1))
            .build())
    return MultiLayerNetwork(conf)


def mlp_mnist(seed: int = 42) -> MultiLayerNetwork:
    conf = (NeuralNetConfiguration.builder()
            .seed(seed).updater(Adam(1e-3))
            .list()
            .layer(DenseLayer(n_out=1024, activation="relu"))
            .layer(DenseLayer(n_out=1024, activation="relu"))
            .layer(OutputLayer(n_out=10, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(784))
            .build())
    return MultiLayerNetwork(conf)


def char_rnn(vocab_size: int = 77, lstm_size: int = 200, seq_len: int = 64,
             seed: int = 42, tbptt: int = 50) -> MultiLayerNetwork:
    """GravesLSTM char-RNN (BASELINE config #3) — the reference's
    char-modelling example topology: 2xLSTM + RnnOutputLayer, TBPTT."""
    from ..nn.conf import BackpropType
    from ..nn.layers import GravesLSTM, RnnOutputLayer

    conf = (NeuralNetConfiguration.builder()
            .seed(seed)
            .updater(Adam(2e-3))
            .list()
            .layer(GravesLSTM(n_out=lstm_size, activation="tanh"))
            .layer(GravesLSTM(n_out=lstm_size, activation="tanh"))
            .layer(RnnOutputLayer(n_out=vocab_size, activation="softmax",
                                  loss="mcxent"))
            .set_input_type(InputType.recurrent(vocab_size, seq_len))
            .backprop_type(BackpropType.TRUNCATED_BPTT)
            .t_bptt_forward_length(tbptt)
            .t_bptt_backward_length(tbptt)
            .build())
    return MultiLayerNetwork(conf)


def resnet50(n_classes: int = 1000, image: int = 224, seed: int = 42,
             updater=None, blocks=(3, 4, 6, 3), width: int = 64,
             compute_dtype: str | None = "bfloat16",
             remat: str | None = None,
             activation_store_dtype: str | None = None):
    """ResNet-50 as a ComputationGraph (BASELINE config #2): bottleneck
    residual blocks via ElementWiseVertex(add) — the reference expresses
    ResNet the same way with its vertex API. NHWC, bottleneck 1-3-1 convs,
    BN+ReLU. Default policy: bf16 compute on the MXU, f32 master weights."""
    from ..nn.conf import InputType
    from ..nn.conf.graph import ElementWiseVertex
    from ..nn.graph import ComputationGraph
    from ..nn.layers import (ActivationLayer, BatchNormalization,
                             GlobalPoolingLayer)

    b = (NeuralNetConfiguration.builder()
         .seed(seed)
         .updater(updater or Adam(1e-3))
         .weight_init("relu")
         .compute_dtype(compute_dtype)
         .remat(remat)
         .activation_store_dtype(activation_store_dtype)
         .graph_builder()
         .add_inputs("input")
         .set_input_types(InputType.convolutional(image, image, 3)))

    def conv_bn_relu(name, inp, n_out, k, s, relu=True):
        b.add_layer(f"{name}_conv",
                    ConvolutionLayer(n_out=n_out, kernel_size=(k, k),
                                     stride=(s, s), activation="identity",
                                     convolution_mode=ConvolutionMode.SAME,
                                     has_bias=False), inp)
        b.add_layer(f"{name}_bn",
                    BatchNormalization(activation="relu" if relu else "identity"),
                    f"{name}_conv")
        return f"{name}_bn"

    top = conv_bn_relu("stem", "input", width, 7, 2)
    b.add_layer("stem_pool",
                SubsamplingLayer(pooling_type=PoolingType.MAX,
                                 kernel_size=(3, 3), stride=(2, 2),
                                 convolution_mode=ConvolutionMode.SAME),
                top)
    top = "stem_pool"

    ch = width
    for stage, n_blocks in enumerate(blocks):
        out_ch = ch * 4
        for blk in range(n_blocks):
            name = f"s{stage}b{blk}"
            stride = 2 if (blk == 0 and stage > 0) else 1
            t1 = conv_bn_relu(f"{name}_1", top, ch, 1, stride)
            t2 = conv_bn_relu(f"{name}_2", t1, ch, 3, 1)
            t3 = conv_bn_relu(f"{name}_3", t2, out_ch, 1, 1, relu=False)
            if blk == 0:
                sc = conv_bn_relu(f"{name}_sc", top, out_ch, 1, stride,
                                  relu=False)
            else:
                sc = top
            b.add_vertex(f"{name}_add", ElementWiseVertex(op="add"), t3, sc)
            b.add_layer(f"{name}_relu", ActivationLayer(activation="relu"),
                        f"{name}_add")
            top = f"{name}_relu"
        ch *= 2

    b.add_layer("avgpool", GlobalPoolingLayer(pooling_type=PoolingType.AVG),
                top)
    b.add_layer("fc", OutputLayer(n_out=n_classes, activation="softmax",
                                  loss="mcxent"), "avgpool")
    b.set_outputs("fc")
    return ComputationGraph(b.build())


def _vgg(cfg, n_classes, image, seed, updater) -> MultiLayerNetwork:
    b = (NeuralNetConfiguration.builder()
         .seed(seed)
         .updater(updater or Nesterovs(learning_rate=0.01, momentum=0.9))
         .weight_init("relu")
         .list())
    for v in cfg:
        if v == "M":
            b.layer(SubsamplingLayer(pooling_type=PoolingType.MAX,
                                     kernel_size=(2, 2), stride=(2, 2)))
        else:
            b.layer(ConvolutionLayer(n_out=v, kernel_size=(3, 3),
                                     stride=(1, 1), activation="relu",
                                     convolution_mode=ConvolutionMode.SAME))
    b.layer(DenseLayer(n_out=4096, activation="relu"))
    b.layer(DenseLayer(n_out=4096, activation="relu"))
    b.layer(OutputLayer(n_out=n_classes, activation="softmax", loss="mcxent"))
    conf = b.set_input_type(InputType.convolutional(image, image, 3)).build()
    return MultiLayerNetwork(conf)


def vgg16(n_classes: int = 1000, image: int = 224, seed: int = 42,
          updater=None) -> MultiLayerNetwork:
    """VGG-16 (BASELINE config #5 uses this for multi-host data parallel).
    Mirrors the reference's TrainedModels.VGG16 topology."""
    cfg = [64, 64, "M", 128, 128, "M", 256, 256, 256, "M",
           512, 512, 512, "M", 512, 512, 512, "M"]
    return _vgg(cfg, n_classes, image, seed, updater)


def alexnet(n_classes: int = 1000, image: int = 224, seed: int = 42,
            updater=None) -> MultiLayerNetwork:
    """AlexNet (Krizhevsky 2012, single-tower variant — the topology the
    reference era's model zoo shipped). NHWC; LRN after the first two conv
    blocks as in the paper."""
    from ..nn.conf import InputType
    from ..nn.layers import LocalResponseNormalization

    b = (NeuralNetConfiguration.builder()
         .seed(seed)
         .updater(updater or Nesterovs(learning_rate=0.01, momentum=0.9))
         .weight_init("relu")
         .list()
         .layer(ConvolutionLayer(n_out=96, kernel_size=(11, 11),
                                 stride=(4, 4), activation="relu",
                                 convolution_mode=ConvolutionMode.SAME))
         .layer(LocalResponseNormalization())
         .layer(SubsamplingLayer(pooling_type=PoolingType.MAX,
                                 kernel_size=(3, 3), stride=(2, 2)))
         .layer(ConvolutionLayer(n_out=256, kernel_size=(5, 5),
                                 stride=(1, 1), activation="relu",
                                 convolution_mode=ConvolutionMode.SAME))
         .layer(LocalResponseNormalization())
         .layer(SubsamplingLayer(pooling_type=PoolingType.MAX,
                                 kernel_size=(3, 3), stride=(2, 2)))
         .layer(ConvolutionLayer(n_out=384, kernel_size=(3, 3),
                                 stride=(1, 1), activation="relu",
                                 convolution_mode=ConvolutionMode.SAME))
         .layer(ConvolutionLayer(n_out=384, kernel_size=(3, 3),
                                 stride=(1, 1), activation="relu",
                                 convolution_mode=ConvolutionMode.SAME))
         .layer(ConvolutionLayer(n_out=256, kernel_size=(3, 3),
                                 stride=(1, 1), activation="relu",
                                 convolution_mode=ConvolutionMode.SAME))
         .layer(SubsamplingLayer(pooling_type=PoolingType.MAX,
                                 kernel_size=(3, 3), stride=(2, 2)))
         .layer(DenseLayer(n_out=4096, activation="relu", dropout=0.5))
         .layer(DenseLayer(n_out=4096, activation="relu", dropout=0.5))
         .layer(OutputLayer(n_out=n_classes, activation="softmax",
                            loss="mcxent")))
    conf = b.set_input_type(InputType.convolutional(image, image, 3)).build()
    return MultiLayerNetwork(conf)


def vgg19(n_classes: int = 1000, image: int = 224, seed: int = 42,
          updater=None) -> MultiLayerNetwork:
    """VGG-19 (TrainedModels.VGG19 topology analog): VGG-16 with the extra
    conv in blocks 3-5."""
    cfg = [64, 64, "M", 128, 128, "M", 256, 256, 256, 256, "M",
           512, 512, 512, 512, "M", 512, 512, 512, 512, "M"]
    return _vgg(cfg, n_classes, image, seed, updater)


def googlenet(n_classes: int = 1000, image: int = 224, seed: int = 42,
              updater=None):
    """GoogLeNet / Inception-v1 (Szegedy 2014) as a ComputationGraph:
    inception modules = four parallel branches concatenated with
    MergeVertex — the multi-branch DAG workload the vertex API exists for
    (reference expresses it identically with its graph API)."""
    from ..nn.conf import InputType
    from ..nn.conf.graph import MergeVertex
    from ..nn.graph import ComputationGraph
    from ..nn.layers import GlobalPoolingLayer

    b = (NeuralNetConfiguration.builder()
         .seed(seed)
         .updater(updater or Adam(1e-3))
         .weight_init("relu")
         .graph_builder()
         .add_inputs("input")
         .set_input_types(InputType.convolutional(image, image, 3)))

    def conv(name, inp, n_out, k, s=1):
        b.add_layer(name, ConvolutionLayer(
            n_out=n_out, kernel_size=(k, k), stride=(s, s),
            activation="relu", convolution_mode=ConvolutionMode.SAME), inp)
        return name

    def pool(name, inp, k=3, s=2):
        b.add_layer(name, SubsamplingLayer(
            pooling_type=PoolingType.MAX, kernel_size=(k, k), stride=(s, s),
            convolution_mode=ConvolutionMode.SAME), inp)
        return name

    def inception(name, inp, c1, c3r, c3, c5r, c5, pp):
        b1 = conv(f"{name}_1x1", inp, c1, 1)
        b3 = conv(f"{name}_3x3", conv(f"{name}_3x3r", inp, c3r, 1), c3, 3)
        b5 = conv(f"{name}_5x5", conv(f"{name}_5x5r", inp, c5r, 1), c5, 5)
        bp = conv(f"{name}_poolproj",
                  pool(f"{name}_pool", inp, 3, 1), pp, 1)
        b.add_vertex(f"{name}_concat", MergeVertex(), b1, b3, b5, bp)
        return f"{name}_concat"

    top = conv("stem1", "input", 64, 7, 2)
    top = pool("stem1_pool", top)
    top = conv("stem2a", top, 64, 1)
    top = conv("stem2b", top, 192, 3)
    top = pool("stem2_pool", top)
    top = inception("i3a", top, 64, 96, 128, 16, 32, 32)
    top = inception("i3b", top, 128, 128, 192, 32, 96, 64)
    top = pool("pool3", top)
    top = inception("i4a", top, 192, 96, 208, 16, 48, 64)
    top = inception("i4b", top, 160, 112, 224, 24, 64, 64)
    top = inception("i4c", top, 128, 128, 256, 24, 64, 64)
    top = inception("i4d", top, 112, 144, 288, 32, 64, 64)
    top = inception("i4e", top, 256, 160, 320, 32, 128, 128)
    top = pool("pool4", top)
    top = inception("i5a", top, 256, 160, 320, 32, 128, 128)
    top = inception("i5b", top, 384, 192, 384, 48, 128, 128)
    b.add_layer("gap", GlobalPoolingLayer(pooling_type=PoolingType.AVG),
                top)
    b.add_layer("out", OutputLayer(n_out=n_classes, activation="softmax",
                                   loss="mcxent", dropout=0.6), "gap")
    conf = b.set_outputs("out").build()
    return ComputationGraph(conf)


def sample_characters(net, char_to_idx: dict, seed_text: str, n_chars: int,
                      temperature: float = 1.0, rng_seed: int = 0):
    """Generate text with a trained char-RNN via stateful rnn_time_step
    (the reference's GravesLSTMCharModellingExample sampling loop)."""
    if not seed_text:
        raise ValueError("seed_text must contain at least one character")
    idx_to_char = {i: c for c, i in char_to_idx.items()}
    vocab = len(char_to_idx)
    net.rnn_clear_previous_state()
    out = None
    for ch in seed_text:
        x = np.zeros((1, vocab), np.float32)
        x[0, char_to_idx[ch]] = 1.0
        out = net.rnn_time_step(x)
    rng = np.random.default_rng(rng_seed)
    generated = []
    for _ in range(n_chars):
        p = np.asarray(out, np.float64).reshape(-1)
        if temperature != 1.0:
            logp = np.log(np.maximum(p, 1e-12)) / temperature
            p = np.exp(logp - logp.max())
        p = p / p.sum()
        nxt = int(rng.choice(vocab, p=p))
        generated.append(idx_to_char[nxt])
        x = np.zeros((1, vocab), np.float32)
        x[0, nxt] = 1.0
        out = net.rnn_time_step(x)
    net.rnn_clear_previous_state()
    return "".join(generated)
