"""Registry-wide TRAINING smoke sweep.

The round-4 BiLSTM finding: a layer whose gradchecks were green had been
un-trainable since round 1, because gradient checks bypass the updater and
nothing ever ran `fit()` per layer type. This sweep closes that class of
latent bug for good — EVERY registered layer type trains for two real
steps through the full `fit()` path (forward, `jax.value_and_grad`,
gradient normalization, tree-aware updater, param write-back) with Adam
(stateful updater trees) and must (a) produce a finite score and
(b) actually move its parameters.
"""
import numpy as np
import pytest

from deeplearning4j_tpu import (Adam, DataSet, DenseLayer, InputType,
                                MultiLayerNetwork, NeuralNetConfiguration,
                                OutputLayer)
from deeplearning4j_tpu.nn.conf.base import LAYER_REGISTRY


def _ff_data(n=16, f=12, c=3, seed=0):
    r = np.random.default_rng(seed)
    x = r.normal(size=(n, f)).astype(np.float32)
    y = np.eye(c, dtype=np.float32)[r.integers(0, c, n)]
    return x, y


def _conv_data(n=8, h=8, w=8, ch=3, c=3, seed=0):
    r = np.random.default_rng(seed)
    x = r.normal(size=(n, h, w, ch)).astype(np.float32)
    y = np.eye(c, dtype=np.float32)[r.integers(0, c, n)]
    return x, y


def _rnn_data(n=8, t=6, f=5, c=3, seed=0):
    r = np.random.default_rng(seed)
    x = r.normal(size=(n, t, f)).astype(np.float32)
    y = np.eye(c, dtype=np.float32)[r.integers(0, c, (n, t))]
    return x, y


def _build(layers, input_type):
    b = (NeuralNetConfiguration.builder().seed(5).updater(Adam(1e-2))
         .list())
    for l in layers:
        b = b.layer(l)
    return MultiLayerNetwork(
        b.set_input_type(input_type).build()).init()


def _case(name):
    """(layers, input_type, (x, y)) template for one registry entry."""
    from deeplearning4j_tpu.nn.layers import (
        ActivationLayer, AutoEncoder, BatchNormalization,
        CenterLossOutputLayer, Convolution1DLayer, ConvolutionLayer,
        DropoutLayer, EmbeddingLayer, EmbeddingSequenceLayer,
        GlobalPoolingLayer, GravesBidirectionalLSTM, GravesLSTM,
        LastTimeStep, LocalResponseNormalization, LossLayer,
        MixtureOfExpertsLayer, RMSNormLayer, RnnOutputLayer,
        ShortcutMoEBlock, SparseExpertsLayer, Subsampling1DLayer,
        HybridSSMBlock, SambaYBlock, CrossDecoderBlock, LayerNormLayer,
        NemotronHBlock, SubsamplingLayer, TransformerBlock, VariationalAutoencoder,
        ZeroPaddingLayer)
    from deeplearning4j_tpu.nn.layers import RBM

    ff = InputType.feed_forward(12)
    conv = InputType.convolutional(8, 8, 3)
    rnn = InputType.recurrent(5)
    head = OutputLayer(n_out=3, loss="mcxent")
    rnn_head = RnnOutputLayer(n_out=3, activation="softmax", loss="mcxent")
    fx = _ff_data()
    cx = _conv_data()
    rx = _rnn_data()
    table = {
        "DenseLayer": lambda: ([DenseLayer(n_out=8, activation="tanh"), head],
                       ff, fx),
        "ActivationLayer": lambda: ([DenseLayer(n_out=8, activation="identity"),
                             ActivationLayer(activation="relu"), head],
                            ff, fx),
        "DropoutLayer": lambda: ([DenseLayer(n_out=8, activation="tanh"),
                          DropoutLayer(dropout=0.5), head], ff, fx),
        "AutoEncoder": lambda: ([AutoEncoder(n_out=8), head], ff, fx),
        "RBM": lambda: ([RBM(n_out=8), head], ff, fx),
        "VariationalAutoencoder": lambda: (
            [VariationalAutoencoder(n_out=4, encoder_layer_sizes=(8,),
                                    decoder_layer_sizes=(8,),
                                    activation="tanh"), head], ff, fx),
        "MixtureOfExpertsLayer": lambda: (
            [MixtureOfExpertsLayer(n_out=8, n_experts=2, top_k=1,
                                   expert_hidden=6), head], ff, fx),
        "OutputLayer": lambda: ([DenseLayer(n_out=8, activation="tanh"), head],
                        ff, fx),
        "LossLayer": lambda: ([DenseLayer(n_out=3, activation="softmax"),
                       LossLayer(loss="mcxent")], ff, fx),
        "CenterLossOutputLayer": lambda: (
            [DenseLayer(n_out=8, activation="tanh"),
             CenterLossOutputLayer(n_out=3, loss="mcxent")], ff, fx),
        "EmbeddingLayer": lambda: ([EmbeddingLayer(n_in=20, n_out=6), head],
                           InputType.feed_forward(1),
                           (np.random.default_rng(0).integers(
                               0, 20, (16, 1)).astype(np.float32),
                            _ff_data()[1])),
        "ConvolutionLayer": lambda: (
            [ConvolutionLayer(n_out=4, kernel_size=(3, 3)), head],
            conv, cx),
        "SubsamplingLayer": lambda: (
            [ConvolutionLayer(n_out=4, kernel_size=(3, 3)),
             SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)), head],
            conv, cx),
        "BatchNormalization": lambda: (
            [ConvolutionLayer(n_out=4, kernel_size=(3, 3)),
             BatchNormalization(), head], conv, cx),
        "LocalResponseNormalization": lambda: (
            [ConvolutionLayer(n_out=4, kernel_size=(3, 3)),
             LocalResponseNormalization(), head], conv, cx),
        "ZeroPaddingLayer": lambda: (
            [ZeroPaddingLayer(pad=(1, 1)),
             ConvolutionLayer(n_out=4, kernel_size=(3, 3)), head],
            conv, cx),
        "Convolution1DLayer": lambda: (
            [Convolution1DLayer(n_out=4, kernel_size=3), rnn_head],
            rnn, rx),
        "Subsampling1DLayer": lambda: (
            [Convolution1DLayer(n_out=4, kernel_size=3, padding=1),
             Subsampling1DLayer(kernel_size=3, stride=1, padding=1),
             rnn_head], rnn, rx),
        "GravesLSTM": lambda: ([GravesLSTM(n_out=6, activation="tanh"), rnn_head],
                       rnn, rx),
        "GravesBidirectionalLSTM": lambda: (
            [GravesBidirectionalLSTM(n_out=6, activation="tanh"),
             rnn_head], rnn, rx),
        "RnnOutputLayer": lambda: ([GravesLSTM(n_out=6, activation="tanh"),
                            rnn_head], rnn, rx),
        "LastTimeStep": lambda: ([GravesLSTM(n_out=6, activation="tanh"),
                          LastTimeStep(), head],
                         rnn, (rx[0], _ff_data(8, c=3)[1][:8])),
        "GlobalPoolingLayer": lambda: (
            [ConvolutionLayer(n_out=4, kernel_size=(3, 3)),
             GlobalPoolingLayer(), head], conv, cx),
        "TransformerBlock": lambda: (
            [TransformerBlock(n_heads=2), rnn_head],
            InputType.recurrent(8, 6), _rnn_data(f=8)),
        "RMSNormLayer": lambda: (
            [DenseLayer(n_out=8, activation="tanh"), RMSNormLayer(), head],
            ff, fx),
        "SparseExpertsLayer": lambda: (
            [SparseExpertsLayer(n_experts=4, n_identity=2, top_k=2,
                                expert_hidden=8, held_experts=[0, 2]),
             rnn_head], InputType.recurrent(8, 6), _rnn_data(f=8)),
        "ShortcutMoEBlock": lambda: (
            [ShortcutMoEBlock(n_heads=2, q_rank=8, kv_rank=4, qk_nope=4,
                              qk_rope=2, v_head=4, ffn_hidden=16,
                              n_experts=4, n_identity=2, top_k=2,
                              expert_hidden=8), rnn_head],
            InputType.recurrent(8, 6), _rnn_data(f=8)),
        "HybridSSMBlock": lambda: (
            [HybridSSMBlock(mixer="mamba", ssm_heads=4, ssm_head_dim=4,
                            ssm_state=4, chunk=4, n_experts=4, top_k=2,
                            expert_hidden=8, shared_hidden=8),
             HybridSSMBlock(mixer="attention", n_heads=4, n_kv_heads=2,
                            head_dim=2, attention_multiplier=0.5,
                            n_experts=4, top_k=2, expert_hidden=8,
                            held_experts=[0, 2]), rnn_head],
            InputType.recurrent(8, 6), _rnn_data(f=8)),
        "NemotronHBlock": lambda: (
            [NemotronHBlock(mixer="mamba", ssm_heads=4, ssm_head_dim=4,
                            ssm_state=4, ssm_groups=2, chunk=4),
             NemotronHBlock(mixer="moe", n_experts=8, top_k=3,
                            expert_hidden=8, shared_hidden=8, latent=4,
                            routed_scaling=2.0, held_experts=[0, 4]),
             NemotronHBlock(mixer="attention", n_heads=4, n_kv_heads=2,
                            head_dim=2, attention_multiplier=0.5), rnn_head],
            InputType.recurrent(8, 6), _rnn_data(f=8)),
        "SambaYBlock": lambda: (
            [SambaYBlock(mixer="mamba", ssm_state=4, dt_rank=2, chunk=4,
                         n_heads=2, n_kv_heads=1, head_dim=2, window=3,
                         mlp_hidden=8),
             SambaYBlock(mixer="window", n_heads=2, n_kv_heads=1, head_dim=2,
                         window=3, mlp_hidden=8), rnn_head],
            InputType.recurrent(8, 6), _rnn_data(f=8)),
        "CrossDecoderBlock": lambda: (
            [CrossDecoderBlock(layers=4, first_layer=2, ssm_state=4,
                               dt_rank=2, chunk=4, n_heads=2, n_kv_heads=1,
                               head_dim=2, mlp_hidden=8), rnn_head],
            InputType.recurrent(8, 6), _rnn_data(f=8)),
        "LayerNormLayer": lambda: (
            [DenseLayer(n_out=8, activation="tanh"), LayerNormLayer(), head],
            ff, fx),
        "EmbeddingSequenceLayer": lambda: (
            [EmbeddingSequenceLayer(n_in=20, n_out=8), rnn_head],
            InputType.recurrent(1, 6),
            (np.random.default_rng(0).integers(
                0, 20, (8, 6, 1)).astype(np.float32),
             _rnn_data()[1])),
    }
    thunk = table.get(name)
    return thunk() if thunk else None


@pytest.mark.parametrize("name", sorted(LAYER_REGISTRY))
def test_layer_type_trains(name):
    case = _case(name)
    assert case is not None, (
        f"no training-sweep template for registered layer {name!r} — "
        "add one (this sweep exists so every layer type exercises the "
        "full fit() path, not just gradchecks)")
    import jax

    layers, input_type, (x, y) = case
    net = _build(layers, input_type)
    before = jax.tree_util.tree_map(lambda a: np.asarray(a).copy(),
                                    net.params)
    ds = DataSet(x, y)
    net.fit(ds)
    net.fit(ds)
    assert np.isfinite(net.score()), name
    # PER-LAYER movement: the round-4 BiLSTM bug left one layer's nested
    # subtree untouched while the head still trained — a global norm
    # check would have missed it. Every param-carrying layer must move
    # (ANY leaf: supervised fit legitimately leaves e.g. a VAE decoder or
    # an RBM visible bias without gradient).
    for i, (b, a) in enumerate(zip(before, net.params)):
        b_leaves = jax.tree_util.tree_leaves(b)
        a_leaves = jax.tree_util.tree_leaves(a)
        if not b_leaves:
            continue
        moved = any(float(np.max(np.abs(np.asarray(al) - bl))) > 0.0
                    for bl, al in zip(b_leaves, a_leaves))
        assert moved, (f"{name}: layer {i} "
                       f"({type(net.layers[i]).__name__}) params did not "
                       "move after two fit() steps")
