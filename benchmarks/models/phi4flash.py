"""Builds the program's Phi-4-mini-flash-reasoning from a configuration file,
by the public builder: EmbeddingSequenceLayer (no positional table: the model
has none; the layer states the served context) -> SambaYBlock x L/2 (the
self-decoder: Mamba and window layers in turn, each window layer with its own
lambda_init) -> CrossDecoderBlock (the other L/2 layers: a Mamba layer that
hands on its memory, the full-attention layer whose keys and values the
cross layers share, GMU and cross layers) -> LayerNormLayer -> RnnOutputLayer
without a bias. The weights are the benchmark's, made from the seed in
bfloat16 by `reference/phi4flash.py` and handed to the program as its
parameters (`MultiLayerNetwork.init(params=...)`): they never exist in
float32 on the device. The program's output layer has its own matrix: it gets
the second copy of the table's values that the reference makes (the
configuration file lists the departure)."""
from __future__ import annotations


def build(config: dict, seed: int, reference, *, train: bool):
    """A `MultiLayerNetwork` holding the seed's weights. Serving only: at 16
    bytes a parameter the model needs 61.6 GB."""
    from deeplearning4j_tpu import (CrossDecoderBlock, EmbeddingSequenceLayer,
                                    InputType, LayerNormLayer,
                                    MultiLayerNetwork, NeuralNetConfiguration,
                                    RnnOutputLayer, SambaYBlock, Sgd)
    from deeplearning4j_tpu.nn.layers.sambay import lambda_init

    if train:
        raise ValueError("phi4flash is built for serving only")
    m = reference.dims(config)
    dtype = config["precision"]["weights"]
    widths = dict(ssm_state=m.n, conv_kernel=m.k, expand=m.e // m.d,
                  dt_rank=m.r, chunk=m.chunk, n_heads=m.heads,
                  n_kv_heads=m.kv_heads, head_dim=m.head, window=m.window,
                  mlp_hidden=m.mlp, eps=m.eps, dtype=dtype)
    b = (NeuralNetConfiguration.builder().seed(int(seed) & 0x7FFFFFFF)
         .updater(Sgd(0.0)).list()
         .layer(EmbeddingSequenceLayer(
             n_in=m.vocab, n_out=m.d, positional=False,
             max_timesteps=m.positions, dtype=dtype)))
    half = m.layers // 2
    for layer, kind in enumerate(reference.kinds(m)[:half]):
        b = b.layer(SambaYBlock(
            mixer=kind, lambda_init=lambda_init(layer) if kind == "window"
            else 0.8, **widths))
    conf = (b.layer(CrossDecoderBlock(layers=half, first_layer=half, **widths))
            .layer(LayerNormLayer(eps=m.eps, dtype=dtype))
            .layer(RnnOutputLayer(n_out=m.vocab, activation="softmax",
                                  loss="mcxent", has_bias=False, dtype=dtype))
            .set_input_type(InputType.recurrent(1, m.positions)).build())
    return MultiLayerNetwork(conf).init(
        params=reference.init_params(config, seed))
