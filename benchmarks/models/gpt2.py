"""Builds the program's GPT-2 family model from a configuration file, by the
public builder: EmbeddingSequenceLayer -> TransformerBlock x n_layer ->
RnnOutputLayer. The weights are the benchmark's, made from the seed by
`reference/gpt2.py` and handed to the program as an input."""
from __future__ import annotations

import jax


def build(config: dict, seed: int, reference, *, train: bool):
    """A `MultiLayerNetwork` holding the seed's weights. `train` keeps the
    configuration's updater (Adam and its state); a served model takes plain
    SGD, which has no state to hold beside the cache."""
    from deeplearning4j_tpu import (Adam, EmbeddingSequenceLayer, InputType,
                                    MultiLayerNetwork, NeuralNetConfiguration,
                                    RnnOutputLayer, Sgd, TransformerBlock)

    d, layers, heads, vocab, positions, ffn = reference.dims(config)
    if ffn % d:
        raise ValueError("the program's block takes a whole ffn multiple")
    prec = config["precision"]
    if train:
        a = config["updater"]["adam"]
        updater = Adam(a["learning_rate"], a["beta1"], a["beta2"], a["epsilon"])
    else:
        updater = Sgd(0.0)
    b = NeuralNetConfiguration.builder().seed(int(seed) & 0x7FFFFFFF)
    b = b.updater(updater)
    if prec.get("compute_dtype"):
        b = b.compute_dtype(prec["compute_dtype"])
    b = b.list().layer(EmbeddingSequenceLayer(n_in=vocab, n_out=d))
    for _ in range(layers):
        b = b.layer(TransformerBlock(n_heads=heads, ffn_mult=ffn // d))
    conf = (b.layer(RnnOutputLayer(n_out=vocab, activation="softmax",
                                   loss="mcxent"))
            .set_input_type(InputType.recurrent(1, positions)).build())
    model = MultiLayerNetwork(conf).init()
    params = reference.init_params(config, seed)
    want = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), model.params)
    got = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), params)
    if want != got:
        raise ValueError("the reference's weights do not fit the program's "
                         "model: layouts differ")
    model.params = params
    return model
