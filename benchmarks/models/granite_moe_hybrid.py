"""Builds the program's Granite 4.0-H share from a configuration file, by the
public builder: EmbeddingSequenceLayer (no positional table: the model has no
positional encoding, the layer states the served context; the token vectors
times `embedding_multiplier`) -> HybridSSMBlock x num_hidden_layers, each
with the mixer `layer_types` names -> RMSNormLayer (times 1 /
`logits_scaling`) -> RnnOutputLayer without a bias. The weights are the
benchmark's, made from the seed in bfloat16 by
`reference/granite_moe_hybrid.py` and handed to the program as its parameters
(`MultiLayerNetwork.init(params=...)`): they never exist in float32 on the
device. The program's output layer has its own matrix: it gets the second
copy of the table's values that the reference makes (the configuration file
lists the departure)."""
from __future__ import annotations


def build(config: dict, seed: int, reference, *, train: bool):
    """A `MultiLayerNetwork` holding the seed's weights. Serving only: at 16
    bytes a parameter no cut within the guide's floors fits a chip."""
    from deeplearning4j_tpu import (EmbeddingSequenceLayer, HybridSSMBlock,
                                    InputType, MultiLayerNetwork,
                                    NeuralNetConfiguration, RMSNormLayer,
                                    RnnOutputLayer, Sgd)

    if train:
        raise ValueError("the granite_moe_hybrid share is built for serving "
                         "only")
    m = reference.dims(config)
    dtype = config["precision"]["weights"]
    b = (NeuralNetConfiguration.builder().seed(int(seed) & 0x7FFFFFFF)
         .updater(Sgd(0.0)).list()
         .layer(EmbeddingSequenceLayer(
             n_in=m.vocab, n_out=m.d, positional=False,
             max_timesteps=m.positions, multiplier=m.embedding_mult,
             dtype=dtype)))
    for mixer in m.mixers:
        b = b.layer(HybridSSMBlock(
            mixer=mixer, ssm_heads=m.ssm_heads, ssm_head_dim=m.ssm_head,
            ssm_state=m.ssm_state, conv_kernel=m.conv, chunk=m.chunk,
            n_heads=m.heads, n_kv_heads=m.kv_heads, head_dim=m.head,
            attention_multiplier=m.attention_mult, n_experts=m.routed,
            top_k=m.top_k, expert_hidden=m.expert_ffn,
            shared_hidden=m.shared_ffn, held_experts=[m.held_lo, m.held_hi],
            residual_multiplier=m.residual_mult, eps=m.eps, dtype=dtype))
    conf = (b.layer(RMSNormLayer(eps=m.eps, scale=1.0 / m.logits_scaling,
                                 dtype=dtype))
            .layer(RnnOutputLayer(n_out=m.vocab, activation="softmax",
                                  loss="mcxent", has_bias=False, dtype=dtype))
            .set_input_type(InputType.recurrent(1, m.positions)).build())
    return MultiLayerNetwork(conf).init(
        params=reference.init_params(config, seed))
