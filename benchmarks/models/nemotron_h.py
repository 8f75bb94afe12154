"""Builds the program's Nemotron 3 Super share from a configuration file, by
the public builder: EmbeddingSequenceLayer (no positional table: the model
has no positional encoding, the layer states the served context) ->
NemotronHBlock x num_hidden_layers, each the part `hybrid_override_pattern`
names (Mamba-2 with `n_groups` groups, attention, or LatentMoE) ->
RMSNormLayer -> RnnOutputLayer without a bias (the untied head). The weights
are the benchmark's, made from the seed in bfloat16 by
`reference/nemotron_h.py` and handed to the program as its parameters
(`MultiLayerNetwork.init(params=...)`): they never exist in float32 on the
device."""
from __future__ import annotations

import math


def build(config: dict, seed: int, reference, *, train: bool):
    """A `MultiLayerNetwork` holding the seed's weights. Serving only: at 16
    bytes a parameter no cut within the guide's floors fits a chip."""
    from deeplearning4j_tpu import (EmbeddingSequenceLayer, InputType,
                                    MultiLayerNetwork, NemotronHBlock,
                                    NeuralNetConfiguration, RMSNormLayer,
                                    RnnOutputLayer, Sgd)

    if train:
        raise ValueError("the nemotron_h share is built for serving only")
    m = reference.dims(config)
    dtype = config["precision"]["weights"]
    b = (NeuralNetConfiguration.builder().seed(int(seed) & 0x7FFFFFFF)
         .updater(Sgd(0.0)).list()
         .layer(EmbeddingSequenceLayer(
             n_in=m.vocab, n_out=m.d, positional=False,
             max_timesteps=m.positions, dtype=dtype)))
    for part in m.parts:
        b = b.layer(NemotronHBlock(
            mixer=part, ssm_heads=m.ssm_heads, ssm_head_dim=m.ssm_head,
            ssm_state=m.ssm_state, ssm_groups=m.groups, conv_kernel=m.conv,
            chunk=m.chunk, n_heads=m.heads, n_kv_heads=m.kv_heads,
            head_dim=m.head, attention_multiplier=1.0 / math.sqrt(m.head),
            n_experts=m.routed, top_k=m.top_k, expert_hidden=m.expert_ffn,
            shared_hidden=m.shared_ffn, latent=m.latent,
            routed_scaling=m.scaling, held_experts=[m.held_lo, m.held_hi],
            eps=m.eps, dtype=dtype))
    conf = (b.layer(RMSNormLayer(eps=m.eps, dtype=dtype))
            .layer(RnnOutputLayer(n_out=m.vocab, activation="softmax",
                                  loss="mcxent", has_bias=False, dtype=dtype))
            .set_input_type(InputType.recurrent(1, m.positions)).build())
    return MultiLayerNetwork(conf).init(
        params=reference.init_params(config, seed))
