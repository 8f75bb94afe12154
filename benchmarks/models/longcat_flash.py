"""Builds the program's LongCat-Flash share from a configuration file, by the
public builder: EmbeddingSequenceLayer (no positional table; it states the
served context) -> ShortcutMoEBlock x num_layers -> RMSNormLayer ->
RnnOutputLayer without a bias. The weights are the benchmark's, made from the
seed in bfloat16 by `reference/longcat_flash.py` and handed to the program as
its parameters (`MultiLayerNetwork.init(params=...)`): they never exist in
float32 on the device, and never twice."""
from __future__ import annotations


def build(config: dict, seed: int, reference, *, train: bool):
    """A `MultiLayerNetwork` holding the seed's weights. Serving only: at 16
    bytes a parameter the share does not fit a chip."""
    from deeplearning4j_tpu import (EmbeddingSequenceLayer, InputType,
                                    MultiLayerNetwork, NeuralNetConfiguration,
                                    RMSNormLayer, RnnOutputLayer, Sgd,
                                    ShortcutMoEBlock)

    if train:
        raise ValueError("the longcat_flash share is built for serving only")
    if bool(config["mla_scale_q_lora"]) != bool(config["mla_scale_kv_lora"]):
        raise ValueError("the program's block scales both latents or neither")
    m = reference.dims(config)
    dtype = config["precision"]["weights"]
    b = (NeuralNetConfiguration.builder().seed(int(seed) & 0x7FFFFFFF)
         .updater(Sgd(0.0)).list()
         .layer(EmbeddingSequenceLayer(
             n_in=m.vocab, n_out=m.d, positional=False,
             max_timesteps=m.positions, dtype=dtype)))
    for _ in range(m.layers):
        b = b.layer(ShortcutMoEBlock(
            n_heads=m.heads, q_rank=m.q_rank, kv_rank=m.kv_rank,
            qk_nope=m.nope, qk_rope=m.rope, v_head=m.v_head,
            ffn_hidden=m.ffn, n_experts=m.routed, n_identity=m.identity,
            top_k=m.top_k, expert_hidden=m.expert_ffn,
            routed_scaling=m.scaling, held_experts=[m.held_lo, m.held_hi],
            mla_scale=bool(config["mla_scale_q_lora"]),
            rope_theta=m.theta, eps=m.eps, dtype=dtype))
    conf = (b.layer(RMSNormLayer(eps=m.eps, dtype=dtype))
            .layer(RnnOutputLayer(n_out=m.vocab, activation="softmax",
                                  loss="mcxent", has_bias=False, dtype=dtype))
            .set_input_type(InputType.recurrent(1, m.positions)).build())
    return MultiLayerNetwork(conf).init(
        params=reference.init_params(config, seed))
