"""Operations and bytes that LongCat-Flash's equations require of one chip's
share (`reference/longcat_flash.py` states them), from shapes alone and from
the count of (token, held expert) pairs the program's router made.

Nothing recomputed and nothing an implementation adds is counted: the latent
is expanded into heads once a token (`W_kvb`, whichever side of the product
the program puts it on), a token at position p attends to p + 1 keys of
`qk_nope + qk_rope` and values of `v_head` a head (the absorbed form's wider
products over the latent are the program's choice), an expert multiplies only
for the pairs routed to it, the identity experts not at all."""
from __future__ import annotations


def dims(config: dict) -> dict:
    return {"d": int(config["hidden_size"]), "layers": int(config["num_layers"]),
            "heads": int(config["num_attention_heads"]),
            "q_rank": int(config["q_lora_rank"]),
            "kv_rank": int(config["kv_lora_rank"]),
            "nope": int(config["qk_nope_head_dim"]),
            "rope": int(config["qk_rope_head_dim"]),
            "v": int(config["v_head_dim"]),
            "ffn": int(config["ffn_hidden_size"]),
            "expert_ffn": int(config["expert_ffn_hidden_size"]),
            "held": int(config["n_routed_experts"]),
            "routes": int(config["published"]["n_routed_experts"])
            + int(config["zero_expert_num"]),
            "vocab": int(config["vocab_size"])}


def mla_weights(config: dict) -> int:
    """Matrix elements of one attention sub-layer (its two norm gains left
    out): W_qa, W_qb, W_kva, W_kvb, W_o."""
    m = dims(config)
    qk = m["nope"] + m["rope"]
    return (m["d"] * m["q_rank"] + m["q_rank"] * m["heads"] * qk
            + m["d"] * (m["kv_rank"] + m["rope"])
            + m["kv_rank"] * m["heads"] * (m["nope"] + m["v"])
            + m["heads"] * m["v"] * m["d"])


def dense_flops_per_token(config: dict) -> float:
    """Matrix products of all blocks for one token outside attention's
    scores and the experts: two attentions' projections, two dense SwiGLU
    FFNs and the router, 2 operations a multiply-add."""
    m = dims(config)
    layer = (2 * mla_weights(config) + 2 * 3 * m["d"] * m["ffn"]
             + m["d"] * m["routes"])
    return 2.0 * m["layers"] * layer


def attention_flops(config: dict, context: int) -> float:
    """QK^T and PV of ONE token that attends to `context` keys, in both
    attentions of every block."""
    m = dims(config)
    return 2.0 * context * m["heads"] * (m["nope"] + m["rope"] + m["v"]) \
        * 2 * m["layers"]


def expert_pair_flops(config: dict) -> float:
    """One (token, expert) pair: three products of hidden x expert width."""
    m = dims(config)
    return 6.0 * m["d"] * m["expert_ffn"]


def head_flops_per_token(config: dict) -> float:
    m = dims(config)
    return 2.0 * m["d"] * m["vocab"]


def serve_flops(config: dict, prompt_lens, generated, held_pairs: float = 0.0
                ) -> float:
    """Required operations of served requests: every prompt token and every
    generated token that was fed back passes the blocks, attending to its own
    context; the head is needed only where a token is sampled; the held
    experts multiply for `held_pairs` (token, expert) pairs in all (the
    program's counter `dl4j_moe_held_pairs_total` over the same work)."""
    dense, head = dense_flops_per_token(config), head_flops_per_token(config)
    total = held_pairs * expert_pair_flops(config)
    for n, g in zip(prompt_lens, generated):
        fed = n + max(0, g - 1)           # the last sampled token is not fed
        total += fed * dense + attention_flops(config, 1) * fed * (fed + 1) / 2.0
        total += g * head
    return total


def tick_bytes(config: dict, contexts, experts_hit: int,
               weight_bytes: int = 2, cache_bytes: int = 2) -> float:
    """Bytes one decode tick has to read: every weight outside the experts
    once (attention, dense FFNs, router, head), the weights of the
    `experts_hit` (layer, held expert) pairs that some row picked, and the
    cached latent (`kv_lora_rank + qk_rope_head_dim` values a token and
    attention, unpadded) of each row's `contexts` tokens. Activations and the
    logits are left out (under a thousandth)."""
    m = dims(config)
    outside = m["layers"] * (2 * mla_weights(config) + 6 * m["d"] * m["ffn"]
                             + m["d"] * m["routes"]) + m["d"] * m["vocab"]
    experts = experts_hit * 3 * m["d"] * m["expert_ffn"]
    cache = sum(contexts) * 2 * m["layers"] * (m["kv_rank"] + m["rope"])
    return float((outside + experts) * weight_bytes + cache * cache_bytes)
