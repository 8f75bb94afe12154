"""Operations and bytes that Granite 4.0-H's equations require of one chip's
share (`reference/granite_moe_hybrid.py` states them), from shapes alone and
from the counts the program's spans carry: (token, held expert) pairs, held
experts hit, live rows, live pages.

Nothing recomputed and nothing an implementation adds is counted. A
state-space layer's recurrence is counted token by token as the equations
write it, 5 operations a state element (the decay, `dt xs B` and its sum,
`H C` and its sum): the chunked form's quadratic products inside a chunk are
the program's choice. A token at position p attends to p + 1 keys and values
of `head` a query head; an expert multiplies only for the pairs routed to
it, the shared expert for every token."""
from __future__ import annotations


def dims(config: dict) -> dict:
    layers = int(config["num_hidden_layers"])
    mixers = list(config["layer_types"][:layers])
    d, heads = int(config["hidden_size"]), int(config["num_attention_heads"])
    return {"d": d, "mamba_layers": mixers.count("mamba"),
            "attention_layers": mixers.count("attention"), "layers": layers,
            "ssm_heads": int(config["mamba_n_heads"]),
            "ssm_head": int(config["mamba_d_head"]),
            "ssm_state": int(config["mamba_d_state"]),
            "conv": int(config["mamba_d_conv"]),
            "heads": heads, "kv_heads": int(config["num_key_value_heads"]),
            "head": d // heads, "expert_ffn": int(config["intermediate_size"]),
            "shared_ffn": int(config["shared_intermediate_size"]),
            "held": int(config["num_local_experts"]),
            "routes": int(config["published"]["num_local_experts"]),
            "vocab": int(config["vocab_size"])}


def mamba_weights(config: dict) -> int:
    """Matrix elements of one Mamba-2 mixer: W_in and W_out (the
    convolution's taps, the per-head scalars and the gains left out)."""
    m = dims(config)
    inner = m["ssm_heads"] * m["ssm_head"]
    return m["d"] * (2 * inner + 2 * m["ssm_state"] + m["ssm_heads"]) \
        + inner * m["d"]


def attention_weights(config: dict) -> int:
    """W_q, W_k, W_v, W_o of the grouped-query attention mixer."""
    m = dims(config)
    return 2 * m["d"] * m["heads"] * m["head"] \
        + 2 * m["d"] * m["kv_heads"] * m["head"]


def state_elements(config: dict) -> int:
    """What one Mamba-2 layer keeps for a sequence: the recurrent state and
    the convolution's stored inputs."""
    m = dims(config)
    inner = m["ssm_heads"] * m["ssm_head"]
    return inner * m["ssm_state"] \
        + (m["conv"] - 1) * (inner + 2 * m["ssm_state"])


def outside_experts_weights(config: dict) -> int:
    """Every matrix a token passes whatever it picks: the mixers, the shared
    experts, the routers."""
    m = dims(config)
    every_layer = 3 * m["d"] * m["shared_ffn"] + m["d"] * m["routes"]
    return (m["mamba_layers"] * mamba_weights(config)
            + m["attention_layers"] * attention_weights(config)
            + m["layers"] * every_layer)


def dense_flops_per_token(config: dict) -> float:
    """All layers for one token outside attention's scores and the routed
    experts: the mixers' projections, the convolution, the recurrence, the
    shared expert and the router; 2 operations a multiply-add."""
    m = dims(config)
    inner = m["ssm_heads"] * m["ssm_head"]
    scan = 5 * inner * m["ssm_state"] \
        + 2 * m["conv"] * (inner + 2 * m["ssm_state"])
    return 2.0 * outside_experts_weights(config) + m["mamba_layers"] * scan


def attention_flops(config: dict, context: int) -> float:
    """QK^T and PV of ONE token that attends to `context` keys, in every
    attention layer."""
    m = dims(config)
    return 4.0 * context * m["heads"] * m["head"] * m["attention_layers"]


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
    generated token that was fed back passes the layers, attending to its
    own context; the head is needed only where a token is sampled; the held
    experts multiply for `held_pairs` (token, expert) pairs in all."""
    dense, head = dense_flops_per_token(config), head_flops_per_token(config)
    total = held_pairs * expert_pair_flops(config)
    for n, g in zip(prompt_lens, generated):
        fed = n + max(0, g - 1)           # the last sampled token is not fed
        total += fed * dense + attention_flops(config, 1) * fed * (fed + 1) / 2.0
        total += g * head
    return total


def tick_bytes(config: dict, rows: int, experts_hit: int, pages: int,
               block_len: int = 16, weight_bytes: int = 2,
               state_bytes: int = 4, cache_bytes: int = 2) -> float:
    """Bytes one decode tick has to move: every weight outside the routed
    experts once (mixers, shared experts, routers, the head), the weights of
    the `experts_hit` (layer, held expert) pairs that some row picked, the
    `rows` live sequences' state in every Mamba-2 layer read and written,
    and the `pages` live pages of `block_len` tokens, keys and values, of
    every attention layer. Activations, the logits and the token written to
    a page are left out (under a thousandth)."""
    m = dims(config)
    weights = outside_experts_weights(config) + m["d"] * m["vocab"] \
        + experts_hit * 3 * m["d"] * m["expert_ffn"]
    state = 2 * rows * m["mamba_layers"] * state_elements(config)
    cache = pages * block_len * 2 * m["kv_heads"] * m["head"] \
        * m["attention_layers"]
    return float(weights * weight_bytes + state * state_bytes
                 + cache * cache_bytes)
