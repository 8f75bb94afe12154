"""Operations and bytes that Nemotron 3 Super's equations require of one
chip's share (`reference/nemotron_h.py` states them), from shapes alone and
from the counts the program's spans carry: (token, held expert) pairs, held
experts hit, live rows, live pages.

Nothing recomputed and nothing an implementation adds is counted. A Mamba-2
layer's recurrence is counted token by token as the equations write it, 5
operations a state element (the decay, `dt xs B` and its sum, `S C` and its
sum): the chunked form's quadratic products inside a chunk are the program's
choice. A token at position p attends to p + 1 keys and values of `head` a
query head; a routed expert multiplies only for the pairs routed to it (two
products of the latent x the expert's width), every token passes the
router, the two latent projections and the shared expert."""
from __future__ import annotations


def dims(config: dict) -> dict:
    layers = int(config["num_hidden_layers"])
    parts = config["hybrid_override_pattern"][:layers]
    return {"d": int(config["hidden_size"]), "mamba_layers": parts.count("M"),
            "attention_layers": parts.count("*"),
            "moe_layers": parts.count("E"),
            "ssm_heads": int(config["mamba_num_heads"]),
            "ssm_head": int(config["mamba_head_dim"]),
            "ssm_state": int(config["ssm_state_size"]),
            "groups": int(config["n_groups"]),
            "conv": int(config["conv_kernel"]),
            "heads": int(config["num_attention_heads"]),
            "kv_heads": int(config["num_key_value_heads"]),
            "head": int(config["head_dim"]),
            "expert_ffn": int(config["moe_intermediate_size"]),
            "shared_ffn": int(config["moe_shared_expert_intermediate_size"]),
            "latent": int(config["moe_latent_size"]),
            "routes": int(config["published"]["n_routed_experts"]),
            "vocab": int(config["vocab_size"])}


def _conv_width(m: dict) -> int:
    return m["ssm_heads"] * m["ssm_head"] + 2 * m["groups"] * m["ssm_state"]


def mamba_weights(config: dict) -> int:
    """Matrix elements of one Mamba-2 mixer: W_in and W_out (the
    convolution's taps, the per-head scalars and the gains left out)."""
    m = dims(config)
    inner = m["ssm_heads"] * m["ssm_head"]
    return m["d"] * (inner + _conv_width(m) + m["ssm_heads"]) + inner * m["d"]


def attention_weights(config: dict) -> int:
    """W_q, W_k, W_v, W_o of the grouped-query attention layer."""
    m = dims(config)
    return 2 * m["d"] * m["heads"] * m["head"] \
        + 2 * m["d"] * m["kv_heads"] * m["head"]


def moe_weights(config: dict) -> int:
    """Every matrix of a LatentMoE layer that a token passes whatever it
    picks: the router, W_down, W_up, the shared expert."""
    m = dims(config)
    return m["d"] * m["routes"] + 2 * m["d"] * m["latent"] \
        + 2 * m["d"] * m["shared_ffn"]


def expert_bytes(config: dict, weight_bytes: int = 2) -> int:
    """One routed expert's two matrices, U [latent, h] and V [h, latent]."""
    m = dims(config)
    return 2 * m["latent"] * m["expert_ffn"] * weight_bytes


def state_elements(config: dict) -> int:
    """What one Mamba-2 layer keeps for a sequence: the recurrent state and
    the convolution's stored inputs."""
    m = dims(config)
    return m["ssm_heads"] * m["ssm_head"] * m["ssm_state"] \
        + (m["conv"] - 1) * _conv_width(m)


def outside_experts_weights(config: dict) -> int:
    """Every matrix a token passes whatever it picks, but the head."""
    m = dims(config)
    return (m["mamba_layers"] * mamba_weights(config)
            + m["attention_layers"] * attention_weights(config)
            + m["moe_layers"] * moe_weights(config))


def dense_flops_per_token(config: dict) -> float:
    """All layers for one token outside attention's scores and the routed
    experts: the mixers' projections, the convolution, the recurrence, the
    router, the latent projections and the shared expert; 2 operations a
    multiply-add."""
    m = dims(config)
    scan = 5 * m["ssm_heads"] * m["ssm_head"] * m["ssm_state"] \
        + 2 * m["conv"] * _conv_width(m)
    return 2.0 * outside_experts_weights(config) + m["mamba_layers"] * scan


def attention_flops(config: dict, context: int) -> float:
    """QK^T and PV of ONE token that attends to `context` keys, in every
    attention layer."""
    m = dims(config)
    return 4.0 * context * m["heads"] * m["head"] * m["attention_layers"]


def expert_pair_flops(config: dict) -> float:
    """One (token, expert) pair: two products of the latent x expert
    width."""
    m = dims(config)
    return 4.0 * m["latent"] * m["expert_ffn"]


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


def tick_bytes(config: dict, rows: int, experts_hit: float, pages: float,
               block_len: int = 16, weight_bytes: int = 2,
               state_bytes: int = 4, cache_bytes: int = 2) -> float:
    """Bytes one decode tick has to move: every weight outside the routed
    experts once (mixers, routers, latent projections, shared experts, the
    head), the two matrices of the `experts_hit` (layer, held expert) pairs
    that some row picked, the `rows` live sequences' state in every Mamba-2
    layer read and written, and the `pages` live pages of `block_len`
    tokens, keys and values, of the attention layer. Activations, the
    logits and the token written to a page are left out (under a
    thousandth)."""
    m = dims(config)
    weights = outside_experts_weights(config) + m["d"] * m["vocab"]
    state = 2 * rows * m["mamba_layers"] * state_elements(config)
    cache = pages * block_len * 2 * m["kv_heads"] * m["head"] \
        * m["attention_layers"]
    return float(weights * weight_bytes
                 + experts_hit * expert_bytes(config, weight_bytes)
                 + state * state_bytes + cache * cache_bytes)
