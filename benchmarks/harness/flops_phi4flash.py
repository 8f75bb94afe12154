"""Operations and bytes that Phi-4-mini-flash-reasoning's equations require
(`reference/phi4flash.py` states them), from shapes alone and from the counts
the program's spans carry: live rows, live pages, live window slots.

Nothing recomputed and nothing an implementation adds is counted. A Mamba
layer's recurrence is counted token by token as the equations write it, 5
operations a state element (the decay, `dt c B` and its sum, `H C` and its
sum), the convolution at 2 a tap and channel. Attention of one query over S
keys is 8 H Dh S: the two score maps (2 x 2 H Dh S) and the weighted sum of
values of 2 Dh with (A1 - lambda A2) (2 x 2 H Dh S). A window layer's token at
position p reads min(p + 1, W) keys, a full or cross layer's p + 1.

A prefill is counted as the skip leaves it: every prompt token passes the
self-decoder, the cross-decoder's Mamba layer and the full layer's keys and
values; the queries, GMUs, cross layers and MLPs of the cross-decoder run for
the prompt's last token alone, whose head gives the first served token."""
from __future__ import annotations


def dims(config: dict) -> dict:
    d, heads = int(config["hidden_size"]), int(config["num_attention_heads"])
    layers = int(config["num_hidden_layers"])
    kv = int(config["num_key_value_heads"]) // 2
    head = d // heads
    return {"d": d, "e": int(config["mamba_expand"]) * d,
            "n": int(config["mamba_d_state"]), "r": int(config["mamba_dt_rank"]),
            "k": int(config["mamba_d_conv"]), "heads": heads // 2,
            "kv_heads": kv, "head": head, "kv_width": kv * 2 * head,
            "window": int(config["sliding_window"]),
            "mlp": int(config["intermediate_size"]), "layers": layers,
            "half": layers // 2, "vocab": int(config["vocab_size"])}


def matrices(config: dict) -> dict:
    """Matrix elements of one layer of each kind, its MLP apart; the MLP."""
    m = dims(config)
    d, e, q = m["d"], m["e"], m["heads"] * 2 * m["head"]
    return {"mamba": d * 2 * e + e * (m["r"] + 2 * m["n"]) + m["r"] * e + e * d,
            "window": 2 * d * q + 2 * d * m["kv_width"],
            "full_kv": 2 * d * m["kv_width"], "query_out": 2 * d * q,
            "gmu": 2 * d * e, "mlp": 3 * d * m["mlp"]}


def counts(config: dict) -> dict:
    """Layers of each kind: 9 Mamba, 8 window, 1 full, 7 GMU, 7 cross."""
    half = dims(config)["half"]
    return {"mamba": half // 2 + 1, "window": half // 2, "gmu": half // 2 - 1,
            "cross": half // 2 - 1, "readers": half // 2}


def weights(config: dict) -> int:
    """Every matrix element of the 32 layers (the table and head apart)."""
    w, c = matrices(config), counts(config)
    return (c["mamba"] * w["mamba"] + c["window"] * w["window"]
            + w["full_kv"] + c["readers"] * w["query_out"]
            + c["gmu"] * w["gmu"] + dims(config)["layers"] * w["mlp"])


def _scan(config: dict) -> float:
    m = dims(config)
    return 5.0 * m["e"] * m["n"] + 2.0 * m["k"] * m["e"]


def self_flops_per_token(config: dict) -> float:
    """A token through the self-decoder, the cross-decoder's Mamba layer and
    the full layer's keys and values, attention's scores apart."""
    w, c, m = matrices(config), counts(config), dims(config)
    mats = (c["mamba"] * w["mamba"] + c["window"] * w["window"] + w["full_kv"]
            + (m["half"] + 1) * w["mlp"])
    return 2.0 * mats + c["mamba"] * _scan(config)


def cross_flops_per_token(config: dict) -> float:
    """What the cross-decoder adds after the full layer's keys and values:
    the readers' queries and outputs, the GMUs, their MLPs."""
    w, c, m = matrices(config), counts(config), dims(config)
    return 2.0 * (c["readers"] * w["query_out"] + c["gmu"] * w["gmu"]
                  + (m["half"] - 1) * w["mlp"])


def attention_flops(config: dict, keys: float) -> float:
    """One query of one layer over `keys` keys."""
    m = dims(config)
    return 8.0 * m["heads"] * m["head"] * keys


def _window_keys(config: dict, lo: int, hi: int) -> float:
    """Keys the window layers' queries at positions lo .. hi-1 read, summed."""
    w = dims(config)["window"]
    inside = max(0, min(hi, w) - lo)          # positions below the window
    first = min(lo, w)
    total = inside * (first + 1 + first + inside) / 2.0
    return total + max(0, hi - max(lo, w)) * w


def serve_flops(config: dict, prompt_lens, generated) -> float:
    """Required operations of served requests: a prefill as the skip leaves
    it (module docstring), then every generated token that was fed back
    through all layers, attending to its own context; the head where a token
    is sampled."""
    m, c = dims(config), counts(config)
    selfs, cross = self_flops_per_token(config), cross_flops_per_token(config)
    head = 2.0 * m["d"] * m["vocab"]
    total = 0.0
    for n, g in zip(prompt_lens, generated):
        fed = max(0, g - 1)               # the last sampled token is not fed
        total += n * selfs + cross + fed * (selfs + cross) + g * head
        total += c["window"] * attention_flops(
            config, _window_keys(config, 0, n + fed))
        # the readers: the prompt's last token over n keys, then each fed
        # token at position p over p + 1
        reads = n + fed * (2 * n + fed + 1) / 2.0
        total += c["readers"] * attention_flops(config, reads)
    return total


def tick_bytes(config: dict, rows: int, pages: int, window_live: int,
               block_len: int = 16, weight_bytes: int = 2,
               cache_bytes: int = 2) -> float:
    """Bytes one decode tick has to move: every weight once (the 32 layers'
    matrices and the head), the `pages` live pages of the shared keys and
    values read by each of the 8 reading layers, the `window_live` live ring
    slots (keys and values) of each window layer, and the `rows` live
    sequences' Mamba state, read and written, in every Mamba layer.
    Activations, norms, the logits and the token written are left out."""
    m, c = dims(config), counts(config)
    weights_ = weights(config) + m["d"] * m["vocab"]
    kv = 2 * m["kv_width"] * cache_bytes          # a token's keys and values
    shared = pages * block_len * kv * c["readers"]
    window = window_live * kv * c["window"]
    state = 2 * rows * c["mamba"] * (m["e"] * m["n"] + (m["k"] - 1) * m["e"])
    return float(weights_ * weight_bytes + shared + window + 4 * state)


def kernel_bytes(config: dict, pages: float, block_len: int = 16,
                 cache_bytes: int = 2) -> float:
    """What one `paged_diff_attention` call must read: the live pages' keys
    and values."""
    return float(pages * block_len * 2 * dims(config)["kv_width"] * cache_bytes)
