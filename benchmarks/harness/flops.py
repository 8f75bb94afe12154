"""Operations and bytes that the algorithm requires, from shapes alone.

Every share of a peak or of a roofline that the benchmark reports divides by
a number computed here. Nothing recomputed (a remat, a kernel that builds the
score matrix twice) is counted: these are the operations of the mathematics,
not of an implementation.
"""
from __future__ import annotations


def lm_dims(config: dict) -> dict:
    """The sizes the formulas need, from a configuration file."""
    d = int(config["n_embd"])
    return {"d": d, "layers": int(config["n_layer"]),
            "heads": int(config["n_head"]),
            "d_head": d // int(config["n_head"]),
            "vocab": int(config["assumed"]["padded_vocab_size"]),
            "ffn": int(config["assumed"]["n_inner"])}


def lm_block_flops_per_token(config: dict) -> float:
    """Matrix products of all blocks for one token, forward: four d x d
    projections and the two FFN products, 2 operations a multiply-add."""
    m = lm_dims(config)
    return 2.0 * m["layers"] * (4 * m["d"] ** 2 + 2 * m["d"] * m["ffn"])


def lm_head_flops_per_token(config: dict) -> float:
    m = lm_dims(config)
    return 2.0 * m["d"] * m["vocab"]


def lm_attention_flops(config: dict, context: int) -> float:
    """QK^T and PV for ONE token that attends to `context` keys."""
    m = lm_dims(config)
    return 4.0 * context * m["d"] * m["layers"]


def lm_forward_flops_per_token(config: dict, seq_len: int) -> float:
    """Mean over a causal sequence of `seq_len` tokens: 2(12 L d^2 + d V) +
    2 T d L. Token t attends to t + 1 keys, half of T on average."""
    return (lm_block_flops_per_token(config) + lm_head_flops_per_token(config)
            + lm_attention_flops(config, seq_len) / 2.0)


def lm_train_flops_per_token(config: dict, seq_len: int) -> float:
    """Forward plus backward (twice the forward): 3x, no recomputation."""
    return 3.0 * lm_forward_flops_per_token(config, seq_len)


def lm_serve_flops(config: dict, prompt_lens, generated) -> float:
    """Required operations of a served window. Every prompt token and every
    generated token that was fed back passes the blocks, attending to its own
    context; the head is needed only where a token is sampled (once a
    prefill, once a tick). `prompt_lens[i]` prompt tokens and `generated[i]`
    sampled tokens for request i, all of it done inside the window."""
    block = lm_block_flops_per_token(config)
    head = lm_head_flops_per_token(config)
    total = 0.0
    for n, g in zip(prompt_lens, generated):
        fed = n + max(0, g - 1)           # the last sampled token is not fed
        # token at position p attends to p + 1 keys: sum_{p<fed} (p + 1)
        total += fed * block + lm_attention_flops(config, 1) * fed * (fed + 1) / 2.0
        total += g * head
    return total


FLASH_KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def flash_kernel_flops(kernel: str, bh: int, t: int, d_head: int,
                       causal: bool = True) -> float:
    """Two required T x T x Dh products in each of the three kernels
    (forward QK^T, PV; dq: dO V^T, dS K; dkv: P^T dO, dS^T Q). The score
    matrix that the backward kernels build again is not counted."""
    if kernel not in FLASH_KERNELS:
        raise KeyError(kernel)
    f = 2 * 2.0 * t * t * d_head * bh
    return f / 2.0 if causal else f


def flash_step_flops(bh: int, t: int, d_head: int, causal: bool = True) -> float:
    return sum(flash_kernel_flops(k, bh, t, d_head, causal)
               for k in FLASH_KERNELS)


def flash_kernel_bytes(kernel: str, bh: int, t: int, d_head: int,
                       itemsize: int = 2) -> float:
    """Each kernel's inputs and outputs once: [BH, T, Dh] tensors of
    `itemsize` bytes, and the f32 row statistics (logsumexp, dO.O)."""
    big, row = bh * t * d_head * itemsize, bh * t * 4
    return {"flash_fwd": 4 * big + row,            # q k v -> o, lse
            "flash_bwd_dq": 5 * big + 2 * row,     # q k v do lse dsum -> dq
            "flash_bwd_dkv": 6 * big + 2 * row,    # ... -> dk, dv
            }[kernel]


def roofline_seconds(flops: float, nbytes: float, peak: dict):
    """(least seconds, which bound holds)."""
    tc = flops / peak["bf16_flops_per_s"]
    tm = nbytes / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
