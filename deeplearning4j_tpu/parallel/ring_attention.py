"""Ring attention — sequence/context parallelism over ICI.

NEW capability relative to the reference (SURVEY.md §5 "long-context"): DL4J's
only long-sequence tool is truncated BPTT (`MultiLayerNetwork.doTruncatedBPTT`,
:1119) which *approximates* long-range gradients. Ring attention shards the
time dimension across devices and computes EXACT attention over sequences
larger than one device's memory: each device holds a query block and passes
its key/value block around the ring (`jax.lax.ppermute` over ICI), folding
each incoming block into a numerically-stable streaming softmax
(flash-attention style m/l/o accumulators).

API:
  * `blockwise_attention(q, k, v)` — single-device reference (used in tests)
  * `ring_self_attention(q, k, v, axis_name)` — inside shard_map, seq axis
    sharded on `axis_name`
  * `ring_attention_sharded(q, k, v, mesh, axis)` — host-level wrapper that
    shards [B, T, H] tensors on T and runs the ring under jit
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["blockwise_attention", "ring_self_attention",
           "ring_attention_sharded", "local_attention_reference"]


def local_attention_reference(q, k, v, causal: bool = False):
    """Plain softmax attention (the correctness oracle). q,k,v: [B, T, H].
    Single oracle shared with the kernel tier (kernels/attention.py)."""
    from ..kernels.attention import attention_reference

    return attention_reference(q, k, v, causal=causal)


def _fold_block(q, k_blk, v_blk, m, l, o, scale, blk_mask=None):
    """Fold one K/V block into streaming-softmax accumulators.
    m: [B,T,1] running max; l: [B,T,1] running denominator; o: [B,T,H]."""
    logits = jnp.einsum("bqh,bkh->bqk", q, k_blk) * scale
    if blk_mask is not None:
        logits = jnp.where(blk_mask, logits, -jnp.inf)
    m_blk = jnp.max(logits, axis=-1, keepdims=True)
    m_new = jnp.maximum(m, m_blk)
    # guard -inf (fully masked rows) from producing nan in exp(-inf - -inf)
    m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    p = jnp.exp(logits - m_safe)
    if blk_mask is not None:
        p = jnp.where(blk_mask, p, 0.0)
    corr = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m_safe))
    l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
    o_new = o * corr + jnp.einsum("bqk,bkh->bqh", p, v_blk)
    return m_new, l_new, o_new


def blockwise_attention(q, k, v, block_size: int = 128,
                        causal: bool = False):
    """Single-device blockwise (memory-efficient) attention over K/V blocks —
    identical math to the ring, with the ring permute replaced by a scan over
    local blocks. On TPU this dispatches to the Pallas flash kernel
    (`kernels/attention.py`, the accelerated-helper tier); the jnp scan
    below is the reference path (and what CPU CI exercises)."""
    from ..kernels import flash_attention, pallas_supported

    if pallas_supported():
        return flash_attention(q, k, v, causal=causal, block_q=block_size,
                               block_k=block_size)
    B, T, H = q.shape
    S = k.shape[1]
    scale = 1.0 / jnp.sqrt(jnp.asarray(H, q.dtype))
    nb = max(1, (S + block_size - 1) // block_size)
    pad = nb * block_size - S
    k_p = jnp.pad(k, ((0, 0), (0, pad), (0, 0)))
    v_p = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
    kv_idx = jnp.arange(nb * block_size)
    valid = kv_idx < S
    k_blocks = k_p.reshape(B, nb, -1, H).swapaxes(0, 1)   # [nb, B, bs, H]
    v_blocks = v_p.reshape(B, nb, -1, H).swapaxes(0, 1)
    valid_blocks = valid.reshape(nb, -1)
    kv_idx_blocks = kv_idx.reshape(nb, -1)
    q_idx = jnp.arange(T)

    m = jnp.full((B, T, 1), -jnp.inf, q.dtype)
    l = jnp.zeros((B, T, 1), q.dtype)
    o = jnp.zeros((B, T, H), q.dtype)

    def body(carry, blk):
        m, l, o = carry
        k_b, v_b, val, ki = blk
        mask = val[None, None, :]
        if causal:
            mask = mask & (ki[None, None, :] <= q_idx[None, :, None])
        m, l, o = _fold_block(q, k_b, v_b, m, l, o, scale, blk_mask=mask)
        return (m, l, o), None

    (m, l, o), _ = jax.lax.scan(
        body, (m, l, o),
        (k_blocks, v_blocks, valid_blocks, kv_idx_blocks))
    return o / jnp.maximum(l, 1e-30)


def ring_self_attention(q, k, v, axis_name: str, causal: bool = False):
    """Ring attention body — call inside shard_map with q/k/v sharded on the
    sequence axis. Each step folds the resident K/V block and permutes K/V to
    the next device; after `n` steps every query block has seen every K/V
    block. One ICI hop per step, compute/communication overlapped by XLA.

    causal=True masks by GLOBAL sequence position: the K/V block resident
    at step i originated on device (me - i) mod n, so its rows sit at
    global offset src*T; a block strictly right of this device's query
    range folds in fully masked (contributing nothing), the diagonal block
    gets the triangular mask, and blocks to the left fold in whole."""
    n = jax.lax.psum(1, axis_name)
    me = jax.lax.axis_index(axis_name)
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], q.dtype))
    B, T, H = q.shape

    m = jnp.full((B, T, 1), -jnp.inf, q.dtype)
    l = jnp.zeros((B, T, 1), q.dtype)
    o = jnp.zeros((B, T, H), q.dtype)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(i, carry):
        m, l, o, k_blk, v_blk = carry
        if causal:
            src = (me - i) % n
            q_pos = me * T + jnp.arange(T)[:, None]       # [T, 1]
            kv_pos = src * T + jnp.arange(T)[None, :]     # [1, S]
            blk_mask = (kv_pos <= q_pos)[None]            # [1, T, S]
            m, l, o = _fold_block(q, k_blk, v_blk, m, l, o, scale,
                                  blk_mask=blk_mask)
        else:
            m, l, o = _fold_block(q, k_blk, v_blk, m, l, o, scale)
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return m, l, o, k_blk, v_blk

    m, l, o, _, _ = jax.lax.fori_loop(0, n, body, (m, l, o, k, v))
    return o / jnp.maximum(l, 1e-30)


def ring_attention_sharded(q, k, v, mesh: Mesh, axis: str = "seq",
                           causal: bool = False):
    """Host-level entry: shard [B, T, H] on T over `axis` and run the ring."""
    from jax import shard_map

    spec = P(None, axis, None)
    fn = shard_map(functools.partial(ring_self_attention, axis_name=axis,
                                     causal=causal),
                   mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
                   check_vma=False)
    sh = NamedSharding(mesh, spec)
    q = jax.device_put(q, sh)
    k = jax.device_put(k, sh)
    v = jax.device_put(v, sh)
    from ..telemetry.compile_watch import watch_compiles
    return watch_compiles(jax.jit(fn), "parallel/ring_attention")(q, k, v)
