"""Flash attention — blockwise streaming-softmax attention as a Pallas
TPU kernel.

The single-device building block of the long-context stack: exact softmax
attention in O(T) memory, with the K/V stream tiled through VMEM and the
running (m, l, acc) statistics held on-chip instead of materializing the
[T, S] score matrix in HBM. The ring layer
(`parallel/ring_attention.py`) runs the same math across devices; this
kernel is the within-device tier (the reference's analog of a cuDNN
helper, `CudnnConvolutionHelper.java:49` pattern — selected when
available, plain-XLA `blockwise_attention` otherwise).

Grid layout: (batch, q_blocks, kv_blocks) — the kv axis is innermost so
the (m, l, acc) VMEM scratch carries across kv steps of one q block
(TPU grids are sequential). Causal masking and ragged (non-multiple)
sequence lengths are handled with index masks.

Backward pass: blockwise Pallas kernels (FlashAttention-2 style). The
forward additionally emits the per-row logsumexp L = m + log(l); the
backward recomputes each [bq, bk] probability tile from (q, k, L) in VMEM
— never materializing the [T, S] matrix in HBM — and accumulates
  dv += p^T do,   ds = p * (do v^T - D),   dq += ds k,   dk += ds^T q
with D = rowsum(do * o). Memory stays O(T), matching the forward.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention", "flash_attention_heads", "flash_attention_spmd",
           "attention_reference"]

_NEG_INF = float("-inf")


def attention_reference(q, k, v, causal: bool = False,
                        sm_scale: Optional[float] = None,
                        q_positions=None, kv_length=None):
    """Plain softmax attention oracle. q: [B, T, D], k/v: [B, S, D].

    Decode extension (serving/decode): queries may sit at arbitrary
    offsets inside a LONGER key cache, so a square causal mask is not
    enough. `q_positions` [B, T] gives each query row's absolute key
    index (causal then means key j attends iff j <= q_positions[b, t] —
    a causal OFFSET, defaulting to the classic arange diagonal), and
    `kv_length` ([B] or scalar) the per-row count of valid cache slots:
    keys at j >= kv_length[b] (block-table padding, slots not yet
    written) get no attention weight. A row whose mask admits zero keys
    produces NaN — callers guarantee kv_length >= 1 for live rows (the
    decode plane parks padded batch slots at position 0 of a reserved
    block, so every row keeps one valid key)."""
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    logits = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * sm_scale
    T, S = logits.shape[-2], logits.shape[-1]
    ki = jnp.arange(S)
    if causal:
        if q_positions is None:
            qi = jnp.arange(T)[None, :]            # classic diagonal
        else:
            qi = jnp.asarray(q_positions)           # [B, T] offsets
        logits = jnp.where(ki[None, None, :] <= qi[:, :, None],
                           logits, _NEG_INF)
    if kv_length is not None:
        lengths = jnp.reshape(jnp.asarray(kv_length, jnp.int32), (-1,))
        logits = jnp.where(ki[None, None, :] < lengths[:, None, None],
                           logits, _NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", w, v.astype(jnp.float32)).astype(q.dtype)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _make_kernel(causal: bool, sm_scale: float, bq: int, bk: int,
                 s_len: int, emit_lse: bool = True):
    def kernel(q_ref, k_ref, v_ref, o_ref, *rest):
        if emit_lse:
            lse_ref, m_ref, l_ref, acc_ref = rest
        else:
            m_ref, l_ref, acc_ref = rest
        i = pl.program_id(1)
        j = pl.program_id(2)

        @pl.when(j == 0)
        def _():
            m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
            l_ref[:] = jnp.zeros_like(l_ref)
            acc_ref[:] = jnp.zeros_like(acc_ref)

        # causal: a kv block strictly above the q block's diagonal is dead
        live = (j * bk <= i * bq + bq - 1) if causal else (j >= 0)

        @pl.when(live)
        def _():
            q_blk = q_ref[0]                    # [bq, D]
            k_blk = k_ref[0]                    # [bk, D]
            v_blk = v_ref[0]                    # [bk, D]
            s = jax.lax.dot_general(
                q_blk, k_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            kv_idx = j * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1)
            mask = kv_idx < s_len               # ragged tail
            if causal:
                q_idx = i * bq + jax.lax.broadcasted_iota(
                    jnp.int32, (bq, bk), 0)
                mask = mask & (kv_idx <= q_idx)
            s = jnp.where(mask, s, _NEG_INF)

            m_prev = m_ref[:]                   # [bq, 128] lane-replicated
            m_cur = jnp.max(s, axis=-1, keepdims=True)     # [bq, 1]
            m_new = jnp.maximum(m_prev, m_cur)
            m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
            p = jnp.exp(s - m_safe[:, :1])
            p = jnp.where(mask, p, 0.0)
            corr = jnp.where(jnp.isneginf(m_prev), 0.0,
                             jnp.exp(m_prev - m_safe))
            m_ref[:] = m_new
            l_ref[:] = l_ref[:] * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[:] = acc_ref[:] * corr[:, :1] + jax.lax.dot_general(
                p, v_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        @pl.when(j == pl.num_programs(2) - 1)
        def _():
            o_ref[0] = (acc_ref[:]
                        / jnp.maximum(l_ref[:, :1], 1e-30)).astype(
                            o_ref.dtype)
            if emit_lse:
                m_safe = jnp.where(jnp.isneginf(m_ref[:]), 0.0, m_ref[:])
                lse_ref[0] = m_safe + jnp.log(jnp.maximum(l_ref[:], 1e-30))

    return kernel


def _flash_fwd_impl(q, k, v, causal, sm_scale, block_q, block_k, interpret,
                    emit_lse: bool = True):
    """emit_lse=False (the primal/inference path) skips computing AND
    writing the lane-replicated [B, Tp, 128] f32 logsumexp output — that
    write is up to 2x the HBM output traffic of a bf16 D=128 out row, and
    only the fwd-for-vjp path needs it."""
    B, T, D = q.shape
    S = k.shape[1]
    bq = min(block_q, _round_up(T, 8))
    bk = min(block_k, _round_up(S, 8))
    Tp, Sp = _round_up(T, bq), _round_up(S, bk)
    qp = jnp.pad(q, ((0, 0), (0, Tp - T), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, Sp - S), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, Sp - S), (0, 0)))
    grid = (B, Tp // bq, Sp // bk)
    kernel = _make_kernel(causal, sm_scale, bq, bk, S, emit_lse)
    o_spec = pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0),
                          memory_space=pltpu.VMEM)
    lse_spec = pl.BlockSpec((1, bq, 128), lambda b, i, j: (b, i, 0),
                            memory_space=pltpu.VMEM)
    out_shape = (jax.ShapeDtypeStruct((B, Tp, D), q.dtype),)
    out_specs = (o_spec,)
    if emit_lse:
        out_shape += (jax.ShapeDtypeStruct((B, Tp, 128), jnp.float32),)
        out_specs += (lse_spec,)
    res = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),   # running max m
            pltpu.VMEM((bq, 128), jnp.float32),   # running denom l
            pltpu.VMEM((bq, D), jnp.float32),     # output accumulator
        ],
        interpret=interpret,
        name="flash_fwd",
    )(qp, kp, vp)
    if not emit_lse:
        return res[0][:, :T], None
    out, lse = res
    # keep only one lane of the lane-replicated LSE: the residual held from
    # forward to backward is [B, Tp], not [B, Tp, 128]
    return out[:, :T], lse[:, :, 0]


def _bwd_masks(causal, bq, bk, i, j, t_len, s_len):
    """[bq, bk] validity mask for tile (i, j): ragged tails + causal."""
    q_idx = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kv_idx = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = (q_idx < t_len) & (kv_idx < s_len)
    if causal:
        mask = mask & (kv_idx <= q_idx)
    return mask


def _make_dq_kernel(causal, sm_scale, bq, bk, t_len, s_len):
    def kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dsum_ref,
               dq_ref, acc_ref):
        i = pl.program_id(1)
        j = pl.program_id(2)

        @pl.when(j == 0)
        def _():
            acc_ref[:] = jnp.zeros_like(acc_ref)

        live = (j * bk <= i * bq + bq - 1) if causal else (j >= 0)

        @pl.when(live)
        def _():
            q_blk = q_ref[0]
            k_blk = k_ref[0]
            v_blk = v_ref[0]
            do_blk = do_ref[0].astype(jnp.float32)
            s = jax.lax.dot_general(
                q_blk, k_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            mask = _bwd_masks(causal, bq, bk, i, j, t_len, s_len)
            p = jnp.where(mask, jnp.exp(s - lse_ref[0][:, :1]), 0.0)
            dp = jax.lax.dot_general(
                do_blk, v_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = p * (dp - dsum_ref[0][:, :1]) * sm_scale
            acc_ref[:] += jax.lax.dot_general(
                ds, k_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        @pl.when(j == pl.num_programs(2) - 1)
        def _():
            dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)

    return kernel


def _make_dkv_kernel(causal, sm_scale, bq, bk, t_len, s_len):
    """Grid (B, kv_blocks, q_blocks) — q axis innermost so the dk/dv VMEM
    accumulators carry across q steps of one kv block."""
    def kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dsum_ref,
               dk_ref, dv_ref, dk_acc, dv_acc):
        j = pl.program_id(1)   # kv block
        i = pl.program_id(2)   # q block (inner)

        @pl.when(i == 0)
        def _():
            dk_acc[:] = jnp.zeros_like(dk_acc)
            dv_acc[:] = jnp.zeros_like(dv_acc)

        live = (i * bq + bq - 1 >= j * bk) if causal else (i >= 0)

        @pl.when(live)
        def _():
            q_blk = q_ref[0]
            k_blk = k_ref[0]
            v_blk = v_ref[0]
            do_blk = do_ref[0].astype(jnp.float32)
            s = jax.lax.dot_general(
                q_blk, k_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            mask = _bwd_masks(causal, bq, bk, i, j, t_len, s_len)
            p = jnp.where(mask, jnp.exp(s - lse_ref[0][:, :1]), 0.0)
            dv_acc[:] += jax.lax.dot_general(
                p, do_blk, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(
                do_blk, v_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = p * (dp - dsum_ref[0][:, :1]) * sm_scale
            dk_acc[:] += jax.lax.dot_general(
                ds, q_blk, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        @pl.when(i == pl.num_programs(2) - 1)
        def _():
            dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
            dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)

    return kernel


def _flash_bwd_impl(q, k, v, o, lse, g, causal, sm_scale, block_q, block_k,
                    interpret):
    B, T, D = q.shape
    S = k.shape[1]
    bq = min(block_q, _round_up(T, 8))
    bk = min(block_k, _round_up(S, 8))
    Tp, Sp = _round_up(T, bq), _round_up(S, bk)
    qp = jnp.pad(q, ((0, 0), (0, Tp - T), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, Sp - S), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, Sp - S), (0, 0)))
    gp = jnp.pad(g, ((0, 0), (0, Tp - T), (0, 0)))
    # lane-replicate the [B, Tp] row statistics at kernel-call time
    lse = jnp.broadcast_to(lse[:, :, None], (B, lse.shape[1], 128))
    dsum = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    dsum = jnp.pad(dsum, ((0, 0), (0, Tp - T)))
    dsum = jnp.broadcast_to(dsum[:, :, None], (B, Tp, 128))

    q_spec = pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0),
                          memory_space=pltpu.VMEM)
    kv_spec = pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0),
                           memory_space=pltpu.VMEM)
    row_spec = pl.BlockSpec((1, bq, 128), lambda b, i, j: (b, i, 0),
                            memory_space=pltpu.VMEM)
    dq = pl.pallas_call(
        _make_dq_kernel(causal, sm_scale, bq, bk, T, S),
        out_shape=jax.ShapeDtypeStruct((B, Tp, D), q.dtype),
        grid=(B, Tp // bq, Sp // bk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(qp, kp, vp, gp, lse, dsum)

    # kv-major grid: swap the roles of the index maps
    q_spec2 = pl.BlockSpec((1, bq, D), lambda b, j, i: (b, i, 0),
                           memory_space=pltpu.VMEM)
    kv_spec2 = pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0),
                            memory_space=pltpu.VMEM)
    row_spec2 = pl.BlockSpec((1, bq, 128), lambda b, j, i: (b, i, 0),
                             memory_space=pltpu.VMEM)
    dk, dv = pl.pallas_call(
        _make_dkv_kernel(causal, sm_scale, bq, bk, T, S),
        out_shape=(jax.ShapeDtypeStruct((B, Sp, D), k.dtype),
                   jax.ShapeDtypeStruct((B, Sp, D), v.dtype)),
        grid=(B, Sp // bk, Tp // bq),
        in_specs=[q_spec2, kv_spec2, kv_spec2, q_spec2, row_spec2, row_spec2],
        out_specs=(kv_spec2, kv_spec2),
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qp, kp, vp, gp, lse, dsum)
    return dq[:, :T], dk[:, :S], dv[:, :S]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    out, _ = _flash_fwd_impl(q, k, v, causal, sm_scale, block_q, block_k,
                             interpret, emit_lse=False)
    return out


def _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    out, lse = _flash_fwd_impl(q, k, v, causal, sm_scale, block_q, block_k,
                               interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, sm_scale, block_q, block_k, interpret, res, g):
    q, k, v, o, lse = res
    return _flash_bwd_impl(q, k, v, o, lse, g, causal, sm_scale, block_q,
                           block_k, interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, causal: bool = False,
                    sm_scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None):
    """Blockwise flash attention. q: [B, T, D], k/v: [B, S, D].

    Compiled Pallas on TPU; `interpret=True` (automatic off-TPU) runs the
    identical kernel through the Pallas interpreter so CPU CI validates the
    same code path the TPU executes."""
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    # TPU lowering needs sublane-dim blocks in multiples of 8
    bq = max(8, _round_up(int(block_q), 8))
    bk = max(8, _round_up(int(block_k), 8))
    return _flash(q, k, v, bool(causal), float(sm_scale), bq, bk,
                  bool(interpret))


def flash_attention_heads(q, k, v, causal: bool = False,
                          sm_scale: Optional[float] = None,
                          block_q: int = 128, block_k: int = 128,
                          interpret: Optional[bool] = None):
    """Multi-head flash attention: q [B, T, H, Dh], k/v [B, S, H, Dh] ->
    [B, T, H, Dh]. Heads fold into the kernel's batch axis ([B*H, T, Dh]).
    A `vmap` over the head axis does not lower: it leaves the head as a
    squeezed block dimension second from last, and Mosaic needs the last
    two block dimensions to be the (sequence block, Dh) tile."""
    B, T, H, D = q.shape
    fold = lambda a: a.transpose(0, 2, 1, 3).reshape(B * H, a.shape[1], D)
    out = flash_attention(fold(q), fold(k), fold(v), causal, sm_scale,
                          block_q, block_k, interpret)
    return out.reshape(B, H, T, D).transpose(0, 2, 1, 3)


def flash_attention_spmd(q, k, v, causal: bool = False, *, mesh,
                         data_axis: str = "data", model_axis: str = "model",
                         sm_scale: Optional[float] = None,
                         block_q: int = 128, block_k: int = 128,
                         interpret: Optional[bool] = None):
    """Multi-head flash attention under `shard_map` over a (data, model)
    mesh: q/k/v [B, T, H, Dh] with the batch axis sharded over
    `data_axis` and the head axis over `model_axis` (the Megatron layout
    `nn/layers/transformer.py` produces — column-parallel QKV projections
    leave the head axis model-sharded).

    GSPMD has no partitioning rule for a Pallas custom call, so a flash
    kernel placed directly inside a sharded jit forces replication (or
    fails to partition). Attention, however, is INDEPENDENT per
    (batch row, head): each shard's local [B/d, T, H/m, Dh] block is
    exactly a standalone multi-head attention problem, so running the
    kernel per-shard inside `shard_map` needs ZERO collectives — the IR
    probes budget the surrounding step at the einsum baseline's per-axis
    bytes to prove nothing leaked. Requires B % d == 0 and H % m == 0
    (the trainer's batch sharding and `tp_validate` already enforce
    both)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    spec = P(data_axis, None, model_axis, None)

    def local_block(qb, kb, vb):
        return flash_attention_heads(qb, kb, vb, causal, sm_scale, block_q,
                                     block_k, interpret)

    return shard_map(local_block, mesh=mesh,
                     in_specs=(spec, spec, spec), out_specs=spec,
                     check_vma=False)(q, k, v)
