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

Schedule: one grid step is sized to keep the chip busy, not to the smallest
legal tile. `flash_tiling` derives the tiling from what a call can see (T, S,
Dh, the operand dtype; `block_q` / `block_k` only cap it): query tiles and
key chunks of several hundred rows, held to a VMEM budget, and the whole K/V
of a head (Q/dO for the kv-major backward kernel) resident in VMEM across a
head's steps wherever it fits — at every length a chip trains at. The grid is
(batch x heads, outer tiles, resident blocks of the streamed side); inside a
step a `fori_loop` walks the resident chunks and the running (m, l, acc) —
or the dq / dk, dv accumulators — carry in VMEM scratch. Under the causal
mask the loop's bounds come from the tile's own rows: chunks above the
diagonal are never visited, only chunks that cross it (or hold the ragged
tail) build the index mask, and where K/V is streamed in blocks a dead step
re-names the block already resident, so nothing is fetched for it. Matrix
products take their operands in the inputs' dtype (bfloat16 in, bfloat16
MXU passes) and accumulate in float32; the softmax statistics, `exp` and
every accumulator are float32. A `dl4j/kernels/flash_tiling` record in the
span log says, once per call shape, which tiling was chosen and how many
grid steps and dead chunks it makes.

Backward pass: blockwise Pallas kernels (FlashAttention-2 style). The
forward additionally emits the per-row logsumexp L = m + log(l), at its
real size `[B, T]` with a tile's rows along lanes; the backward recomputes
each [bq, bk] probability tile from (q, k, L) in VMEM — never materializing
the [T, S] matrix in HBM — and accumulates
  dv += p^T do,   ds = p * (do v^T - D),   dq += ds k,   dk += ds^T q
with D = rowsum(do * o). Memory stays O(T), matching the forward.

All three kernels hold the score tile transposed, [bk, bq] — keys on
sublanes, queries along lanes. Every per-query statistic (m, l, L, D) is
then a [1, bq] row: the forward's max and sum reduce over sublanes (VPU
adds, no cross-lane shuffles), the rows broadcast over the tile for free,
and L and D travel between the kernels at their real size. The
query-major results (o, dq) accumulate as [D, bq] and are transposed once,
when their tile is done. On the v5e at T 1024, Dh 64 this layout took the
forward from 0.58 to 0.36 ms a call and dq from 0.45 to 0.40 (PERF.md).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention", "flash_attention_heads", "flash_attention_spmd",
           "flash_tiling", "FlashTiling",
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


_LANES = 128
# What a masked score is set to. Finite, so a row that has met only masked
# keys never yields exp(-inf - -inf); exp(_MASK - m) is exactly 0.
_MASK = -0.7 * float(jnp.finfo(jnp.float32).max)
_TILE_ROWS = 512             # rows of a derived tile, before the budget
_VMEM_BUDGET = 16 << 20      # bytes the chooser lets one grid step hold
_VMEM_LIMIT = 32 << 20       # scoped limit handed to Mosaic (default 16 MiB)
_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_NN = (((1,), (0,)), ((), ()))   # a @ b
_TN = (((0,), (0,)), ((), ()))   # a.T @ b


class FlashTiling(NamedTuple):
    """How the three kernels cover the [T, S] score matrix. A grid step of
    `flash_fwd` / `flash_bwd_dq` owns `block_q` query rows and loops over
    the `block_k`-row chunks of the `k_chunks` chunks of K/V resident in
    VMEM; a step of `flash_bwd_dkv` owns `block_k` key rows and loops over
    `block_q`-row chunks of the `q_chunks` resident chunks of Q/dO."""
    block_q: int
    block_k: int
    q_chunks: int
    k_chunks: int
    t_pad: int
    s_pad: int
    vmem_bytes: int


def _vmem_bytes(bq, bk, cq, ck, d, item):
    """Estimated VMEM of the hungriest of the three kernels' grid steps."""
    tile = bq * bk * (4 * 4 + 2 * item)     # s, p, dp, ds f32; p, ds cast
    q_major = (3 * 2 * bq * d * item + bq * d * 4       # q, do, dq; acc
               + 2 * 2 * ck * bk * d * item)            # resident K, V
    kv_major = (4 * 2 * bk * d * item + 2 * bk * d * 4  # k, v, dk, dv; accs
                + 2 * 2 * cq * bq * d * item            # resident Q, dO
                + 2 * 2 * cq * 8 * bq * 4)              # lse, dsum rows
    return tile + max(q_major, kv_major)


def flash_tiling(T: int, S: int, Dh: int, dtype, causal: bool,
                 block_q: Optional[int] = None,
                 block_k: Optional[int] = None) -> FlashTiling:
    """The tiling for a `[T, Dh]` x `[S, Dh]` attention of `dtype` operands,
    from what the call can see. Tiles are `_TILE_ROWS` rows (or the caller's
    cap), multiples of 128 — of the dtype's sublane count under a cap below
    128 — halved while the step's working set is over `_VMEM_BUDGET`; as
    many chunks of the streamed side stay resident as the budget allows,
    all of them at any length a chip trains at. `causal` does not change
    the tiles, only which of them a kernel visits (`_kv_range`,
    `_q_range`)."""
    item = jnp.dtype(dtype).itemsize
    sub = 8 * max(1, 4 // item)

    def rows(n, cap):
        want = _TILE_ROWS if cap is None else int(cap)
        unit = _LANES if want >= _LANES else sub
        return min(max(want // unit, 1) * unit, _round_up(n, unit))

    # (static ints all: shapes and caps are jit-static arguments)
    bq, bk = rows(T, block_q), rows(S, block_k)
    while (_vmem_bytes(bq, bk, 1, 1, Dh, item) > _VMEM_BUDGET  # graftlint: disable=traced-value-branch
           and max(bq, bk) > _LANES):
        if bk >= bq:  # graftlint: disable=traced-value-branch
            bk = max(bk // 2 // _LANES, 1) * _LANES
        else:
            bq = max(bq // 2 // _LANES, 1) * _LANES

    def resident(n, b, is_q):
        """chunks of `b` rows kept in VMEM at a time, and the padded length:
        whole resident blocks, evened out so that the last is not mostly
        padding."""
        total = pl.cdiv(n, b)
        need = lambda c: _vmem_bytes(bq, bk, c if is_q else 1,
                                     1 if is_q else c, Dh, item)
        fit = next((c for c in range(total, 1, -1)
                    if need(c) <= _VMEM_BUDGET), 1)
        blocks = pl.cdiv(total, fit)
        per = pl.cdiv(total, blocks)
        return per, blocks * per * b

    cq, t_pad = resident(T, bq, True)
    ck, s_pad = resident(S, bk, False)
    return FlashTiling(bq, bk, cq, ck, t_pad, s_pad,
                       _vmem_bytes(bq, bk, cq, ck, Dh, item))


def _ops(*xs):
    """(minimum, maximum) for chunk indices: the builtins where every index
    is a Python int (`flash_schedule`), jnp's inside a kernel."""
    if all(isinstance(x, int) for x in xs):
        return min, max
    return jnp.minimum, jnp.maximum


def _kv_range(i, first, til: FlashTiling, s_len: int, causal: bool):
    """For query tile `i` and the resident K/V block whose first chunk is
    `first`: local chunk indices (lo, hi). Chunks [0, lo) lie wholly on the
    live side of every mask, [lo, hi) cross the diagonal or hold the ragged
    tail, and chunks from hi on are dead and never visited."""
    bq, bk = til.block_q, til.block_k
    lower, upper = _ops(i, first)
    n_full, n_live = s_len // bk, pl.cdiv(s_len, bk)
    if causal:
        n_full = lower(n_full, (i * bq + 1) // bk)
        n_live = lower(n_live, ((i + 1) * bq + bk - 1) // bk)
    clip = lambda n: lower(upper(n - first, 0), til.k_chunks)
    return clip(n_full), clip(n_live)


def _q_range(j, first, til: FlashTiling, t_len: int, s_len: int,
             causal: bool):
    """For key tile `j` and the resident Q/dO block whose first chunk is
    `first`: local chunk indices (lo, mid, hi). Chunks before lo are dead
    (every query precedes every key), [lo, mid) need the mask, [mid, hi)
    lie wholly below the diagonal; chunks from hi on hold only padding."""
    bq, bk = til.block_q, til.block_k
    lower, upper = _ops(j, first)
    n_q = pl.cdiv(t_len, bq)
    live_from, full_from = 0, 0
    if causal:
        live_from = (j * bk) // bq
        full_from = ((j + 1) * bk + bq - 2) // bq
    if til.s_pad != s_len:      # the tile that holds padded keys masks all
        full_from = upper(full_from, n_q * ((j + 1) * bk > s_len))
    lo = lower(upper(live_from - first, 0), til.q_chunks)
    hi = lower(upper(n_q - first, 0), til.q_chunks)
    return lo, lower(upper(full_from - first, lo), hi), hi


def flash_schedule(til: FlashTiling, T: int, S: int, causal: bool) -> dict:
    """What the kernels' loops visit for one folded head: grid steps of the
    q-major kernels (forward, dq) and of dkv, [block_q, block_k] score
    chunks visited, chunks never visited as dead, and grid steps that visit
    no chunk at all."""
    nq, nk = til.t_pad // til.block_q, til.s_pad // til.block_k
    visited = dead_steps = 0
    for i in range(nq):
        for first in range(0, nk, til.k_chunks):
            hi = _kv_range(i, first, til, S, causal)[1]
            visited += hi
            dead_steps += hi == 0
    return {"steps_q_major": nq * (nk // til.k_chunks),
            "steps_kv_major": nk * (nq // til.q_chunks),
            "chunks_visited": visited, "chunks_dead": nq * nk - visited,
            "dead_steps": dead_steps}


@functools.lru_cache(maxsize=256)
def _planned(B, T, S, Dh, dtype_name, causal, block_q, block_k) -> FlashTiling:
    """`flash_tiling` for one call shape, worked out once a process. Working
    it out leaves the record `dl4j/kernels/flash_tiling` in the span log:
    it is written while a kernel is built, never while one runs."""
    from ..telemetry import tracer

    til = flash_tiling(T, S, Dh, dtype_name, causal, block_q, block_k)
    sched = flash_schedule(til, T, S, causal)
    tracer().instant("dl4j/kernels/flash_tiling", batch=B, T=T, S=S, Dh=Dh,
                     dtype=dtype_name, causal=causal, **til._asdict(),
                     steps_a_call=B * sched["steps_q_major"], **sched)
    return til


def _live(causal, q0, k0, til: FlashTiling, s_len):
    """[bk, bq] mask of the scores that count in the tile whose first key is
    `k0` and first query `q0`: key below `s_len`, and not after its query
    when causal. None where nothing is masked."""
    padded = til.s_pad != s_len
    if not (causal or padded):
        return None
    shape = (til.block_k, til.block_q)
    kv = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    mask = kv < s_len if padded else None
    if causal:
        qi = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        mask = (kv <= qi) if mask is None else mask & (kv <= qi)
    return mask


def _scores(k_blk, q_blk, sm_scale, mask):
    """[bk, bq] scaled scores k q^T in float32, the masked ones at `_MASK`."""
    s = jax.lax.dot_general(k_blk, q_blk, _NT,
                            preferred_element_type=jnp.float32) * sm_scale
    return s if mask is None else jnp.where(mask, s, _MASK)


def _two_ranges(lo, mid, hi, chunk, first_masked: bool):
    """Run chunk(c, masked) over [lo, mid) and [mid, hi)."""
    jax.lax.fori_loop(lo, mid, lambda c, _: chunk(c, first_masked), None)
    jax.lax.fori_loop(mid, hi, lambda c, _: chunk(c, not first_masked), None)


def _make_kernel(causal: bool, sm_scale: float, til: FlashTiling,
                 s_len: int, emit_lse: bool = True):
    """Grid (B, q tiles, resident K/V blocks). Like the backward kernels it
    holds the score tile transposed, [bk, bq]: the running max and sum are
    then rows along lanes, reduced over sublanes and broadcast for free, and
    the output accumulates as [D, bq], transposed once when the tile is done."""
    bq, bk, ck = til.block_q, til.block_k, til.k_chunks

    def kernel(q_ref, k_ref, v_ref, o_ref, *rest):
        if emit_lse:
            lse_ref, m_ref, l_ref, acc_ref = rest
        else:
            m_ref, l_ref, acc_ref = rest
        i = pl.program_id(1)
        jm = pl.program_id(2)

        @pl.when(jm == 0)
        def _():
            m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
            l_ref[:] = jnp.zeros_like(l_ref)
            acc_ref[:] = jnp.zeros_like(acc_ref)

        q_blk = q_ref[0]                        # [bq, D]
        lo, hi = _kv_range(i, jm * ck, til, s_len, causal)

        def chunk(c, masked):
            rows = pl.ds(pl.multiple_of(c * bk, bk), bk)
            k_blk = k_ref[0, rows, :]           # [bk, D]
            v_blk = v_ref[0, rows, :]
            s = _scores(k_blk, q_blk, sm_scale, _live(
                causal, i * bq, (jm * ck + c) * bk, til, s_len)
                if masked else None)
            m_prev = m_ref[:]                   # [1, bq]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            m_ref[:] = m_new
            l_ref[:] = l_ref[:] * corr + jnp.sum(p, axis=0, keepdims=True)
            acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
                v_blk, p.astype(v_blk.dtype), _TN,
                preferred_element_type=jnp.float32)     # [D, bq]

        # chunk 0 holds key 0, which every query may see: after it each
        # row's running max is a real score and the masked ones weigh 0
        _two_ranges(0, lo, hi, chunk, first_masked=False)

        @pl.when(jm == pl.num_programs(2) - 1)
        def _():
            l = l_ref[:]
            o_ref[0] = (acc_ref[:] / l).T.astype(o_ref.dtype)
            if emit_lse:
                lse_ref[0, 0] = m_ref[:] + jnp.log(l)

    return kernel


def _params(interpret):
    """Mosaic's compiler options; the interpreter takes none."""
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)}


def _specs(til: FlashTiling, D: int, causal: bool):
    """BlockSpecs of the q-major kernels (forward, dq): a query tile, the
    resident K/V block, a row of statistics. Under the causal mask a step
    whose K/V block is dead re-names the last live one, so nothing is
    fetched for it."""
    bq, bkm = til.block_q, til.block_k * til.k_chunks

    def kv_map(b, i, jm):
        if causal and bkm != til.s_pad:
            jm = jnp.minimum(jm, ((i + 1) * bq - 1) // bkm)
        return (b, jm, 0)

    q_spec = pl.BlockSpec((1, bq, D), lambda b, i, jm: (b, i, 0),
                          memory_space=pltpu.VMEM)
    kv_spec = pl.BlockSpec((1, bkm, D), kv_map, memory_space=pltpu.VMEM)
    row_spec = pl.BlockSpec((1, 1, 1, bq), lambda b, i, jm: (b, i, 0, 0),
                            memory_space=pltpu.VMEM)
    return q_spec, kv_spec, row_spec


# Jitted on their own so that the identical calls of a model's layers are
# traced and lowered to Mosaic once, not once a layer (24 forward kernels of
# a GPT-2 medium cost seconds of every process's set-up otherwise). XLA inlines
# the call. Their names hold no kernel's name: the benchmark finds kernels
# in a trace by `flash_fwd` / `flash_bwd_*` in an operation's name. They run
# inside a model's own (watched) step, never as an entry point.
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8))  # graftlint: disable=unwatched-jit-entry
def _fwd_call(q, k, v, causal, sm_scale, block_q, block_k, interpret,
              emit_lse: bool = True):
    """emit_lse=False (the primal/inference path) skips computing AND
    writing the logsumexp; only the fwd-for-vjp path needs it. It leaves
    the kernel at its real size, `[B, T]` laid along lanes."""
    B, T, D = q.shape
    S = k.shape[1]
    til = _planned(B, T, S, D, q.dtype.name, causal, block_q, block_k)
    bq, Tp, Sp = til.block_q, til.t_pad, til.s_pad
    qp = jnp.pad(q, ((0, 0), (0, Tp - T), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, Sp - S), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, Sp - S), (0, 0)))
    q_spec, kv_spec, row_spec = _specs(til, D, causal)
    out_shape = (jax.ShapeDtypeStruct((B, Tp, D), q.dtype),)
    out_specs = (q_spec,)
    if emit_lse:
        out_shape += (jax.ShapeDtypeStruct((B, Tp // bq, 1, bq),
                                           jnp.float32),)
        out_specs += (row_spec,)
    res = pl.pallas_call(
        _make_kernel(causal, sm_scale, til, S, emit_lse),
        out_shape=out_shape,
        grid=(B, Tp // bq, Sp // (til.block_k * til.k_chunks)),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((1, bq), jnp.float32),        # running max m
            pltpu.VMEM((1, bq), jnp.float32),        # running denom l
            pltpu.VMEM((D, bq), jnp.float32),        # output accumulator
        ],
        interpret=interpret,
        name="flash_fwd",
        **_params(interpret),
    )(qp, kp, vp)
    if not emit_lse:
        return res[0][:, :T], None
    out, lse = res
    return out[:, :T], lse.reshape(B, Tp)[:, :T]


def _make_dq_kernel(causal, sm_scale, til: FlashTiling, s_len):
    """Grid and tile as the forward's; dq accumulates as [D, bq]."""
    bq, bk, ck = til.block_q, til.block_k, til.k_chunks

    def kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dsum_ref,
               dq_ref, acc_ref):
        i = pl.program_id(1)
        jm = pl.program_id(2)

        @pl.when(jm == 0)
        def _():
            acc_ref[:] = jnp.zeros_like(acc_ref)

        q_blk = q_ref[0]
        do_blk = do_ref[0]
        lse = lse_ref[0, 0]                     # [1, bq]
        dsum = dsum_ref[0, 0]
        lo, hi = _kv_range(i, jm * ck, til, s_len, causal)

        def chunk(c, masked):
            rows = pl.ds(pl.multiple_of(c * bk, bk), bk)
            k_blk = k_ref[0, rows, :]
            v_blk = v_ref[0, rows, :]
            s = _scores(k_blk, q_blk, sm_scale, _live(
                causal, i * bq, (jm * ck + c) * bk, til, s_len)
                if masked else None)
            p = jnp.exp(s - lse)
            dp = jax.lax.dot_general(
                v_blk, do_blk, _NT, preferred_element_type=jnp.float32)
            ds = p * (dp - dsum)
            acc_ref[:] += jax.lax.dot_general(
                k_blk, ds.astype(k_blk.dtype), _TN,
                preferred_element_type=jnp.float32)      # [D, bq]

        _two_ranges(0, lo, hi, chunk, first_masked=False)

        @pl.when(jm == pl.num_programs(2) - 1)
        def _():
            dq_ref[0] = (acc_ref[:] * sm_scale).T.astype(dq_ref.dtype)

    return kernel


def _make_dkv_kernel(causal, sm_scale, til: FlashTiling, t_len, s_len):
    """Grid (B, kv tiles, resident Q/dO blocks). The score tile is held
    transposed, [bk, bq], so that the per-query statistics broadcast from a
    row along lanes and both accumulating products are plain a @ b."""
    bq, bk, cq = til.block_q, til.block_k, til.q_chunks

    def kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dsum_ref,
               dk_ref, dv_ref, dk_acc, dv_acc):
        j = pl.program_id(1)
        im = pl.program_id(2)

        @pl.when(im == 0)
        def _():
            dk_acc[:] = jnp.zeros_like(dk_acc)
            dv_acc[:] = jnp.zeros_like(dv_acc)

        k_blk = k_ref[0]                        # [bk, D]
        v_blk = v_ref[0]
        lo, mid, hi = _q_range(j, im * cq, til, t_len, s_len, causal)

        def chunk(c, masked):
            rows = pl.ds(pl.multiple_of(c * bq, bq), bq)
            q_blk = q_ref[0, rows, :]           # [bq, D]
            do_blk = do_ref[0, rows, :]
            s = _scores(k_blk, q_blk, sm_scale, _live(
                causal, (im * cq + c) * bq, j * bk, til, s_len)
                if masked else None)
            # a padded query row has do = dsum = 0 and a finite p: it adds 0
            p = jnp.exp(s - lse_ref[0, c])      # [bk, bq] - [1, bq]
            dv_acc[:] += jax.lax.dot_general(
                p.astype(do_blk.dtype), do_blk, _NN,
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(
                v_blk, do_blk, _NT, preferred_element_type=jnp.float32)
            ds = p * (dp - dsum_ref[0, c])
            dk_acc[:] += jax.lax.dot_general(
                ds.astype(q_blk.dtype), q_blk, _NN,
                preferred_element_type=jnp.float32)

        _two_ranges(lo, mid, hi, chunk, first_masked=True)

        @pl.when(im == pl.num_programs(2) - 1)
        def _():
            dk_ref[0] = (dk_acc[:] * sm_scale).astype(dk_ref.dtype)
            dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)

    return kernel


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9, 10))  # graftlint: disable=unwatched-jit-entry
def _bwd_call(q, k, v, o, lse, g, causal, sm_scale, block_q, block_k,
              interpret):
    B, T, D = q.shape
    S = k.shape[1]
    til = _planned(B, T, S, D, q.dtype.name, causal, block_q, block_k)
    bq, bk, Tp, Sp = til.block_q, til.block_k, til.t_pad, til.s_pad
    qp = jnp.pad(q, ((0, 0), (0, Tp - T), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, Sp - S), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, Sp - S), (0, 0)))
    gp = jnp.pad(g, ((0, 0), (0, Tp - T), (0, 0)))
    # the [B, T] row statistics go in at their real size, a tile's rows
    # along lanes; the kernels broadcast them in VMEM
    dsum = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    rows = lambda a: jnp.pad(a, ((0, 0), (0, Tp - T))).reshape(
        B, Tp // bq, 1, bq)
    lse, dsum = rows(lse), rows(dsum)

    q_spec, kv_spec, row_spec = _specs(til, D, causal)
    dq = pl.pallas_call(
        _make_dq_kernel(causal, sm_scale, til, S),
        out_shape=jax.ShapeDtypeStruct((B, Tp, D), q.dtype),
        grid=(B, Tp // bq, Sp // (bk * til.k_chunks)),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((D, bq), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
        **_params(interpret),
    )(qp, kp, vp, gp, lse, dsum)

    # kv-major grid: the roles of the index maps swap
    cq, bqm = til.q_chunks, bq * til.q_chunks

    def q_block(j, im):
        if causal and bqm != Tp:    # a dead block re-names the first live
            im = jnp.maximum(im, (j * bk) // bqm)
        return im

    q_spec2 = pl.BlockSpec((1, bqm, D),
                           lambda b, j, im: (b, q_block(j, im), 0),
                           memory_space=pltpu.VMEM)
    kv_spec2 = pl.BlockSpec((1, bk, D), lambda b, j, im: (b, j, 0),
                            memory_space=pltpu.VMEM)
    row_spec2 = pl.BlockSpec((1, cq, 1, bq),
                             lambda b, j, im: (b, q_block(j, im), 0, 0),
                             memory_space=pltpu.VMEM)
    dk, dv = pl.pallas_call(
        _make_dkv_kernel(causal, sm_scale, til, T, S),
        out_shape=(jax.ShapeDtypeStruct((B, Sp, D), k.dtype),
                   jax.ShapeDtypeStruct((B, Sp, D), v.dtype)),
        grid=(B, Sp // bk, Tp // bqm),
        in_specs=[q_spec2, kv_spec2, kv_spec2, q_spec2, row_spec2, row_spec2],
        out_specs=(kv_spec2, kv_spec2),
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dkv",
        **_params(interpret),
    )(qp, kp, vp, gp, lse, dsum)
    return dq[:, :T], dk[:, :S], dv[:, :S]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    out, _ = _fwd_call(q, k, v, causal, sm_scale, block_q, block_k,
                       interpret, False)
    return out


def _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    out, lse = _fwd_call(q, k, v, causal, sm_scale, block_q, block_k,
                         interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, sm_scale, block_q, block_k, interpret, res, g):
    q, k, v, o, lse = res
    return _bwd_call(q, k, v, o, lse, g, causal, sm_scale, block_q, block_k,
                     interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, causal: bool = False,
                    sm_scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """Blockwise flash attention. q: [B, T, D], k/v: [B, S, D].

    The tiling comes from the shape (`flash_tiling`); `block_q` / `block_k`
    cap it. Compiled Pallas on TPU; `interpret=True` (automatic off-TPU)
    runs the identical kernel through the Pallas interpreter so CPU CI
    validates the same code path the TPU executes."""
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    cap = lambda b: None if b is None else int(b)
    return _flash(q, k, v, bool(causal), float(sm_scale), cap(block_q),
                  cap(block_k), bool(interpret))


def flash_attention_heads(q, k, v, causal: bool = False,
                          sm_scale: Optional[float] = None,
                          block_q: Optional[int] = None,
                          block_k: Optional[int] = None,
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
                         block_q: Optional[int] = None,
                         block_k: Optional[int] = None,
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
