"""Paged decode attention — one new token a row against the paged KV arena,
read through the block table inside a Pallas TPU kernel.

The decode plane's tick (`serving/decode/engine.py`) holds every sequence's
keys and values in one arena `[2L, num_blocks, block_len, H*Dh]`
(`serving/decode/cache.py`); a row owns the blocks its table names. The
plain path gathers each row's whole table into a `[B, W*block_len, H, Dh]`
view and attends over it: at the served size that is a 64 MiB gather and a
128 MiB relayout a layer and channel, for rows that hold a few hundred live
tokens. This kernel makes no view. The arena stays in HBM (`pl.ANY`); the
tables, the lengths and the layer's channel are scalar-prefetched; a grid
step is one row, and inside it a loop walks the row's live chunks of
`pages_a_chunk` table columns. A chunk's K and V pages are copied to VMEM by
one DMA a page (a page is `[block_len, H*Dh]`: contiguous, whole (8, 128)
tiles) into one of two slots, the next chunk's — or the next row's first —
in flight while this one is computed.

- A chunk covers `_CHUNK_TOKENS` cache slots (8 pages of 16): 1 MiB of K and
  V, 1.3 us of HBM time, against a loop iteration's fixed cost. A grid of one
  step a chunk (the arena handed over once a page, the ordinary pipeline
  fetching through index maps) measured twice the time on the v5e: 60 of its
  128 steps were dead, and a dead step still costs, and hides nothing.
- Dead pages are never read: the loop ends with the row's last live chunk,
  and in that chunk the columns past the row's last live page name that page
  again. What a dead table slot points at (the trash block, a block since
  given to another sequence) never reaches VMEM. The ragged tail is masked
  by the length, exactly the `kv_length` mask of `attention_reference`; in a
  tick the query is the newest token, so the causal mask adds nothing.
- All heads at once, on the merged `H*Dh` lanes: the row's query becomes the
  block-diagonal `[H, H*Dh]` (row h holds head h's `Dh` lanes, zero
  elsewhere), a chunk's scores are `Qbd @ K_chunk^T -> [H, slots]` on the
  MXU, the running max and sum are `[H, 1]`, and `acc [H, H*Dh] += p @
  V_chunk`, of which row h's own `Dh` lanes are the answer. No `[.., H, Dh]`
  array exists anywhere. The MXU columns this wastes do not show: the kernel
  is bound by HBM.
- The arena stays as it is, float32 or bfloat16. Products take their
  operands in the arena's dtype (float32 at the default precision: on the
  TPU one bfloat16 pass, as XLA's matrix products of these models) and
  accumulate in float32; the softmax statistics, `exp` and the accumulator
  are float32.
- Grouped queries (`n_kv_heads` < `n_heads`: the arena holds `Hkv*Dh` lanes,
  `n_heads / n_kv_heads` query heads read each key/value head): the same
  loop over the same pages, the block-diagonal query `[H, Hkv*Dh]` with row
  h on the lanes of key/value head `h // group`. XLA lays it out before the
  call and takes each head's own lanes after it (`[B, H, Hkv*Dh]`, some
  130 kB a row beside the row's megabytes of pages), so the kernel never
  reshapes lanes into sublanes.
- Rows are independent: a row's chunks depend on its own length and table
  only, so its result is the same whoever else is in the batch.

`dl4j/kernels/paged_attention` in the span log says, once per call shape,
what a call is made of (rows, table width, pages a chunk, grid steps, VMEM
estimated).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import _MASK, _NEG_INF, _NN, _NT, _round_up

__all__ = ["paged_decode_attention", "paged_latent_attention", "PagedPlan",
           "paged_attention_supported", "paged_plan", "latent_plan"]

_CHUNK_TOKENS = 128     # cache slots one iteration of a row's loop covers
_SUBLANES = 8           # float32 rows of a tile: heads are padded up to it


class PagedPlan(NamedTuple):
    """What one call is made of."""
    pages_a_chunk: int
    chunks_a_row: int    # at most: table columns / pages a chunk
    steps_a_call: int    # grid steps: one a row
    heads_padded: int
    vmem_bytes: int      # estimated: the page slots, the rows, the values


def paged_attention_supported(width: int, block_len: int,
                              dtype="float32") -> bool:
    """Whether the compiled kernel takes an arena of `width` = Hkv*Dh lanes
    and `block_len`-slot pages of `dtype`: whole tiles a page, (8, 128) of
    float32, (16, 128) of bfloat16."""
    rows = {"float32": _SUBLANES, "bfloat16": 2 * _SUBLANES}.get(
        jnp.dtype(dtype).name)
    return rows is not None and width % 128 == 0 and block_len % rows == 0


def paged_plan(rows: int, table_width: int, block_len: int, n_heads: int,
               width: int, itemsize: int = 4) -> PagedPlan:
    """Pages a chunk from the shape: `_CHUNK_TOKENS` cache slots' worth, no
    more than the table is wide. `width` is the arena's, `itemsize` its
    dtype's."""
    pages = max(1, min(_CHUNK_TOKENS // block_len, table_width))
    hp = _round_up(n_heads, _SUBLANES)
    chunk = pages * block_len * width * itemsize
    vmem = (2 * 2 * chunk                   # two slots each of K and V pages
            + 2 * 2 * rows * width * 4      # the queries and the output
            + 2 * chunk                     # a chunk's K and V as values
            + 2 * hp * width * 4)           # the block-diagonal query; acc
    return PagedPlan(pages, pl.cdiv(table_width, pages), rows, hp, vmem)


@functools.lru_cache(maxsize=64)
def _planned(rows, table_width, block_len, n_heads, width, num_blocks,
             n_kv_heads=None, dtype="float32") -> PagedPlan:
    """`paged_plan` for one call shape, worked out once a process; working
    it out leaves the record `dl4j/kernels/paged_attention` in the span
    log: written while a kernel is built, never while one runs."""
    from ..telemetry import tracer

    plan = paged_plan(rows, table_width, block_len, n_heads, width,
                      jnp.dtype(dtype).itemsize)
    grouped = {} if n_kv_heads is None else {"n_kv_heads": n_kv_heads,
                                             "dtype": dtype}
    tracer().instant("dl4j/kernels/paged_attention", rows=rows,
                     table_width=table_width, block_len=block_len,
                     n_heads=n_heads, width=width, num_blocks=num_blocks,
                     **grouped, **plan._asdict())
    return plan


def _make_kernel(n_heads: int, d_head: int, plan: PagedPlan, block_len: int,
                 sm_scale: float, n_kv_heads: Optional[int] = None,
                 dtype=jnp.float32):
    """Grid (rows,). A row's loop holds `pages_a_chunk` pages of K and of V
    at a time; the scores are `[heads, slots]`, the statistics `[heads, 1]`.
    With `n_kv_heads` the query arrives block-diagonal, `[1, heads, width]`
    a grid step, and the output leaves so (module docstring)."""
    pages, hp, n_rows = plan.pages_a_chunk, plan.heads_padded, plan.steps_a_call
    grouped = n_kv_heads is not None
    width = (n_kv_heads if grouped else n_heads) * d_head
    chunk = plan.pages_a_chunk * block_len

    def kernel(tables_ref, lengths_ref, channel_ref, q_ref, kv_ref, o_ref,
               k_buf, v_buf, sems, slot_ref):
        b = pl.program_id(0)
        channel = channel_ref[0]

        def copies(row, c, slot):
            """The DMAs of chunk `c` of `row` into `slot`: a page of K and a
            page of V a table column; past the row's last live page, that
            page again."""
            last = jnp.maximum(lengths_ref[row] - 1, 0) // block_len
            out = []
            for i in range(pages):
                blk = tables_ref[row, jnp.minimum(c * pages + i, last)]
                dst = pl.ds(i * block_len, block_len)
                out.append(pltpu.make_async_copy(
                    kv_ref.at[channel, blk], k_buf.at[slot, dst, :],
                    sems.at[slot, 0]))
                out.append(pltpu.make_async_copy(
                    kv_ref.at[channel + 1, blk], v_buf.at[slot, dst, :],
                    sems.at[slot, 1]))
            return out

        def start(row, c, slot):
            for copy in copies(row, c, slot):
                copy.start()

        @pl.when(b == 0)
        def _():
            slot_ref[0] = 0
            start(0, 0, 0)

        length = lengths_ref[b]
        n_chunks = jnp.maximum(length - 1, 0) // chunk + 1
        first = slot_ref[0]              # the slot this row's chunk 0 is in
        if grouped:
            qbd = q_ref[0].astype(dtype)                        # [hp, width]
        else:
            head = jax.lax.broadcasted_iota(jnp.int32, (hp, width), 0)
            lane = jax.lax.broadcasted_iota(jnp.int32, (hp, width), 1)
            own = (lane >= head * d_head) & (lane < (head + 1) * d_head)
            qbd = jnp.where(own, q_ref[pl.ds(b, 1), :], 0.0).astype(dtype)

        def body(c, carry):
            m_prev, l_prev, acc = carry
            slot = (first + c) % 2

            @pl.when(c + 1 < n_chunks)
            def _():
                start(b, c + 1, 1 - slot)

            @pl.when((c + 1 == n_chunks) & (b + 1 < n_rows))
            def _():
                start(b + 1, 0, 1 - slot)

            for copy in copies(b, c, slot):
                copy.wait()
            s = jax.lax.dot_general(
                qbd, k_buf[slot], _NT,
                preferred_element_type=jnp.float32) * sm_scale
            at = c * chunk + jax.lax.broadcasted_iota(
                jnp.int32, (hp, chunk), 1)
            # chunk 0 holds slot 0, which every row may see: after it the
            # running max is a real score and a masked one weighs exactly 0
            s = jnp.where(at < length, s, _MASK)                # [hp, chunk]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
            acc = acc * corr + jax.lax.dot_general(
                p.astype(dtype), v_buf[slot], _NN,
                preferred_element_type=jnp.float32)             # [hp, width]
            return m_new, l_new, acc

        _, l, acc = jax.lax.fori_loop(
            0, n_chunks, body,
            (jnp.full((hp, 1), _NEG_INF, jnp.float32),
             jnp.zeros((hp, 1), jnp.float32),
             jnp.zeros((hp, width), jnp.float32)))
        slot_ref[0] = (first + n_chunks) % 2
        if grouped:
            o_ref[0] = acc / l
        else:
            mine = jnp.where(own, acc / l, 0.0)
            o_ref[pl.ds(b, 1), :] = jnp.sum(mine, axis=0, keepdims=True)

    return kernel


def paged_decode_attention(q, kv, channel, tables, lengths, *, n_heads: int,
                           n_kv_heads: Optional[int] = None,
                           sm_scale: Optional[float] = None,
                           interpret: Optional[bool] = None):
    """Attention of one query a row over its paged cache.

    q [B, H*Dh] float32 (heads merged, as the arena holds them); kv the arena
    `[2L, num_blocks, block_len, Hkv*Dh]`, float32 or bfloat16, read in
    place; `channel` (int32 scalar, may be traced) the layer's key channel,
    its values are channel + 1; tables [B, W] int32 block ids; lengths [B]
    int32 live cache slots a row (>= 1; slot `lengths - 1` is the query's
    own). `n_kv_heads` (None: as many as `n_heads`) says how many key/value
    heads the arena's lanes hold. Returns [B, H*Dh]: `attention_reference`
    over the gathered view with `kv_length=lengths`, head by head, each
    query head on its key/value head. Compiled Pallas on the TPU;
    `interpret=True` (automatic off it) runs the same kernel through the
    interpreter."""
    B, q_width = q.shape
    _, num_blocks, block_len, width = kv.shape
    grouped = n_kv_heads is not None and n_kv_heads != n_heads
    if q_width % n_heads or n_heads % (n_kv_heads or n_heads) \
            or width != (q_width // n_heads) * (n_kv_heads or n_heads):
        raise ValueError(f"q {q.shape} in {n_heads} heads and arena "
                         f"{kv.shape} in {n_kv_heads or n_heads} disagree "
                         "on H*Dh")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    d_head = q_width // n_heads
    if sm_scale is None:
        sm_scale = 1.0 / (d_head ** 0.5)
    if grouped or kv.dtype != jnp.float32:
        plan = _planned(B, tables.shape[1], block_len, n_heads, width,
                        num_blocks, n_kv_heads or n_heads, kv.dtype.name)
    else:
        plan = _planned(B, tables.shape[1], block_len, n_heads, width,
                        num_blocks)
    slots = (2, plan.pages_a_chunk * block_len, width)
    q = q.astype(jnp.float32)
    if grouped:
        # row h of the block-diagonal query lies on its key/value head's
        # lanes; padded heads are zero rows (their output is not taken)
        hp, group = plan.heads_padded, n_heads // n_kv_heads
        on = jnp.arange(n_heads)[:, None] // group == jnp.arange(n_kv_heads)
        q = jnp.where(on[None, :, :, None],
                      q.reshape(B, n_heads, 1, d_head), 0.0)
        q = jnp.pad(q.reshape(B, n_heads, width),
                    ((0, 0), (0, hp - n_heads), (0, 0)))
        rows = pl.BlockSpec((1, hp, width), lambda b, *_: (b, 0, 0))
        out_shape = jax.ShapeDtypeStruct((B, hp, width), jnp.float32)
    else:
        rows = pl.BlockSpec((B, width), lambda b, *_: (0, 0))
        out_shape = jax.ShapeDtypeStruct((B, width), jnp.float32)
    params = {} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("arbitrary",))}
    out = pl.pallas_call(
        _make_kernel(n_heads, d_head, plan, block_len, float(sm_scale),
                     n_kv_heads if grouped else None, kv.dtype),
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[rows, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=rows,
            scratch_shapes=[
                pltpu.VMEM(slots, kv.dtype),                # K pages
                pltpu.VMEM(slots, kv.dtype),                # V pages
                pltpu.SemaphoreType.DMA((2, 2)),            # [slot, K | V]
                pltpu.SMEM((1,), jnp.int32),    # the slot of the next chunk 0
            ]),
        interpret=interpret,
        name="paged_decode_attention",
        **params,
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32),
      jnp.reshape(channel, (1,)).astype(jnp.int32), q, kv)
    if not grouped:
        return out
    # head h's answer lies on its key/value head's lanes of row h
    out = out[:, :n_heads].reshape(B, n_heads, n_kv_heads, d_head)
    return jnp.sum(jnp.where(on[None, :, :, None], out, 0.0),
                   axis=2).reshape(B, q_width)


# ---------------------------------------------------------------------------
# Latent attention: one absorbed query a row over a latent cache's pages.
#
# A latent-attention layer (`nn/layers/shortcut_moe.py`) caches one vector a
# token and attention, `[c | k_rope]` zero-padded to the arena's `width`
# lanes, and its tick's query arrives with the up-projection absorbed,
# `[q_nope W_uk | q_rope | 0]` a head. A page then holds the keys AND the
# values: the scores are `q @ page^T` over all `width` lanes, the weighted
# sum `p @ page` over the first `v_width` (the latent `c`). So a page is
# copied once (not as K and then as V) into one of two slots, a chunk's
# pages waited for at once, and every head multiplies at once on the MXU: `[H, width] @ [width, slots]`, then `[H, slots] @ [slots,
# v_width]`, in the dtype of the query and the pages, summed in float32.
# The walk is `paged_decode_attention`'s: a grid step a row, its live chunks
# in a loop, the next chunk (or the next row's first) in flight, dead table
# slots never read, the ragged tail masked by the length.
#
# A chunk covers `_LATENT_CHUNK_TOKENS` cache slots (32 pages of 16). At the
# LongCat-Flash cell's shape (32 rows of some 540 slots, 64 heads, 640
# bfloat16 lanes, 8 attentions) a tick's eight calls took 0.88 / 0.66 / 0.61
# / 0.75 ms of the v5e's time with chunks of 128 / 256 / 512 / 1,024 slots
# (every page copy written out), against 2.70 ms for XLA's gathered view:
# a chunk's two products at 64 rows keep the MXU about half busy, and each
# iteration costs beside them. DMAs that skip the last chunk's repeated
# pages saved nothing.
# ---------------------------------------------------------------------------
_LATENT_CHUNK_TOKENS = 512      # cache slots one iteration of a row's loop covers
_LATENT_DMA_UNROLL = 8          # page copies written out in a DMA loop's body


def _value_lanes(width: int, v_width: int) -> int:
    """The lanes the weighted sum keeps: `v_width` in whole lane tiles."""
    return min(width, _round_up(v_width, 128))


def latent_plan(rows: int, table_width: int, block_len: int, n_heads: int,
                width: int, v_width: int, itemsize: int = 2) -> PagedPlan:
    """Pages a chunk from the shape: `_LATENT_CHUNK_TOKENS` cache slots'
    worth, no more than the table is wide; the heads padded to whole tiles
    of the query's dtype (`itemsize`: the pages' and the query's)."""
    pages = max(1, min(_LATENT_CHUNK_TOKENS // block_len, table_width))
    hp = _round_up(n_heads, _SUBLANES * max(1, 4 // itemsize))
    slots, vw = pages * block_len, _value_lanes(width, v_width)
    vmem = (3 * slots * width * itemsize        # two slots of pages; a value
            + 2 * hp * width * itemsize         # the row's query
            + 2 * hp * vw * 4                   # the row's output
            + 2 * hp * slots * 4                # scores, weights
            + hp * vw * 4)                      # the accumulator
    return PagedPlan(pages, pl.cdiv(table_width, pages), rows, hp, vmem)


@functools.lru_cache(maxsize=64)
def _planned_latent(rows, table_width, block_len, n_heads, width, v_width,
                    num_blocks, dtype) -> PagedPlan:
    """`latent_plan` for one call shape, worked out once a process; it
    leaves the record `dl4j/kernels/paged_attention` with `latent` 1."""
    from ..telemetry import tracer

    plan = latent_plan(rows, table_width, block_len, n_heads, width, v_width,
                       jnp.dtype(dtype).itemsize)
    tracer().instant("dl4j/kernels/paged_attention", rows=rows,
                     table_width=table_width, block_len=block_len,
                     n_heads=n_heads, width=width, num_blocks=num_blocks,
                     latent=1, v_width=v_width, dtype=dtype, **plan._asdict())
    return plan


def _make_latent_kernel(plan: PagedPlan, block_len: int, v_lanes: int,
                        sm_scale: float, dtype):
    """Grid (rows,). A row's loop holds `pages_a_chunk` pages at a time in
    one of two slots; the query is `[1, heads, width]` a grid step, the
    output `[1, heads, v_lanes]`; scores `[heads, slots]`, statistics
    `[heads, 1]`, all float32."""
    from math import gcd

    pages, hp, n_rows = plan.pages_a_chunk, plan.heads_padded, plan.steps_a_call
    chunk, unrolled = pages * block_len, gcd(pages, _LATENT_DMA_UNROLL)

    def kernel(tables_ref, lengths_ref, channel_ref, q_ref, kv_ref, o_ref,
               buf, sems, slot_ref):
        b = pl.program_id(0)
        channel = channel_ref[0]

        def start(row, c, slot):
            """The DMAs of chunk `c` of `row` into `slot`, a page a table
            column; past the row's last live page, that page again. A loop
            over groups of `unrolled` copies: all 32 written out made each
            tick executable trace and lower for seconds more; a loop of one
            copy an iteration made the kernel 40% slower."""
            last = jnp.maximum(lengths_ref[row] - 1, 0) // block_len

            def group(g, carry):
                for j in range(unrolled):
                    i = g * unrolled + j
                    blk = tables_ref[row, jnp.minimum(c * pages + i, last)]
                    pltpu.make_async_copy(kv_ref.at[channel, blk],
                                          buf.at[slot, i],
                                          sems.at[slot]).start()
                return carry

            jax.lax.fori_loop(0, pages // unrolled, group, 0)

        @pl.when(b == 0)
        def _():
            slot_ref[0] = 0
            start(0, 0, 0)

        length = lengths_ref[b]
        n_chunks = jnp.maximum(length - 1, 0) // chunk + 1
        first = slot_ref[0]              # the slot this row's chunk 0 is in
        q = q_ref[0].astype(dtype)                              # [hp, width]

        def body(c, carry):
            m_prev, l_prev, acc = carry
            slot = (first + c) % 2
            more = c + 1 < n_chunks

            @pl.when(more | (b + 1 < n_rows))
            def _():         # the next chunk, or the next row's first
                start(jnp.where(more, b, b + 1), jnp.where(more, c + 1, 0),
                      1 - slot)

            # one wait for the chunk's pages: the semaphore counts bytes
            pltpu.make_async_copy(buf.at[slot], buf.at[slot],
                                  sems.at[slot]).wait()
            page = buf[slot].reshape(chunk, -1).astype(dtype)   # [chunk, width]
            s = jax.lax.dot_general(
                q, page, _NT, preferred_element_type=jnp.float32) * sm_scale
            at = c * chunk + jax.lax.broadcasted_iota(
                jnp.int32, (hp, chunk), 1)
            # chunk 0 holds slot 0, which every row may see: after it the
            # running max is a real score and a masked one weighs exactly 0
            s = jnp.where(at < length, s, _MASK)                # [hp, chunk]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
            acc = acc * corr + jax.lax.dot_general(
                p.astype(dtype), page[:, :v_lanes], _NN,
                preferred_element_type=jnp.float32)             # [hp, v_lanes]
            return m_new, l_new, acc

        _, l, acc = jax.lax.fori_loop(
            0, n_chunks, body,
            (jnp.full((hp, 1), _NEG_INF, jnp.float32),
             jnp.zeros((hp, 1), jnp.float32),
             jnp.zeros((hp, v_lanes), jnp.float32)))
        slot_ref[0] = (first + n_chunks) % 2
        o_ref[0] = acc / l

    return kernel


def paged_latent_attention(q, kv, channel, tables, lengths, *, v_width: int,
                           sm_scale: float, interpret: Optional[bool] = None):
    """Attention of one absorbed query a row over its latent cache's pages.

    q [B, H, width]: a head's `[q_nope W_uk | q_rope | 0]`, as many lanes as
    the arena; kv the arena `[C, num_blocks, block_len, width]`, float32 or
    bfloat16, read in place; `channel` (int32 scalar, may be traced) the
    attention's latent channel; tables [B, W] int32 block ids; lengths [B]
    int32 live cache slots a row (>= 1; slot `lengths - 1` is the query's
    own). The products take their operands in the dtype of q and kv
    promoted and sum in float32; the softmax is float32. Returns
    [B, H, v_width] float32: softmax(q . page * sm_scale) over the row's
    slots, times the pages' first `v_width` lanes. Compiled Pallas on the
    TPU; `interpret=True` (automatic off it) runs the same kernel through
    the interpreter."""
    B, n_heads, width = q.shape
    _, num_blocks, block_len, kv_width = kv.shape
    if width != kv_width or not 0 < v_width <= width:
        raise ValueError(f"q {q.shape} and arena {kv.shape} disagree on the "
                         f"latent's width, or v_width {v_width} lies outside it")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _latent_call(q, kv, channel, tables, lengths, int(v_width),
                        float(sm_scale), bool(interpret))


@functools.partial(jax.jit, static_argnums=(5, 6, 7))  # graftlint: disable=unwatched-jit-entry
def _latent_call(q, kv, channel, tables, lengths, v_width, sm_scale,
                 interpret):
    """`paged_latent_attention`'s kernel, jitted on its own: the calls of
    one shape in a step (two attentions a block, blocks traced again) then
    trace once a process and lower once an executable."""
    B, n_heads, width = q.shape
    _, num_blocks, block_len, _ = kv.shape
    dtype = jnp.promote_types(q.dtype, kv.dtype)
    plan = _planned_latent(B, tables.shape[1], block_len, n_heads, width,
                           v_width, num_blocks, jnp.dtype(dtype).name)
    hp, v_lanes = plan.heads_padded, _value_lanes(width, v_width)
    q = jnp.pad(q.astype(dtype), ((0, 0), (0, hp - n_heads), (0, 0)))
    params = {} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("arbitrary",))}
    out = pl.pallas_call(
        _make_latent_kernel(plan, block_len, v_lanes, sm_scale, dtype),
        out_shape=jax.ShapeDtypeStruct((B, hp, v_lanes), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[pl.BlockSpec((1, hp, width), lambda b, *_: (b, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, hp, v_lanes), lambda b, *_: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, plan.pages_a_chunk, block_len, width),
                           kv.dtype),                       # the page slots
                pltpu.SemaphoreType.DMA((2,)),              # a slot each
                pltpu.SMEM((1,), jnp.int32),    # the slot of the next chunk 0
            ]),
        interpret=interpret,
        name="paged_latent_attention",
        **params,
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32),
      jnp.reshape(channel, (1,)).astype(jnp.int32), q, kv)
    return out[:, :n_heads, :v_width]


# ---------------------------------------------------------------------------
# Differential attention: two score maps a head over halves of the keys, one
# value.
#
# A differential-attention layer (`nn/layers/sambay.py`) caches, a token, the
# keys of its Hkv key/value heads, each a pair (k1, k2) of Dh lanes, and their
# values of 2 Dh, each merged into Hkv*2Dh lanes; head i of H reads key/value
# head floor(i / (H/Hkv)), its query q1 against k1 and q2 against k2. That is
# `paged_decode_attention`'s grouped walk over a block-diagonal query of 2H
# rows: row (s, i) holds q^s of head i on the k^s lanes of its key/value head
# (`diff_block_diagonal`), so one product gives both maps' scores, each row
# keeps its own softmax statistics, and `acc [2H, Hkv*2Dh] += p @ V_chunk`
# holds each row's weighted value on its key/value head's 2 Dh lanes
# (`diff_own_lanes`). The body is the grouped kernel's, called on that query
# under a name of its own, `paged_diff_attention`, so that a trace tells it
# from other layers' calls: each K and V page is read once a layer and row,
# for both maps.
# ---------------------------------------------------------------------------
def diff_block_diagonal(q, kv_heads: int):
    """q [B, H, 2, Dh] -> [B, 2H, Hkv*2Dh]: row (s, i) holds q^s of head i on
    the lanes of k^s of its key/value head, zeros elsewhere."""
    b, h, _, dh = q.shape
    own = jnp.arange(h)[:, None] // (h // kv_heads) == jnp.arange(kv_heads)
    sel = own[None, :, :, None] & jnp.eye(2, dtype=bool)[:, None, None, :]
    qb = q.transpose(0, 2, 1, 3)[:, :, :, None, None, :]
    return jnp.where(sel[None, ..., None], qb, 0.0).reshape(
        b, 2 * h, kv_heads * 2 * dh)


def diff_own_lanes(o, heads: int, kv_heads: int):
    """o [..., R, Hkv*V]: row r is head r mod `heads`; its answer lies on the
    V lanes of that head's key/value head -> [..., R, V]."""
    rows = o.shape[-2]
    own = (jnp.arange(rows)[:, None] % heads) // (heads // kv_heads) \
        == jnp.arange(kv_heads)
    o = o.reshape(*o.shape[:-1], kv_heads, -1)
    return jnp.sum(jnp.where(own[..., None], o, 0.0), axis=-2)


@functools.lru_cache(maxsize=64)
def _planned_diff(rows, table_width, block_len, n_heads, q_rows, width,
                  num_blocks, dtype) -> PagedPlan:
    """`paged_plan` for one call shape of `paged_diff_attention` (its 2H
    query rows padded to `q_rows`, whole tiles of the arena's dtype), worked
    out once a process; it leaves the record `dl4j/kernels/paged_attention`
    with `diff` 1."""
    from ..telemetry import tracer

    plan = paged_plan(rows, table_width, block_len, q_rows, width,
                      jnp.dtype(dtype).itemsize)
    tracer().instant("dl4j/kernels/paged_attention", rows=rows,
                     table_width=table_width, block_len=block_len,
                     n_heads=n_heads, width=width, num_blocks=num_blocks,
                     diff=1, dtype=dtype, **plan._asdict())
    return plan


def paged_diff_attention(q, kv, channel, tables, lengths, *, n_kv_heads: int,
                         sm_scale: float, interpret: Optional[bool] = None):
    """Differential attention's two maps, one query pair a row, over its
    paged cache.

    q [B, H, 2, Dh] (head i's q1 and q2); kv the arena `[C, num_blocks,
    block_len, Hkv*2Dh]`, float32 or bfloat16, read in place: channel
    `channel` (int32 scalar, may be traced) holds each key/value head's
    (k1, k2), channel + 1 its value of 2 Dh; tables [B, W] int32 block ids;
    lengths [B] int32 live cache slots a row (>= 1). Returns [B, 2, H, 2Dh]
    float32: for s = 1, 2, softmax(q^s_i . k^s * sm_scale) over the row's
    slots times the values, head i on key/value head floor(i / (H/Hkv)).
    Compiled Pallas on the TPU; `interpret=True` (automatic off it) runs the
    same kernel through the interpreter."""
    B, n_heads, two, d_head = q.shape
    width = kv.shape[-1]
    if two != 2 or n_heads % n_kv_heads or width != n_kv_heads * 2 * d_head:
        raise ValueError(f"q {q.shape} in pairs on {n_kv_heads} key/value "
                         f"heads and arena {kv.shape} disagree on the lanes")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _diff_call(q, kv, channel, tables, lengths, int(n_kv_heads),
                      float(sm_scale), bool(interpret))


@functools.partial(jax.jit, static_argnums=(5, 6, 7))  # graftlint: disable=unwatched-jit-entry
def _diff_call(q, kv, channel, tables, lengths, n_kv_heads, sm_scale,
               interpret):
    """`paged_diff_attention`'s kernel, jitted on its own: the calls of one
    shape in a step (a full layer and its cross layers) trace once a
    process and lower once an executable."""
    B, n_heads, _, d_head = q.shape
    _, num_blocks, block_len, width = kv.shape
    rows = 2 * n_heads
    q_rows = _round_up(rows, _SUBLANES * max(1, 4 // kv.dtype.itemsize))
    plan = _planned_diff(B, tables.shape[1], block_len, n_heads, q_rows,
                         width, num_blocks, kv.dtype.name)
    qbd = diff_block_diagonal(q.astype(jnp.float32), n_kv_heads)
    qbd = jnp.pad(qbd, ((0, 0), (0, plan.heads_padded - rows), (0, 0)))
    block = pl.BlockSpec((1, plan.heads_padded, width),
                         lambda b, *_: (b, 0, 0))
    params = {} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("arbitrary",))}
    out = pl.pallas_call(
        _make_kernel(plan.heads_padded, 2 * d_head, plan, block_len, sm_scale,
                     n_kv_heads, kv.dtype),
        out_shape=jax.ShapeDtypeStruct((B, plan.heads_padded, width),
                                       jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[block, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=block,
            scratch_shapes=[
                pltpu.VMEM((2, plan.pages_a_chunk * block_len, width),
                           kv.dtype),                       # K pages
                pltpu.VMEM((2, plan.pages_a_chunk * block_len, width),
                           kv.dtype),                       # V pages
                pltpu.SemaphoreType.DMA((2, 2)),            # [slot, K | V]
                pltpu.SMEM((1,), jnp.int32),    # the slot of the next chunk 0
            ]),
        interpret=interpret,
        name="paged_diff_attention",
        **params,
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32),
      jnp.reshape(channel, (1,)).astype(jnp.int32), qbd, kv)
    out = diff_own_lanes(out[:, :rows], n_heads, n_kv_heads)
    return out.reshape(B, 2, n_heads, 2 * d_head)
