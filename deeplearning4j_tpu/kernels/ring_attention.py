"""Ring differential attention: a tick's query pairs over each row's own ring
of window keys and values, read in place by one Pallas TPU kernel.

A window layer of the SambaY stack (`nn/layers/sambay.py`) keeps, for each
sequence, a RING of `W` slots of keys and values: the leaves `k`, `v`
`[slots, W, Hkv*2Dh]`, a token's (k1, k2) of each key/value head and its value
of 2 Dh merged into the lanes. Token t lies in ring slot t mod W and the
layer has no positions, so the order of the slots does not matter: a row at
position p reads the `live = min(p + 1, W)` slots 0 .. live-1 of its own
ring.

- No view and no copy: both leaves stay in HBM (`pl.ANY`); the rows' ring
  slots and live counts are scalar-prefetched; a grid step is one row, and a
  loop walks its live chunks of `chunk` ring slots, a chunk's keys and values
  copied to VMEM into one of two slots, the next chunk's (or the next row's
  first) in flight while this one is computed.
- Live slots only: a chunk is copied in pieces of `piece` slots (16, a
  bfloat16 tile's rows), as few DMAs as the bits of its number of live
  pieces (one DMA for a whole chunk; else pieces of 16, 8, 4, 2, 1 x 16
  slots).
  A dead slot is read only inside the row's last live piece; its score is
  masked and its value zeroed in VMEM (0 x NaN is NaN), so whatever a dead
  slot or another row's ring holds never reaches the result.
- The head split happens in the kernel: the query arrives as the projection
  gives it, `[B, H*2Dh]` (head i's q1 | q2 on lanes 2Dh i ..), and the row's
  block-diagonal query `[2Hp, Hkv*2Dh]` is built in VMEM: row s Hp + i holds
  q^s of head i on the k^s lanes of its key/value head i // (H/Hkv), zeros
  elsewhere. One product gives both maps' scores `[2Hp, chunk]`, each row
  keeps its own softmax statistics, and `acc [2Hp, Hkv*2Dh] += p @ V` holds
  each row's weighted value on its key/value head's 2 Dh lanes, which the
  kernel takes before it writes `[2, H, 2Dh]` a row. XLA relays out
  neither the query nor `W_q`.
- Arithmetic as `paged_diff_attention`'s: the products take their operands
  in the rings' dtype and sum in float32; the softmax statistics, `exp` and
  the accumulator are float32.
- Rows are independent: a row's chunks depend on its own slot and live
  count, so its result is the same whoever else is in the batch.

A chunk covers `_RING_CHUNK_SLOTS` ring slots: at Phi-4-mini-flash's cell
(64 rows of some 445 live slots of 512, 20 heads on 10 key/value heads,
1,280 bfloat16 lanes, 8 layers chained) the v5e read the live bytes at 79%,
87% and 87% of its 819 GB/s with chunks of 128, 256 and 512 slots, and at
89% with the products left out: the kernel is bound by its DMAs. XLA's
einsum over all 65 rings took 2.06 ms where the kernel takes 1.64.

`dl4j/kernels/ring_attention` in the span log says, once per call shape,
what a call is made of (rows, slots, window, chunk, pieces, grid steps, VMEM
estimated). The device operation is named `ring_diff_attention`: no name of
another kernel lies inside it, so a trace's readers that match kernels by
name never count one for the other.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import _MASK, _NEG_INF, _NN, _NT, _round_up

__all__ = ["ring_diff_attention", "ring_attention_supported", "ring_plan",
           "RingPlan"]

_RING_CHUNK_SLOTS = 512     # ring slots one iteration of a row's loop covers
_RING_PIECE = 16            # ring slots a DMA covers at least: a bf16 tile
_SUBLANES = 8


class RingPlan(NamedTuple):
    """What one call is made of."""
    chunk: int           # ring slots an iteration covers
    piece: int           # ring slots the smallest DMA covers
    chunks_a_row: int    # at most: window / chunk
    steps_a_call: int    # grid steps: one a row
    heads_padded: int    # a map's query rows, Hp
    query_rows: int      # the block-diagonal query's rows, 2 Hp in tiles
    vmem_bytes: int      # estimated: the slots, the rows, the values


def ring_attention_supported(head_pair: int, window: int,
                             dtype="float32") -> bool:
    """Whether the compiled kernel takes rings of `window` slots of `dtype`
    whose heads hold `head_pair` = 2Dh lanes: a head's pair and a key/value
    head's lanes whole lane tiles, the window whole pieces."""
    return (jnp.dtype(dtype).name in ("float32", "bfloat16")
            and head_pair % 128 == 0 and window % _RING_PIECE == 0)


def ring_plan(rows: int, window: int, n_heads: int, n_kv_heads: int,
              width: int, itemsize: int = 2) -> RingPlan:
    """The chunk and pieces from the shape: a piece is `_RING_PIECE` slots
    (fewer where the window is not whole pieces), a chunk a power of two
    pieces, `_RING_CHUNK_SLOTS` at most and no more than the window."""
    piece = math.gcd(window, _RING_PIECE)
    chunk = piece << int(math.log2(min(_RING_CHUNK_SLOTS, window) // piece))
    hp = _round_up(n_heads, _SUBLANES)
    q_rows = _round_up(2 * hp, _SUBLANES * max(1, 4 // itemsize))
    q_width = width * n_heads // n_kv_heads
    vmem = (2 * 2 * chunk * width * itemsize    # two slots each of K and V
            + 2 * rows * q_width * 4            # the rows' queries
            + q_rows * q_width * 4              # a row's query on every row
            + 2 * chunk * width * itemsize      # a chunk's K and V as values
            + q_rows * width * (4 + itemsize)   # the block-diagonal query
            + q_rows * width * 4                # acc
            + 2 * q_rows * chunk * 4)           # scores, weights
    return RingPlan(chunk, piece, pl.cdiv(window, chunk), rows, hp, q_rows,
                    vmem)


@functools.lru_cache(maxsize=64)
def _planned_ring(rows, slots, window, n_heads, n_kv_heads, width,
                  dtype) -> RingPlan:
    """`ring_plan` for one call shape, worked out once a process; working it
    out leaves the record `dl4j/kernels/ring_attention` in the span log:
    written while a kernel is built, never while one runs."""
    from ..telemetry import tracer

    plan = ring_plan(rows, window, n_heads, n_kv_heads, width,
                     jnp.dtype(dtype).itemsize)
    tracer().instant("dl4j/kernels/ring_attention", rows=rows, slots=slots,
                     window=window, n_heads=n_heads, n_kv_heads=n_kv_heads,
                     width=width, dtype=dtype, **plan._asdict())
    return plan


def _make_ring_kernel(plan: RingPlan, n_heads: int, n_kv_heads: int,
                      head_pair: int, sm_scale: float, dtype):
    """Grid (rows,). A row's loop holds `chunk` ring slots of K and of V at a
    time in one of two slots; the query is the rows' `[B, H*2Dh]` (row b
    taken a grid step), the output `[1, 2, H, 2Dh]`; scores `[2Hp, chunk]`,
    statistics `[2Hp, 1]`, all float32 (module docstring)."""
    chunk, piece, hp = plan.chunk, plan.piece, plan.heads_padded
    q_rows, n_rows = plan.query_rows, plan.steps_a_call
    group, dh = n_heads // n_kv_heads, head_pair // 2
    width = n_kv_heads * head_pair
    sizes = []                   # pieces a DMA covers, largest first
    size = chunk // piece
    while size:
        sizes.append(size)
        size //= 2

    def kernel(slot_ref, live_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf,
               q_all, sems, next_ref):
        b = pl.program_id(0)

        def copies(row, c, buf):
            """(whether, DMA) of chunk `c` of `row` into slot `buf`: K and V
            of the chunk's live pieces, as few DMAs as the bits of their
            number (module docstring)."""
            ring = slot_ref[row]
            n = jnp.clip((live_ref[row] - c * chunk + piece - 1) // piece, 0,
                         chunk // piece)
            out = []
            for size in sizes:
                at = pl.multiple_of((n // (2 * size)) * (2 * size) * piece,
                                    piece)   # the pieces the larger DMAs took
                for j, (hbm, vmem) in enumerate(((k_hbm, k_buf),
                                                 (v_hbm, v_buf))):
                    out.append(((n & size) != 0, pltpu.make_async_copy(
                        hbm.at[ring, pl.ds(c * chunk + at, size * piece)],
                        vmem.at[buf, pl.ds(at, size * piece)],
                        sems.at[buf, j])))
            return out

        def start(row, c, buf):
            for whether, copy in copies(row, c, buf):
                pl.when(whether)(copy.start)

        @pl.when(b == 0)
        def _():
            # a value slot never copied to must not hold NaN: it is weighed
            # by 0 where it lies past a row's live slots
            v_buf[...] = jnp.zeros(v_buf.shape, v_buf.dtype)
            next_ref[0] = 0
            start(0, 0, 0)

        live = live_ref[b]
        n_chunks = (live - 1) // chunk + 1
        first = next_ref[0]              # the slot this row's chunk 0 is in
        # the row's query on every row of a scratch, whose lane tiles are
        # then loaded whole: a head's lanes sliced off the loaded row do not
        # broadcast over sublanes in the compiled kernel
        q_all[...] = jnp.broadcast_to(q_ref[pl.ds(b, 1), :], q_all.shape)
        r = jax.lax.broadcasted_iota(jnp.int32, (q_rows, head_pair), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (q_rows, head_pair), 1)
        tiles = []
        for g in range(n_kv_heads):
            tile = jnp.zeros((q_rows, head_pair), jnp.float32)
            for h in range(g * group, (g + 1) * group):
                # row h holds q1 on the k1 lanes, row Hp + h q2 on the k2's
                keep = (((r == h) & (lane < dh))
                        | ((r == hp + h) & (lane >= dh)))
                tile = tile + jnp.where(
                    keep, q_all[:, h * head_pair:(h + 1) * head_pair], 0.0)
            tiles.append(tile)
        qbd = jnp.concatenate(tiles, axis=1).astype(dtype)  # [q_rows, width]

        def body(c, carry):
            m_prev, l_prev, acc = carry
            buf = (first + c) % 2
            more = c + 1 < n_chunks

            @pl.when(more | (b + 1 < n_rows))
            def _():         # the next chunk, or the next row's first
                start(jnp.where(more, b, b + 1), jnp.where(more, c + 1, 0),
                      1 - buf)

            for whether, copy in copies(b, c, buf):
                pl.when(whether)(copy.wait)
            # the chunk's last live piece: its values past `live` zeroed
            tail = jnp.minimum(live - c * chunk, chunk)
            at = pl.multiple_of((tail - 1) // piece * piece, piece)
            slots = at + jax.lax.broadcasted_iota(jnp.int32, (piece, width), 0)
            last = v_buf[buf, pl.ds(at, piece), :]
            v_buf[buf, pl.ds(at, piece), :] = jnp.where(
                slots < tail, last, jnp.zeros_like(last))
            s = jax.lax.dot_general(
                qbd, k_buf[buf], _NT,
                preferred_element_type=jnp.float32) * sm_scale
            at = c * chunk + jax.lax.broadcasted_iota(
                jnp.int32, (q_rows, chunk), 1)
            # chunk 0 holds slot 0, which every row may see: after it the
            # running max is a real score and a masked one weighs exactly 0
            s = jnp.where(at < live, s, _MASK)              # [q_rows, chunk]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
            acc = acc * corr + jax.lax.dot_general(
                p.astype(dtype), v_buf[buf], _NN,
                preferred_element_type=jnp.float32)         # [q_rows, width]
            return m_new, l_new, acc

        _, l, acc = jax.lax.fori_loop(
            0, n_chunks, body,
            (jnp.full((q_rows, 1), _NEG_INF, jnp.float32),
             jnp.zeros((q_rows, 1), jnp.float32),
             jnp.zeros((q_rows, width), jnp.float32)))
        next_ref[0] = (first + n_chunks) % 2
        # row (s, h)'s answer lies on the lanes of key/value head h // group
        out = jnp.zeros((q_rows, head_pair), jnp.float32)
        for g in range(n_kv_heads):
            mine = ((r >= g * group) & (r < (g + 1) * group)) \
                | ((r >= hp + g * group) & (r < hp + (g + 1) * group))
            out = out + jnp.where(
                mine, acc[:, g * head_pair:(g + 1) * head_pair], 0.0)
        out = out / l
        o_ref[0, 0] = out[:n_heads]
        o_ref[0, 1] = out[hp:hp + n_heads]

    return kernel


def ring_diff_attention(q, k, v, slot, live, *, n_heads: int,
                        n_kv_heads: int, sm_scale: float,
                        interpret: Optional[bool] = None):
    """Differential attention's two maps, one query pair a head and row, over
    the row's own ring.

    q [B, H*2Dh] (head i's q1 | q2, as the projection gives them); k, v the
    rings `[slots, W, Hkv*2Dh]`, float32 or bfloat16, read in place: ring
    slot j of sequence slot `slot[b]` holds each key/value head's (k1, k2)
    and its value of 2 Dh; slot [B] int32; live [B] int32, the ring slots
    0 .. live-1 a row reads (1 <= live <= W). Returns [B, 2, H, 2Dh]
    float32: for s = 1, 2, softmax(q^s_i . k^s * sm_scale) over the row's
    live slots times the values, head i on key/value head floor(i / (H/Hkv)).
    Compiled Pallas on the TPU; `interpret=True` (automatic off it) runs the
    same kernel through the interpreter."""
    B, q_width = q.shape
    slots, window, width = k.shape
    if (n_heads % n_kv_heads or q_width % (2 * n_heads)
            or width * n_heads != q_width * n_kv_heads or v.shape != k.shape):
        raise ValueError(f"q {q.shape} in {n_heads} pairs and rings "
                         f"{k.shape}, {v.shape} on {n_kv_heads} key/value "
                         "heads disagree on the lanes")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _ring_call(q, k, v, slot, live, int(n_heads), int(n_kv_heads),
                      float(sm_scale), bool(interpret))


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8))  # graftlint: disable=unwatched-jit-entry
def _ring_call(q, k, v, slot, live, n_heads, n_kv_heads, sm_scale,
               interpret):
    """`ring_diff_attention`'s kernel, jitted on its own: the window layers
    of a step trace it once a process and lower it once an executable."""
    B, q_width = q.shape
    slots, window, width = k.shape
    head_pair = q_width // n_heads
    plan = _planned_ring(B, slots, window, n_heads, n_kv_heads, width,
                         k.dtype.name)
    params = {} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("arbitrary",))}
    return pl.pallas_call(
        _make_ring_kernel(plan, n_heads, n_kv_heads, head_pair, sm_scale,
                          k.dtype),
        out_shape=jax.ShapeDtypeStruct((B, 2, n_heads, head_pair),
                                       jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[pl.BlockSpec((B, q_width), lambda b, *_: (0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, 2, n_heads, head_pair),
                                   lambda b, *_: (b, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, plan.chunk, width), k.dtype),    # K slots
                pltpu.VMEM((2, plan.chunk, width), k.dtype),    # V slots
                pltpu.VMEM((plan.query_rows, q_width), jnp.float32),  # q
                pltpu.SemaphoreType.DMA((2, 2)),            # [slot, K | V]
                pltpu.SMEM((1,), jnp.int32),    # the slot of the next chunk 0
            ]),
        interpret=interpret,
        name="ring_diff_attention",
        **params,
    )(slot.astype(jnp.int32), live.astype(jnp.int32), q.astype(jnp.float32),
      k, v)
