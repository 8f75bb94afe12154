"""Paged KV-cache arena + block-pool allocator (vLLM/PagedAttention,
Kwon et al., SOSP 2023, applied to our layer stack).

One preallocated arena per servable holds EVERY concurrent sequence's
keys and values:

    arena  [2*L, num_blocks, block_len, H*Dh]

where channel ``2l`` is layer l's keys and ``2l+1`` its values (the GPT
shape; in general ``[channels, num_blocks, block_len, width]``, below). A
sequence owns an ordered list of block ids (its BLOCK TABLE); cache slot
``t`` of a sequence lives at ``(table[t // block_len], t % block_len)``
of every channel.

Why this order. The heads are merged into the last dimension because the
TPU tiles an array's two minor dimensions (8 x 128 for float32): a last
dimension of ``Dh`` = 64 is half a lane tile, so the device stores a
``[..., H, Dh]`` arena with ANOTHER dimension minor (``num_blocks``,
padded up), and every executable converts the whole arena to row-major
on entry and back on exit: two arena-sized transposing copies a call.
With ``H*Dh`` last the entry layout is row-major and the donated arena
is updated in place. The channel comes first so that a layer's keys (or
values) are one contiguous slab, not a strided slice of every block, and
a block of one channel — a PAGE, ``[block_len, H*Dh]`` — is contiguous
and whole (8, 128) tiles: what the tick's paged attention kernel
(`kernels/paged_attention.py`) copies to VMEM, one DMA a page, for the
pages a row's length spans and no other. The plain path's gather through
the block tables, ``kv[c, tables]``, reads the rows' own blocks only
(written ``kv[c][tables]`` the slab was copied out first, 64 MiB a
channel at the served size).
`tests/test_flash_compile_tpu.py` compiles both steps for a described
v5e and holds them to it.

The compiled steps scatter new K/V by block index and read a sequence's
cache through its table: the tick's kernel page by page where it can run
(`TransformerBlock.decode_attention`), else as a gathered view of the whole table —
HBM is shared at block granularity, so thousands of sequences with
wildly different lengths pack the arena with at most ``block_len - 1``
wasted slots each, instead of every sequence reserving a max-context
rectangle.

Block 0 is RESERVED (the "trash" block): padded batch slots and
overflow prompt positions write there and their reads are always masked
by the per-row valid length (the paged kernel does not read it at all
but for a pad row's one slot), so the compiled step needs no branches
for dead rows. Allocation never hands out block 0.

The arena is not bound to keys and values of heads: a layer says how many
CHANNELS of what WIDTH it writes for a token (`engine.py`, the layers'
contract), and the arena is ``[channels, num_blocks, block_len, width]``.
A GPT block writes two (keys, values) of ``H*Dh``; a latent-attention
block writes one a attention, the compressed latent beside the rotated
shared key, zero-padded to whole 128-lane tiles (576 -> 640) so that the
layout argument above holds for it too.

``kv_dtype="bf16"`` stores what a bfloat16 model computes as it computes
it: half the float32 arena, no scales.

Beside the pages the cache may hold PER-SEQUENCE STATE: what a layer keeps
for a sequence whatever its length (a Mamba-2 layer of `nn/layers/
hybrid_ssm.py`: the recurrent state `[H, P, N]` float32 and the last
inputs of its convolution). A layer states the leaves (`decode_state`),
the cache pytree holds them under ``"state"``, one dict a stateful layer,
each leaf with an axis of `state_slots` SLOTS; they are donated with the
arena and updated in place. Slot 0 is the trash slot, as block 0 is the
trash block: pad rows read and write it. The `BlockPool` owns the slots
too, and a caller never names one: a sequence is known by its FIRST block.
A slot is taken when a prefill first meets that block (`slot_for`; the
prefill overwrites the slot's state, which is the reset), found by the
ticks (`slots_of`), and freed when `release` returns the block: finish,
failure, eviction and a flush after a swap all go through `release`. A
stack with no stateful layer has no such leaf, no slot and no argument
for one.

int8 KV (``kv_dtype="int8"``): the arena stores int8 plus a per-slot
scale arena ``[2*L, num_blocks, block_len]`` — `serving/quantize.py`'s
per-tensor symmetric scheme (scale = absmax / 127) applied per cached
(layer, K|V, position) vector of ``H*Dh``, quantized at scatter time and
dequantized inside the gather (the paged kernel takes the float32 arena
only: an int8 cache ticks on the gathered view). Halves-of-halves memory
for the cache at ~1e-2-level logit drift; the equivalence/bit-exactness
contracts are asserted on the fp32 cache only.
"""
from __future__ import annotations

import math
import threading
from dataclasses import dataclass
from typing import Dict, List

import jax.numpy as jnp

__all__ = ["KvCacheSpec", "CacheIO", "BlockPool", "OutOfBlocksError"]


KV_DTYPES = {"fp32": jnp.float32, "bf16": jnp.bfloat16, "int8": jnp.int8}


class OutOfBlocksError(RuntimeError):
    """Allocation against an exhausted pool — the scheduler's cue to
    evict (preempt) a running sequence."""


@dataclass(frozen=True)
class KvCacheSpec:
    """Static shape contract of one servable's paged cache. Part of the
    compiled signature: every decode executable is specialized to it."""

    channels: int          # what the stack's layers write for a token,
    width: int             # summed: 2L of H*Dh for L GPT blocks
    block_len: int         # cache slots per block
    num_blocks: int        # arena height, INCLUDING the reserved block 0
    max_context: int       # hard cap (what the embedding layer states)
    kv_dtype: str = "fp32"   # "fp32" | "bf16" | "int8"
    # per-sequence state: for each stateful layer its leaves ((name, shape
    # with "slots" for the slot axis, dtype), ...), and the slots of each
    # leaf, the trash slot 0 included
    state: tuple = ()
    state_slots: int = 0

    def __post_init__(self):
        if self.kv_dtype not in KV_DTYPES:
            raise ValueError(f"kv_dtype must be fp32|bf16|int8, got "
                             f"{self.kv_dtype!r}")
        if self.channels < 1 or self.width < 1:
            raise ValueError("a paged cache needs channels and width >= 1")
        if self.num_blocks < 2:
            raise ValueError("num_blocks must be >= 2 (block 0 is the "
                             "reserved trash block)")
        if self.block_len < 1 or self.max_context < 1:
            raise ValueError("block_len and max_context must be >= 1")
        if self.state and self.state_slots < 2:
            raise ValueError("state_slots must be >= 2 (slot 0 is the "
                             "reserved trash slot)")

    @property
    def table_width(self) -> int:
        """Block-table columns per sequence: enough for max_context."""
        return -(-self.max_context // self.block_len)

    @property
    def usable_blocks(self) -> int:
        return self.num_blocks - 1

    def blocks_for(self, n_tokens: int) -> int:
        """Blocks a sequence of `n_tokens` cache slots occupies."""
        return -(-max(1, n_tokens) // self.block_len)

    def arena_nbytes(self) -> int:
        slots = self.num_blocks * self.block_len * self.channels
        if self.kv_dtype == "int8":
            return slots * self.width + slots * 4   # int8 data + f32 scales
        return slots * self.width * jnp.dtype(KV_DTYPES[self.kv_dtype]).itemsize

    def state_shapes(self):
        """The per-sequence leaves, one dict a stateful layer: {name:
        (shape, dtype)} with the slots filled in."""
        return tuple(
            {name: (tuple(self.state_slots if n == "slots" else int(n)
                          for n in shape), jnp.dtype(dtype))
             for name, shape, dtype in layer} for layer in self.state)

    def state_nbytes(self) -> int:
        return sum(math.prod(shape) * dtype.itemsize
                   for layer in self.state_shapes()
                   for shape, dtype in layer.values())


def make_cache(spec: KvCacheSpec) -> Dict[str, jnp.ndarray]:
    """Fresh zeroed cache pytree — ONE donated argument of the compiled
    steps. fp32: {"kv": arena}; int8 adds the per-slot scale arena; a
    stack with stateful layers adds "state", their per-sequence leaves."""
    shape = (spec.channels, spec.num_blocks, spec.block_len, spec.width)
    if spec.kv_dtype == "int8":
        cache = {"kv": jnp.zeros(shape, jnp.int8),
                 "scale": jnp.ones(shape[:3], jnp.float32)}
    else:
        cache = {"kv": jnp.zeros(shape, KV_DTYPES[spec.kv_dtype])}
    if spec.state:
        cache["state"] = tuple(
            {name: jnp.zeros(shape, dtype)
             for name, (shape, dtype) in layer.items()}
            for layer in spec.state_shapes())
    return cache


def pack_kv(spec: KvCacheSpec, x):
    """Prepare a channel's vectors [..., width] for a cache scatter.
    Returns (values, scales_or_None): int8 quantizes per leading-index
    vector (per-tensor symmetric over the trailing width), bf16 rounds."""
    if spec.kv_dtype == "bf16":
        return x.astype(jnp.bfloat16), None
    if spec.kv_dtype != "int8":
        return x, None
    absmax = jnp.max(jnp.abs(x), axis=-1)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.float32)


def unpack_kv(spec: KvCacheSpec, q, scale):
    """Dequantize a gathered cache view (inverse of `pack_kv`; a bf16
    view stays bfloat16 and the layer that reads it decides)."""
    if spec.kv_dtype != "int8":
        return q
    return q.astype(jnp.float32) * scale[..., None]


class CacheIO:
    """What a layer's traced decode step is handed to reach the arena: it
    owns the math, this the layout and the quantization. `kv`/`sc` are
    the arena and its scale arena (None but for int8), threaded through
    the step."""

    def __init__(self, spec: KvCacheSpec):
        self.spec = spec

    def scatter(self, kv, sc, values, blk, off, channel):
        """Write `values` (leading index shape == blk/off; what follows
        it, a [H, Dh] or a [width], merged into the width) into the arena
        at (channel, blk, off), quantizing for int8 caches."""
        vals, scales = pack_kv(self.spec, values.reshape(*blk.shape, -1))
        kv = kv.at[channel, blk, off].set(vals)
        if scales is not None:
            sc = sc.at[channel, blk, off].set(scales)
        return kv, sc

    def gather(self, kv, sc, tables, channel):
        """One channel's view [B, W, block_len, width] through the rows'
        tables, dequantized: every row reads its own blocks (dead table
        slots point at the trash block; always length-masked). ONE gather
        from the arena; only the view is reshaped, never the arena."""
        view = kv[channel, tables]
        if sc is not None:
            view = unpack_kv(self.spec, view, sc[channel, tables])
        return view


class BlockPool:
    """Host-side free-list allocator over the arena's block ids, and over
    the slots of the per-sequence state where the stack keeps any (module
    docstring: a sequence's slot hangs on its first block).

    The pool owns the DEVICE cache arrays too (`cache` — replaced after
    every compiled step with the donated step's output), so eviction,
    reuse and accounting share one lock. Thread-safe; the scheduler
    worker is the only writer of `cache`."""

    def __init__(self, spec: KvCacheSpec, metrics=None, name: str = "model"):
        self.spec = spec
        self.name = name
        self.cache = make_cache(spec)
        self._lock = threading.Lock()
        # LIFO free list: a just-freed (hot, possibly still resident)
        # block is reused first — also what makes the reuse-after-evict
        # bit-exactness test deterministic about WHICH blocks recycle
        self._free: List[int] = list(range(spec.num_blocks - 1, 0, -1))
        self._free_slots: List[int] = list(range(spec.state_slots - 1, 0, -1))
        self._slot_of: Dict[int, int] = {}      # first block -> slot
        self._blocks_g = self._slots_g = None
        if metrics is not None:
            self._blocks_g = metrics.gauge(
                "dl4j_decode_kv_blocks",
                "paged KV arena blocks by state (block 0 reserved)",
                labels=("model", "state"))
            if spec.state:
                self._slots_g = metrics.gauge(
                    "dl4j_decode_state_slots",
                    "slots of the per-sequence state by state (slot 0 "
                    "reserved)", labels=("model", "state"))
            self._report()

    def _report(self):
        if self._blocks_g is not None:
            free = len(self._free)
            self._blocks_g.set(free, model=self.name, state="free")
            self._blocks_g.set(self.spec.usable_blocks - free,
                               model=self.name, state="used")
        if self._slots_g is not None:
            self._slots_g.set(len(self._free_slots), model=self.name,
                              state="free")
            self._slots_g.set(len(self._slot_of), model=self.name,
                              state="used")

    def free_blocks(self) -> int:
        with self._lock:
            return len(self._free)

    def used_blocks(self) -> int:
        return self.spec.usable_blocks - self.free_blocks()

    def alloc(self, n: int) -> List[int]:
        """Take `n` blocks or raise OutOfBlocksError (all-or-nothing: a
        partial grab under pressure would deadlock two growing
        sequences against each other)."""
        with self._lock:
            if n > len(self._free):
                raise OutOfBlocksError(
                    f"{self.name}: need {n} KV blocks, {len(self._free)} "
                    f"free of {self.spec.usable_blocks}")
            taken = [self._free.pop() for _ in range(n)]
            self._report()
            return taken

    def release(self, blocks: List[int]):
        with self._lock:
            for b in blocks:
                if not 0 < b < self.spec.num_blocks:
                    raise ValueError(f"bad KV block id {b}")
            self._free.extend(reversed(blocks))
            if self._slot_of:
                # a sequence's slot goes with its first block
                self._free_slots.extend(
                    self._slot_of.pop(b) for b in blocks if b in self._slot_of)
            self._report()

    def slot_for(self, first_block: int) -> int:
        """The state slot of the sequence whose table starts with
        `first_block`, taken now if it has none (a prefill's call: the
        prefill then overwrites the slot's state)."""
        with self._lock:
            slot = self._slot_of.get(first_block)
            if slot is None:
                if not self._free_slots:
                    raise OutOfBlocksError(
                        f"{self.name}: no free state slot of "
                        f"{self.spec.state_slots - 1}")
                slot = self._slot_of[first_block] = self._free_slots.pop()
                self._report()
            return slot

    def slots_of(self, first_blocks) -> List[int]:
        """The slots of the sequences whose tables start with
        `first_blocks` (a tick's call); KeyError for a sequence no prefill
        has met."""
        with self._lock:
            return [self._slot_of[b] for b in first_blocks]

    def used_slots(self) -> int:
        with self._lock:
            return len(self._slot_of)
