"""The KV-cache generation forward: AOT-compiled prefill + decode-tick
steps over the transformer stack's decode mode.

Two compiled signatures per servable, both AOT-lowered through the
registry's shared executable cache (`ModelRegistry.compile_cached`, keys
namespaced ("decode", sig, phase, bucket)) so the server-lifetime
invariant of the stateless plane extends to generation: ONE XLA compile
per (model, bucket, phase), no cold compile on any request path, and a
same-architecture hot-swap reuses every decode executable.

  prefill(data, cache, tokens [1, Tp], lengths [1], tables [1, W])
      -> (cache', next_logits [1, V])
    The whole (right-padded) prompt runs as one causal forward — the
    standard full-sequence math, row-masked by `lengths` — while every
    layer's K/V projections scatter into the paged arena through the
    sequence's block table. Prompt attention uses the LOCAL (exact)
    projections, so int8 cache quantization only affects later ticks.

  decode(data, cache, tokens [B], positions [B], tables [B, W])
      -> (cache', logits [B, V])
    One token per row: embed at its absolute position, scatter its K/V
    into the arena, attend over the row's live cache slots, project
    logits. The attention reads the arena through the block table inside
    one Pallas kernel (`kernels.paged_attention`: live pages only, all
    heads on the merged lanes, no view of the cache made) where
    `tick_attention` finds the TPU, a float32 arena and pages of whole
    tiles. Elsewhere — the CPU, the int8 arena, odd widths — it gathers
    the row's whole table into a view and attends with causal offsets +
    per-row valid length (`kernels.attention` kv_length path): the
    kernel's oracle.

The cache pytree is DONATED and laid out `[2L, num_blocks, block_len,
H*Dh]` (`cache.py` says why), so the arena updates in place on device: a
tick costs one [B,*] pass plus the rows' live pages, never an arena copy.
That holds on the chip and is kept by a test:
`tests/test_flash_compile_tpu.py::test_decode_steps_update_the_arena_in_place_on_v5e`
compiles both steps for a described v5e and refuses an arena-sized
temporary, a copy of a channel's slab or a lost alias; every executable
built leaves its `temp_bytes` beside `arena_bytes` in the span log
(`dl4j/engine/executable`).
Rows are independent throughout (no cross-row reductions), which is
what makes token-granularity join/leave bit-exact for the rows that
stay — the continuous-batching isolation contract the tests assert.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...kernels import pallas_supported
from ...kernels.paged_attention import (paged_attention_supported,
                                         paged_decode_attention)
from ...telemetry.compile_watch import watch_compiles
from ...telemetry.runtime import span as _span
from ...telemetry.tracing import named_step, tracer as _tracer
from ..registry import ServingError, _abstract_sig
from .cache import BlockPool, KvCacheSpec, make_cache, pack_kv, unpack_kv

__all__ = ["DecodeEngine", "build_prefill_fn", "build_decode_fn",
           "split_decode_layers", "tick_attention"]


def split_decode_layers(model):
    """(embedding, [blocks...], head) of a generate-capable stack, or
    ServingError. The decode plane supports exactly the GPT shape:
    EmbeddingSequenceLayer -> TransformerBlock* -> an output layer with
    `preout` (logits before the softmax activation)."""
    from ...nn.layers.transformer import (EmbeddingSequenceLayer,
                                          TransformerBlock)

    layers = getattr(model, "layers", None)
    if not layers or len(layers) < 3 \
            or not isinstance(layers[0], EmbeddingSequenceLayer) \
            or not all(isinstance(b, TransformerBlock)
                       for b in layers[1:-1]) \
            or not hasattr(layers[-1], "preout"):
        raise ServingError(
            "generation needs an EmbeddingSequenceLayer -> "
            "TransformerBlock* -> output-layer stack; got "
            f"{[type(l).__name__ for l in (layers or [])]}")
    if getattr(model.conf, "preprocessors", None):
        raise ServingError(
            "generation does not support input preprocessors between "
            "decode layers")
    return layers[0], list(layers[1:-1]), layers[-1]


def _cache_arg_specs(spec: KvCacheSpec):
    """The cache pytree's shapes, with no arena made to learn them."""
    return jax.eval_shape(lambda: make_cache(spec))


def _scatter(spec, kv, sc, values, blk, off, channel):
    """Write K or V `values` [..., H, Dh] (leading index shape ==
    blk/off) into the arena at (channel, blk, off), heads merged,
    quantizing for int8 caches."""
    vals, scales = pack_kv(spec, values.reshape(*values.shape[:-2], -1))
    kv = kv.at[channel, blk, off].set(vals)
    if scales is not None:
        sc = sc.at[channel, blk, off].set(scales)
    return kv, sc


def _gather(spec, kv, sc, tables, channel):
    """Sequence-major cache view [B, W*block_len, H, Dh] of one channel,
    dequantized: every row reads its own blocks through its table (dead
    table slots point at the trash block; always length-masked). Only
    the gathered view is reshaped, never the arena."""
    view = kv[channel, tables]                   # [B, W, bl, H*Dh]
    if sc is not None:
        view = unpack_kv(spec, view, sc[channel, tables])
    return view.reshape(tables.shape[0], -1, spec.n_heads, spec.d_head)


def _repack(cache, kv, sc):
    return {"kv": kv, "scale": sc} if "scale" in cache else {"kv": kv}


def _shared_steps(blocks, make):
    """`make(block)` jitted, one for each of `blocks`, shared by blocks that
    differ in nothing but their name: a stack's identical blocks are then
    traced and lowered once, not once each in every one of a servable's
    executables (24 blocks, on the chip's host: 0.88 s of tracing and
    lowering an executable became 0.21, eight executables a set-up). The
    layer's channel is an argument for that; XLA inlines the calls and
    folds it, so the arena is still updated in place (the v5e compile
    test holds both steps to it)."""
    keys = [dataclasses.replace(block, name=None) for block in blocks]
    first = [keys.index(key) for key in keys]   # the first block equal to it
    steps = {i: jax.jit(make(blocks[i]))  # graftlint: disable=unwatched-jit-entry,jit-in-loop
             for i in set(first)}
    return [steps[i] for i in first]


def build_prefill_fn(model, snapshot, spec: KvCacheSpec):
    """Pure prefill step (see module docstring). Closed over the layer
    configs and the snapshot's dequantization structure only — the flat
    `data` tuple stays a runtime argument, so re-quantized checkpoints
    share the executable (the stateless plane's convention)."""
    emb, blocks, head = split_decode_layers(model)

    def layer_step(layer):
        def step(p, x, kv, sc, channel, blk, off, pos, lengths):
            q, k, v = layer.decode_qkv(p, x)
            kv, sc = _scatter(spec, kv, sc, k, blk, off, channel)
            kv, sc = _scatter(spec, kv, sc, v, blk, off, channel + 1)
            a = layer.decode_attend(q, k, v, pos, lengths)
            return layer.decode_finish(p, x, a), kv, sc
        return step

    steps = _shared_steps(blocks, layer_step)

    def prefill(data, cache, tokens, lengths, tables):
        params = snapshot.rebuild(data)
        b, tp = tokens.shape
        pos = jnp.broadcast_to(jnp.arange(tp, dtype=jnp.int32), (b, tp))
        x = emb.decode_embed(params[0], tokens, pos)
        kv, sc = cache["kv"], cache.get("scale")
        tidx = jnp.arange(tp, dtype=jnp.int32)
        # right-padded prompt slots scatter too (their K/V derive
        # deterministically from the pad token, and table slots past the
        # allocation point at the trash block), so a reused block is
        # overwritten wholesale — reuse is bit-identical to fresh
        blk = tables[:, tidx // spec.block_len]
        off = jnp.broadcast_to(tidx % spec.block_len, (b, tp))
        for i, step in enumerate(steps):
            x, kv, sc = step(params[1 + i], x, kv, sc, jnp.int32(2 * i),
                             blk, off, pos, lengths)
        logits = head.preout(params[-1], {}, x)          # [B, Tp, V]
        last = jnp.take_along_axis(
            logits, (lengths - 1)[:, None, None], axis=1)[:, 0]
        return _repack(cache, kv, sc), last.astype(jnp.float32)

    return named_step("prefill", prefill)


def tick_attention(spec: KvCacheSpec) -> str:
    """How a tick attends over this cache, from what the code can see:
    "paged_kernel" where the backend is the TPU (`pallas_supported`: and
    the kernels are not switched off) and the arena is float32 in pages
    of whole (8, 128) tiles, else "gather" (the view through the tables
    and `decode_attend`)."""
    if (pallas_supported() and spec.kv_dtype == "fp32"
            and paged_attention_supported(spec.n_heads * spec.d_head,
                                          spec.block_len)):
        return "paged_kernel"
    return "gather"


def build_decode_fn(model, snapshot, spec: KvCacheSpec,
                    attention: Optional[str] = None):
    """Pure one-token decode tick (see module docstring). `attention` is
    `tick_attention(spec)` unless given: "paged_kernel" is the compiled
    kernel, whatever the process's default backend (a test compiles it
    for a described chip)."""
    emb, blocks, head = split_decode_layers(model)
    attention = attention or tick_attention(spec)
    if attention not in ("paged_kernel", "gather"):
        raise ValueError(f"attention must be paged_kernel|gather, got "
                         f"{attention!r}")

    def layer_step(layer):
        def step(p, x, kv, sc, channel, blk, off, tables, positions, lengths):
            q, k, v = layer.decode_qkv(p, x)
            kv, sc = _scatter(spec, kv, sc, k[:, 0], blk, off, channel)
            kv, sc = _scatter(spec, kv, sc, v[:, 0], blk, off, channel + 1)
            if attention == "paged_kernel":
                a = paged_decode_attention(
                    q.reshape(q.shape[0], -1), kv, channel, tables, lengths,
                    n_heads=spec.n_heads, interpret=False)
            else:
                k_all = _gather(spec, kv, sc, tables, channel)
                v_all = _gather(spec, kv, sc, tables, channel + 1)
                a = layer.decode_attend(q, k_all, v_all, positions[:, None],
                                        lengths)
            return layer.decode_finish(p, x, a), kv, sc
        return step

    steps = _shared_steps(blocks, layer_step)

    def decode(data, cache, tokens, positions, tables):
        params = snapshot.rebuild(data)
        b = tokens.shape[0]
        lengths = positions + 1          # pad rows: position 0 -> length 1
        x = emb.decode_embed(params[0], tokens[:, None], positions[:, None])
        kv, sc = cache["kv"], cache.get("scale")
        blk = tables[jnp.arange(b), positions // spec.block_len]
        off = positions % spec.block_len
        for i, step in enumerate(steps):
            x, kv, sc = step(params[1 + i], x, kv, sc, jnp.int32(2 * i),
                             blk, off, tables, positions, lengths)
        logits = head.preout(params[-1], {}, x)[:, 0]
        return _repack(cache, kv, sc), logits.astype(jnp.float32)

    return named_step("tick", decode)


def _i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def _pow2_buckets(lo: int, hi: int) -> Tuple[int, ...]:
    out, b = [], lo
    while b < hi:
        out.append(b)
        b *= 2
    out.append(hi)
    return tuple(sorted(set(out)))


class DecodeEngine:
    """Compiled-step frontend for one servable's generation plane.

    Owns the static cache geometry (`spec`) and the bucket ladders; the
    executables live in the registry's per-model cache so swaps and the
    compile accounting behave exactly like the stateless runners. The
    scheduler calls `run_prefill` / `run_tick` with host data; both only
    ever invoke finished executables."""

    def __init__(self, registry, name: str, *, block_len: int = 16,
                 num_blocks: Optional[int] = None, kv_dtype: str = "fp32",
                 decode_buckets: Sequence[int] = (1, 2, 4, 8),
                 prompt_buckets: Optional[Sequence[int]] = None):
        self.registry = registry
        self.name = name
        v = registry.get(name)
        if v.model is None:
            raise ServingError(
                f"{name}: servable holds no live model object — "
                "generation needs the layer stack")
        emb, blocks, head = split_decode_layers(v.model)
        d = emb.n_out
        heads = blocks[0].n_heads
        if any(blk.n_heads != heads for blk in blocks):
            raise ServingError(f"{name}: blocks disagree on n_heads")
        max_context = int(np.asarray(v.model.params[0]["P"]).shape[0])
        self.decode_buckets = tuple(sorted(int(b) for b in decode_buckets))
        if num_blocks is None:
            # default: full residency for a max-bucket batch of
            # max-context sequences, plus the reserved trash block
            per_seq = -(-max_context // block_len)
            num_blocks = 1 + per_seq * self.decode_buckets[-1]
        self.spec = KvCacheSpec(
            n_layers=len(blocks), n_heads=heads, d_head=d // heads,
            block_len=int(block_len), num_blocks=int(num_blocks),
            max_context=max_context, kv_dtype=kv_dtype)
        self.attention = tick_attention(self.spec)
        self.prompt_buckets = (tuple(sorted(int(b) for b in prompt_buckets))
                               if prompt_buckets else
                               _pow2_buckets(min(8, max_context),
                                             max_context))
        if self.prompt_buckets[-1] > max_context:
            raise ServingError(
                f"{name}: prompt bucket {self.prompt_buckets[-1]} exceeds "
                f"the positional table ({max_context})")

    # -- geometry --------------------------------------------------------
    @property
    def max_context(self) -> int:
        return self.spec.max_context

    def new_pool(self, metrics=None) -> BlockPool:
        return BlockPool(self.spec, metrics=metrics, name=self.name)

    def prompt_bucket_for(self, n: int) -> int:
        for b in self.prompt_buckets:
            if n <= b:
                return b
        raise ServingError(
            f"{self.name}: prompt of {n} tokens exceeds the context "
            f"window {self.max_context}")

    def decode_bucket_for(self, rows: int) -> int:
        for b in self.decode_buckets:
            if rows <= b:
                return b
        raise ServingError(
            f"{self.name}: decode batch {rows} exceeds bucket "
            f"{self.decode_buckets[-1]}")

    # -- AOT executables -------------------------------------------------
    def _check_version(self, v):
        # a hot-swap to a different architecture would silently change
        # the cache geometry under live sequences — fail loudly instead
        emb, blocks, _ = split_decode_layers(v.model)
        if (len(blocks) != self.spec.n_layers
                or blocks[0].n_heads != self.spec.n_heads
                or emb.n_out != self.spec.n_heads * self.spec.d_head):
            raise ServingError(
                f"{self.name}: swapped architecture no longer matches the "
                "generation cache geometry; re-enable generation")
        return v

    def _compile(self, v, build_fn, phase: str, bucket: int, *arg_specs,
                 **options):
        """Lower and compile one step over the abstract donated cache, and
        leave the record that says whether the arena is updated in place:
        the span-log instant `dl4j/engine/executable`, once per
        executable built (`temp_bytes` beside `arena_bytes`: a program
        that converts or copies the arena holds a temporary of its size;
        `alias_bytes` is what the donation gave back). `options` go to
        the builder and into the record: a tick's `attention`."""
        spec = self.spec
        step = watch_compiles(
            jax.jit(build_fn(v.model, v.snapshot, spec, **options),
                    donate_argnums=(1,)),
            f"serving/decode:{self.name}/{phase}-{bucket}").__wrapped__
        compiled = step.lower(v.snapshot.data, _cache_arg_specs(spec),
                              *arg_specs).compile()
        mem = compiled.memory_analysis()
        _tracer().instant(
            "dl4j/engine/executable", model=self.name, phase=phase,
            bucket=bucket, arena_bytes=spec.arena_nbytes(),
            temp_bytes=getattr(mem, "temp_size_in_bytes", None),
            alias_bytes=getattr(mem, "alias_size_in_bytes", None), **options)
        return compiled

    def prefill_exec(self, v, t_bucket: int):
        sig = _abstract_sig(v.snapshot, v.state, v.precision)
        w = self.spec.table_width
        return self.registry.compile_cached(
            self.name, ("decode", sig, "prefill", t_bucket),
            lambda: self._compile(v, build_prefill_fn, "prefill", t_bucket,
                                  _i32(1, t_bucket), _i32(1), _i32(1, w)),
            f"prefill-t{t_bucket}")

    def decode_exec(self, v, bucket: int):
        sig = _abstract_sig(v.snapshot, v.state, v.precision)
        w = self.spec.table_width
        return self.registry.compile_cached(
            self.name, ("decode", sig, "tick", bucket),
            lambda: self._compile(v, build_decode_fn, "tick", bucket,
                                  _i32(bucket), _i32(bucket), _i32(bucket, w),
                                  attention=self.attention),
            f"decode-b{bucket}")

    # -- host-facing phases ----------------------------------------------
    def _pad_table(self, table: Sequence[int]) -> List[int]:
        w = self.spec.table_width
        if len(table) > w:
            raise ServingError(f"{self.name}: block table of {len(table)} "
                               f"exceeds width {w}")
        return list(table) + [0] * (w - len(table))

    def run_prefill(self, v, pool: BlockPool, prompt: Sequence[int],
                    table: Sequence[int], observe=None) -> np.ndarray:
        """Write `prompt`'s K/V through `table`, return the next-token
        logits [V]. Batch 1: one compile per prompt bucket. Three spans,
        children of whatever span the caller has open: prepare (host),
        dispatch (uploads + enqueue), fetch (the wait for the device and
        the copy down); `observe(span)` sees each once it has closed."""
        with _span("dl4j/engine/prefill.prepare") as prepare:
            self._check_version(v)
            n = len(prompt)
            tb = self.prompt_bucket_for(n)
            prepare.set(bucket=tb, tokens=n)
            tokens = np.zeros((1, tb), np.int32)
            tokens[0, :n] = np.asarray(prompt, np.int32)
            tab = np.asarray([self._pad_table(table)], np.int32)
            exec_ = self.prefill_exec(v, tb)
        with _span("dl4j/engine/prefill.dispatch") as dispatch:
            pool.cache, logits = exec_(
                v.snapshot.data, pool.cache, jnp.asarray(tokens),
                jnp.asarray([n], jnp.int32), jnp.asarray(tab))
        with _span("dl4j/engine/prefill.fetch") as fetch:
            out = np.asarray(logits)[0]
            fetch.set(bytes=out.nbytes)
        if observe is not None:
            for sp in (prepare, dispatch, fetch):
                observe(sp)
        return out

    def run_tick(self, v, pool: BlockPool, tokens: Sequence[int],
                 positions: Sequence[int], tables: Sequence[Sequence[int]],
                 bucket: int, observe=None) -> np.ndarray:
        """One decode tick over `len(tokens)` live rows padded up to
        `bucket` (pad rows park at the trash block, length 1, and their
        logits are discarded by the caller). Returns logits [rows, V].
        Spans and `observe` as in `run_prefill`."""
        with _span("dl4j/engine/tick.prepare", bucket=bucket) as prepare:
            self._check_version(v)
            rows = len(tokens)
            if rows > bucket:
                raise ServingError(f"{rows} rows > decode bucket {bucket}")
            tok = np.zeros(bucket, np.int32)
            pos = np.zeros(bucket, np.int32)
            tab = np.zeros((bucket, self.spec.table_width), np.int32)
            tok[:rows] = np.asarray(tokens, np.int32)
            pos[:rows] = np.asarray(positions, np.int32)
            for i, t in enumerate(tables):
                tab[i] = self._pad_table(t)
            # the pages the rows' lengths span, of those the tables name:
            # what the paged kernel reads, of what the gather path reads
            prepare.set(
                pages_live=int((pos // self.spec.block_len + 1).sum()),
                pages_table=tab.size)
            exec_ = self.decode_exec(v, bucket)
        with _span("dl4j/engine/tick.dispatch") as dispatch:
            pool.cache, logits = exec_(
                v.snapshot.data, pool.cache, jnp.asarray(tok),
                jnp.asarray(pos), jnp.asarray(tab))
        with _span("dl4j/engine/tick.fetch") as fetch:
            full = np.asarray(logits)
            fetch.set(bytes=full.nbytes)
        if observe is not None:
            for sp in (prepare, dispatch, fetch):
                observe(sp)
        return full[:rows]
