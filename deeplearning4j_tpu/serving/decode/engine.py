"""The KV-cache generation forward: AOT-compiled prefill + decode-tick
steps over the transformer stack's decode mode.

Two compiled signatures per servable, both AOT-lowered through the
registry's shared executable cache (`ModelRegistry.compile_cached`, keys
namespaced ("decode", sig, phase, bucket)) so the server-lifetime
invariant of the stateless plane extends to generation: ONE XLA compile
per (model, bucket, phase), no cold compile on any request path, and a
same-architecture hot-swap reuses every decode executable. The `sig` of
those keys is the version's own (`ServableVersion.sig`, which the
registry computed when it built the version): the per-call path walks
neither the model nor the weights, it meets a version once
(`DecodeEngine._check_version`) and knows it by identity afterwards.

  prefill(data, cache, tokens [1, Tp], lengths [1], tables [1, W]
          [, slot [1]])
      -> (cache', next_logits [1, V])
    The whole (right-padded) prompt runs as one causal forward — the
    standard full-sequence math, row-masked by `lengths` — while every
    layer's K/V projections scatter into the paged arena through the
    sequence's block table. Prompt attention uses the LOCAL (exact)
    projections, so int8 cache quantization only affects later ticks.

  decode(data, cache, tokens [B], positions [B], tables [B, W]
         [, slots [B]])
      -> (cache', logits [B, V])
    One token per row: embed at its absolute position, scatter its K/V
    into the arena, attend over the row's live cache slots, project
    logits. The attention reads the arena through the block table inside
    one Pallas kernel (`kernels.paged_attention`: live pages only, all
    heads on the merged lanes, no view of the cache made; a latent block's
    pages through `paged_latent_attention`) where the block's
    `decode_attention` finds the TPU and pages of whole float32 or bfloat16
    tiles. Elsewhere — the CPU, the int8 arena, odd widths — it gathers
    the row's whole table into a view and attends with causal offsets +
    per-row valid length (`kernels.attention` kv_length path): the
    kernel's oracle.

  tick(data, cache, last [Bmax], tokens [B], positions [B], tables [B, W]
       [, slots [B]])
      -> (cache', ids [Bmax], logits [B, V])
    What is compiled and served for a decode bucket (`build_tick_fn`): the
    decode step above between two small selections. Before it, a row's
    input token is taken ON THE DEVICE from `last`, the ids the previous
    tick returned, where `tokens` names a row of it (an entry `-(i + 1)`
    is row `i` of `last`; an entry >= 0 is the token itself, from the
    host). After it, `ids` is the argmax of each row's logits, padded to
    the largest bucket so that any tick's ids feed any bucket's tick. A
    tick whose rows are all greedy is therefore started from ids that
    never visit the host (`DecodeEngine.start_tick(..., after=)`), while
    its predecessor's ids are still on their way down; the host fetches B
    ids in place of `[B, V]` float32 (2 to 3 MB), which the scheduler
    thread would read once, row by row, for the same argmax (PERF.md
    section 6, PRs 34 and 37). The logits are an output all the same: a
    tick with a row at a temperature fetches them instead.

The layers' contract. The engine names no model: a stack can be served
if its first layer embeds (`decode_embed(params, tokens, positions)`)
and states its context (`decode_context(params)`: a positional table's
rows, or what a table-free embedding states), its last layer has
`preout` (logits before the activation), and every layer between
answers
    decode_cache(width)              -> (channels, width) it writes for
                                        a token; (0, 0) for a layer that
                                        keeps none (a norm)
    decode_state(width)              -> what it keeps for a SEQUENCE,
                                        whatever its length: {name: (shape
                                        with "slots" for the sequences'
                                        axis, dtype)}; None for a layer
                                        that keeps none (all but a
                                        state-space layer)
    decode_attention(phase, spec)    -> the name of the attention path
                                        that phase takes over that cache,
                                        or None where there is no choice
    decode_prefill_step(io, attention), decode_tick_step(io, attention)
                                     -> the traced step
A step is `step(p, x, kv, sc, channel, blk, off, ...) -> (x, kv, sc,
counts)`: `channel` is the first of the layer's channels, `blk`/`off`
where each token is written (`io.scatter`; block 0 is the trash block,
so a tick row whose `blk` is 0 is a pad row), `io.gather` the view of a
channel through the tables; a prefill gets `pos, lengths` after them, a
tick `tables, positions, lengths`. `counts` is None or a few int32 (an
expert layer's picks): the executable returns them stacked, beside the
logits, and the engine adds them to counters and to its `*.fetch`
spans. A layer that keeps state is handed two more arguments, `state`
(its own leaves of the cache, as it stated them) and `slot` (`[B]`: each
row's slot on the leaves' slot axis; slot 0 is the trash slot, a pad
row's), and returns its new leaves as a fifth result: a prefill writes
the state its prompt leaves after its last REAL token, a tick reads its
rows' slots and writes them back. The GPT block
(`nn/layers/transformer.py`) writes K and V of `H*Dh`; the
latent-attention block (`nn/layers/shortcut_moe.py`) one latent of 640 an
attention, expanded into heads over a prompt and attended as it lies,
with the up-projection absorbed, in a tick; the hybrid block
(`nn/layers/hybrid_ssm.py`) K and V of its `Hkv*Dh` key/value heads where
its mixer is grouped-query attention, and where it is a Mamba-2 layer no
page at all but a recurrent state `[H, P, N]` float32 and the last three
inputs of its convolution for each sequence; the SambaY blocks
(`nn/layers/sambay.py`) keep a Mamba layer's state or a window layer's
ring of keys and values a sequence, and the cross-decoder pages ONE pair
of channels that its full-attention layer writes and its cross layers
read. Such a stack's executables take one argument more, the rows' slots
(a fourth upload a tick); a stack without a stateful layer has no such
leaf, argument or upload. A prefill step may hand on each row's last
real token alone, `[B, 1, d]` (the cross-decoder: the layers after its
shared keys and values run for that token only); the head then reads it
as it stands. A layer may state, for the records and nothing else, the
window of keys a tick reads at most (`decode_window`), how many layers
read its pages (`decode_shared_readers`) and that its prefill hands on the
last token alone (`decode_prefill_last`): the executable's instant carries
`window` and `shared_readers`, `tick.prepare` `window_live` (the ring
slots the rows read), `prefill.prepare` `cross_tokens`. A layer with
routed experts also answers `decode_experts(phase, width)`, the path of
its held experts' products (`grouped_kernel`: one Pallas kernel a layer,
on the TPU; `cond`: a conditional an expert), and its tick step takes
that answer as a third argument; the tick's instant carries it as
`experts`. A window layer that keeps a ring answers
`decode_window_attention(phase, width)` the same way (`ring_kernel`: its
rows' rings read in place by one Pallas kernel, on the TPU; `ring_gather`:
the rings gathered), is handed it as `window_attention`, and the tick's
instant carries it under that name.

The cache pytree is DONATED and laid out `[2L, num_blocks, block_len,
H*Dh]` (`cache.py` says why), so the arena updates in place on device: a
tick costs one [B,*] pass plus the rows' live pages, never an arena copy.
The per-sequence state of a stateful stack (`cache["state"]`, one dict of
leaves a stateful layer, `state_slots` slots each) rides the same
donation: a tick reads and writes the rows' slots where they lie, a
prefill writes one slot, and no executable may hold a temporary of the
state's size (`test_decode_steps_update_the_state_in_place_on_v5e`; the
record `dl4j/engine/executable` carries `state_bytes` beside
`temp_bytes`).
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
import functools
import time
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...telemetry.compile_watch import watch_compiles
from ...telemetry.runtime import span as _span
from ...telemetry.tracing import named_step, tracer as _tracer
from ..registry import ServingError, _abstract_sig
from .cache import BlockPool, CacheIO, KvCacheSpec, make_cache

__all__ = ["DecodeEngine", "build_prefill_fn", "build_decode_fn",
           "build_tick_fn", "split_decode_layers", "cache_geometry"]


_STEP_CONTRACT = ("decode_cache", "decode_state", "decode_attention",
                  "decode_prefill_step", "decode_tick_step")


def split_decode_layers(model):
    """(embedding, [blocks...], head) of a generate-capable stack, or
    ServingError: a first layer that embeds and states its context,
    layers that answer the step contract (module docstring), an output
    layer with `preout`."""
    layers = getattr(model, "layers", None)
    if not layers or len(layers) < 3 \
            or not hasattr(layers[0], "decode_embed") \
            or not all(hasattr(b, name) for b in layers[1:-1]
                       for name in _STEP_CONTRACT) \
            or not hasattr(layers[-1], "preout"):
        raise ServingError(
            "generation needs an embedding layer (decode_embed) -> layers "
            "with decode steps (TransformerBlock: pages of K and V; "
            "ShortcutMoEBlock: pages of a latent; HybridSSMBlock: pages of "
            "grouped K and V, or a state-space layer's per-sequence state; "
            "SambaYBlock: per-sequence state or a window's ring; "
            "CrossDecoderBlock: one shared pair of pages; RMSNormLayer, "
            "LayerNormLayer: neither) -> an output layer; got "
            f"{[type(l).__name__ for l in (layers or [])]}")
    if getattr(model.conf, "preprocessors", None):
        raise ServingError(
            "generation does not support input preprocessors between "
            "decode layers")
    return layers[0], list(layers[1:-1]), layers[-1]


def cache_geometry(model):
    """(channels, width, context, state) of a generate-capable stack, as
    its layers state them, or ServingError: the channels summed over the
    layers that page (some may not: a norm, a state-space layer), one
    width for all that do, the embedding's context, and the per-sequence
    leaves of the layers that keep state, as `KvCacheSpec.state` holds
    them (empty where none does)."""
    emb, blocks, _ = split_decode_layers(model)
    wrote = [blk.decode_cache(emb.n_out) for blk in blocks]
    widths = {int(w) for c, w in wrote if c}
    if len(widths) != 1:
        raise ServingError(
            f"the stack's layers write cache widths {sorted(widths)}: the "
            "paged arena holds one, and a sequence is known by its pages "
            "(a stack needs a layer that pages)")
    context = emb.decode_context(model.params[0])
    if not context:
        raise ServingError(
            "the embedding layer states no context (no positional table "
            "and no max_timesteps)")
    kept = (blk.decode_state(emb.n_out) for blk in blocks)
    state = tuple(tuple((name, tuple(shape), str(dtype))
                        for name, (shape, dtype) in sorted(leaves.items()))
                  for leaves in kept if leaves)
    return sum(int(c) for c, _ in wrote), widths.pop(), int(context), state


def _layer_confs(model):
    """The stack's layer configurations, names left out: what the compiled
    steps close over beside the shapes (heads, top-k, rotary base, ...)."""
    return [dataclasses.replace(layer, name=None) for layer in model.layers]


def _first_channels(blocks, width):
    """Each block's first cache channel: the channels before it."""
    out, at = [], 0
    for blk in blocks:
        out.append(at)
        at += blk.decode_cache(width)[0]
    return out


def _answer_of(blocks, question, phase, arg):
    """The path of the stack's `phase` that its layers answer to
    `question`: `decode_attention` over the cache's spec (every layer),
    `decode_experts` over the width (the layers with routed experts). They
    must agree; None where none has a choice."""
    names = {getattr(blk, question)(phase, arg) for blk in blocks
             if hasattr(blk, question)} - {None}
    if len(names) > 1:
        raise ServingError(f"layers disagree on the {phase}'s {question}: "
                           f"{sorted(names)}")
    return names.pop() if names else None


def _cache_arg_specs(spec: KvCacheSpec):
    """The cache pytree's shapes, with no arena made to learn them."""
    return jax.eval_shape(lambda: make_cache(spec))


def _through(blocks, steps, width, params, cache, x, slot, *where):
    """`x` through the stack's traced `steps`, each handed its parameters,
    the arena, its first channel and `where` (the step's own arguments); a
    layer that keeps state also its leaves of `cache["state"]` and `slot`.
    Returns (x, the new cache pytree, each layer's counts or None)."""
    kv, sc = cache["kv"], cache.get("scale")
    state, counts, at = list(cache.get("state", ())), [], 0
    for block, step, p, channel in zip(blocks, steps, params,
                                       _first_channels(blocks, width)):
        args = (p, x, kv, sc, jnp.int32(channel), *where)
        if block.decode_state(width) is None:
            x, kv, sc, n = step(*args)
        else:
            x, kv, sc, n, state[at] = step(*args, state[at], *slot)
            at += 1
        counts.append(n)
    out = {"kv": kv, "scale": sc} if "scale" in cache else {"kv": kv}
    if state:
        out["state"] = tuple(state)
    return x, out, counts


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


def _stack_counts(counts):
    """The layers' counts that are not None, stacked [layers, n] (an
    empty tuple where no layer counts: the executable then returns the
    cache and the logits alone, as it always did)."""
    counts = [c for c in counts if c is not None]
    return (jnp.stack(counts).astype(jnp.int32),) if counts else ()


def build_prefill_fn(model, snapshot, spec: KvCacheSpec,
                     attention: Optional[str] = None):
    """Pure prefill step (see module docstring). Closed over the layer
    configs and the snapshot's dequantization structure only — the flat
    `data` tuple stays a runtime argument, so re-quantized checkpoints
    share the executable (the stateless plane's convention)."""
    emb, blocks, head = split_decode_layers(model)
    attention = attention or _answer_of(blocks, "decode_attention",
                                        "prefill", spec)
    io = CacheIO(spec)
    steps = _shared_steps(
        blocks, lambda layer: layer.decode_prefill_step(io, attention))

    def prefill(data, cache, tokens, lengths, tables, *slot):
        params = snapshot.rebuild(data)
        b, tp = tokens.shape
        pos = jnp.broadcast_to(jnp.arange(tp, dtype=jnp.int32), (b, tp))
        x = emb.decode_embed(params[0], tokens, pos)
        tidx = jnp.arange(tp, dtype=jnp.int32)
        # right-padded prompt slots scatter too (their K/V derive
        # deterministically from the pad token, and table slots past the
        # allocation point at the trash block), so a reused block is
        # overwritten wholesale — reuse is bit-identical to fresh
        blk = tables[:, tidx // spec.block_len]
        off = jnp.broadcast_to(tidx % spec.block_len, (b, tp))
        x, cache, counts = _through(blocks, steps, emb.n_out, params[1:-1],
                                    cache, x, slot, blk, off, pos, lengths)
        if x.shape[1] == 1 < tp:    # the layers handed on the last token
            return (cache, head.preout(params[-1], {}, x)[:, 0].astype(
                jnp.float32), *_stack_counts(counts))
        logits = head.preout(params[-1], {}, x)          # [B, Tp, V]
        last = jnp.take_along_axis(
            logits, (lengths - 1)[:, None, None], axis=1)[:, 0]
        return cache, last.astype(jnp.float32), *_stack_counts(counts)

    return named_step("prefill", prefill)


def _decode_step(model, snapshot, spec: KvCacheSpec,
                 attention: Optional[str] = None,
                 experts: Optional[str] = None,
                 window_attention: Optional[str] = None):
    emb, blocks, head = split_decode_layers(model)
    attention = attention or _answer_of(blocks, "decode_attention", "tick",
                                        spec)
    # a layer that answers a question of its own is handed its answer
    paths = {"experts": (experts, "decode_experts"),
             "window_attention": (window_attention, "decode_window_attention")}
    paths = {name: given or _answer_of(blocks, question, "tick", emb.n_out)
             for name, (given, question) in paths.items()}
    io = CacheIO(spec)
    steps = _shared_steps(blocks, lambda layer: layer.decode_tick_step(
        io, attention, **{name: path for name, path in paths.items()
                          if hasattr(layer, f"decode_{name}")}))

    def decode(data, cache, tokens, positions, tables, *slot):
        params = snapshot.rebuild(data)
        b = tokens.shape[0]
        lengths = positions + 1          # pad rows: position 0 -> length 1
        x = emb.decode_embed(params[0], tokens[:, None], positions[:, None])
        blk = tables[jnp.arange(b), positions // spec.block_len]
        off = positions % spec.block_len
        x, cache, counts = _through(blocks, steps, emb.n_out, params[1:-1],
                                    cache, x, slot, blk, off, tables,
                                    positions, lengths)
        logits = head.preout(params[-1], {}, x)[:, 0]
        return cache, logits.astype(jnp.float32), *_stack_counts(counts)

    return decode


def build_decode_fn(model, snapshot, spec: KvCacheSpec,
                    attention: Optional[str] = None,
                    experts: Optional[str] = None,
                    window_attention: Optional[str] = None):
    """Pure one-token decode step (see module docstring). `attention`,
    `experts` and `window_attention` are what the stack's layers answer for
    a tick over `spec` unless given: a GPT block's "paged_kernel", an expert
    layer's "grouped_kernel" or a window layer's "ring_kernel" is the
    compiled kernel, whatever the process's default backend (a test
    compiles it for a described chip)."""
    return named_step("tick", _decode_step(model, snapshot, spec, attention,
                                           experts, window_attention))


def build_tick_fn(model, snapshot, spec: KvCacheSpec, rows_max: int,
                  attention: Optional[str] = None,
                  experts: Optional[str] = None,
                  window_attention: Optional[str] = None):
    """The served tick (see module docstring): the decode step, its input
    tokens selected on the device between the host's and the previous
    tick's ids `last` `[rows_max]`, and its rows' argmax returned beside
    the logits, padded to `rows_max`."""
    decode = _decode_step(model, snapshot, spec, attention, experts,
                          window_attention)

    def tick(data, cache, last, tokens, positions, tables, *slot):
        tokens = jnp.where(tokens < 0, last[jnp.maximum(-tokens - 1, 0)],
                           tokens)
        cache, logits, *counts = decode(data, cache, tokens, positions,
                                        tables, *slot)
        ids = jnp.zeros(rows_max, jnp.int32).at[:tokens.shape[0]].set(
            jnp.argmax(logits, axis=-1).astype(jnp.int32))
        return cache, ids, logits, *counts

    return named_step("tick", tick)


def _i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def _pow2_buckets(lo: int, hi: int) -> Tuple[int, ...]:
    out, b = [], lo
    while b < hi:
        out.append(b)
        b *= 2
    out.append(hi)
    return tuple(sorted(set(out)))


@dataclasses.dataclass(frozen=True)
class _Started:
    """A prefill or tick that was dispatched and not yet waited for: its
    results, still on the device, and the spans that timed its start."""
    rows: int
    bucket: int
    ids: Optional[jax.Array]        # a tick's: [largest bucket], its argmax
    logits: jax.Array
    counts: list
    spans: tuple


class DecodeEngine:
    """Compiled-step frontend for one servable's generation plane.

    Owns the static cache geometry (`spec`) and the bucket ladders; the
    executables live in the registry's per-model cache so swaps and the
    compile accounting behave exactly like the stateless runners. The
    scheduler calls `start_prefill` / `start_tick` with host data and
    `finish_prefill` / `finish_tick` with what those returned (`run_prefill`
    / `run_tick` are the two in one call); all only ever invoke finished
    executables. Between a `start_*` and the next, nothing on the host may
    read `pool.cache` or a started step's results: they are futures on
    the device's one in-order stream.

    What depends on the version alone is resolved once a version, in
    `_check_version`: that its layers are the ones the executables were
    built for, and the signature that keys them, which the version
    states (`ServableVersion.sig`). A call then keeps what depends on
    its rows: host arrays, two dictionary hits, three uploads (four
    where the stack keeps per-sequence state: the rows' slots)."""

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
        channels, width, max_context, state = cache_geometry(v.model)
        self.decode_buckets = tuple(sorted(int(b) for b in decode_buckets))
        if num_blocks is None:
            # default: full residency for a max-bucket batch of
            # max-context sequences, plus the reserved trash block
            per_seq = -(-max_context // block_len)
            num_blocks = 1 + per_seq * self.decode_buckets[-1]
        self.spec = KvCacheSpec(
            channels=channels, width=width,
            block_len=int(block_len), num_blocks=int(num_blocks),
            max_context=max_context, kv_dtype=kv_dtype, state=state,
            # a slot for every row of the largest tick, and the trash slot
            state_slots=1 + self.decode_buckets[-1] if state else 0)
        emb, blocks, _ = split_decode_layers(v.model)
        self.attention = _answer_of(blocks, "decode_attention", "tick",
                                    self.spec)
        self.prefill_attention = _answer_of(blocks, "decode_attention",
                                            "prefill", self.spec)
        self.experts = _answer_of(blocks, "decode_experts", "tick",
                                  emb.n_out)
        self.window_attention = _answer_of(
            blocks, "decode_window_attention", "tick", emb.n_out)
        # what a stack may state of itself beside the contract, for the
        # records: a window of keys a tick reads at most, the layers that
        # read one shared pair of channels, a prefill that hands on each
        # prompt's last token alone past some layer
        said = lambda name: [getattr(b, name) for b in blocks
                             if getattr(b, name, None)]
        self.window = max(said("decode_window"), default=0)
        self.shared_readers = sum(said("decode_shared_readers"))
        self.prefill_last = bool(said("decode_prefill_last"))
        self._moe_picks = self._moe_pairs = None    # made with the first counts
        # what a tick with no predecessor in flight is handed as `last`
        self._no_ids = jnp.asarray(np.zeros(self.decode_buckets[-1], np.int32))
        self._layers = _layer_confs(v.model)
        self._checked = self._sig = None    # the last version met, its sig
        self.prompt_buckets = (tuple(sorted(int(b) for b in prompt_buckets))
                               if prompt_buckets else
                               _pow2_buckets(min(8, max_context),
                                             max_context))
        if self.prompt_buckets[-1] > max_context:
            raise ServingError(
                f"{name}: prompt bucket {self.prompt_buckets[-1]} exceeds "
                f"the context the model states ({max_context})")

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
        """`v`, once it is known to fit this engine's executables; the
        version the last call held is known by identity. A new one is met
        once: its layers are compared, its signature taken (the version's
        own; computed here, once, for a version that states none), and
        the span log gets the instant `dl4j/engine/version` (`leaves`,
        `computed`, and `ms` where it was)."""
        # a hot-swap to a different architecture would silently change
        # the cache geometry under live sequences, and one of the same
        # shapes but other layer options (heads, top-k) would run
        # executables closed over the old ones: they are keyed by shapes
        # and dtypes alone — fail loudly instead
        if v is self._checked:
            return v
        spec = self.spec
        if cache_geometry(v.model) != (spec.channels, spec.width,
                                       spec.max_context, spec.state) \
                or _layer_confs(v.model) != self._layers:
            raise ServingError(
                f"{self.name}: swapped architecture no longer matches the "
                "generation cache geometry and the layers its executables "
                "were built for; re-enable generation")
        sig = getattr(v, "sig", None)
        record = {"computed": int(sig is None)}
        if sig is None:
            t0 = time.perf_counter()
            sig = _abstract_sig(v.snapshot, v.state, v.precision)
            record["ms"] = round((time.perf_counter() - t0) * 1e3, 3)
        _tracer().instant(
            "dl4j/engine/version", model=self.name,
            version=getattr(v, "version", None), leaves=len(v.snapshot.data),
            **record)
        self._sig, self._checked = sig, v
        return v

    def _compile(self, v, build_fn, phase: str, bucket: int, *arg_specs,
                 **options):
        """Lower and compile one step over the abstract donated cache, and
        leave the record that says whether the arena is updated in place:
        the span-log instant `dl4j/engine/executable`, once per
        executable built (`temp_bytes` beside `arena_bytes`: a program
        that converts or copies the arena holds a temporary of its size;
        `alias_bytes` is what the donation gave back), with the cache's
        `channels` and `width`, and where the stack keeps per-sequence
        state its `state_bytes` and `state_slots` (a program that copies
        the state holds a temporary of that size). A stateful stack's
        program takes the rows' slots after `arg_specs`, as many as its
        tables have rows. `options` go to the builder and into the
        record: the phase's `attention`, where its layers have a choice
        (`paged_kernel` / `gather`, `mla_paged` / `mla_absorbed` /
        `mla_expanded`), a tick's `experts` (`grouped_kernel` /
        `cond`), where it has layers with experts, and its
        `window_attention` (`ring_kernel` / `ring_gather`), where it has
        window layers."""
        spec = self.spec
        options = {k: o for k, o in options.items() if o is not None}
        record = {k: n for k, n in (("window", self.window),
                                    ("shared_readers", self.shared_readers))
                  if n}
        if spec.state:
            arg_specs += (_i32(arg_specs[-1].shape[0]),)
            record.update(state_bytes=spec.state_nbytes(),
                          state_slots=spec.state_slots)
        step = watch_compiles(
            jax.jit(build_fn(v.model, v.snapshot, spec, **options),
                    donate_argnums=(1,)),
            f"serving/decode:{self.name}/{phase}-{bucket}").__wrapped__
        compiled = step.lower(v.snapshot.data, _cache_arg_specs(spec),
                              *arg_specs).compile()
        mem = compiled.memory_analysis()
        _tracer().instant(
            "dl4j/engine/executable", model=self.name, phase=phase,
            bucket=bucket, channels=spec.channels, width=spec.width,
            arena_bytes=spec.arena_nbytes(),
            temp_bytes=getattr(mem, "temp_size_in_bytes", None),
            alias_bytes=getattr(mem, "alias_size_in_bytes", None), **record,
            **options)
        return compiled

    def prefill_exec(self, v, t_bucket: int):
        """The prompt bucket's executable for `v`: a dictionary hit in the
        registry's cache, keyed by the signature `v` states (`v.sig`, as
        `_check_version` took it the one time it met `v`), a compile the
        first time a signature asks. Nothing here walks the model or the
        weights: this runs in every prefill."""
        self._check_version(v)
        w = self.spec.table_width
        return self.registry.compile_cached(
            self.name, ("decode", self._sig, "prefill", t_bucket),
            lambda: self._compile(v, build_prefill_fn, "prefill", t_bucket,
                                  _i32(1, t_bucket), _i32(1), _i32(1, w),
                                  attention=self.prefill_attention),
            f"prefill-t{t_bucket}")

    def decode_exec(self, v, bucket: int):
        """The decode bucket's tick for `v` (`build_tick_fn`), found as
        `prefill_exec` finds a prefill (it runs in every tick: two
        dictionary hits, no walk)."""
        self._check_version(v)
        w, rows_max = self.spec.table_width, self.decode_buckets[-1]
        return self.registry.compile_cached(
            self.name, ("decode", self._sig, "tick", bucket),
            lambda: self._compile(
                v, functools.partial(build_tick_fn, rows_max=rows_max), "tick",
                bucket, _i32(rows_max), _i32(bucket), _i32(bucket),
                _i32(bucket, w), attention=self.attention,
                experts=self.experts, window_attention=self.window_attention),
            f"decode-b{bucket}")

    # -- host-facing phases ----------------------------------------------
    def _count_picks(self, fetch, phase: str, counts):
        """An expert layer's counts, `[layers, 5]` int32 (picks of live
        tokens, of them on identity experts, on experts held here, held
        experts hit, the largest load of a held expert): onto the fetch
        span (summed over the layers) and into the counters."""
        n = np.asarray(counts[0], np.int64)
        picks, identity, held, hit, load = (int(c) for c in n.sum(axis=0))
        fetch.set(moe_layers=len(n), moe_picks=picks, moe_identity=identity,
                  moe_held=held, moe_held_hit=hit, moe_held_load_max=load)
        if self._moe_picks is None:
            metrics = self.registry.metrics
            self._moe_picks = metrics.counter(
                "dl4j_moe_picks_total",
                "router picks of live tokens, by where the expert is: an "
                "identity expert, an expert held here, one absent (another "
                "chip's)", labels=("model", "phase", "kind"))
            self._moe_pairs = metrics.counter(
                "dl4j_moe_held_pairs_total",
                "(token, expert) pairs computed by the experts held here",
                labels=("model", "phase"))
        for kind, k in (("identity", identity), ("held", held),
                        ("absent", picks - identity - held)):
            self._moe_picks.inc(k, model=self.name, phase=phase, kind=kind)
        self._moe_pairs.inc(held, model=self.name, phase=phase)

    def _pad_table(self, table: Sequence[int]) -> List[int]:
        w = self.spec.table_width
        if len(table) > w:
            raise ServingError(f"{self.name}: block table of {len(table)} "
                               f"exceeds width {w}")
        return list(table) + [0] * (w - len(table))

    def start_prefill(self, v, pool: BlockPool, prompt: Sequence[int],
                      table: Sequence[int], observe=None) -> "_Started":
        """Dispatch the prefill that writes `prompt`'s K/V through `table`
        (batch 1: one compile per prompt bucket); `finish_prefill` waits
        for it. Two spans, prepare (host) and dispatch (uploads +
        enqueue), held until the finish writes them beside its own;
        `observe(span)` sees each once it has closed."""
        with _span("dl4j/engine/prefill.prepare").hold() as prepare:
            n = len(prompt)
            tb = self.prompt_bucket_for(n)
            prepare.set(bucket=tb, tokens=n)
            if self.prefill_last:   # tokens the layers after the cut compute
                prepare.set(cross_tokens=1)
            tokens = np.zeros((1, tb), np.int32)
            tokens[0, :n] = np.asarray(prompt, np.int32)
            tab = np.asarray([self._pad_table(table)], np.int32)
            # a stateful stack: the sequence's slot, taken now if this is
            # the first prefill to meet its first block
            slot = ([np.asarray([pool.slot_for(table[0])], np.int32)]
                    if self.spec.state else [])
            exec_ = self.prefill_exec(v, tb)
        with _span("dl4j/engine/prefill.dispatch").hold() as dispatch:
            pool.cache, logits, *counts = exec_(
                v.snapshot.data, pool.cache, jnp.asarray(tokens),
                jnp.asarray([n], jnp.int32), jnp.asarray(tab),
                *map(jnp.asarray, slot))
        if observe is not None:
            observe(prepare)
            observe(dispatch)
        return _Started(1, tb, None, logits, counts, (prepare, dispatch))

    def finish_prefill(self, started: "_Started", observe=None) -> np.ndarray:
        """The next-token logits [V] of a started prefill. Writes its
        prepare and dispatch spans and a third, fetch (the wait for the
        device and the copy down), as children of whatever span the
        caller has open."""
        for sp in started.spans:
            sp.write()
        with _span("dl4j/engine/prefill.fetch") as fetch:
            out = np.asarray(started.logits)[0]
            fetch.set(bytes=out.nbytes)
            if started.counts:
                self._count_picks(fetch, "prefill", started.counts)
        if observe is not None:
            observe(fetch)
        return out

    def run_prefill(self, v, pool: BlockPool, prompt: Sequence[int],
                    table: Sequence[int], observe=None) -> np.ndarray:
        """Write `prompt`'s K/V through `table`, return the next-token
        logits [V]: `start_prefill` and `finish_prefill` in one call."""
        return self.finish_prefill(
            self.start_prefill(v, pool, prompt, table, observe), observe)

    def start_tick(self, v, pool: BlockPool, tokens: Sequence[int],
                   positions: Sequence[int], tables: Sequence[Sequence[int]],
                   bucket: int, observe=None,
                   after: Optional["_Started"] = None) -> "_Started":
        """Dispatch one decode tick over `len(tokens)` live rows padded up
        to `bucket` (pad rows park at the trash block, length 1, and their
        results are discarded by the caller); `finish_tick` waits for it.
        A token `-(i + 1)` is row `i` of the ids of `after`, a tick started
        before this one and perhaps not finished: the device selects it,
        the host never sees it. Spans and `observe` as in
        `start_prefill`."""
        with _span("dl4j/engine/tick.prepare",
                   bucket=bucket).hold() as prepare:
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
            if self.window:     # the ring slots a window layer reads
                prepare.set(window_live=int(
                    np.minimum(pos[:rows] + 1, self.window).sum()))
            slot = []
            if self.spec.state:     # pad rows keep the trash slot, 0
                slot = [np.zeros(bucket, np.int32)]
                slot[0][:rows] = pool.slots_of([t[0] for t in tables])
                prepare.set(state_slots_live=rows)
            exec_ = self.decode_exec(v, bucket)
            last = self._no_ids if after is None else after.ids
        with _span("dl4j/engine/tick.dispatch").hold() as dispatch:
            pool.cache, ids, logits, *counts = exec_(
                v.snapshot.data, pool.cache, last, jnp.asarray(tok),
                jnp.asarray(pos), jnp.asarray(tab), *map(jnp.asarray, slot))
        if observe is not None:
            observe(prepare)
            observe(dispatch)
        return _Started(rows, bucket, ids, logits, counts,
                        (prepare, dispatch))

    def finish_tick(self, started: "_Started", greedy: bool = False,
                    observe=None) -> np.ndarray:
        """The logits [rows, V] of a started tick, or with `greedy` the
        rows' argmax [rows] int32, taken on the device. Spans as in
        `finish_prefill`."""
        for sp in started.spans:
            sp.write()
        with _span("dl4j/engine/tick.fetch") as fetch:
            full = np.asarray(started.ids if greedy else started.logits)
            fetch.set(bytes=full.nbytes)
            if started.counts:
                self._count_picks(fetch, "tick", started.counts)
        if observe is not None:
            observe(fetch)
        return full[:started.rows]

    def run_tick(self, v, pool: BlockPool, tokens: Sequence[int],
                 positions: Sequence[int], tables: Sequence[Sequence[int]],
                 bucket: int, observe=None, greedy: bool = False
                 ) -> np.ndarray:
        """One decode tick, started and finished in one call: the same
        executable a scheduler's tick in flight runs."""
        return self.finish_tick(
            self.start_tick(v, pool, tokens, positions, tables, bucket,
                            observe), greedy, observe)
