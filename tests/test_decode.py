"""Autoregressive decode plane (ISSUE 16): KV-cache generation.

The contracts under test, from strongest to weakest:

  * BIT-exact: a row's decode logits are identical whether its batch
    neighbours exist or not (join/leave isolation), and a reused KV
    block produces bit-identical logits to a fresh allocation — both
    fall out of exact-zero masked softmax weights plus row-independent
    compiled steps.
  * Greedy-exact: prefill + N decode ticks produce the IDENTICAL token
    sequence as running the full forward from scratch each step (the
    argmax survives the reduction-grouping noise), across evictions,
    re-prefills and hot-swaps.
  * allclose: the decode-path logits match the full-sequence forward to
    f32 tolerance (reduction trees differ with padding).

Plus the serving integration: one XLA compile per (model, phase,
bucket) for the server's lifetime including same-architecture swaps,
decode/fwd executable-cache keys that never collide, the FlushEma
bucket-extrapolation fix, continuous batching under KV pressure, the
/generate HTTP endpoint, and the decode IR probes (clean + seeded
donation mutation).
"""
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax.numpy as jnp

from deeplearning4j_tpu import (Adam, EmbeddingSequenceLayer, InputType,
                                MultiLayerNetwork, NeuralNetConfiguration,
                                RnnOutputLayer, TransformerBlock)
from deeplearning4j_tpu.kernels.attention import attention_reference
from deeplearning4j_tpu.serving.batcher import FlushEma
from deeplearning4j_tpu.serving.decode import (DecodeEngine,
                                               GenerationError,
                                               GenerationScheduler,
                                               OutOfBlocksError)
from deeplearning4j_tpu.serving.registry import ModelRegistry, ServingError

pytestmark = pytest.mark.sanitize(
    allow_threads=("dl4j-decode-sched-", "dl4j-serving-http"))

VOCAB, WIDTH, TMAX = 32, 16, 32


def lm(seed=0, vocab=VOCAB, width=WIDTH, t=TMAX, blocks=2, heads=4):
    b = (NeuralNetConfiguration.builder().seed(seed).updater(Adam(1e-3))
         .list().layer(EmbeddingSequenceLayer(n_in=vocab, n_out=width)))
    for _ in range(blocks):
        b = b.layer(TransformerBlock(n_heads=heads))
    conf = (b.layer(RnnOutputLayer(n_out=vocab, activation="softmax",
                                   loss="mcxent"))
            .set_input_type(InputType.recurrent(1, t)).build())
    return MultiLayerNetwork(conf).init()


def eager_logits(model, ctx):
    """Full-sequence forward, eager (no jit, no padding): next-token
    logits after `ctx` — the decode plane's ground truth."""
    x = jnp.asarray(ctx, jnp.int32)[None, :, None]
    h, _, _, _ = model._forward(model.params, model.state, x, False, None,
                                upto=len(model.layers) - 1)
    return np.asarray(
        model.layers[-1].preout(model.params[-1], {}, h)[0, -1],
        np.float32)


def eager_greedy(model, prompt, n):
    ctx = list(prompt)
    for _ in range(n):
        ctx.append(int(np.argmax(eager_logits(model, ctx))))
    return ctx[len(prompt):]


@pytest.fixture(scope="module")
def served():
    """Module-shared registry + greedy continuous scheduler over a tiny
    2-block LM — decode/prefill compiles amortized across tests."""
    reg = ModelRegistry()
    model = lm(seed=3)
    reg.register("gen", model, buckets=(1,))
    sched = GenerationScheduler(reg, "gen", block_len=4,
                                decode_buckets=(1, 2, 4))
    yield reg, model, sched
    sched.stop()


@pytest.fixture
def span_log():
    """A fresh process-wide span log for one test."""
    from deeplearning4j_tpu.telemetry import Tracer, install_tracer

    log = Tracer()
    prev = install_tracer(log)
    yield log
    install_tracer(prev)


# ---------------------------------------------------------------------------
# kernels: explicit per-row valid length
# ---------------------------------------------------------------------------

def test_attention_kv_length_matches_sliced_full():
    """`kv_length` masking == running full attention over only the
    valid prefix, per row (the gather's trash-slot reads must be exact
    no-ops)."""
    r = np.random.default_rng(0)
    B, T, D = 3, 8, 4
    q = jnp.asarray(r.normal(size=(B, 1, D)).astype(np.float32))
    k = jnp.asarray(r.normal(size=(B, T, D)).astype(np.float32))
    v = jnp.asarray(r.normal(size=(B, T, D)).astype(np.float32))
    lengths = [3, 8, 5]
    out = attention_reference(
        q, k, v, causal=True,
        q_positions=jnp.asarray([[n - 1] for n in lengths], jnp.int32),
        kv_length=jnp.asarray(lengths, jnp.int32))
    for b, n in enumerate(lengths):
        ref = attention_reference(q[b:b + 1], k[b:b + 1, :n],
                                  v[b:b + 1, :n])
        np.testing.assert_array_equal(np.asarray(out[b]),
                                      np.asarray(ref[0]))


def test_attention_kv_length_garbage_slots_inert():
    """Slots past kv_length may hold ANY finite garbage without
    changing a single output bit (the decode plane's trash block)."""
    r = np.random.default_rng(1)
    q = jnp.asarray(r.normal(size=(2, 1, 4)).astype(np.float32))
    k = jnp.asarray(r.normal(size=(2, 6, 4)).astype(np.float32))
    v = jnp.asarray(r.normal(size=(2, 6, 4)).astype(np.float32))
    kw = dict(causal=True,
              q_positions=jnp.asarray([[3], [2]], jnp.int32),
              kv_length=jnp.asarray([4, 3], jnp.int32))
    a = attention_reference(q, k, v, **kw)
    k2 = k.at[:, 4:].set(1e9)
    v2 = v.at[:, 4:].set(-1e9)
    b = attention_reference(q, k2, v2, **kw)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# tentpole: prefill + ticks vs full-sequence forward
# ---------------------------------------------------------------------------

def test_engine_prefill_and_ticks_match_full_forward(served):
    """Per-step logits allclose + greedy argmax identical: the KV-cache
    path IS the full forward, incrementally."""
    reg, model, sched = served
    eng, v = sched.engine, reg.get("gen")
    pool = eng.new_pool()
    prompt = [5, 11, 2, 29, 7]
    blocks = pool.alloc(eng.spec.blocks_for(len(prompt)))
    logits = eng.run_prefill(v, pool, prompt, blocks)
    ctx = list(prompt)
    for step in range(10):
        ref = eager_logits(model, ctx)
        np.testing.assert_allclose(logits, ref, rtol=2e-5, atol=1e-6)
        assert int(np.argmax(logits)) == int(np.argmax(ref)), \
            f"greedy diverged at step {step}"
        tok = int(np.argmax(logits))
        ctx.append(tok)
        pos = len(ctx) - 1
        need = eng.spec.blocks_for(pos + 1) - len(blocks)
        if need:
            blocks += pool.alloc(need)
        logits = eng.run_tick(v, pool, [tok], [pos], [blocks], bucket=1)[0]
    pool.release(blocks)


def test_scheduler_greedy_identical_to_full_forward(served):
    reg, model, sched = served
    prompt = [3, 7, 1, 4, 9, 2]
    res = sched.submit(prompt, max_tokens=10, timeout=300)
    assert res["tokens"] == eager_greedy(model, prompt, 10)
    assert res["finish_reason"] == "length"
    assert res["generated_tokens"] == 10
    # deterministic: resubmitting replays the identical sequence
    assert sched.submit(prompt, max_tokens=10,
                        timeout=300)["tokens"] == res["tokens"]


def test_scheduler_concurrent_clients_all_greedy_exact(served):
    """Token-granularity joins/leaves while neighbours are mid-flight:
    every client still gets its exact single-sequence greedy answer."""
    reg, model, sched = served
    prompts = [[1 + i, 8, 2 * i + 1, 5] for i in range(6)]
    want = [eager_greedy(model, p, 6 + i % 3)
            for i, p in enumerate(prompts)]
    got = [None] * len(prompts)

    def client(i):
        got[i] = sched.submit(prompts[i], max_tokens=6 + i % 3,
                              timeout=300)["tokens"]

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert got == want


def _ticks(log):
    """The attributes of the log's `dl4j/sched/tick` spans, in order."""
    return [e["attrs"] for e in log.snapshot()
            if e["ph"] == "X" and e["name"] == "dl4j/sched/tick"]


def test_stop_token_and_context_cap(served, span_log):
    reg, model, sched = served
    prompt = [3, 7, 1, 4, 9, 2]
    full = eager_greedy(model, prompt, 10)
    stop = full[3]
    res = sched.submit(prompt, max_tokens=10, stop=[stop], timeout=300)
    assert res["finish_reason"] == "stop"
    # cut at the stop token's FIRST occurrence (greedy chains repeat)
    assert res["tokens"] == full[:full.index(stop)]
    # the stop id is met one tick late: the sequence's row of the tick that
    # was already in flight is computed and discarded, and nothing of it
    # is left held once that tick has been retired
    deadline = time.monotonic() + 60
    while sum(t["wasted_rows"] for t in _ticks(span_log)) < 1:
        assert time.monotonic() < deadline
        time.sleep(0.001)
    ticks = _ticks(span_log)
    assert [t["wasted_rows"] for t in ticks] == [0] * (len(ticks) - 1) + [1]
    assert len(ticks) == len(res["tokens"]) + 1
    assert sched.pool.used_blocks() == sched.pool.used_slots() == 0
    res = sched.submit(prompt, max_tokens=10_000, timeout=300)
    assert res["finish_reason"] == "context"
    assert len(prompt) + res["generated_tokens"] == TMAX
    with pytest.raises(GenerationError):
        sched.submit(list(range(TMAX)), timeout=300)


def test_temperature_sampling_seeded(served):
    reg, model, sched = served
    kw = dict(max_tokens=8, temperature=0.9, seed=42, timeout=300)
    a = sched.submit([4, 9, 1], **kw)
    b = sched.submit([4, 9, 1], **kw)
    assert a["tokens"] == b["tokens"]
    assert all(0 <= t < VOCAB for t in a["tokens"])


@pytest.mark.parametrize("rows", [1, 2])
def test_greedy_tick_returns_the_argmax_of_the_logits_tick(served, rows):
    """`run_tick(greedy=True)` hands back the rows' argmax, taken on the
    device by the tick's own executable: the same tokens as np.argmax
    over the logits of the same tick (exact: the comparison of float32
    values rounds nothing)."""
    reg, model, sched = served
    eng, v = sched.engine, reg.get("gen")
    prompts = [[5, 11, 2, 29, 7], [1, 2, 3]][:rows]

    def run(greedy):
        pool = eng.new_pool()
        tables = [pool.alloc(eng.spec.blocks_for(len(p) + 1))
                  for p in prompts]
        first = [int(np.argmax(eng.run_prefill(v, pool, p, t)))
                 for p, t in zip(prompts, tables)]
        return eng.run_tick(v, pool, first, [len(p) for p in prompts],
                            tables, bucket=2, greedy=greedy)

    logits, tokens = run(False), run(True)
    assert logits.shape == (rows, VOCAB)
    assert tokens.shape == (rows,) and tokens.dtype == np.int32
    np.testing.assert_array_equal(tokens, logits.argmax(axis=1))


def test_greedy_row_beside_a_sampled_row_stays_exact(served, span_log):
    """A tick with one row at a temperature goes by the logits for every
    row: the greedy neighbour still gets its single-sequence answer."""
    reg, model, sched = served
    prompt, got = [3, 7, 1, 4, 9, 2], {}

    def client(name, **kw):
        got[name] = sched.submit(prompt, max_tokens=8, timeout=300, **kw)

    threads = [threading.Thread(target=client, args=("greedy",)),
               threading.Thread(target=client, args=("sampled",),
                                kwargs=dict(temperature=0.9, seed=3))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert got["greedy"]["tokens"] == eager_greedy(model, prompt, 8)
    # the sampled row's answer is its own, whoever stood beside it
    assert got["sampled"]["tokens"] == sched.submit(
        prompt, max_tokens=8, timeout=300, temperature=0.9, seed=3)["tokens"]
    # and while it ran no tick was started ahead of its predecessor: its
    # next token does not exist before the host has sampled it
    both = [t for t in _ticks(span_log) if t["rows"] == 2]
    assert all(t["overlapped"] == 0 and t["device_ids"] == 0 for t in both)


# ---------------------------------------------------------------------------
# bit-exactness: isolation + block reuse
# ---------------------------------------------------------------------------

def test_join_leave_neighbour_isolation_bitexact(served):
    """A row's tick logits are bit-identical with and without a batch
    neighbour (same bucket, so the compiled step is the same)."""
    reg, model, sched = served
    eng, v = sched.engine, reg.get("gen")
    pa, pb = [5, 11, 2, 29, 7], [1, 2, 3]

    def run(with_neighbour):
        pool = eng.new_pool()
        ba = pool.alloc(eng.spec.blocks_for(len(pa) + 1))
        la = eng.run_prefill(v, pool, pa, ba)
        rows = [(int(np.argmax(la)), len(pa), ba)]
        if with_neighbour:
            bb = pool.alloc(eng.spec.blocks_for(len(pb) + 1))
            lb = eng.run_prefill(v, pool, pb, bb)
            rows.append((int(np.argmax(lb)), len(pb), bb))
        out = eng.run_tick(v, pool, [r[0] for r in rows],
                           [r[1] for r in rows], [r[2] for r in rows],
                           bucket=2)
        return la, out[0]

    la2, tick2 = run(True)
    la1, tick1 = run(False)
    np.testing.assert_array_equal(la1, la2)       # prefill: same blocks
    np.testing.assert_array_equal(tick1, tick2)   # tick: neighbour inert


def test_kv_block_reuse_after_release_bitexact(served):
    """Blocks freed by one sequence and recycled by another behave
    bit-identically to a fresh allocation — logits AND the arena slots
    actually covered by the new sequence."""
    reg, model, sched = served
    eng, v = sched.engine, reg.get("gen")
    pa, pb = [9, 9, 9, 9, 9, 9, 9], [4, 1, 6, 2, 8]

    def gen3(pool, blocks):
        out = [eng.run_prefill(v, pool, pb, blocks)]
        ctx = list(pb)
        for _ in range(3):
            tok = int(np.argmax(out[-1]))
            ctx.append(tok)
            need = eng.spec.blocks_for(len(ctx)) - len(blocks)
            if need:
                blocks += pool.alloc(need)
            out.append(eng.run_tick(v, pool, [tok], [len(ctx) - 1],
                                    [blocks], bucket=1)[0])
        return blocks, out

    pool1 = eng.new_pool()
    stale = pool1.alloc(eng.spec.blocks_for(len(pa)))
    eng.run_prefill(v, pool1, pa, stale)          # dirty the blocks
    pool1.release(stale)
    reused = pool1.alloc(eng.spec.blocks_for(len(pb)))
    assert set(reused) <= set(stale)              # LIFO recycles them
    reused, out1 = gen3(pool1, reused)

    pool2 = eng.new_pool()
    fresh = pool2.alloc(eng.spec.blocks_for(len(pb)))
    fresh, out2 = gen3(pool2, fresh)
    for a, b in zip(out1, out2):
        np.testing.assert_array_equal(a, b)
    kv1 = np.asarray(pool1.cache["kv"])[:, reused]    # [2L, blocks, bl, H*Dh]
    kv2 = np.asarray(pool2.cache["kv"])[:, fresh]
    assert kv1.shape[:2] == (eng.spec.channels, len(reused))
    assert np.abs(kv1).max() > 0
    np.testing.assert_array_equal(kv1, kv2)


@pytest.mark.parametrize("heads,d_head", [(4, 8), (2, 64)],
                         ids=["HDh32", "HDh128"])
@pytest.mark.parametrize("kv_dtype", ["fp32", "int8"])
def test_cache_scatter_gather_round_trip(kv_dtype, heads, d_head):
    """What `CacheIO.scatter` writes through a block table, `gather` reads back
    through it — heads merged on the way in and split on the way out —
    and nothing else in the arena moves: other channels and unowned
    blocks stay zero, and every dead table slot (a prompt's overflow, a
    pad row) lands in and reads from the trash block."""
    from deeplearning4j_tpu.serving.decode.cache import (CacheIO,
                                                         KvCacheSpec,
                                                         make_cache,
                                                         pack_kv, unpack_kv)

    spec = KvCacheSpec(channels=4, width=heads * d_head, block_len=4,
                       num_blocks=6, max_context=12, kv_dtype=kv_dtype)
    io = CacheIO(spec)
    cache = make_cache(spec)
    assert cache["kv"].shape == (4, 6, 4, heads * d_head)
    # row 0 owns blocks 3 and 5 (8 slots), its third table slot is dead;
    # row 1 is a pad row: every slot dead
    tables = jnp.asarray([[3, 5, 0], [0, 0, 0]], jnp.int32)
    tidx = jnp.arange(12)
    blk = tables[:, tidx // spec.block_len]
    off = jnp.broadcast_to(tidx % spec.block_len, (2, 12))
    x = jnp.asarray(np.random.default_rng(0).normal(
        size=(2, 12, heads, d_head)), jnp.float32)
    kv, sc = io.scatter(cache["kv"], cache.get("scale"), x, blk, off, 1)
    view = np.asarray(io.gather(kv, sc, tables, 1)).reshape(
        2, 12, heads, d_head)
    assert view.shape == (2, 12, heads, d_head)

    q, scale = pack_kv(spec, x.reshape(2, 12, -1))
    want = np.asarray(unpack_kv(spec, q, scale)).reshape(x.shape)
    np.testing.assert_array_equal(view[0, :8], want[0, :8])
    if kv_dtype == "int8":
        assert kv.dtype == jnp.int8 and sc.shape == (4, 6, 4)
        step = np.abs(np.asarray(x[0, :8])).max(axis=(-2, -1)) / 127.0
        assert np.all(np.abs(view[0, :8] - np.asarray(x[0, :8]))
                      <= 0.5 * step[:, None, None] + 1e-6)
    else:
        np.testing.assert_array_equal(want, np.asarray(x))
    # the dead slots all read the one trash block, which holds one of the
    # vectors that were sent there
    trash = view[1, :4]
    for dead in (view[0, 8:], view[1, 4:8], view[1, 8:]):
        np.testing.assert_array_equal(dead, trash)
    sent = np.concatenate([want[0, 8:], want[1]])
    for slot in range(4):
        assert any(np.array_equal(trash[slot], v) for v in sent[slot::4])
    arena = np.asarray(kv)
    assert not arena[[0, 2, 3]].any()             # other channels
    assert not arena[1][[1, 2, 4]].any()          # unowned blocks
    assert arena[1][[3, 5]].any()


def test_eviction_resume_greedy_exact_and_counted():
    """Under KV-block pressure the scheduler preempts sequences (blocks
    freed, ctx re-prefilled on re-admission) — every client still gets
    the exact greedy answer and the eviction counter moved."""
    from deeplearning4j_tpu.telemetry.registry import MetricsRegistry

    reg = ModelRegistry()
    model = lm(seed=5)
    reg.register("gen", model, buckets=(1,))
    metrics = MetricsRegistry()
    # 7 usable blocks of 4 slots: three 16-token sequences cannot all
    # be resident -> continuous batching must juggle via eviction
    sched = GenerationScheduler(reg, "gen", block_len=4, num_blocks=8,
                                decode_buckets=(1, 2, 4),
                                metrics=metrics)
    try:
        prompts = [[1, 2, 3, 4], [5, 6, 7, 8], [9, 10, 11, 12]]
        want = [eager_greedy(model, p, 12) for p in prompts]
        got = [None] * 3

        def client(i):
            got[i] = sched.submit(prompts[i], max_tokens=12,
                                  timeout=300)["tokens"]

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert got == want
        evicted = metrics.counter(
            "dl4j_decode_evictions_total", "",
            labels=("model",)).value(model="gen")
        assert evicted >= 1, "pressure never forced an eviction"
        assert sched.pool.used_blocks() == 0
        # the rows were greedy, so the evictions met ticks in flight
        ticks = metrics.counter("dl4j_decode_ticks_total", "",
                                labels=("model", "mode"))
        assert ticks.value(model="gen", mode="overlapped") >= 1
        assert sum(ticks.values().values()) == metrics.get(
            "dl4j_decode_phase_seconds").count(model="gen", phase="decode")
    finally:
        sched.stop()


def test_single_sequence_larger_than_pool_fails_cleanly():
    reg = ModelRegistry()
    reg.register("gen", lm(seed=5), buckets=(1,))
    sched = GenerationScheduler(reg, "gen", block_len=4, num_blocks=3,
                                decode_buckets=(1,))
    try:
        with pytest.raises((GenerationError, OutOfBlocksError)):
            sched.submit([1, 2, 3], max_tokens=20, timeout=300)
        assert sched.pool.used_blocks() == 0
    finally:
        sched.stop()


@pytest.mark.parametrize("phase", ["prefill", "tick"])
def test_identical_blocks_are_traced_and_lowered_once(phase):
    """A stack's equal blocks share one traced step (set-up pays the
    tracing of a block once an executable, not once a block); a block
    that differs gets its own."""
    import re

    import jax

    from deeplearning4j_tpu.serving.decode.cache import KvCacheSpec
    from deeplearning4j_tpu.serving.decode.engine import (_cache_arg_specs,
                                                          build_decode_fn,
                                                          build_prefill_fn)
    from deeplearning4j_tpu.serving.registry import _snapshot_params

    b = (NeuralNetConfiguration.builder().seed(0).updater(Adam(1e-3)).list()
         .layer(EmbeddingSequenceLayer(n_in=VOCAB, n_out=WIDTH)))
    for mult in (4, 4, 2, 4):
        b = b.layer(TransformerBlock(n_heads=4, ffn_mult=mult))
    conf = (b.layer(RnnOutputLayer(n_out=VOCAB, activation="softmax",
                                   loss="mcxent"))
            .set_input_type(InputType.recurrent(1, TMAX)).build())
    model = MultiLayerNetwork(conf).init()
    snapshot = _snapshot_params(model, "fp32")
    spec = KvCacheSpec(channels=8, width=WIDTH, block_len=4,
                       num_blocks=9, max_context=TMAX)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    w = spec.table_width
    if phase == "tick":
        fn, args = build_decode_fn, (i32(2), i32(2), i32(2, w))
    else:
        fn, args = build_prefill_fn, (i32(1, 8), i32(1), i32(1, w))
    text = jax.jit(fn(model, snapshot, spec), donate_argnums=(1,)).lower(
        snapshot.data, _cache_arg_specs(spec), *args).as_text()
    steps = re.findall(r"func\.func private @(step\w*)\(", text)
    calls = re.findall(r"call @(step\w*)\(", text)
    assert len(steps) == 2 and len(calls) == 4
    assert sorted(calls.count(s) for s in steps) == [1, 3]


# ---------------------------------------------------------------------------
# int8 KV cache
# ---------------------------------------------------------------------------

def test_int8_kv_cache_generates():
    reg = ModelRegistry()
    model = lm(seed=6)
    reg.register("gen", model, buckets=(1,))
    sched = GenerationScheduler(reg, "gen", block_len=4, kv_dtype="int8",
                                decode_buckets=(1, 2))
    try:
        spec = sched.engine.spec
        kv, scale = sched.pool.cache["kv"], sched.pool.cache["scale"]
        assert kv.dtype == jnp.int8
        assert kv.shape == (spec.channels, spec.num_blocks,
                            spec.block_len, spec.width)
        assert scale.shape == kv.shape[:3]
        res = sched.submit([3, 7, 1, 4], max_tokens=6, timeout=300)
        assert res["generated_tokens"] == 6
        # prefill attends over the LOCAL (unquantized) projections, so
        # the FIRST sampled token is exact even with an int8 cache
        assert res["tokens"][0] == eager_greedy(model, [3, 7, 1, 4], 1)[0]
        # 4 + 6 cache slots were written, in blocks the pool handed out:
        # the block axis is the arena's second, and the trash block is 0
        written = np.flatnonzero(
            np.asarray(sched.pool.cache["kv"]).any(axis=(0, 2, 3)))
        assert len(set(written) - {0}) == spec.blocks_for(4 + 6 - 1)
    finally:
        sched.stop()


# ---------------------------------------------------------------------------
# compile accounting + executable-cache keys
# ---------------------------------------------------------------------------

def test_swap_and_generate_one_compile_per_signature():
    """Server-lifetime compile budget: decode + prefill executables
    compile ONCE per (phase, bucket) even across a same-architecture
    hot-swap, and generation picks up the new weights (running
    sequences re-prefill)."""
    from deeplearning4j_tpu import telemetry
    from deeplearning4j_tpu.util.serializer import ModelSerializer

    m1, m2 = lm(seed=7), lm(seed=8)
    with telemetry.enabled() as sess:
        reg = ModelRegistry(metrics=sess.registry)
        reg.register("gen", m1, buckets=(1,))
        sched = GenerationScheduler(reg, "gen", block_len=4,
                                    decode_buckets=(1, 2))
        try:
            prompt = [3, 7, 1, 4]
            assert sched.submit(prompt, max_tokens=5, timeout=300)[
                "tokens"] == eager_greedy(m1, prompt, 5)
            import tempfile
            with tempfile.TemporaryDirectory() as d:
                ModelSerializer.write_model(m2, f"{d}/m2.zip")
                reg.swap("gen", f"{d}/m2.zip")
            assert sched.submit(prompt, max_tokens=5, timeout=300)[
                "tokens"] == eager_greedy(reg.get("gen").model, prompt, 5)
        finally:
            sched.stop()
        decode_compiles = {
            k: v["count"] for k, v in sess.compiles.report().items()
            if k.startswith("serving/gen:b")
            and ("decode" in k or "prefill" in k)}
        assert decode_compiles, "decode compiles never recorded"
        assert all(c == 1 for c in decode_compiles.values()), \
            decode_compiles


def test_registry_decode_and_fwd_cache_keys_disjoint():
    """Regression (satellite f): the decode plane's executables live
    under ("decode", sig, phase, bucket) keys and the stateless plane's
    under ("fwd", sig, bucket) — enabling generation on a servable must
    not evict its forward runners, nor vice versa."""
    reg = ModelRegistry()
    model = lm(seed=9)
    reg.register("gen", model, buckets=(1,))
    entry = reg._entries["gen"]
    fwd_keys = {k for k in entry.compiled if k[0] == "fwd"}
    assert fwd_keys, "stateless runners missing"
    eng = DecodeEngine(reg, "gen", block_len=4, decode_buckets=(1,))
    v = reg.get("gen")
    eng.prefill_exec(v, 8)
    eng.decode_exec(v, 1)
    keys = set(entry.compiled)
    assert fwd_keys <= keys, "decode compilation evicted fwd runners"
    decode_keys = {k for k in keys if k[0] == "decode"}
    assert {k[2:] for k in decode_keys} == {("prefill", 8), ("tick", 1)}
    # stateless twin under another name: its own per-model cache holds
    # only fwd keys — the planes can never evict each other
    reg.register("twin", lm(seed=9), buckets=(1,))
    assert all(k[0] == "fwd" for k in reg._entries["twin"].compiled)


# ---------------------------------------------------------------------------
# a version is resolved once (ISSUE 35)
# ---------------------------------------------------------------------------

@pytest.fixture
def sig_calls(monkeypatch):
    """`_abstract_sig` counted, under both names it is called by."""
    from deeplearning4j_tpu.serving import registry as registry_mod
    from deeplearning4j_tpu.serving.decode import engine as engine_mod

    calls, real = [], registry_mod._abstract_sig

    def counting(snapshot, state, precision):
        calls.append(len(snapshot.data))
        return real(snapshot, state, precision)

    monkeypatch.setattr(registry_mod, "_abstract_sig", counting)
    monkeypatch.setattr(engine_mod, "_abstract_sig", counting)
    return calls


def _version_instants(log):
    return [e["attrs"] for e in log.snapshot()
            if e["ph"] == "i" and e["name"] == "dl4j/engine/version"]


def _drive(eng, v, prompts=([5, 11, 2], [7, 3], [9, 1, 4, 6]), ticks=5):
    """Three prefills and `ticks` ticks of all three rows on a fresh
    arena, greedy on the host: the ticks' logits [ticks, 3, V]."""
    pool = eng.new_pool()
    tables = [pool.alloc(eng.spec.blocks_for(len(p) + ticks))
              for p in prompts]
    last = [int(np.argmax(eng.run_prefill(v, pool, p, t)))
            for p, t in zip(prompts, tables)]
    out = []
    for i in range(ticks):
        out.append(eng.run_tick(v, pool, last, [len(p) + i for p in prompts],
                                tables, bucket=4))
        last = [int(t) for t in np.argmax(out[-1], axis=-1)]
    return np.stack(out)


@pytest.mark.parametrize("stated", [True, False],
                         ids=["the-version-states-it", "a-stand-in-without"])
def test_engine_walks_the_leaves_at_most_once_a_version(
        sig_calls, span_log, stated):
    """Five ticks and three prefills on one version: the signature that
    keys their executables is the version's own, so the engine never
    computes one; a version object built by hand, with no `sig`, is
    served all the same, and walked once."""
    import types

    reg = ModelRegistry()
    reg.register("gen", lm(seed=20), buckets=(1,))
    real = reg.get("gen")
    assert real.sig is not None and sig_calls    # the registry's own walk
    v = real if stated else types.SimpleNamespace(
        snapshot=real.snapshot, state=real.state, precision=real.precision,
        model=real.model)
    eng = DecodeEngine(reg, "gen", block_len=4, decode_buckets=(4,))
    del sig_calls[:]
    got = _drive(eng, v)
    assert len(sig_calls) == (0 if stated else 1)
    (record,) = _version_instants(span_log)
    assert record["model"] == "gen"
    assert record["leaves"] == len(real.snapshot.data) == 4 + 2 * 16
    assert record["computed"] == (0 if stated else 1)
    assert ("ms" in record) == (not stated)
    assert record["version"] == (1 if stated else None)
    if not stated:      # the same executables, the same weights
        assert eng._sig == real.sig
        np.testing.assert_array_equal(got, _drive(eng, real))
        assert len(sig_calls) == 1 and len(_version_instants(span_log)) == 2


def test_a_swap_of_the_same_architecture_compiles_nothing_and_runs_its_weights(
        span_log):
    """New weights under the old signature: the executables are reused
    with no compile, the engine meets the version once more, and what
    comes back is the NEW weights' (they are call arguments)."""
    from deeplearning4j_tpu import telemetry

    new = lm(seed=22)
    with telemetry.enabled() as sess:
        reg = ModelRegistry(metrics=sess.registry)
        reg.register("gen", lm(seed=21), buckets=(1,))
        eng = DecodeEngine(reg, "gen", block_len=4, decode_buckets=(4,))
        before = _drive(eng, reg.get("gen"))
        compiles = reg.metrics.counter("dl4j_serving_compiles_total",
                                       labels=("model", "bucket"))
        n_compiles = sum(compiles.values().values())
        aot = dict(sess.compiles.report())
        marks = len(span_log.snapshot())
        v2 = reg.swap("gen", new, buckets=(1,))
        after = _drive(eng, v2)
        assert sum(compiles.values().values()) == n_compiles
        assert dict(sess.compiles.report()) == aot
        assert not [e for e in span_log.snapshot()[marks:]
                    if e["name"] in ("xla/compile", "dl4j/engine/executable")]
    assert v2.sig == eng._sig and v2.version == 2
    assert [(r["version"], r["computed"])
            for r in _version_instants(span_log)] == [(1, 0), (2, 0)]
    assert np.abs(after - before).max() > 1e-3
    fresh = ModelRegistry()
    fresh.register("gen", new, buckets=(1,))
    np.testing.assert_array_equal(after, _drive(
        DecodeEngine(fresh, "gen", block_len=4, decode_buckets=(4,)),
        fresh.get("gen")))


@pytest.mark.parametrize("other,why", [
    (dict(width=32), "cache geometry"),
    (dict(heads=2), "layers its executables"),
], ids=["another-geometry", "other-layer-options"])
def test_a_swap_the_executables_do_not_fit_fails_every_call(other, why):
    """A version of another geometry, or of the same shapes under other
    layer options (two heads where there were four: the same signature),
    is refused by prefills, ticks and lookups alike, each time it is
    offered; the version the engine knows is served on."""
    reg = ModelRegistry()
    reg.register("gen", lm(seed=23), buckets=(1,))
    eng = DecodeEngine(reg, "gen", block_len=4, decode_buckets=(4,))
    v1 = reg.get("gen")
    before = _drive(eng, v1)
    v2 = reg.swap("gen", lm(seed=23, **other), buckets=(1,))
    assert (v2.sig == v1.sig) == ("heads" in other)
    pool = eng.new_pool()
    table = pool.alloc(2)
    for call in (lambda: eng.run_prefill(v2, pool, [1, 2, 3], table),
                 lambda: eng.run_tick(v2, pool, [4], [3], [table], bucket=4),
                 lambda: eng.prefill_exec(v2, 8),
                 lambda: eng.decode_exec(v2, 4)):
        with pytest.raises(ServingError, match=why):
            call()
    np.testing.assert_array_equal(before, _drive(eng, v1))


def test_flush_ema_bucket_extrapolation():
    """Regression (satellite f): estimating an UNSAMPLED bucket must
    scale from the nearest LARGER sampled bucket (floored by smaller
    ones), not the nearest-by-distance — with {1: 0.1ms, 32: 10ms}
    sampled, bucket 8's estimate comes from 32, not from 1."""
    ema = FlushEma()
    ema.observe(1, 1e-4)
    ema.observe(32, 1e-2)
    est = ema.estimate(8)
    assert est == pytest.approx(1e-2 * 8 / 32)     # from bucket 32
    assert est > 1e-4                              # monotone floor
    # above the largest sample: linear extrapolation from it
    assert ema.estimate(64) == pytest.approx(1e-2 * 64 / 32)
    # sampled buckets return their own EMA untouched
    assert ema.estimate(32) == pytest.approx(1e-2)
    # flush choice: at avail=5 with a fast full bucket 4 vs padding to
    # 8, rows/s decides
    ema2 = FlushEma()
    ema2.observe(4, 1e-3)
    ema2.observe(8, 1e-2)       # padding up is 10x worse
    assert ema2.pick_rows(5, [1, 2, 4, 8], 8) == 4
    ema3 = FlushEma()
    ema3.observe(4, 1e-3)
    ema3.observe(8, 1.1e-3)     # padding up is nearly free
    assert ema3.pick_rows(5, [1, 2, 4, 8], 8) == 5


# ---------------------------------------------------------------------------
# one tick in flight (ISSUE 37)
# ---------------------------------------------------------------------------

@pytest.fixture
def counted():
    """(registry, model, scheduler, metrics) of a scheduler of its own,
    with the `/metrics` families."""
    from deeplearning4j_tpu.telemetry.registry import MetricsRegistry

    reg = ModelRegistry()
    model = lm(seed=13)
    reg.register("gen", model, buckets=(1,))
    metrics = MetricsRegistry()
    sched = GenerationScheduler(reg, "gen", block_len=4,
                                decode_buckets=(1, 2, 4), metrics=metrics)
    yield reg, model, sched, metrics
    sched.stop()


def _modes(metrics):
    ticks = metrics.counter("dl4j_decode_ticks_total", "",
                            labels=("model", "mode"))
    return {m: int(ticks.value(model="gen", mode=m))
            for m in ("overlapped", "serial")}


@pytest.mark.parametrize("temperature,mode", [(0.0, "overlapped"),
                                              (0.8, "serial")])
def test_ticks_by_mode_add_up_to_the_ticks_run(counted, span_log,
                                               temperature, mode):
    """A greedy sequence's ticks are each started from the ids of the one
    before, which never visit the host, but for the first; a sequence at a
    temperature has every tick retired before the next is composed. The
    spans' `overlapped`, the `/metrics` counter and the phase histogram
    count the same ticks."""
    reg, model, sched, metrics = counted
    res = sched.submit([3, 7, 1, 4], max_tokens=9, temperature=temperature,
                       seed=5, timeout=300)
    assert res["generated_tokens"] == 9
    if temperature == 0.0:
        assert res["tokens"] == eager_greedy(model, [3, 7, 1, 4], 9)
    ticks = _ticks(span_log)
    assert [t["tick"] for t in ticks] == list(range(1, 9))
    late = 1 if mode == "overlapped" else 0
    assert [t["overlapped"] for t in ticks] == [0] + [late] * 7
    assert [t["device_ids"] for t in ticks] == [0] + [late] * 7
    assert sum(t["wasted_rows"] for t in ticks) == 0
    modes = _modes(metrics)
    assert modes["overlapped"] == sum(t["overlapped"] for t in ticks)
    assert sum(modes.values()) == 8 == metrics.get(
        "dl4j_decode_phase_seconds").count(model="gen", phase="decode")
    assert sched.pool.used_blocks() == 0


def test_a_swap_with_a_tick_in_flight_restarts_on_the_new_weights(counted):
    """A version swapped in mid-sequence: the tick in flight is retired
    under the old weights, the sequence re-prefills under the new ones and
    goes on: its tokens are the old model's greedy tokens up to some point
    and the new model's continuation of exactly those from there."""
    reg, model, sched, metrics = counted
    new, prompt, got = lm(seed=14), [3, 7, 1, 4], {}
    t = threading.Thread(target=lambda: got.update(
        sched.submit(prompt, max_tokens=24, timeout=300)))
    t.start()
    deadline = time.monotonic() + 300
    while sum(_modes(metrics).values()) < 3:        # ticks are running
        assert time.monotonic() < deadline and t.is_alive()
        time.sleep(0.001)
    reg.swap("gen", new, buckets=(1,))
    t.join()
    tokens, old = got["tokens"], eager_greedy(model, prompt, 24)
    assert len(tokens) == 24
    k = next(i for i in range(25) if tokens[:i] != old[:i]) - 1
    assert 3 <= k < 24, "the swap never took hold"
    assert tokens[k:] == eager_greedy(new, prompt + tokens[:k], 24 - k)
    assert sched.pool.used_blocks() == 0


def test_a_tick_in_flight_that_fails_fails_its_rows_and_no_more(counted,
                                                                monkeypatch):
    """An error that the wait for a tick raises reaches the rows' callers,
    the tick queued behind it is dropped with it, and the scheduler serves
    the next request as if nothing had been."""
    reg, model, sched, metrics = counted
    real, calls = sched.engine.finish_tick, []

    def failing(started, *a, **kw):
        calls.append(started)
        if len(calls) == 3:
            raise RuntimeError("the device lost the tick")
        return real(started, *a, **kw)

    monkeypatch.setattr(sched.engine, "finish_tick", failing)
    with pytest.raises(RuntimeError, match="lost the tick"):
        sched.submit([3, 7, 1, 4], max_tokens=9, timeout=300)
    assert sched.pool.used_blocks() == 0
    assert sched.submit([3, 7, 1, 4], max_tokens=9, timeout=300)[
        "tokens"] == eager_greedy(model, [3, 7, 1, 4], 9)


def test_run_tick_and_run_prefill_are_the_schedulers_executables(
        counted, span_log):
    """The two calls other code drives the engine by keep their arguments
    and returns, and go through the executables the scheduler's ticks in
    flight go through: after a served request they compile nothing."""
    import inspect

    reg, model, sched, metrics = counted
    eng, v = sched.engine, reg.get("gen")
    assert list(inspect.signature(eng.run_prefill).parameters) == [
        "v", "pool", "prompt", "table", "observe"]
    assert list(inspect.signature(eng.run_tick).parameters) == [
        "v", "pool", "tokens", "positions", "tables", "bucket", "observe",
        "greedy"]
    prompt = [3, 7, 1, 4]
    want = sched.submit(prompt, max_tokens=3, timeout=300)["tokens"]
    built = lambda: [e["attrs"] for e in span_log.snapshot()
                     if e["name"] in ("dl4j/engine/executable", "xla/compile")]
    n_built = len(built())
    assert n_built >= 2
    pool = eng.new_pool()
    table = pool.alloc(eng.spec.blocks_for(len(prompt) + 2))
    logits = eng.run_prefill(v, pool, prompt, table)
    assert logits.shape == (VOCAB,) and logits.dtype == np.float32
    got = [int(np.argmax(logits))]
    logits = eng.run_tick(v, pool, got, [len(prompt)], [table], bucket=1)
    assert logits.shape == (1, VOCAB) and logits.dtype == np.float32
    got.append(int(np.argmax(logits[0])))
    ids = eng.run_tick(v, pool, [got[-1]], [len(prompt) + 1], [table],
                       bucket=1, greedy=True)
    assert ids.shape == (1,) and ids.dtype == np.int32
    assert got + [int(ids[0])] == want
    assert len(built()) == n_built
    assert eng.decode_exec(v, 1) is eng.decode_exec(v, 1)


def test_a_served_window_traces_and_compiles_nothing(counted, span_log):
    """The hot path meets no compile listener: with every bucket built and
    one request served (the benchmark's warm-up and ramp), concurrent
    requests of every prompt and batch size write no `xla/*` span. One
    that did would name the step that recompiled."""
    reg, model, sched, metrics = counted
    eng, v = sched.engine, reg.get("gen")
    for tb in eng.prompt_buckets:
        eng.prefill_exec(v, tb)
    for b in eng.decode_buckets:
        eng.decode_exec(v, b)
    sched.submit([3, 7, 1], max_tokens=3, timeout=300)
    marks = len(span_log.snapshot())
    prompts = [[1 + i % 5] * (1 + 3 * i) for i in range(6)]
    threads = [threading.Thread(target=sched.submit, args=(p,),
                                kwargs={"max_tokens": 2 + i, "timeout": 300})
               for i, p in enumerate(prompts)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(300)
    assert not any(t.is_alive() for t in threads)
    window = span_log.snapshot()[marks:]
    assert len(_ticks(span_log)) > 6
    assert [(e["name"], e["attrs"].get("fun"), e["parent"]) for e in window
            if e["name"].startswith("xla/")] == []


# ---------------------------------------------------------------------------
# HTTP endpoint
# ---------------------------------------------------------------------------

def _http(method, url, body=None, timeout=120):
    req = urllib.request.Request(
        url, None if body is None else json.dumps(body).encode(),
        {"Content-Type": "application/json"}, method=method)
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def test_generate_http_endpoint():
    from deeplearning4j_tpu.serving.server import InferenceServer

    model = lm(seed=11)
    srv = InferenceServer(batching=False).start()
    try:
        srv.registry.register("gen", model, buckets=(1,))
        srv.enable_generation("gen", block_len=4, decode_buckets=(1, 2))
        base = f"http://{srv.host}:{srv.port}"
        prompt = [3, 7, 1, 4]
        out = _http("POST", f"{base}/v1/models/gen/generate",
                    {"prompt": prompt, "max_tokens": 6})
        assert out["tokens"] == eager_greedy(model, prompt, 6)
        assert out["finish_reason"] == "length" and out["version"] == 1
        out2 = _http("POST", f"{base}/v1/models/gen/generate",
                     {"prompt": prompt, "max_tokens": 6})
        assert out2["tokens"] == out["tokens"]
        # generation metrics exported on /metrics
        req = urllib.request.Request(f"{base}/metrics")
        with urllib.request.urlopen(req, timeout=60) as resp:
            text = resp.read().decode()
        for family in ("dl4j_decode_tokens_total", "dl4j_decode_kv_blocks",
                       "dl4j_decode_admissions_total",
                       "dl4j_decode_phase_seconds"):
            assert family in text, f"{family} missing from /metrics"
        with pytest.raises(urllib.error.HTTPError) as ei:
            _http("POST", f"{base}/v1/models/gen/generate", {})
        assert ei.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as ei:
            _http("POST", f"{base}/v1/models/gen/generate",
                  {"prompt": prompt, "max_tokens": "lots of"})
        assert ei.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as ei:
            _http("POST", f"{base}/v1/models/nope/generate",
                  {"prompt": prompt})
        assert ei.value.code == 404
        # a non-generate-capable model -> 400 (ServingError), not 500
        from deeplearning4j_tpu import (DenseLayer, OutputLayer, Sgd)
        conf = (NeuralNetConfiguration.builder().seed(0).updater(Sgd(0.1))
                .list().layer(DenseLayer(n_out=8, activation="relu"))
                .layer(OutputLayer(n_out=4, loss="mcxent"))
                .set_input_type(InputType.feed_forward(6)).build())
        srv.registry.register("mlp", MultiLayerNetwork(conf).init(),
                              buckets=(1,))
        with pytest.raises(urllib.error.HTTPError) as ei:
            _http("POST", f"{base}/v1/models/mlp/generate",
                  {"prompt": prompt})
        assert ei.value.code == 400
    finally:
        srv.stop()


def test_scheduler_stop_rejects_new_submissions():
    reg = ModelRegistry()
    reg.register("gen", lm(seed=12), buckets=(1,))
    sched = GenerationScheduler(reg, "gen", block_len=4,
                                decode_buckets=(1,))
    res = sched.submit([1, 2, 3], max_tokens=2, timeout=300)
    assert res["generated_tokens"] == 2
    sched.stop()
    with pytest.raises(GenerationError):
        sched.submit([1, 2, 3], max_tokens=2)


def test_scheduler_takes_no_mode(served):
    """Continuous admission is the scheduler, not a mode of it: the
    constructor takes no `mode`, and a waiting sequence is admitted
    while another is still decoding. Read off the span log, whose order
    no clock decides: the newcomer's `dl4j/sched/admit` span is followed
    by ticks of the sequence that was already running."""
    from deeplearning4j_tpu.telemetry import Tracer, install_tracer

    reg, model, sched = served
    with pytest.raises(TypeError):
        GenerationScheduler(reg, "gen", mode="static")
    log = Tracer()
    prev = install_tracer(log)
    try:
        long_res = {}
        t = threading.Thread(target=lambda: long_res.update(
            sched.submit([5, 9], max_tokens=28, timeout=300)))
        t.start()
        deadline = time.monotonic() + 300
        while sched.pool.used_blocks() == 0:    # until it is admitted
            assert time.monotonic() < deadline and t.is_alive()
            time.sleep(0.001)
        # one token: sampled at the prefill, so it never joins a tick
        short = sched.submit([7, 3, 1], max_tokens=1, timeout=300)
        t.join()
        events = log.snapshot()
    finally:
        install_tracer(prev)
    assert short["generated_tokens"] == 1
    assert long_res["tokens"] == eager_greedy(model, [5, 9], 28)
    admits = [e for e in events if e["name"] == "dl4j/sched/admit"]
    ticks = [e for e in events if e["name"] == "dl4j/sched/tick"]
    assert [a["attrs"]["prompt_len"] for a in admits] == [2, 3]
    assert any(k["t0"] > admits[1]["t1"] for k in ticks)


def test_non_transformer_stack_rejected():
    from deeplearning4j_tpu import DenseLayer, OutputLayer, Sgd

    conf = (NeuralNetConfiguration.builder().seed(0).updater(Sgd(0.1))
            .list().layer(DenseLayer(n_out=8, activation="relu"))
            .layer(OutputLayer(n_out=4, loss="mcxent"))
            .set_input_type(InputType.feed_forward(6)).build())
    reg = ModelRegistry()
    reg.register("mlp", MultiLayerNetwork(conf).init(), buckets=(1,))
    with pytest.raises(ServingError):
        DecodeEngine(reg, "mlp")


# ---------------------------------------------------------------------------
# IR probes (satellite a)
# ---------------------------------------------------------------------------

def test_ir_decode_probes_clean():
    """Both decode-plane jit entries (prefill, tick) trace, lower and
    compile clean: the donated cache pytree aliases its output arena
    and a single-device step measures zero collective bytes."""
    from deeplearning4j_tpu.analysis import ir, ir_probes

    for entry in ir_probes.decode_entries():
        found = ir.analyze_entry(entry)
        assert not found, [f.render() for f in found]


def test_ir_decode_donated_tokens_caught():
    """Seeded mutation (acceptance): donating the int32 token ids —
    which can alias nothing in the f32 outputs — must trip
    ir-ineffective-donation on the decode tick entry."""
    from deeplearning4j_tpu.analysis import ir, ir_probes

    entry = ir_probes.decode_entry("tick", mutate="donate_tokens")
    found = ir.analyze_entry(entry)
    assert any(f.rule == "ir-ineffective-donation" for f in found), \
        [f.render() for f in found]
