"""Paged decode attention (ISSUE 33): the tick's kernel against its oracle.

The kernel (`kernels/paged_attention.py`) reads K/V pages from the arena
through the block table; the oracle is the decode plane's plain path, the
view `CacheIO.gather` makes through the same table and `TransformerBlock.
decode_attend` (`attention_reference` head by head) over it. Here the kernel
runs through the Pallas interpreter; `tests/test_flash_compile_tpu.py`
compiles it for a described v5e.

  * allclose to the oracle over ragged lengths, the boundaries of a page
    and of a grid step's chunk, a pad row, full context, one row and
    sixteen, 12 and 16 heads of 64, and a table whose dead slots name a
    block of NaN (a dead page must never be read), any pages a chunk;
  * BIT-exact: a row's result is the same alone and among fifteen others;
  * the engine picks the kernel only where it can run (`TransformerBlock.decode_attention`),
    says which path an executable took, and its tick through the kernel
    agrees with its tick through the view;
  * the differential kernels of the SambaY stack: over paged keys
    (`paged_diff_attention`) and over each row's own ring of window keys
    (`kernels/ring_attention.py`, against `diff_attend_rows` over the
    gathered rings: live edges, several chunks, NaN in every slot it must
    not read, scattered slots, a row alone and among 63).
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu import (Adam, EmbeddingSequenceLayer, InputType,
                                MultiLayerNetwork, NeuralNetConfiguration,
                                RnnOutputLayer, TransformerBlock, telemetry)
from deeplearning4j_tpu.kernels import paged_attention as paged_mod
from deeplearning4j_tpu.kernels import ring_attention as ring_mod
from deeplearning4j_tpu.kernels.paged_attention import (
    paged_attention_supported, paged_decode_attention, paged_latent_attention,
    paged_plan)
from deeplearning4j_tpu.nn.layers.sambay import Widths, diff_attend_rows
from deeplearning4j_tpu.serving.decode import engine as engine_mod
from deeplearning4j_tpu.serving.decode.cache import CacheIO, KvCacheSpec
from deeplearning4j_tpu.serving.decode.engine import DecodeEngine
from deeplearning4j_tpu.serving.registry import ModelRegistry

BL, W = 16, 64          # the served cell's pages and table width


def _paged(lengths, heads, seed=0, dead_block=0, layers=2):
    """(q, arena, tables, lengths) for rows of the given lengths: every
    row's live pages are blocks of its own, drawn in a shuffled order; the
    dead table slots name `dead_block`. Block 0 is the trash block."""
    r = np.random.default_rng(seed)
    width = heads * 64
    pages = [-(-n // BL) for n in lengths]
    num_blocks = 2 + sum(pages)
    kv = r.normal(size=(2 * layers, num_blocks, BL, width)).astype(np.float32)
    tables = np.full((len(lengths), W), dead_block, np.int32)
    ids = 2 + r.permutation(sum(pages))         # block 1 is kept for NaN
    for row, n in enumerate(pages):
        tables[row, :n], ids = ids[:n], ids[n:]
    q = r.normal(size=(len(lengths), width)).astype(np.float32)
    return (jnp.asarray(q), jnp.asarray(kv), jnp.asarray(tables),
            jnp.asarray(lengths, jnp.int32))


def _oracle(q, kv, channel, tables, lengths, heads):
    """The decode plane's plain path: the gathered view, heads split out,
    `attention_reference` with the tick's causal offsets and lengths."""
    width = q.shape[1]
    spec = KvCacheSpec(channels=kv.shape[0], width=width,
                       block_len=kv.shape[2], num_blocks=kv.shape[1],
                       max_context=tables.shape[1] * kv.shape[2])
    view = lambda c: CacheIO(spec).gather(kv, None, tables, c).reshape(
        q.shape[0], -1, heads, width // heads)
    k_all, v_all = view(channel), view(channel + 1)
    out = TransformerBlock(n_heads=heads).decode_attend(
        q.reshape(q.shape[0], 1, heads, -1), k_all, v_all,
        (lengths - 1)[:, None], lengths)
    return np.asarray(out).reshape(q.shape)


RAGGED = [167, 880, 1, 422, 512, 513, 96, 1008, 300, 33, 640, 255, 129, 784,
          16, 471]


@pytest.mark.parametrize("lengths,heads,channel", [
    (RAGGED, 16, 0),                     # ragged over the 16 rows of a tick
    (RAGGED[:8], 12, 2),                 # H*Dh 768
    ([1], 16, 0),                        # bucket 1: a pad row at the trash block
    ([1, 1, 5, 1], 16, 2),
    ([32, 33], 16, 0),                   # on a page boundary, and one past it
    ([16, 17, 15], 12, 2),
    ([128, 129, 127], 16, 0),            # on a chunk's boundary (8 pages)
    ([256, 384, 257], 12, 0),
    ([1024], 16, 2),                     # full context, one row
    ([1024, 1023, 1009], 12, 2),         # full context, H*Dh 768
    ([700], 12, 0),                      # bucket 1, H*Dh 768
    ([64, 200], 8, 0),                   # 8 heads: no head is padded
], ids=lambda v: None if isinstance(v, int) else
    "x".join(map(str, v[:3])) + ("+" if len(v) > 3 else ""))
def test_paged_kernel_matches_the_gathered_view(lengths, heads, channel):
    q, kv, tables, lens = _paged(lengths, heads, layers=1 + channel // 2)
    got = np.asarray(paged_decode_attention(
        q, kv, jnp.int32(channel), tables, lens, n_heads=heads,
        interpret=True))
    want = _oracle(q, kv, channel, tables, lens, heads)
    assert got.shape == want.shape == (len(lengths), heads * 64)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("lengths,heads", [
    ([167, 1, 512, 513, 129, 1008], 16), ([40, 128, 1], 12)],
    ids=["HDh1024", "HDh768"])
def test_dead_pages_are_never_read(lengths, heads):
    """Dead table slots name a block full of NaN, and so does every slot of
    a row past its last live page: were one page of it read, its NaN would
    reach the result through the weights-times-values product (0 x NaN).
    The oracle reads the trash block there, and masks it."""
    q, kv, tables, lens = _paged(lengths, heads, seed=1, dead_block=1)
    kv = kv.at[:, 1].set(jnp.nan)
    got = np.asarray(paged_decode_attention(
        q, kv, jnp.int32(0), tables, lens, n_heads=heads, interpret=True))
    assert np.isfinite(got).all()
    live = jnp.arange(W)[None, :] * BL < lens[:, None]
    want = _oracle(q, kv, 0, jnp.where(live, tables, 0), lens, heads)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


def test_a_row_is_bit_identical_alone_and_among_fifteen():
    """Rows are independent: a row's chunks depend on its own length and
    table only (the join/leave contract of the decode plane)."""
    q, kv, tables, lens = _paged(RAGGED, 16, seed=2, layers=1)
    run = functools.partial(paged_decode_attention, n_heads=16,
                            interpret=True)
    among = np.asarray(run(q, kv, jnp.int32(0), tables, lens))
    for row in (0, 7, 15):
        alone = np.asarray(run(q[row:row + 1], kv, jnp.int32(0),
                               tables[row:row + 1], lens[row:row + 1]))
        np.testing.assert_array_equal(alone[0], among[row])
    # and whatever the neighbours hold: other lengths, other queries
    q2 = q.at[1:].set(q[1:] * 3.0)
    lens2 = lens.at[1:].set(jnp.minimum(lens[1:], 40))
    np.testing.assert_array_equal(
        np.asarray(run(q2, kv, jnp.int32(0), tables, lens2))[0], among[0])


def test_plan_walks_a_row_in_chunks_of_128_slots():
    plan = paged_plan(16, 64, 16, 16, 1024)
    assert (plan.pages_a_chunk, plan.chunks_a_row, plan.steps_a_call) == (
        8, 8, 16)
    assert plan.heads_padded == 16 and plan.vmem_bytes < 16 << 20
    assert paged_plan(16, 64, 16, 12, 768).heads_padded == 16
    # pages of 128 slots: one a chunk; a table narrower than a chunk's pages
    assert paged_plan(4, 8, 128, 8, 512).pages_a_chunk == 1
    assert paged_plan(2, 3, 8, 8, 512)[:2] == (3, 1)
    assert paged_attention_supported(1024, 16)
    assert paged_attention_supported(768, 8)
    assert not paged_attention_supported(64, 16)
    assert not paged_attention_supported(1024, 4)


@pytest.mark.parametrize("pages", [1, 3, 16])
def test_the_result_does_not_depend_on_the_chunking(monkeypatch, pages):
    """Any pages a chunk: one (a loop of 64), one that does not divide the
    table (the last chunk's columns run past it), a quarter of the table."""
    monkeypatch.setattr(paged_mod, "_CHUNK_TOKENS", pages * BL)
    paged_mod._planned.cache_clear()
    try:
        q, kv, tables, lens = _paged([167, 1, 1024, 48], 16, layers=1)
        assert paged_plan(4, W, BL, 16, 1024).pages_a_chunk == pages
        got = np.asarray(paged_decode_attention(
            q, kv, jnp.int32(0), tables, lens, n_heads=16, interpret=True))
    finally:
        paged_mod._planned.cache_clear()
    np.testing.assert_allclose(got, _oracle(q, kv, 0, tables, lens, 16),
                               rtol=2e-5, atol=2e-6)


# ---------------------------------------------------------------------------
# the call site: serving/decode/engine.py
# ---------------------------------------------------------------------------

def _spec(**kw):
    base = dict(channels=4, width=1024, block_len=16, num_blocks=9,
                max_context=64)
    return KvCacheSpec(**{**base, **kw})


def tick_attention(spec):
    return TransformerBlock(n_heads=2).decode_attention("tick", spec)


@pytest.mark.parametrize("backend,kw,want", [
    ("tpu", {}, "paged_kernel"),
    ("tpu", {"width": 768}, "paged_kernel"),            # H*Dh 768
    ("tpu", {"kv_dtype": "int8"}, "gather"),
    ("tpu", {"width": 16}, "gather"),                   # 16 lanes
    ("tpu", {"width": 192}, "gather"),                  # 192: no multiple of 128
    ("tpu", {"block_len": 4}, "gather"),                # half a sublane tile
    ("cpu", {}, "gather"),
], ids=["fp32", "HDh768", "int8", "HDh16", "HDh192", "block4", "cpu"])
def test_the_engine_picks_the_kernel_only_where_it_can_run(monkeypatch,
                                                          backend, kw, want):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert tick_attention(_spec(**kw)) == want


def test_the_kernels_kill_switch_takes_the_tick_to_the_view(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setenv("DL4J_TPU_DISABLE_PALLAS", "1")
    assert tick_attention(_spec()) == "gather"


def _lm(width, heads, vocab=32, t=64, blocks=2, seed=5):
    b = (NeuralNetConfiguration.builder().seed(seed).updater(Adam(1e-3))
         .list().layer(EmbeddingSequenceLayer(n_in=vocab, n_out=width)))
    for _ in range(blocks):
        b = b.layer(TransformerBlock(n_heads=heads))
    conf = (b.layer(RnnOutputLayer(n_out=vocab, activation="softmax",
                                   loss="mcxent"))
            .set_input_type(InputType.recurrent(1, t)).build())
    return MultiLayerNetwork(conf).init()


@pytest.mark.parametrize("width,kv_dtype", [(16, "fp32"), (128, "int8")],
                         ids=["HDh16", "int8"])
def test_executable_records_say_which_attention_a_tick_took(width, kv_dtype):
    """`dl4j/engine/executable` of a tick carries `attention`; a prefill
    attends over its local K/V and carries none."""
    previous = telemetry.tracer()
    telemetry.install_tracer(telemetry.Tracer())
    try:
        registry = ModelRegistry(buckets=(1,))
        registry.register("gen", _lm(width, 2))
        eng = DecodeEngine(registry, "gen", block_len=8, kv_dtype=kv_dtype,
                           decode_buckets=(1,), prompt_buckets=(8,))
        pool, v = eng.new_pool(), registry.get("gen")
        blocks = pool.alloc(eng.spec.blocks_for(6))
        eng.run_prefill(v, pool, [1, 2, 3], blocks)
        eng.run_tick(v, pool, [4], [3], [blocks], bucket=1)
        records = [e["attrs"] for e in telemetry.tracer().snapshot()
                   if e["name"] == "dl4j/engine/executable"]
    finally:
        telemetry.install_tracer(previous)
    assert [(r["phase"], r.get("attention")) for r in records] == [
        ("prefill", None), ("tick", "gather")]
    with pytest.raises(ValueError, match="paged_kernel|gather"):
        engine_mod.build_decode_fn(v.model, v.snapshot, eng.spec,
                                   attention="view")


def test_the_tick_through_the_kernel_agrees_with_the_tick_through_the_view(
        monkeypatch):
    """The call site: the merged query, the layer's channel, the lengths and
    the tables reach the kernel as the view's path reads them. Here the
    kernel runs through the interpreter (the engine's own choice compiles
    it, which only a TPU takes)."""
    from deeplearning4j_tpu.serving.decode.cache import make_cache
    from deeplearning4j_tpu.serving.decode.engine import (build_decode_fn,
                                                          build_prefill_fn)
    from deeplearning4j_tpu.serving.registry import _snapshot_params

    monkeypatch.setattr(
        paged_mod, "paged_decode_attention",
        lambda *a, interpret, **kw: paged_decode_attention(
            *a, interpret=True, **kw))
    model = _lm(128, 2)
    snapshot = _snapshot_params(model, "fp32")
    spec = KvCacheSpec(channels=4, width=128, block_len=8,
                       num_blocks=17, max_context=64)
    prefill = jax.jit(build_prefill_fn(model, snapshot, spec))
    ticks = {a: jax.jit(build_decode_fn(model, snapshot, spec, attention=a))
             for a in ("gather", "paged_kernel")}
    r = np.random.default_rng(0)
    prompts = [r.integers(0, 32, n).tolist() for n in (5, 16, 11)]
    tables = np.zeros((4, spec.table_width), np.int32)     # row 3: a pad row
    cache, free = make_cache(spec), iter(range(1, 17))
    for row, prompt in enumerate(prompts):
        n = spec.blocks_for(len(prompt) + 4)
        tables[row, :n] = [next(free) for _ in range(n)]
        tokens = np.zeros((1, 16), np.int32)
        tokens[0, :len(prompt)] = prompt
        cache, _ = prefill(snapshot.data, cache, jnp.asarray(tokens),
                           jnp.asarray([len(prompt)], jnp.int32),
                           jnp.asarray(tables[row:row + 1]))
    caches = {a: cache for a in ticks}
    positions = np.asarray([len(p) for p in prompts] + [0], np.int32)
    tokens = np.asarray([3, 7, 11, 0], np.int32)
    for _ in range(4):                       # row 1 crosses a page boundary
        logits = {}
        for a, tick in ticks.items():
            caches[a], logits[a] = tick(
                snapshot.data, caches[a], jnp.asarray(tokens),
                jnp.asarray(positions), jnp.asarray(tables))
        np.testing.assert_allclose(np.asarray(logits["paged_kernel"])[:3],
                                   np.asarray(logits["gather"])[:3],
                                   rtol=2e-5, atol=2e-6)
        tokens = np.asarray(logits["gather"]).argmax(-1).astype(np.int32)
        tokens[3] = 0
        positions[:3] += 1
    # what the ticks wrote: the first layer's K/V to the bit (they do not
    # depend on any attention), the second's as close as the logits
    written = {a: np.asarray(c["kv"]) for a, c in caches.items()}
    np.testing.assert_array_equal(written["paged_kernel"][:2],
                                  written["gather"][:2])
    np.testing.assert_allclose(written["paged_kernel"], written["gather"],
                               rtol=2e-5, atol=2e-6)


# ---------------------------------------------------------------------------
# grouped queries and a bfloat16 arena (the hybrid block's attention layer)
# ---------------------------------------------------------------------------
def _grouped_oracle(q, kv, channel, tables, lengths, heads, kv_heads, scale):
    """Every query head over its key/value head's gathered view, in
    float64 on the host."""
    q, kv = np.asarray(q, np.float64), np.asarray(kv.astype(jnp.float32),
                                                  np.float64)
    b, d = q.shape[0], q.shape[1] // heads
    out = np.zeros((b, heads, d))
    for row in range(b):
        n = int(lengths[row])
        view = lambda c: kv[c][np.asarray(tables[row])].reshape(
            -1, kv_heads, d)[:n]
        k, v = view(channel), view(channel + 1)
        for h in range(heads):
            s = k[:, h // (heads // kv_heads)] @ q[row].reshape(heads, d)[h]
            w = np.exp(s * scale - (s * scale).max())
            out[row, h] = (w / w.sum()) @ v[:, h // (heads // kv_heads)]
    return out.reshape(b, heads * d)


@pytest.mark.parametrize("lengths,heads,kv_heads,dtype,channel", [
    ([167, 880, 1, 422, 513, 96], 8, 2, "float32", 0),
    ([1, 129, 128], 4, 1, "float32", 2),       # one key/value head for all
    ([700, 16], 12, 4, "float32", 0),          # 12 query heads padded to 16
    ([300, 1024, 33], 8, 2, "bfloat16", 2),    # the arena as a bf16 model holds it
    ([64, 200], 4, 4, "bfloat16", 0),          # equal counts, bfloat16 pages
], ids=lambda v: None if isinstance(v, int) else
    (v if isinstance(v, str) else "x".join(map(str, v[:3]))))
def test_grouped_queries_read_their_key_value_heads_pages(lengths, heads,
                                                          kv_heads, dtype,
                                                          channel):
    """The kernel with fewer key/value heads than query heads (the arena's
    lanes hold `Hkv*Dh`), a scale of its own and bfloat16 pages, against
    each query head's attention over its key/value head, computed plainly."""
    _, kv, tables, lens = _paged(lengths, kv_heads, layers=1 + channel // 2)
    kv = kv.astype(dtype)
    q = jnp.asarray(np.random.default_rng(3).normal(
        size=(len(lengths), heads * 64)).astype(np.float32))
    got = np.asarray(paged_decode_attention(
        q, kv, jnp.int32(channel), tables, lens, n_heads=heads,
        n_kv_heads=kv_heads, sm_scale=0.05, interpret=True))
    q_seen = q.astype(dtype).astype(jnp.float32)   # what the products take
    want = _grouped_oracle(q_seen, kv, channel, tables, lens, heads, kv_heads,
                           0.05)
    assert got.shape == want.shape
    tol = dict(rtol=2e-5, atol=2e-6) if dtype == "float32" else \
        dict(rtol=2e-2, atol=2e-2)      # the weights are rounded to bfloat16
    np.testing.assert_allclose(got, want, **tol)
    assert paged_attention_supported(kv_heads * 64, BL, dtype) == (
        kv_heads % 2 == 0)
    assert not paged_attention_supported(128, 8, "bfloat16")
    assert not paged_attention_supported(128, 16, "int8")
    with pytest.raises(ValueError, match="disagree"):
        paged_decode_attention(q, kv, jnp.int32(0), tables, lens,
                               n_heads=heads, n_kv_heads=kv_heads + 1)


# ---------------------------------------------------------------------------
# latent attention (the LongCat-Flash block's tick): one absorbed query a row
# over a latent cache's pages, keys and values in one page
# ---------------------------------------------------------------------------
LATENT = dict(n_heads=64, kv_rank=512, qk_rope=64, qk_nope=128, v_head=128)
LW = 640                        # [c | k_rope] = 576, padded to lane tiles


def _latent_block():
    from deeplearning4j_tpu import ShortcutMoEBlock
    return ShortcutMoEBlock(n_model=64, q_rank=32, **LATENT)


def _latent_paged(lengths, dtype="float32", channels=2, seed=0, dead_block=0,
                  table_width=128):
    """(arena, tables, lengths) of latent pages: lanes past the latent's 576
    hold zeros, as the layer caches them; every row's live pages are blocks
    of its own in a shuffled order, its dead table slots name `dead_block`
    (block 1 is kept for NaN)."""
    r = np.random.default_rng(seed)
    pages = [-(-n // BL) for n in lengths]
    kv = r.normal(size=(channels, 2 + sum(pages), BL, LW)).astype(np.float32)
    kv[..., 576:] = 0.0
    tables = np.full((len(lengths), table_width), dead_block, np.int32)
    ids = 2 + r.permutation(sum(pages))
    for row, n in enumerate(pages):
        tables[row, :n], ids = ids[:n], ids[n:]
    return (jnp.asarray(kv).astype(dtype), jnp.asarray(tables),
            jnp.asarray(lengths, jnp.int32))


def _latent_queries(rows, seed=1):
    """(q_nope, q_rope) [rows, 1, H, .] and the block's W_kvb, float32."""
    r = np.random.default_rng(seed)
    h = LATENT["n_heads"]
    qn = r.normal(size=(rows, 1, h, LATENT["qk_nope"])).astype(np.float32)
    qr = r.normal(size=(rows, 1, h, LATENT["qk_rope"])).astype(np.float32)
    w = r.normal(size=(LATENT["kv_rank"], h * 256)) / np.sqrt(512)
    return (jnp.asarray(qn), jnp.asarray(qr),
            {"W_kvb": jnp.asarray(w.astype(np.float32))})


@pytest.fixture
def latent_interpreted(monkeypatch):
    """The layer's call site runs the kernel through the interpreter (its
    own choice is the compiled kernel, which only a TPU takes)."""
    monkeypatch.setattr(
        paged_mod, "paged_latent_attention",
        lambda *a, interpret, **kw: paged_latent_attention(
            *a, interpret=True, **kw))


def _latent_both(lengths, dtype, channel, **kw):
    """The block's tick attention both ways: through the kernel over the
    pages in place, and `_attend_absorbed` over `CacheIO.gather`'s view."""
    kv, tables, lens = _latent_paged(lengths, dtype, channels=channel + 1,
                                     **kw)
    qn, qr, p = _latent_queries(len(lengths))
    block = _latent_block()
    got = block._attend_paged(p, qn, qr, kv, jnp.int32(channel), tables, lens)
    spec = KvCacheSpec(channels=kv.shape[0], width=LW, block_len=BL,
                       num_blocks=kv.shape[1],
                       max_context=tables.shape[1] * BL)
    view = CacheIO(spec).gather(kv, None, tables, channel)
    want = block._attend_absorbed(
        p, qn, qr, view.reshape(len(lengths), -1, LW), (lens - 1)[:, None],
        lens)
    return np.asarray(got), np.asarray(want)


@pytest.mark.parametrize("lengths,dtype,channel", [
    ([1, 512, 513, 2048], "bfloat16", 2),   # one slot, a chunk, one past, all
    ([1, 512, 513, 2048], "float32", 0),
    ([167, 880, 33, 1024, 1500, 300], "bfloat16", 1),
    ([16, 17, 511], "float32", 3),          # a page, one past, a chunk less one
], ids=lambda v: v if isinstance(v, str) else
    None if isinstance(v, int) else "x".join(map(str, v[:4])))
def test_latent_kernel_matches_absorbed_attention_over_the_view(
        latent_interpreted, lengths, dtype, channel):
    """64 heads over 640 lanes, the weighted sum over the first 512: the
    kernel's result through `W_uv` equals the absorbed attention over the
    gathered view to float32 sums in another order (the pages are read as
    they lie, bfloat16 or float32; the weights are float32 here, so both
    multiply in float32)."""
    got, want = _latent_both(lengths, dtype, channel)
    assert got.shape == want.shape == (len(lengths), 1, 64 * 128)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_latent_dead_pages_are_never_read():
    """Dead table slots name a block of NaN: the result stays finite and
    equal to the oracle's, which reads the trash block there and masks it."""
    lengths = [1, 300, 513, 1100]
    kv, tables, lens = _latent_paged(lengths, "bfloat16", seed=3,
                                     dead_block=1)
    kv = kv.at[:, 1].set(jnp.nan)
    q = jnp.asarray(np.random.default_rng(4).normal(
        size=(len(lengths), 64, LW)).astype(np.float32))
    q = q.at[..., 576:].set(0.0)
    got = np.asarray(paged_latent_attention(
        q, kv, jnp.int32(1), tables, lens, v_width=512, sm_scale=0.07,
        interpret=True))
    assert got.shape == (len(lengths), 64, 512) and np.isfinite(got).all()
    live = jnp.arange(tables.shape[1])[None, :] * BL < lens[:, None]
    safe = jnp.where(live, tables, 0)
    pages = np.asarray(kv[1][safe].astype(jnp.float32), np.float64)
    q64 = np.asarray(q.astype(jnp.bfloat16).astype(jnp.float32), np.float64)
    for row, n in enumerate(lengths):
        view = pages[row].reshape(-1, LW)[:n]
        s = q64[row] @ view.T * 0.07
        w = np.exp(s - s.max(-1, keepdims=True))
        want = (w / w.sum(-1, keepdims=True)) @ view[:, :512]
        np.testing.assert_allclose(got[row], want, rtol=2e-2, atol=2e-2)


def test_latent_row_is_bit_identical_alone_and_among_31_others():
    r = np.random.default_rng(5)
    lengths = r.integers(1, 2049, 32).tolist()
    kv, tables, lens = _latent_paged(lengths, "bfloat16", channels=1, seed=5)
    q = jnp.asarray(r.normal(size=(32, 64, LW)).astype(np.float32))
    run = functools.partial(paged_latent_attention, v_width=512,
                            sm_scale=0.07, interpret=True)
    among = np.asarray(run(q, kv, jnp.int32(0), tables, lens))
    for row in (0, 13, 31):
        alone = np.asarray(run(q[row:row + 1], kv, jnp.int32(0),
                               tables[row:row + 1], lens[row:row + 1]))
        np.testing.assert_array_equal(alone[0], among[row])


def test_latent_plan_walks_a_row_in_chunks_of_512_slots():
    from deeplearning4j_tpu.kernels.paged_attention import latent_plan
    plan = latent_plan(32, 128, 16, 64, 640, 512)
    assert (plan.pages_a_chunk, plan.chunks_a_row, plan.steps_a_call,
            plan.heads_padded) == (32, 4, 32, 64)
    assert plan.vmem_bytes < 16 << 20
    assert latent_plan(4, 8, 16, 4, 128, 16).pages_a_chunk == 8   # a narrow table
    assert latent_plan(4, 8, 16, 4, 128, 16, itemsize=4).heads_padded == 8
    assert latent_plan(4, 8, 16, 4, 128, 16, itemsize=2).heads_padded == 16
    with pytest.raises(ValueError, match="width"):
        paged_latent_attention(jnp.zeros((1, 4, 128)), jnp.zeros((1, 3, 16, 256)),
                               jnp.int32(0), jnp.zeros((1, 2), jnp.int32),
                               jnp.ones((1,), jnp.int32), v_width=16,
                               sm_scale=1.0)


# ---------------------------------------------------------------------------
# differential attention (the SambaY cross-decoder's tick): two score maps a
# head over halves of its key/value head's keys, one value of twice the width
# ---------------------------------------------------------------------------
def _diff_paged(lengths, heads, kv_heads, dtype="float32", channel=0, seed=0,
                dead_block=0):
    """(q [B, H, 2, 64], arena, tables, lengths): the arena's lanes hold
    Hkv x (k1 | k2) on the key channel and Hkv values of 128 on the next;
    block 1 is kept for NaN."""
    _, kv, tables, lens = _paged(lengths, kv_heads * 2, seed=seed,
                                 dead_block=dead_block,
                                 layers=1 + channel // 2)
    q = np.random.default_rng(seed + 7).normal(
        size=(len(lengths), heads, 2, 64)).astype(np.float32)
    return jnp.asarray(q), kv.astype(dtype), tables, lens


def _diff_oracle(q, kv, channel, tables, lengths, kv_heads, scale):
    """For s = 1, 2 and head i: softmax(q^s_i . k^s of key/value head
    i // (H/Hkv)) over the row's live slots, times that head's value, in
    float64 on the host over the gathered view."""
    q = np.asarray(q.astype(kv.dtype).astype(jnp.float32), np.float64)
    kv = np.asarray(kv.astype(jnp.float32), np.float64)
    b, heads = q.shape[:2]
    out = np.zeros((b, 2, heads, 128))
    for row in range(b):
        n = int(lengths[row])
        view = lambda c: kv[c][np.asarray(tables[row])].reshape(
            -1, kv_heads, 128)[:n]
        k, v = view(channel), view(channel + 1)
        for i in range(heads):
            g = i // (heads // kv_heads)
            for s in range(2):
                z = k[:, g, 64 * s:64 * (s + 1)] @ q[row, i, s] * scale
                w = np.exp(z - z.max())
                out[row, s, i] = (w / w.sum()) @ v[:, g]
    return out


@pytest.mark.parametrize("lengths,heads,kv_heads,dtype,channel", [
    ([167, 880, 1, 422, 513, 96], 4, 2, "float32", 0),
    ([33, 129, 1024], 20, 10, "bfloat16", 2),   # the cell's heads and pages
    ([16, 17, 128], 2, 1, "float32", 0),        # a page, one past, a chunk
], ids=lambda v: v if isinstance(v, str) else
    None if isinstance(v, int) else "x".join(map(str, v[:3])))
def test_diff_kernel_matches_both_maps_over_the_gathered_view(
        lengths, heads, kv_heads, dtype, channel):
    q, kv, tables, lens = _diff_paged(lengths, heads, kv_heads, dtype, channel)
    got = np.asarray(paged_mod.paged_diff_attention(
        q, kv, jnp.int32(channel), tables, lens, n_kv_heads=kv_heads,
        sm_scale=0.125, interpret=True))
    want = _diff_oracle(q, kv, channel, tables, lens, kv_heads, 0.125)
    assert got.shape == want.shape == (len(lengths), 2, heads, 128)
    tol = dict(rtol=2e-5, atol=2e-6) if dtype == "float32" else \
        dict(rtol=2e-2, atol=2e-2)      # the products' operands in bfloat16
    np.testing.assert_allclose(got, want, **tol)
    with pytest.raises(ValueError, match="disagree"):
        paged_mod.paged_diff_attention(q, kv, jnp.int32(0), tables, lens,
                                       n_kv_heads=kv_heads * 2, sm_scale=1.0)


def test_diff_dead_pages_are_never_read():
    """Dead table slots name a block of NaN, and so does every slot past a
    row's last live page: the result stays finite and equal to the oracle's,
    which reads the trash block there and masks it."""
    lengths = [167, 1, 512, 513, 129, 1008]
    q, kv, tables, lens = _diff_paged(lengths, 4, 2, seed=1, dead_block=1)
    kv = kv.at[:, 1].set(jnp.nan)
    got = np.asarray(paged_mod.paged_diff_attention(
        q, kv, jnp.int32(0), tables, lens, n_kv_heads=2, sm_scale=0.125,
        interpret=True))
    assert np.isfinite(got).all()
    live = jnp.arange(W)[None, :] * BL < lens[:, None]
    want = _diff_oracle(q, kv, 0, jnp.where(live, tables, 0), lens, 2, 0.125)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


def test_diff_row_is_bit_identical_alone_and_among_others():
    lengths = [300, 17, 1024, 640, 1, 95]
    q, kv, tables, lens = _diff_paged(lengths, 4, 2, "bfloat16", seed=2)
    run = functools.partial(paged_mod.paged_diff_attention, n_kv_heads=2,
                            sm_scale=0.125, interpret=True)
    among = np.asarray(run(q, kv, jnp.int32(0), tables, lens))
    for row in (0, 2, 4):
        alone = np.asarray(run(q[row:row + 1], kv, jnp.int32(0),
                               tables[row:row + 1], lens[row:row + 1]))
        np.testing.assert_array_equal(alone[0], among[row])


def test_diff_kernel_leaves_its_record_once_a_call_shape():
    previous = telemetry.tracer()
    telemetry.install_tracer(telemetry.Tracer())
    paged_mod._planned_diff.cache_clear()
    try:
        q, kv, tables, lens = _diff_paged([40, 90], 4, 2, "bfloat16", seed=3)
        for _ in range(2):
            paged_mod.paged_diff_attention(q, kv, jnp.int32(0), tables, lens,
                                           n_kv_heads=2, sm_scale=0.125,
                                           interpret=True)
        recs = [r["attrs"] for r in telemetry.tracer().snapshot()
                if r["name"] == "dl4j/kernels/paged_attention"]
    finally:
        telemetry.install_tracer(previous)
    assert len(recs) == 1
    assert (recs[0]["diff"], recs[0]["n_heads"], recs[0]["width"],
            recs[0]["dtype"], recs[0]["heads_padded"]) == (
                1, 4, 256, "bfloat16", 16)


# ---------------------------------------------------------------------------
# differential attention over a window layer's rings (the SambaY self-
# decoder's tick): each row reads the live slots of its own ring in place
# ---------------------------------------------------------------------------
def _rings(positions, heads, kv_heads, dtype="float32", window=512, seed=0,
           slots=None, d_head=64):
    """(q [B, H*2Dh], k, v rings [slots, W, Hkv*2Dh], slot [B], positions
    [B]): every row on a ring of its own, the rows' slots drawn in a
    shuffled order from 1 .. slots-1 (slot 0 is the trash slot)."""
    r = np.random.default_rng(seed)
    b = len(positions)
    slots = slots or 2 * b + 1
    width = kv_heads * 2 * d_head
    k, v = (jnp.asarray(r.normal(size=(slots, window, width)), dtype)
            for _ in range(2))
    slot = jnp.asarray(1 + r.permutation(slots - 1)[:b], jnp.int32)
    q = jnp.asarray(r.normal(size=(b, heads * 2 * d_head)), jnp.float32)
    return q, k, v, slot, jnp.asarray(positions, jnp.int32)


def _ring_run(q, k, v, slot, positions, heads, kv_heads, scale=0.125):
    return np.asarray(ring_mod.ring_diff_attention(
        q, k, v, slot, jnp.minimum(positions + 1, k.shape[1]), n_heads=heads,
        n_kv_heads=kv_heads, sm_scale=scale, interpret=True))


def _ring_oracle(q, k, v, slot, positions, heads, kv_heads, lam, scale=0.125):
    """`diff_attend_rows` over the gathered rings, the window layer's plain
    path: (A1 - lam A2) v [B, H, 2Dh]; ring slot j is live once the row has
    reached position j."""
    d_head = q.shape[1] // (2 * heads)
    w = Widths(d=0, e=0, n=0, r=0, k=0, chunk=0, heads=heads,
               kv_heads=kv_heads, head=d_head, window=k.shape[1], mlp=0,
               eps=0.0)
    live = jnp.arange(k.shape[1])[None, :] <= positions[:, None]
    return np.asarray(diff_attend_rows(
        q.reshape(-1, heads, 2, d_head) * (scale * d_head ** 0.5), k[slot],
        v[slot], live, lam, w, k.dtype))


@pytest.mark.parametrize("positions,heads,kv_heads,dtype", [
    # live 1, 15, 16, 17, 300, 511, 512 and a row that has wrapped its ring
    ([0, 14, 15, 16, 299, 510, 511, 1300], 4, 2, "float32"),
    ([33, 128, 700, 4], 20, 10, "bfloat16"),    # the cell's heads
], ids=["live-edges", "cell-heads"])
def test_ring_kernel_matches_both_maps_over_the_gathered_ring(
        positions, heads, kv_heads, dtype):
    q, k, v, slot, pos = _rings(positions, heads, kv_heads, dtype)
    got = _ring_run(q, k, v, slot, pos, heads, kv_heads)
    assert got.shape == (len(positions), 2, heads, 128)
    tol = dict(rtol=2e-5, atol=2e-6) if dtype == "float32" else \
        dict(rtol=2e-2, atol=2e-2)      # the products' operands in bfloat16
    # map 1 alone, and A1 - A2
    for lam, mine in ((0.0, got[:, 0]), (1.0, got[:, 0] - got[:, 1])):
        np.testing.assert_allclose(
            mine, _ring_oracle(q, k, v, slot, pos, heads, kv_heads, lam),
            **tol)
    with pytest.raises(ValueError, match="disagree"):
        ring_mod.ring_diff_attention(q, k, v, slot, pos, n_heads=heads,
                                     n_kv_heads=kv_heads * 4, sm_scale=1.0)


def test_ring_rows_of_several_chunks_match_the_gathered_ring(monkeypatch):
    """Chunks of 64 slots: a row's loop walks up to 8 chunks, the next
    chunk's DMAs in flight while one is computed, and a chunk's live pieces
    are 1 to 4 of 16 (one DMA, or the bits of their number)."""
    monkeypatch.setattr(ring_mod, "_RING_CHUNK_SLOTS", 64)
    ring_mod._planned_ring.cache_clear()
    ring_mod._ring_call.clear_cache()
    try:
        positions = [0, 63, 64, 65, 79, 200, 447, 511, 900, 30]
        q, k, v, slot, pos = _rings(positions, 4, 2, seed=6)
        got = _ring_run(q, k, v, slot, pos, 4, 2)
        assert ring_mod._planned_ring(10, 21, 512, 4, 2, 256,
                                      "float32").chunks_a_row == 8
    finally:
        ring_mod._planned_ring.cache_clear()
        ring_mod._ring_call.clear_cache()
    for lam, mine in ((0.0, got[:, 0]), (1.0, got[:, 0] - got[:, 1])):
        np.testing.assert_allclose(
            mine, _ring_oracle(q, k, v, slot, pos, 4, 2, lam),
            rtol=2e-5, atol=2e-6)


def test_ring_dead_slots_and_other_rings_are_never_read():
    """Every ring slot past a row's live ones, and every ring no row names,
    holds NaN: the result stays finite and bit for bit what the kernel gives
    over clean rings."""
    positions = [0, 14, 16, 300, 511, 2000, 40]
    q, k, v, slot, pos = _rings(positions, 4, 2, seed=1, slots=12)
    live = jnp.zeros((12, 512), bool).at[slot].set(
        jnp.arange(512)[None, :] <= pos[:, None])[..., None]
    dirty = [jnp.where(live, z, jnp.nan) for z in (k, v)]
    got = _ring_run(q, *dirty, slot, pos, 4, 2)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, _ring_run(q, k, v, slot, pos, 4, 2))


def test_ring_rows_on_scattered_slots_read_their_own_rings():
    """Rows on slots 9, 2, 6 of 11 (not contiguous, not in order): each
    row's answer is the oracle's over its own ring alone."""
    q, k, v, _, pos = _rings([70, 200, 5], 4, 2, seed=4, slots=11)
    slot = jnp.asarray([9, 2, 6], jnp.int32)
    got = _ring_run(q, k, v, slot, pos, 4, 2)
    np.testing.assert_allclose(
        got[:, 0], _ring_oracle(q, k, v, slot, pos, 4, 2, 0.0),
        rtol=2e-5, atol=2e-6)
    for row in range(3):
        one = jnp.asarray([row])
        alone = _ring_run(q[one], k[slot[one]], v[slot[one]],
                          jnp.zeros(1, jnp.int32), pos[one], 4, 2)
        np.testing.assert_array_equal(alone[0], got[row])


def test_ring_row_is_bit_identical_alone_and_among_63_others():
    positions = np.random.default_rng(5).integers(0, 700, 64)
    positions[:3] = (0, 511, 95)
    q, k, v, slot, pos = _rings(positions, 4, 2, "bfloat16", window=128,
                                seed=5)
    among = _ring_run(q, k, v, slot, pos, 4, 2)
    for row in (0, 1, 2, 40):
        one = slice(row, row + 1)
        alone = _ring_run(q[one], k, v, slot[one], pos[one], 4, 2)
        np.testing.assert_array_equal(alone[0], among[row])


def test_ring_kernel_leaves_its_record_once_a_call_shape():
    previous = telemetry.tracer()
    telemetry.install_tracer(telemetry.Tracer())
    ring_mod._planned_ring.cache_clear()
    try:
        q, k, v, slot, pos = _rings([40, 900], 20, 10, "bfloat16", seed=3)
        for _ in range(2):
            _ring_run(q, k, v, slot, pos, 20, 10)
        recs = [r["attrs"] for r in telemetry.tracer().snapshot()
                if r["name"] == "dl4j/kernels/ring_attention"]
    finally:
        telemetry.install_tracer(previous)
    assert len(recs) == 1
    assert {key: recs[0][key] for key in (
        "rows", "slots", "window", "chunk", "piece", "chunks_a_row",
        "steps_a_call", "heads_padded", "query_rows", "width", "dtype")} == {
            "rows": 2, "slots": 5, "window": 512, "chunk": 512, "piece": 16,
            "chunks_a_row": 1, "steps_a_call": 2, "heads_padded": 24,
            "query_rows": 48, "width": 1280, "dtype": "bfloat16"}
    assert recs[0]["vmem_bytes"] < 16 << 20
