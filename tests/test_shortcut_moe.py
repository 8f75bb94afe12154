"""The LongCat-Flash block (`nn/layers/shortcut_moe.py`) and the decode
plane's layer contract, at a small size on the CPU, against the benchmark's
plain reference (`benchmarks/reference/longcat_flash.py`: heads expanded,
every held expert over every token under a mask, no cache).

Sizes: hidden 64, 2 layers, 4 heads, ranks 32/16, heads of 16 | 8 | 16,
16 routed + 8 identity experts, top-4. The weights are the reference's, held
in float32 (XLA's CPU backend has no bfloat16 batch product); both sides then
multiply in float32, so what is compared is the mathematics and the order of
summation. Tolerances:

  RTOL 2e-4 / ATOL 2e-5  float32 sums of up to 128 terms in another order
      (heads in chunks, experts gathered, the latent absorbed) over values of
      order 1; a flipped router pick would show as about w_e = 6/24 of an
      expert's output, three orders above it
  exact                  what involves no product at all: the identity
      experts' part, a bfloat16 round trip, counts
"""
import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import (DenseLayer, EmbeddingSequenceLayer, InputType,
                                MultiLayerNetwork, NeuralNetConfiguration,
                                OutputLayer, RMSNormLayer, RnnOutputLayer,
                                ShortcutMoEBlock, SparseExpertsLayer, Sgd,
                                TransformerBlock)
from deeplearning4j_tpu.nn.layers import shortcut_moe
from deeplearning4j_tpu.serving import ModelRegistry
from deeplearning4j_tpu.serving.decode import DecodeEngine
from deeplearning4j_tpu.serving.decode.cache import (CacheIO, KvCacheSpec,
                                                     make_cache)
from deeplearning4j_tpu.serving.decode.engine import (build_decode_fn,
                                                      cache_geometry)
from deeplearning4j_tpu.serving.registry import (ServingError, _abstract_sig,
                                                 _snapshot_params)

BENCH = Path(__file__).resolve().parents[1] / "benchmarks"
RTOL, ATOL = 2e-4, 2e-5


def _load(kind):
    spec = importlib.util.spec_from_file_location(
        f"lcf_{kind}", BENCH / kind / "longcat_flash.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref, models = _load("reference"), _load("models")


def tiny_config(held=(0, 16), layers=2, positions=64):
    """The benchmark's configuration file at test size: same keys."""
    real = json.loads((BENCH / "configs" / "longcat-flash-chat.json").read_text())
    return dict(
        real, name="tiny-lcf", vocab_size=96, hidden_size=64,
        ffn_hidden_size=128, expert_ffn_hidden_size=32, num_layers=layers,
        num_attention_heads=4, kv_lora_rank=16, q_lora_rank=32,
        qk_rope_head_dim=8, v_head_dim=16, qk_nope_head_dim=16,
        n_routed_experts=held[1] - held[0], zero_expert_num=8, moe_topk=4,
        max_position_embeddings=positions,
        published=dict(real["published"], n_routed_experts=16),
        deployment=dict(real["deployment"], held_experts=list(held)),
        precision=dict(real["precision"], weights="float32", registry="fp32",
                       kv_dtype="fp32", reference="float32"))


def f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def build(config, seed=3):
    """The program's model by the benchmark's builder."""
    return models.build(config, seed, ref, train=False)


@pytest.fixture(scope="module")
def served():
    """(config, model, registry, engine) of a share holding experts 0-3."""
    config = tiny_config(held=(0, 4))
    model = build(config)
    registry = ModelRegistry(buckets=(1,))
    registry.register("lcf", model)
    engine = DecodeEngine(registry, "lcf", block_len=4,
                          decode_buckets=(1, 2, 4), prompt_buckets=(8, 16, 32))
    return config, model, registry, engine


def reference_logits(config, model, sequence, first, count):
    return np.asarray(ref.served_logits(config, model.params, sequence, first,
                                        count))


# ---------------------------------------------------------------------------
# the block against the reference
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("held", [(0, 16), (4, 8)], ids=["uncut", "share"])
def test_block_forward_matches_the_reference(held):
    config = tiny_config(held=held)
    m = ref.dims(config)
    model = build(config)
    x = jax.random.normal(jax.random.PRNGKey(0), (40, m.d), jnp.float32)
    want = ref.block(model.params[1], x, m)
    got, _ = model.layers[1].apply(model.params[1], {}, x[None])
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=RTOL, atol=ATOL)


def test_whole_stack_forward_matches_the_reference():
    config = tiny_config()
    model = build(config)
    seq = np.random.default_rng(1).integers(0, 96, 50).tolist()
    x = np.zeros((1, 64, 1), np.float32)
    x[0, :50, 0] = seq
    h = model._forward(model.params, model.state, jnp.asarray(x), False,
                       None, upto=len(model.layers) - 1)[0]
    got = np.asarray(model.layers[-1].preout(model.params[-1], {}, h))[0]
    np.testing.assert_allclose(got[9:49], reference_logits(
        config, model, seq, 10, 40), rtol=RTOL, atol=ATOL)


# ---------------------------------------------------------------------------
# the share
# ---------------------------------------------------------------------------
def test_shares_add_up_to_the_uncut_layer():
    """16 routed experts over 4 shares of 4: every share computes the dense
    path D, the identity part I and its own experts' part E_i, so a share's
    block gives D + I + E_i. The uncut layer is D + I + sum E_i: the shares'
    expert parts, with what every chip computes alike counted once. D + I
    alone is a share whose experts' output weights are zero."""
    whole = tiny_config(held=(0, 16))
    m = ref.dims(whole)
    p = f32(ref.init_params(whole, 3))[1]
    x = jax.random.normal(jax.random.PRNGKey(1), (24, m.d), jnp.float32)
    uncut = np.asarray(ref.block(p, x, m))

    def share(lo, hi, zero=False):
        cut = dict(p, moe=dict(p["moe"], **{
            k: v[lo:hi] * (0.0 if zero and k == "expert_W_d" else 1.0)
            for k, v in p["moe"].items() if k.startswith("expert_")}))
        layer = build(tiny_config(held=(lo, hi))).layers[1]
        return np.asarray(layer.apply(cut, {}, x[None])[0][0], np.float64)

    alike = share(0, 4, zero=True)                       # D + I
    parts = [share(lo, lo + 4) - alike for lo in (0, 4, 8, 12)]
    assert all(np.abs(e).max() > 1e-4 for e in parts)    # every share works
    np.testing.assert_allclose(alike + sum(parts), uncut, rtol=RTOL,
                               atol=ATOL)
    # and the reference, given a share, leaves out what the program leaves out
    np.testing.assert_allclose(
        share(4, 8), np.asarray(ref.block(
            dict(p, moe=dict(p["moe"], **{k: v[4:8] for k, v in p["moe"].items()
                                          if k.startswith("expert_")})),
            x, ref.dims(tiny_config(held=(4, 8))))), rtol=RTOL, atol=ATOL)


def _experts(held=(0, 4), **kw):
    layer = SparseExpertsLayer(n_experts=16, n_identity=8, top_k=4,
                               expert_hidden=32, routed_scaling=6.0,
                               held_experts=list(held), **kw)
    p = f32(ref.init_params(tiny_config(held=held), 5))[1]["moe"]
    return layer, p


def test_identity_picks_give_exactly_the_weighted_token():
    """A token whose picks are all identity experts: sum w_e * u, no product
    and no expert's weights involved; the counts say so."""
    layer, p = _experts()
    p = dict(p, router_bias=p["router_bias"].at[16:].add(10.0))
    u = jax.random.normal(jax.random.PRNGKey(2), (1, 9, 64), jnp.float32)
    ids, w = layer.route(p, u[0])
    assert bool(jnp.all(ids >= 16))
    m, counts = layer.mix(p, u)
    np.testing.assert_array_equal(
        np.asarray(m[0]), np.asarray(jnp.sum(w, -1, keepdims=True) * u[0]))
    assert counts.tolist() == [36, 36, 0, 0, 0]
    # weights are the scaled scores themselves, not renormalised
    s = jax.nn.softmax(u[0] @ p["router_W"], axis=-1)
    np.testing.assert_allclose(
        np.asarray(w), 6.0 * np.asarray(jnp.take_along_axis(s, ids, -1)),
        rtol=1e-5)


@pytest.mark.parametrize("tokens,skew", [(24, False), (400, False),
                                         (400, True)],
                         ids=["every-row", "gathered", "overflow"])
def test_held_experts_grouped_match_every_expert_over_every_token(
        tokens, skew, monkeypatch):
    """The three ways the held experts' part is computed: a batch within an
    expert's slots (every row under its weight), a longer one (rows
    gathered into slots), and one whose routing overflows the slots (the
    overloaded expert takes further passes; no pick is dropped). At this
    size a token picks a sixth of the routes, so 8 slots a mean load hold
    every batch whole: 2 reach the other two ways."""
    monkeypatch.setattr(shortcut_moe, "_SLOT_FACTOR", 2)
    layer, p = _experts(held=(4, 8))
    if skew:        # every token picks held expert 5
        p = dict(p, router_bias=p["router_bias"].at[5].add(10.0))
    assert (tokens <= layer.rows_per_expert(tokens)) == (tokens == 24)
    config = tiny_config(held=(4, 8))
    h = jax.random.normal(jax.random.PRNGKey(3), (tokens, 64), jnp.float32)
    one = jnp.ones((64,), jnp.float32)
    # the reference norms what it is given: the layer gets the normed tokens
    want = ref._moe(p, one, h, m=ref.dims(config), precision="float32")
    u = ref._norm(h, one, 1e-5)
    got, counts = layer.mix(p, u[None])
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=RTOL, atol=ATOL)
    picks, identity, held, hit, load = counts.tolist()
    assert picks == 4 * tokens and 0 < held < picks and 1 <= hit <= 4
    if skew:
        assert load == tokens > layer.rows_per_expert(tokens)
    else:
        assert load <= layer.rows_per_expert(tokens)
    # a token that is not live loads no expert and adds nothing
    live = jnp.arange(tokens) % 2 == 0
    half, n = layer.mix(p, u[None], live[None])
    assert n[0] == 2 * tokens and bool(jnp.all(half[0][~live] == 0))
    np.testing.assert_allclose(np.asarray(half[0][live]),
                               np.asarray(got[0][live]), rtol=RTOL, atol=ATOL)


@pytest.mark.parametrize("tokens,skew", [(24, False), (400, False),
                                         (400, True)],
                         ids=["every-row", "gathered", "overflow"])
def test_held_experts_in_a_loop_match_one_conditional_each(
        tokens, skew, monkeypatch):
    """A layer holding more experts than `_UNROLLED_EXPERTS` runs them in a
    loop under one conditional: the same products in the same order, so the
    same sums to float32 rounding, in each of the three ways."""
    monkeypatch.setattr(shortcut_moe, "_SLOT_FACTOR", 2)
    layer, p = _experts(held=(4, 8))
    if skew:
        p = dict(p, router_bias=p["router_bias"].at[5].add(10.0))
    u = jax.random.normal(jax.random.PRNGKey(3), (1, tokens, 64), jnp.float32)
    live = (jnp.arange(tokens) % 3 != 0)[None]
    want, want_counts = layer.mix(p, u, live)
    monkeypatch.setattr(shortcut_moe, "_UNROLLED_EXPERTS", 2)
    lowered = jax.jit(lambda p, u, live: layer.mix(p, u, live)).lower(
        p, u, live).as_text()
    assert lowered.count("stablehlo.case") == (1 if tokens == 24 else 2)
    got, counts = layer.mix(p, u, live)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    assert counts.tolist() == want_counts.tolist()


# ---------------------------------------------------------------------------
# served: prefill, then ticks through the paged latent cache
# ---------------------------------------------------------------------------
def _serve(engine, v, pool, prompts, steps):
    """Prefill each prompt, then `steps` greedy ticks of all rows together;
    returns (sequences, logits [rows][steps + 1, V], tables)."""
    tables = [pool.alloc(engine.spec.blocks_for(len(p) + steps + 1))
              for p in prompts]
    seqs = [list(p) for p in prompts]
    out = [[engine.run_prefill(v, pool, p, t)]
           for p, t in zip(prompts, tables)]
    for _ in range(steps):
        for s, z in zip(seqs, out):
            s.append(int(np.argmax(z[-1])))
        logits = engine.run_tick(
            v, pool, [s[-1] for s in seqs], [len(s) - 1 for s in seqs],
            tables, bucket=engine.decode_bucket_for(len(seqs)))
        for z, row in zip(out, logits):
            z.append(row)
    return seqs, [np.stack(z) for z in out], tables


def test_prefill_then_ticks_match_the_reference_full_forward(served):
    """Rows of 5, 19 and 12 tokens in one tick (bucket 4: one pad row), six
    ticks, the longer rows crossing pages: every logit that chose a token
    against the reference's full causal forward over prompt + tokens."""
    config, model, registry, engine = served
    assert (engine.spec.channels, engine.spec.width) == (4, 128)
    assert (engine.attention, engine.prefill_attention) == (
        "mla_absorbed", "mla_expanded")
    assert engine.max_context == 64
    pool, v = engine.new_pool(), registry.get("lcf")
    r = np.random.default_rng(0)
    prompts = [r.integers(0, 96, n).tolist() for n in (5, 19, 12)]
    seqs, logits, tables = _serve(engine, v, pool, prompts, 6)
    for prompt, seq, z in zip(prompts, seqs, logits):
        want = reference_logits(config, model, seq, len(prompt), 7)
        np.testing.assert_allclose(z, want, rtol=RTOL, atol=ATOL)

    # a block reused after release: the same prompts over recycled blocks
    # (LIFO) give the same logits to the bit as over fresh ones
    for t in tables:
        pool.release(t)
    again = _serve(engine, v, pool, prompts, 6)
    assert {b for t in again[2] for b in t} & {b for t in tables for b in t}
    for a, b in zip(logits, again[1]):
        np.testing.assert_array_equal(a, b)


def test_absorbed_tick_matches_the_expanded_one(served):
    """The tick's two attention paths over the same latent cache: W_kvb
    absorbed into the query and the output, or heads built from the view."""
    _, model, _, engine = served
    spec = engine.spec
    snapshot = _snapshot_params(model, "fp32")
    ticks = {a: jax.jit(build_decode_fn(model, snapshot, spec, attention=a))
             for a in ("mla_absorbed", "mla_expanded")}
    r = np.random.default_rng(4)
    cache = jax.tree_util.tree_map(
        lambda a: jnp.asarray(r.normal(size=a.shape), a.dtype),
        make_cache(spec))
    tables = jnp.asarray([[1, 2, 3, 4] + [0] * 12, [5, 6, 0, 0] + [0] * 12,
                          [0] * 16], jnp.int32)
    args = (jnp.asarray([3, 9, 0], jnp.int32),
            jnp.asarray([14, 6, 0], jnp.int32), tables)
    got = {a: t(snapshot.data, cache, *args) for a, t in ticks.items()}
    np.testing.assert_allclose(np.asarray(got["mla_absorbed"][1][:2]),
                               np.asarray(got["mla_expanded"][1][:2]),
                               rtol=RTOL, atol=ATOL)
    np.testing.assert_array_equal(          # the counts: 2 live rows, top-4
        np.asarray(got["mla_absorbed"][2])[:, 0], [8, 8])
    with pytest.raises(ValueError, match="mla_absorbed"):
        build_decode_fn(model, snapshot, spec, attention="gather")


@pytest.fixture
def latent_interpreted(monkeypatch):
    """`mla_paged` always means the COMPILED kernel; here it runs through
    the Pallas interpreter instead."""
    from deeplearning4j_tpu.kernels import paged_attention as paged
    kernel = paged.paged_latent_attention
    monkeypatch.setattr(paged, "paged_latent_attention",
                        lambda *a, interpret, **kw: kernel(
                            *a, interpret=True, **kw))


def test_paged_tick_matches_the_absorbed_one(served, latent_interpreted):
    """The tick's kernel path against its oracle over the same latent
    cache: the pages read in place through the tables, or gathered into a
    view; pad row, dead table slots and the arena written alike."""
    _, model, _, engine = served
    spec = engine.spec
    snapshot = _snapshot_params(model, "fp32")
    ticks = {a: jax.jit(build_decode_fn(model, snapshot, spec, attention=a))
             for a in ("mla_paged", "mla_absorbed")}
    r = np.random.default_rng(6)
    cache = jax.tree_util.tree_map(
        lambda a: jnp.asarray(r.normal(size=a.shape), a.dtype),
        make_cache(spec))
    tables = jnp.asarray([[1, 2, 3, 4] + [7] * 12, [5, 6, 0, 0] + [0] * 12,
                          [0] * 16], jnp.int32)
    args = (jnp.asarray([3, 9, 0], jnp.int32),
            jnp.asarray([14, 6, 0], jnp.int32), tables)
    got = {a: t(snapshot.data, cache, *args) for a, t in ticks.items()}
    np.testing.assert_allclose(np.asarray(got["mla_paged"][1][:2]),
                               np.asarray(got["mla_absorbed"][1][:2]),
                               rtol=RTOL, atol=ATOL)
    np.testing.assert_allclose(np.asarray(got["mla_paged"][0]["kv"]),
                               np.asarray(got["mla_absorbed"][0]["kv"]),
                               rtol=RTOL, atol=ATOL)
    np.testing.assert_array_equal(np.asarray(got["mla_paged"][2]),
                                  np.asarray(got["mla_absorbed"][2]))
    int8 = CacheIO(KvCacheSpec(channels=4, width=128, block_len=8,
                               num_blocks=9, max_context=64, kv_dtype="int8"))
    with pytest.raises(ValueError, match="int8"):
        model.layers[1].decode_tick_step(int8, "mla_paged")


def test_served_ticks_through_the_kernel_keep_the_greedy_tokens(
        monkeypatch, latent_interpreted):
    """The served stack on pages of 8 slots, once as the TPU would serve it
    (the layers answer `mla_paged`; the kernel interpreted) and once over
    the gathered view: the same greedy tokens over eight ticks, the longer
    rows crossing pages, logits as close as the file's tolerances. Two
    registries: executables are keyed by shapes, not by the attention."""
    from deeplearning4j_tpu import kernels

    config = tiny_config(held=(0, 4))
    r = np.random.default_rng(7)
    prompts = [r.integers(0, 96, n).tolist() for n in (5, 19, 12)]
    out = {}
    for answer in ("mla_paged", "mla_absorbed"):
        monkeypatch.setattr(kernels, "pallas_supported",
                            lambda: answer == "mla_paged")
        registry = ModelRegistry(buckets=(1,))
        registry.register("lcf", build(config))
        engine = DecodeEngine(registry, "lcf", block_len=8,
                              decode_buckets=(4,), prompt_buckets=(32,))
        assert engine.attention == answer
        out[answer] = _serve(engine, registry.get("lcf"), engine.new_pool(),
                             prompts, 8)[:2]
    assert out["mla_paged"][0] == out["mla_absorbed"][0]
    for a, b in zip(out["mla_paged"][1], out["mla_absorbed"][1]):
        np.testing.assert_allclose(a, b, rtol=RTOL, atol=ATOL)


@pytest.mark.parametrize("backend,kw,want", [
    ("tpu", {}, "mla_paged"),
    ("tpu", {"kv_dtype": "bf16", "block_len": 16}, "mla_paged"),
    ("tpu", {"kv_dtype": "bf16"}, "mla_absorbed"),      # half a bf16 tile
    ("tpu", {"kv_dtype": "int8"}, "mla_absorbed"),
    ("tpu", {"width": 192}, "mla_absorbed"),            # no lane tiles
    ("cpu", {}, "mla_absorbed"),
], ids=["fp32", "bf16", "bf16-block8", "int8", "width192", "cpu"])
def test_the_tick_takes_the_kernel_only_where_it_can_run(monkeypatch,
                                                         backend, kw, want):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    spec = KvCacheSpec(**{**dict(channels=4, width=640, block_len=8,
                                 num_blocks=9, max_context=64), **kw})
    assert _block().decode_attention("tick", spec) == want
    assert _block().decode_attention("prefill", spec) == "mla_expanded"
    monkeypatch.setenv("DL4J_TPU_DISABLE_PALLAS", "1")
    assert _block().decode_attention("tick", spec) == "mla_absorbed"


def test_counts_reach_the_spans_and_the_counters(served):
    from deeplearning4j_tpu import telemetry
    _, _, registry, engine = served
    previous = telemetry.tracer()
    telemetry.install_tracer(telemetry.Tracer())
    try:
        pool, v = engine.new_pool(), registry.get("lcf")
        _serve(engine, v, pool, [[1, 2, 3, 4, 5, 6, 7]], 2)
        log = telemetry.tracer().snapshot()
    finally:
        telemetry.install_tracer(previous)
    fetch = {n: [e["attrs"] for e in log if e["name"] == f"dl4j/engine/{n}.fetch"]
             for n in ("prefill", "tick")}
    assert [a["moe_picks"] for a in fetch["prefill"]] == [7 * 4 * 2]
    assert [a["moe_picks"] for a in fetch["tick"]] == [8, 8]
    for a in fetch["prefill"] + fetch["tick"]:
        assert a["moe_layers"] == 2
        assert 0 <= a["moe_identity"] + a["moe_held"] <= a["moe_picks"]
        assert a["moe_held_hit"] <= a["moe_held"] <= 4 * a["moe_picks"]
    text = registry.metrics.prometheus_text()
    for kind in ("identity", "held", "absent"):
        assert f'dl4j_moe_picks_total{{model="lcf",phase="tick",kind="{kind}"}}' \
            in text
    assert 'dl4j_moe_held_pairs_total{model="lcf",phase="prefill"}' in text


def test_executable_records_carry_the_cache_and_the_attention(served):
    from deeplearning4j_tpu import telemetry
    config = tiny_config(held=(0, 4), layers=1)
    registry = ModelRegistry(buckets=(1,))
    registry.register("one", build(config))
    previous = telemetry.tracer()
    telemetry.install_tracer(telemetry.Tracer())
    try:
        engine = DecodeEngine(registry, "one", block_len=4,
                              decode_buckets=(1,), prompt_buckets=(8,))
        v = registry.get("one")
        engine.prefill_exec(v, 8)
        engine.decode_exec(v, 1)
        records = [e["attrs"] for e in telemetry.tracer().snapshot()
                   if e["name"] == "dl4j/engine/executable"]
    finally:
        telemetry.install_tracer(previous)
    assert [(r["phase"], r["channels"], r["width"], r["attention"],
             r.get("experts")) for r in records] == [
        ("prefill", 2, 128, "mla_expanded", None),
        ("tick", 2, 128, "mla_absorbed", "cond")]


# ---------------------------------------------------------------------------
# the cache and the contract
# ---------------------------------------------------------------------------
def test_bf16_cache_round_trip():
    spec = KvCacheSpec(channels=2, width=128, block_len=4, num_blocks=5,
                       max_context=8, kv_dtype="bf16")
    assert spec.arena_nbytes() * 2 == KvCacheSpec(
        channels=2, width=128, block_len=4, num_blocks=5,
        max_context=8).arena_nbytes()
    cache, io = make_cache(spec), CacheIO(spec)
    assert cache["kv"].dtype == jnp.bfloat16 and "scale" not in cache
    tables = jnp.asarray([[2, 4]], jnp.int32)
    tidx = jnp.arange(8)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 128), jnp.float32)
    kv, sc = io.scatter(cache["kv"], None, x, tables[:, tidx // 4],
                        (tidx % 4)[None], 1)
    view = io.gather(kv, sc, tables, 1)
    assert sc is None and view.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(view.reshape(1, 8, 128), np.float32),
        np.asarray(x.astype(jnp.bfloat16), np.float32))
    assert not np.asarray(kv[0], np.float32).any()
    with pytest.raises(ValueError, match="fp32|bf16|int8"):
        KvCacheSpec(channels=2, width=128, block_len=4, num_blocks=5,
                    max_context=8, kv_dtype="fp16")


def _stack(*layers, emb=None, t=32):
    b = (NeuralNetConfiguration.builder().seed(0).updater(Sgd(0.0)).list()
         .layer(emb or EmbeddingSequenceLayer(n_in=40, n_out=64)))
    for layer in layers:
        b = b.layer(layer)
    conf = (b.layer(RnnOutputLayer(n_out=40, activation="softmax",
                                   loss="mcxent", has_bias=False))
            .set_input_type(InputType.recurrent(1, t)).build())
    return MultiLayerNetwork(conf).init()


def _block(**kw):
    return ShortcutMoEBlock(**dict(
        dict(n_heads=4, q_rank=32, kv_rank=16, qk_nope=16, qk_rope=8,
             v_head=16, ffn_hidden=128, n_experts=16, n_identity=8, top_k=4,
             expert_hidden=32), **kw))


def test_geometry_is_what_the_layers_state():
    gpt = _stack(TransformerBlock(n_heads=4), TransformerBlock(n_heads=2))
    assert cache_geometry(gpt) == (4, 64, 32, ())   # 2 blocks x (K, V), H*Dh
    lcf = _stack(_block(), _block(), RMSNormLayer(),
                 emb=EmbeddingSequenceLayer(n_in=40, n_out=64,
                                            positional=False,
                                            max_timesteps=48))
    assert "P" not in lcf.params[0]
    assert cache_geometry(lcf) == (4, 128, 48, ())  # 2 blocks x 2 latents


@pytest.mark.parametrize("stack,why", [
    (lambda: MultiLayerNetwork(
        NeuralNetConfiguration.builder().seed(0).list()
        .layer(DenseLayer(n_in=4, n_out=8, activation="tanh"))
        .layer(DenseLayer(n_out=8, activation="tanh"))
        .layer(OutputLayer(n_out=3, loss="mcxent")).build()).init(),
     "generation needs"),
    (lambda: _stack(_block(), emb=EmbeddingSequenceLayer(
        n_in=40, n_out=64, positional=False)), "states no context"),
    (lambda: _stack(TransformerBlock(n_heads=4), _block()),
     "cache widths"),
    (lambda: _stack(RMSNormLayer()), "cache widths"),
], ids=["mlp", "no-context", "two-widths", "no-cache"])
def test_engine_refuses_a_stack_it_cannot_serve(stack, why):
    registry = ModelRegistry(buckets=(1,))
    model = stack()
    registry.register("m", model, input_shape=(4,) if why == "generation needs"
                      else None)
    with pytest.raises(ServingError, match=why):
        DecodeEngine(registry, "m")


def test_a_swap_to_another_geometry_is_refused(served):
    _, _, registry, engine = served
    other = ModelRegistry(buckets=(1,))
    other.register("lcf", build(tiny_config(held=(0, 4), positions=32)))
    with pytest.raises(ServingError, match="cache geometry"):
        engine._check_version(other.get("lcf"))
    assert engine._check_version(registry.get("lcf")) is registry.get("lcf")


_NO_TABLE = dict(n_in=40, n_out=64, positional=False, max_timesteps=48)


@pytest.mark.parametrize("emb,first,other", [
    (None, lambda: TransformerBlock(n_heads=4),
     lambda: TransformerBlock(n_heads=2)),
    (_NO_TABLE, lambda: _block(), lambda: _block(top_k=2)),
    (_NO_TABLE, lambda: _block(), lambda: _block(rope_theta=1e7)),
], ids=["gpt-heads", "top-k", "rope-theta"])
def test_a_swap_of_the_same_shapes_but_other_layer_options_is_refused(
        emb, first, other):
    """Executables are keyed by shapes and dtypes; the steps close over the
    layers' options. A version whose leaves have the old shapes but whose
    layers differ would run the old steps, so the engine refuses it; new
    weights under the same layers pass."""
    def registered(block):
        registry = ModelRegistry(buckets=(1,))
        registry.register("m", _stack(
            block(), block(),
            emb=emb and EmbeddingSequenceLayer(**emb)))
        return registry
    registry, same, changed = (registered(first), registered(first),
                               registered(other))
    engine = DecodeEngine(registry, "m", block_len=4)
    sig = lambda r: _abstract_sig(r.get("m").snapshot, r.get("m").state,
                                  r.get("m").precision)
    assert sig(changed) == sig(registry)            # nothing else would tell
    assert engine._check_version(same.get("m")) is same.get("m")
    with pytest.raises(ServingError, match="layers its executables"):
        engine._check_version(changed.get("m"))


def test_init_takes_given_parameters_and_holds_them_to_their_shapes():
    config = tiny_config(held=(0, 4))
    config["precision"] = dict(config["precision"], weights="bfloat16")
    model = models.build(config, 7, ref, train=False)
    given = ref.init_params(config, 7)
    leaves = jax.tree_util.tree_leaves(model.params)
    assert all(a.dtype == jnp.bfloat16 for a in leaves)     # never float32
    assert all(a is b for a, b in zip(
        leaves, jax.tree_util.tree_leaves(model.params)))
    np.testing.assert_array_equal(
        np.asarray(model.params[1]["moe"]["expert_W_g"], np.float32),
        np.asarray(given[1]["moe"]["expert_W_g"], np.float32))
    # a registry at bf16 keeps the very arrays: no second copy of the weights
    snapshot = _snapshot_params(model, "bf16")
    assert all(a is b for a, b in zip(snapshot.data, leaves))
    assert snapshot.nbytes() == sum(a.size * 2 for a in leaves)
    wrong = list(given)
    wrong[1] = dict(given[1], n1=given[1]["n1"][:-1])
    with pytest.raises(ValueError, match="layer 1"):
        MultiLayerNetwork(model.conf).init(params=tuple(wrong))
    with pytest.raises(ValueError, match="entries"):
        MultiLayerNetwork(model.conf).init(params=given[:-1])
    with pytest.raises(ValueError, match="serving only"):
        models.build(config, 7, ref, train=True)
