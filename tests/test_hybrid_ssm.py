"""The Granite 4.0-H block (`nn/layers/hybrid_ssm.py`) and what it brought
to the shared code, at a small size on the CPU against the benchmark's plain
reference (`benchmarks/reference/granite_moe_hybrid.py`): the chunked scan
against the sequential recurrence, grouped-query attention without
positions, the experts' second scoring rule, the shared expert and the
share, the slots of the grouped products at 10 picks of 72, and the served
path: prefill in a larger bucket then ticks through `DecodeEngine` over
pages AND per-sequence state, slots that follow from the block pool's
calls, rows joining and leaving, reuse, eviction. A GPT and a LongCat
engine keep the arguments and the cache they had.

The weights are float32 here (XLA's CPU backend has no bfloat16 batch
product), so the program and the reference differ by the order of their
float32 sums alone: tolerances of 2e-5 relative to the largest value
compared, far under what leaving out any term of the equations gives."""
from __future__ import annotations

import importlib.util
import json
import sys
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.nn.layers import hybrid_ssm, shortcut_moe
from deeplearning4j_tpu.nn.layers.hybrid_ssm import (HybridSSMBlock, ssm_scan,
                                                     ssm_step)
from deeplearning4j_tpu.nn.layers.shortcut_moe import SparseExpertsLayer
from deeplearning4j_tpu.serving import ModelRegistry
from deeplearning4j_tpu.serving.decode import DecodeEngine, GenerationScheduler
from deeplearning4j_tpu.serving.decode.cache import OutOfBlocksError
from deeplearning4j_tpu.serving.decode.engine import cache_geometry
from deeplearning4j_tpu.serving.registry import ServingError
from deeplearning4j_tpu.telemetry import MetricsRegistry, Tracer, install_tracer

BENCH = Path(__file__).resolve().parents[1] / "benchmarks"
TOL = 2e-5          # of the largest value compared (module docstring)


def _load(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"hybrid_test_{kind}_{name}", BENCH / kind / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


ref = _load("reference", "granite_moe_hybrid")
models = _load("models", "granite_moe_hybrid")
REAL = json.loads((BENCH / "configs" / "granite-4.0-h-small.json").read_text())


def tiny_config(held=(0, 4), routed=8, **changes):
    """The cell's configuration at a size a CPU test can run: 4 layers with
    the attention layer second, 8 state-space heads of 16 with a state of
    16 in chunks of 8, 4 query heads on 2 key/value heads, 8 routed experts
    of which `held` live here, 3 picks, a shared expert."""
    config = dict(
        REAL, name="tiny-g4h", hidden_size=64, num_hidden_layers=4,
        layer_types=["mamba", "attention", "mamba", "mamba"],
        mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
        mamba_chunk_size=8, num_attention_heads=4, num_key_value_heads=2,
        intermediate_size=32, shared_intermediate_size=48,
        num_local_experts=held[1] - held[0], num_experts_per_tok=3,
        vocab_size=96, max_position_embeddings=64,
        published=dict(REAL["published"], num_local_experts=routed),
        deployment=dict(REAL["deployment"], held_experts=list(held)),
        precision=dict(REAL["precision"], weights="float32", registry="fp32",
                       kv_dtype="fp32", reference="float32"))
    config.update(changes)
    return config


def build(config, seed=3):
    return models.build(config, seed, ref, train=False)


def close(got, want, tol=TOL):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= tol * np.abs(want).max()


def serve(config, name="g4h", **engine):
    """(model, registry, engine) of a fresh registry (executables are kept
    by registry and name)."""
    model = build(config)
    registry = ModelRegistry(buckets=(1,))
    registry.register(name, model)
    engine = dict(dict(block_len=4, decode_buckets=(1, 2, 4),
                       prompt_buckets=(8, 16, 32)), **engine)
    return model, registry, DecodeEngine(registry, name, **engine)


@pytest.fixture(scope="module")
def served():
    config = tiny_config()
    return (config,) + serve(config)


# ---------------------------------------------------------------------------
# the scan and the mixers against the reference
# ---------------------------------------------------------------------------
def _sequential(xs, dt, a, bm, cm):
    """The recurrence token by token, in float64 on the host."""
    xs, dt, a, bm, cm = (np.asarray(z, np.float64) for z in (xs, dt, a, bm, cm))
    b, t, h, p = xs.shape
    state = np.zeros((b, h, p, bm.shape[-1]))
    ys = np.zeros_like(xs)
    for i in range(t):
        keep = np.exp(dt[:, i] * a)[..., None, None]
        state = keep * state + (dt[:, i, :, None] * xs[:, i])[..., None] \
            * bm[:, i, None, None, :]
        ys[:, i] = np.sum(state * cm[:, i, None, None, :], -1)
    return ys, state


@pytest.mark.parametrize("tokens,chunk", [(29, 8), (8, 8), (5, 8), (33, 16),
                                          (64, 64), (70, 256)])
def test_chunked_scan_matches_the_sequential_recurrence(tokens, chunk):
    """Lengths that are no multiple of the chunk, one chunk, a chunk longer
    than the sequence: outputs and the state after the last token."""
    k = jax.random.split(jax.random.PRNGKey(tokens), 5)
    xs = jax.random.normal(k[0], (2, tokens, 4, 8), jnp.float32)
    dt = jax.nn.softplus(
        jax.random.normal(k[1], (2, tokens, 4), jnp.float32) - 2.0)
    a = -jnp.exp(jax.random.uniform(k[2], (4,), jnp.float32, 0.0, 2.7))
    bm = jax.random.normal(k[3], (2, tokens, 6), jnp.float32)
    cm = jax.random.normal(k[4], (2, tokens, 6), jnp.float32)
    want_y, want_state = _sequential(xs, dt, a, bm, cm)
    y, state = ssm_scan(xs, dt, a, bm, cm, chunk)
    close(y, want_y)
    close(state, want_state)
    # a step of the recurrence continues the scan: the tick's arithmetic
    y1, state1 = ssm_step(state, xs[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0])
    want_y1, want_state1 = _sequential(
        *(jnp.concatenate([z, z[:, :1]], 1) for z in (xs, dt)), a,
        *(jnp.concatenate([z, z[:, :1]], 1) for z in (bm, cm)))
    close(y1, want_y1[:, -1])
    close(state1, want_state1)


def test_scan_carries_the_state_through_positions_whose_step_is_zero():
    """dt = 0 at the padding: the state after 21 real tokens of 32 is the
    state of the 21 alone."""
    k = jax.random.split(jax.random.PRNGKey(1), 4)
    xs = jax.random.normal(k[0], (1, 32, 4, 8), jnp.float32)
    dt = jnp.where(jnp.arange(32)[None, :, None] < 21,
                   jax.nn.softplus(jax.random.normal(
                       k[1], (1, 32, 4), jnp.float32)), 0.0)
    a = -jnp.arange(1.0, 5.0, dtype=jnp.float32)
    bm = jax.random.normal(k[2], (1, 32, 6), jnp.float32)
    cm = jax.random.normal(k[3], (1, 32, 6), jnp.float32)
    _, padded = ssm_scan(xs, dt, a, bm, cm, 8)
    _, alone = ssm_scan(xs[:, :21], dt[:, :21], a, bm[:, :21], cm[:, :21], 8)
    close(padded, alone, 1e-6)


@pytest.mark.parametrize("layer,tokens", [(1, 37), (2, 37), (2, 8), (1, 5)],
                         ids=["mamba-37", "attention-37", "attention-8",
                              "mamba-5"])
def test_block_forward_matches_the_reference(layer, tokens):
    """A Mamba-2 block (chunks of 8 over 37 tokens) and the grouped-query
    attention block (4 queries on 2 key/value heads, no positions)."""
    config = tiny_config()
    m = ref.dims(config)
    model = build(config)
    x = jax.random.normal(jax.random.PRNGKey(0), (tokens, m.d), jnp.float32)
    want = ref.block(model.params[layer], x, m.mixers[layer - 1], m)
    got, _ = model.layers[layer].apply(model.params[layer], {}, x[None])
    close(got[0], want)


def test_whole_stack_forward_matches_the_reference():
    """Embedding multiplier, four layers, the final norm, the head that
    holds the table's values, the logits' scaling."""
    config = tiny_config()
    model = build(config)
    seq = np.random.default_rng(1).integers(0, 96, 50).tolist()
    x = np.zeros((1, 64, 1), np.float32)
    x[0, :50, 0] = seq
    h = model._forward(model.params, model.state, jnp.asarray(x), False,
                       None, upto=len(model.layers) - 1)[0]
    got = np.asarray(model.layers[-1].preout(model.params[-1], {}, h))[0]
    close(got[9:49], ref.served_logits(config, model.params, seq, 10, 40))
    np.testing.assert_array_equal(np.asarray(model.params[-1]["W"]),
                                  np.asarray(model.params[0]["W"]).T)


def test_a_masked_forward_is_the_forward_of_the_real_tokens():
    """`apply` under a mask: what the real tokens get does not depend on the
    padding behind them, in either mixer."""
    config = tiny_config()
    model = build(config)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 24, 64), jnp.float32)
    mask = (jnp.arange(24) < 13)[None]
    for layer in (1, 2):
        full, _ = model.layers[layer].apply(model.params[layer], {}, x,
                                            mask=mask)
        short, _ = model.layers[layer].apply(model.params[layer], {},
                                             x[:, :13])
        close(full[:, :13], short, 1e-6)


# ---------------------------------------------------------------------------
# the experts: the second scoring rule, the shared expert, the share, slots
# ---------------------------------------------------------------------------
def test_softmax_over_the_picked_logits_and_the_old_rule_beside_it():
    d, routes, k = 16, 12, 3
    key = jax.random.split(jax.random.PRNGKey(4), 3)
    u = jax.random.normal(key[0], (9, d), jnp.float32)
    w_r = jax.random.normal(key[1], (d, routes), jnp.float32)
    bias = 0.3 * jax.random.normal(key[2], (routes,), jnp.float32)
    logits = np.asarray(u, np.float64) @ np.asarray(w_r, np.float64)
    # Granite: the k largest logits, softmax over those, no bias
    new = SparseExpertsLayer(n_experts=routes, top_k=k, expert_hidden=8,
                             scoring="softmax_picked", routed_scaling=1.0)
    ids, w = new.route({"router_W": w_r}, u)
    want_ids = np.argsort(-logits, axis=1)[:, :k]
    np.testing.assert_array_equal(np.asarray(ids), want_ids)
    top = np.take_along_axis(logits, want_ids, 1)
    e = np.exp(top - top.max(1, keepdims=True))
    close(w, e / e.sum(1, keepdims=True))
    close(jnp.sum(w, -1), np.ones(9))
    assert "router_bias" not in new.init_params(
        jax.random.PRNGKey(0), None, width=d)
    # LongCat: softmax over all routes, picks by score + bias, the scores
    # themselves times the scaling, not renormalised
    old = SparseExpertsLayer(n_experts=8, n_identity=4, top_k=k,
                             expert_hidden=8, routed_scaling=6.0)
    ids, w = old.route({"router_W": w_r, "router_bias": bias}, u)
    s = np.exp(logits - logits.max(1, keepdims=True))
    s /= s.sum(1, keepdims=True)
    want_ids = np.argsort(-(s + np.asarray(bias)), axis=1)[:, :k]
    np.testing.assert_array_equal(np.asarray(ids), want_ids)
    close(w, 6.0 * np.take_along_axis(s, want_ids, 1))
    with pytest.raises(ValueError, match="scoring"):
        SparseExpertsLayer(scoring="sparsemax")


def test_experts_layer_matches_the_reference_with_its_shared_expert():
    config = tiny_config(held=(0, 8))
    m = ref.dims(config)
    model = build(config)
    p = model.params[1]["moe"]
    h = jax.random.normal(jax.random.PRNGKey(5), (40, 64), jnp.float32)
    one = jnp.ones((64,), jnp.float32)
    want = ref._moe(p, one, h, m=m, precision="float32")
    layer = model.layers[1].experts()
    assert layer.scoring == "softmax_picked" and layer.shared_hidden == 48
    got, counts = layer.mix(p, ref._norm(h, one, 1e-5)[None])
    close(got[0], want)
    assert counts.tolist()[:3] == [120, 0, 120]      # every pick is held


def test_two_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """8 routed experts over 2 chips of 4: each share's layer gives S + E_i
    (the shared expert, which every chip computes alike, and its own
    experts' part). The uncut layer is S + E_0 + E_1, so the shares' outputs
    added up, less the shared expert's counted a second time, are the uncut
    reference layer. The reference, given a share, leaves out what the
    program leaves out."""
    whole = tiny_config(held=(0, 8))
    m = ref.dims(whole)
    p = build(whole).params[1]
    x = jax.random.normal(jax.random.PRNGKey(6), (30, 64), jnp.float32)
    uncut = ref._moe(p["moe"], p["n2"], x, m=m, precision="float32")
    u = ref._norm(x, p["n2"], m.eps)
    shared = ref._gated(u, p["moe"]["shared_W_g"], p["moe"]["shared_W_u"],
                        p["moe"]["shared_W_d"], "float32")

    def share(lo, hi):
        mine = dict(p["moe"], **{k: v[lo:hi] for k, v in p["moe"].items()
                                 if k.startswith("expert_")})
        layer = SparseExpertsLayer(
            n_experts=8, top_k=3, expert_hidden=32, shared_hidden=48,
            held_experts=[lo, hi], scoring="softmax_picked")
        got, counts = layer.mix(mine, u[None])
        close(got[0], ref._moe(mine, p["n2"], x,
                               m=ref.dims(tiny_config(held=(lo, hi))),
                               precision="float32"))
        return got[0], counts

    (a, ca), (b, cb) = share(0, 4), share(4, 8)
    close(a + b - shared, uncut)
    # a token's 3 picks fall on one share or the other
    assert int(ca[2] + cb[2]) == int(ca[0]) == 90


@pytest.mark.parametrize("skew", [False, True], ids=["grouped", "overflow"])
def test_grouped_products_engage_at_ten_picks_of_72(skew):
    """Granite's density: 36 of 72 experts held, 10 picks a token. Among
    512 tokens a held expert has 128 slots a pass (a quarter of the tokens,
    1.8 times its mean load of 71): the rows are gathered into slots, one
    batched product runs every expert's, and the products computed, 36 x
    128 rows, stay under twice the (token, held expert) pairs picked. A
    routing that sends every token to one expert costs that expert twelve
    passes of 32 rows more, each under its conditional, and the others
    none: no pick is dropped either way."""
    layer = SparseExpertsLayer(n_experts=72, top_k=10, expert_hidden=8,
                               shared_hidden=8, held_experts=[0, 36],
                               scoring="softmax_picked")
    n, d = 512, 16
    slots = layer.rows_per_expert(n)
    assert slots == 128 and n > 2 * slots
    assert [layer.rows_per_expert(t) for t in (64, 256, 1024)] == [32, 64, 256]
    p = layer.init_params(jax.random.PRNGKey(7), None, width=d)
    u = jax.random.normal(jax.random.PRNGKey(8), (n, d), jnp.float32)
    if skew:        # every token picks held expert 5 first
        u = u.at[:, 0].set(30.0)
        p = dict(p, router_W=p["router_W"].at[0, 5].set(5.0))
    got, counts = layer.mix(p, u[None])
    picks, _, held, hit, load = counts.tolist()
    assert picks == 10 * n and (skew or hit == 36)
    # what the passes compute, from the routing itself
    ids, w = layer.route(p, u)
    gate = np.zeros((n, 72), np.float32)
    np.put_along_axis(gate, np.asarray(ids), np.asarray(w), 1)
    loads = (np.asarray(ids)[..., None] == np.arange(36)).sum((0, 1))
    assert (loads.sum(), loads.max()) == (held, load)
    products = int((-(-loads // slots) * slots).sum())
    if skew:
        assert load == n and -(-(load - slots) // 32) == 12
    else:
        assert load <= slots and products == 36 * slots < 2 * held
    # against every held expert over every token under its gate
    want = shortcut_moe._swiglu(u, p["shared_W_g"], p["shared_W_u"],
                                p["shared_W_d"])
    for e in range(36):
        want = want + gate[:, e:e + 1] * shortcut_moe._swiglu(
            u, p["expert_W_g"][e], p["expert_W_u"][e], p["expert_W_d"][e])
    close(got[0], want)


def test_longcat_slots_are_what_they_were():
    """12 picks of 768 routes: the factor of 8 decides, as before the
    quarter of the tokens was a bound."""
    lcf = SparseExpertsLayer(n_experts=512, n_identity=256, top_k=12,
                             held_experts=[0, 16])
    assert [lcf.rows_per_expert(n) for n in (1, 8, 32, 128, 256, 512, 1024)] \
        == [1, 8, 32, 32, 32, 64, 128]


# ---------------------------------------------------------------------------
# served: pages and per-sequence state through one engine and one pool
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


def _worst_error(config, model, prompts, seqs, out):
    worst = 0.0
    for p, s, z in zip(prompts, seqs, out):
        want = np.asarray(ref.served_logits(config, model.params, s, len(p),
                                            len(s) - len(p) + 1))
        worst = max(worst, np.abs(z - want).max() / np.abs(want).max())
    return worst


PROMPTS = (5, 19, 12)       # in buckets 8, 32, 16: none fills its bucket


def test_prefill_in_a_larger_bucket_then_ticks_match_the_reference(served):
    """Rows of 5, 19 and 12 tokens, each prefilled in a bucket it does not
    fill, then six ticks together (bucket 4: one pad row on the trash slot):
    every logit that chose a token against the reference's full causal
    forward over prompt + tokens. The geometry: one paging layer of 2
    channels of 2 x 16, three stateful layers, a slot a row of the largest
    tick and the trash slot."""
    config, model, registry, engine = served
    spec = engine.spec
    assert (spec.channels, spec.width, spec.max_context) == (2, 32, 64)
    assert len(spec.state) == 3 and spec.state_slots == 5
    assert spec.state_shapes()[0] == {
        "ssm": ((5, 8, 16, 16), jnp.dtype("float32")),
        "conv": ((3, 5, 160), jnp.dtype("float32"))}
    assert spec.state_nbytes() == 3 * 4 * (5 * 8 * 16 * 16 + 3 * 5 * 160)
    assert cache_geometry(model)[3] == spec.state
    pool, v = engine.new_pool(), registry.get("g4h")
    assert set(pool.cache) == {"kv", "state"} and len(pool.cache["state"]) == 3
    r = np.random.default_rng(0)
    prompts = [r.integers(0, 96, n).tolist() for n in PROMPTS]
    seqs, out, tables = _serve(engine, v, pool, prompts, 6)
    assert _worst_error(config, model, prompts, seqs, out) <= TOL
    assert pool.used_slots() == 3
    for t in tables:
        pool.release(t)
    assert pool.used_slots() == pool.used_blocks() == 0


def test_a_state_taken_at_the_buckets_end_fails_that_comparison(monkeypatch):
    """The fault a right-padded prefill invites: the recurrence run on over
    the padding, the convolution's stored inputs the bucket's last three.
    The same comparison then reads thousands of times its tolerance."""
    at_the_end = HybridSSMBlock._mamba
    monkeypatch.setattr(
        HybridSSMBlock, "_mamba",
        lambda self, p, u, lengths=None: at_the_end(self, p, u, None))
    config = tiny_config()
    model, registry, engine = serve(config, "faulty")
    pool, v = engine.new_pool(), registry.get("faulty")
    r = np.random.default_rng(0)
    prompts = [r.integers(0, 96, n).tolist() for n in PROMPTS]
    seqs, out, _ = _serve(engine, v, pool, prompts, 6)
    # the prefill's own logit is sound (causal: padding lies behind it) ...
    for p, s, z in zip(prompts, seqs, out):
        want = ref.served_logits(config, model.params, s, len(p), 1)
        close(z[:1], want)
    # ... every tick after it is not
    assert _worst_error(config, model, prompts, seqs, out) > 1000 * TOL


def test_rows_joining_and_leaving_leave_the_staying_row_bit_exact(served):
    """A row's ticks with two neighbours, one that joins after its second
    tick and one that leaves: bit for bit what it reads alone in the same
    bucket (rows share nothing: not a page, not a slot)."""
    config, model, registry, engine = served
    v = registry.get("g4h")
    r = np.random.default_rng(3)
    mine, other, late = (r.integers(0, 96, n).tolist() for n in (11, 7, 14))

    def run(neighbours):
        pool = engine.new_pool()
        rows = [[list(mine), pool.alloc(engine.spec.blocks_for(24))]]
        z = engine.run_prefill(v, pool, mine, rows[0][1])
        if neighbours:
            rows.append([list(other), pool.alloc(engine.spec.blocks_for(24))])
            engine.run_prefill(v, pool, other, rows[1][1])
        kept = [z]
        for step in range(6):
            if neighbours and step == 2:        # one joins ...
                rows.append([list(late),
                             pool.alloc(engine.spec.blocks_for(24))])
                engine.run_prefill(v, pool, late, rows[-1][1])
            if neighbours and step == 4:        # ... one leaves
                pool.release(rows.pop(1)[1])
            rows[0][0].append(int(np.argmax(kept[-1])))
            for row in rows[1:]:
                row[0].append(row[0][-1])
            out = engine.run_tick(
                v, pool, [s[-1] for s, _ in rows],
                [len(s) - 1 for s, _ in rows], [t for _, t in rows], bucket=4)
            kept.append(out[0])
        return np.stack(kept)

    np.testing.assert_array_equal(run(True), run(False))


def test_a_slot_reused_after_release_gives_what_a_fresh_one_gives(served):
    """Blocks and the slot freed by one sequence and taken by another: its
    logits are bit for bit a fresh pool's (the prefill overwrites the slot:
    that is the reset), and so is the state it leaves."""
    config, model, registry, engine = served
    v = registry.get("g4h")
    r = np.random.default_rng(4)
    first, second = r.integers(0, 96, 21).tolist(), r.integers(0, 96, 9).tolist()

    def gen(pool):
        seqs, out, tables = _serve(engine, v, pool, [second], 4)
        return out[0], tables[0]

    used = engine.new_pool()
    _, _, stale = _serve(engine, v, used, [first], 3)
    slot = used.slots_of([stale[0][0]])[0]
    used.release(stale[0])
    assert used.used_slots() == 0
    again, table = gen(used)
    assert used.slots_of([table[0]]) == [slot]        # the same slot, reset
    fresh_pool = engine.new_pool()
    fresh, fresh_table = gen(fresh_pool)
    np.testing.assert_array_equal(again, fresh)
    at = fresh_pool.slots_of([fresh_table[0]])[0]
    for mine, theirs in zip(used.cache["state"], fresh_pool.cache["state"]):
        np.testing.assert_array_equal(np.asarray(mine["ssm"][slot]),
                                      np.asarray(theirs["ssm"][at]))
        np.testing.assert_array_equal(np.asarray(mine["conv"][:, slot]),
                                      np.asarray(theirs["conv"][:, at]))
        assert float(jnp.abs(mine["ssm"][slot]).max()) > 0


def test_slots_follow_from_the_pools_calls_alone(served):
    """What the benchmark's driver does and no more: alloc, prefill, tick,
    release. A tick before any prefill has no slot to find; a pool without
    a free slot refuses the prefill as it refuses blocks; the gauge
    follows."""
    config, model, registry, engine = served
    v = registry.get("g4h")
    metrics = MetricsRegistry()
    pool = engine.new_pool(metrics)
    gauge = lambda state: metrics.gauge(
        "dl4j_decode_state_slots", "", labels=("model", "state")).value(
            model="g4h", state=state)
    assert (gauge("free"), gauge("used")) == (4, 0)
    blocks = pool.alloc(3)
    with pytest.raises(KeyError):
        engine.run_tick(v, pool, [1], [0], [blocks], bucket=1)
    engine.run_prefill(v, pool, [1, 2, 3], blocks)
    assert (gauge("free"), gauge("used")) == (3, 1)
    engine.run_prefill(v, pool, [1, 2, 3, 4], blocks)   # the same sequence
    assert pool.used_slots() == 1
    others = [pool.alloc(2) for _ in range(4)]
    for t in others[:3]:
        engine.run_prefill(v, pool, [5, 6], t)
    with pytest.raises(OutOfBlocksError, match="state slot"):
        engine.run_prefill(v, pool, [5, 6], others[3])
    pool.release(blocks)
    assert (gauge("free"), gauge("used")) == (1, 3)
    engine.run_prefill(v, pool, [5, 6], others[3])
    for t in others:
        pool.release(t)
    assert (gauge("free"), gauge("used")) == (4, 0)
    assert pool.used_blocks() == 0


def test_eviction_and_re_prefill_reproduce_the_greedy_sequences():
    """Under block pressure the scheduler evicts (blocks and slot freed by
    `release`), the victim re-prefills prompt + generated, which rebuilds
    its state: every client still gets the tokens an unpressed server
    gives, and nothing is left held."""
    config = tiny_config()
    model = build(config)
    prompts = [[1, 2, 3, 4], [5, 6, 7, 8, 9], [9, 10, 11, 12]]

    def generate(name, **sched):
        registry = ModelRegistry(buckets=(1,))
        registry.register(name, model)
        metrics = MetricsRegistry()
        s = GenerationScheduler(registry, name, block_len=4,
                                decode_buckets=(1, 2, 4), metrics=metrics,
                                **sched)
        got = [None] * len(prompts)

        def client(i):
            got[i] = s.submit(prompts[i], max_tokens=12, timeout=300)["tokens"]

        try:
            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(len(prompts))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert s.pool.used_blocks() == s.pool.used_slots() == 0
        finally:
            s.stop()
        evicted = metrics.counter("dl4j_decode_evictions_total", "",
                                  labels=("model",)).value(model=name)
        return got, evicted

    want, none = generate("roomy")
    # 7 usable blocks of 4 slots: three 17-token sequences cannot all stay
    got, evicted = generate("pressed", num_blocks=8)
    assert none == 0 and evicted >= 1
    assert got == want
    # and the unpressed answer is the reference's greedy continuation
    seq = prompts[1] + want[1]
    z = np.asarray(ref.served_logits(config, model.params, seq, 5, 12))
    assert np.argmax(z, axis=1).tolist() == want[1]


def test_a_stop_id_met_a_tick_late_frees_the_blocks_and_the_slot():
    """The scheduler starts the next tick before it has seen this one's
    ids, so a sequence that ends on a stop id has a row in the tick in
    flight: that row still reads and writes the sequence's pages and state
    slot, which are given up all the same (the next owner's prefill is
    dispatched later). The caller gets the tokens before the stop id, and
    a sequence admitted into the freed slot gets its own greedy tokens."""
    config = tiny_config()
    model = build(config)
    registry = ModelRegistry(buckets=(1,))
    registry.register("stop", model)
    s = GenerationScheduler(registry, "stop", block_len=4,
                            decode_buckets=(1, 2))
    try:
        prompt = [5, 6, 7, 8, 9]
        full = s.submit(prompt, max_tokens=8, timeout=300)["tokens"]
        stop = next(t for t in full[1:] if t != full[0])
        got = s.submit(prompt, max_tokens=8, stop=[stop], timeout=300)
        assert got["finish_reason"] == "stop"
        assert got["tokens"] == full[:full.index(stop)]
        assert s.submit(prompt, max_tokens=8, timeout=300)["tokens"] == full
        deadline = time.monotonic() + 60
        while s.pool.used_blocks() or s.pool.used_slots():
            assert time.monotonic() < deadline
            time.sleep(0.001)
    finally:
        s.stop()


# ---------------------------------------------------------------------------
# spans and counters; what the other families' engines keep
# ---------------------------------------------------------------------------
@pytest.fixture
def span_log():
    previous = telemetry.tracer()
    install_tracer(Tracer())
    hybrid_ssm._scan_record.cache_clear()
    yield telemetry.tracer()
    install_tracer(previous)


def test_state_on_the_instants_the_spans_and_the_fetch_counts(span_log):
    config = tiny_config()
    model, registry, engine = serve(config, "spans")
    pool, v = engine.new_pool(), registry.get("spans")
    _serve(engine, v, pool, [[3, 1, 4, 1, 5], [9, 2, 6]], 2)
    log = span_log.snapshot()
    named = lambda name: [r for r in log if r["name"] == name]
    built = {(r["attrs"]["phase"], r["attrs"]["bucket"]): r["attrs"]
             for r in named("dl4j/engine/executable")}
    assert set(built) == {("prefill", 8), ("tick", 2)}
    for attrs in built.values():
        assert attrs["state_bytes"] == engine.spec.state_nbytes()
        assert attrs["state_slots"] == 5
        assert attrs["arena_bytes"] == engine.spec.arena_nbytes()
    prepares = named("dl4j/engine/tick.prepare")
    assert [r["attrs"]["state_slots_live"] for r in prepares] == [2, 2]
    # the scan's shape, once per call shape: the registry's stateless
    # forward over the 64 positions, then the prefill's 8 tokens, one chunk
    scans = [r["attrs"] for r in named("dl4j/layers/ssm_scan")]
    assert scans == [dict(batch=1, tokens=t, chunk=8, chunks=t // 8, heads=8,
                          head_dim=16, state=16, groups=1,
                          state_bytes=4 * 8 * 16 * 16)
                     for t in (64, 8)]
    # the experts' five counts, summed over the four layers
    fetch = named("dl4j/engine/tick.fetch")[-1]["attrs"]
    assert fetch["moe_layers"] == 4 and fetch["moe_identity"] == 0
    assert fetch["moe_picks"] == 4 * 2 * 3
    assert 0 < fetch["moe_held"] <= fetch["moe_picks"]


def _lcf_engine():
    lcf_ref = _load("reference", "longcat_flash")
    lcf_models = _load("models", "longcat_flash")
    real = json.loads((BENCH / "configs" / "longcat-flash-chat.json").read_text())
    config = dict(
        real, vocab_size=96, hidden_size=64, ffn_hidden_size=128,
        expert_ffn_hidden_size=32, num_layers=1, num_attention_heads=4,
        kv_lora_rank=16, q_lora_rank=32, qk_rope_head_dim=8, v_head_dim=16,
        qk_nope_head_dim=16, n_routed_experts=4, zero_expert_num=8,
        moe_topk=4, max_position_embeddings=32,
        published=dict(real["published"], n_routed_experts=16),
        deployment=dict(real["deployment"], held_experts=[0, 4]),
        precision=dict(real["precision"], weights="float32", registry="fp32",
                       kv_dtype="fp32", reference="float32"))
    return lcf_models.build(config, 3, lcf_ref, train=False)


def _gpt_model():
    from deeplearning4j_tpu import (EmbeddingSequenceLayer, InputType,
                                    MultiLayerNetwork, NeuralNetConfiguration,
                                    RnnOutputLayer, Sgd, TransformerBlock)
    conf = (NeuralNetConfiguration.builder().seed(1).updater(Sgd(0.0)).list()
            .layer(EmbeddingSequenceLayer(n_in=40, n_out=16))
            .layer(TransformerBlock(n_heads=2))
            .layer(RnnOutputLayer(n_out=40, activation="softmax",
                                  loss="mcxent"))
            .set_input_type(InputType.recurrent(1, 32)).build())
    return MultiLayerNetwork(conf).init()


@pytest.mark.parametrize("family", ["gpt", "longcat", "granite"])
def test_executables_take_the_arguments_they_took(family, span_log):
    """A stack with no stateful layer has no new leaf, argument or upload:
    its tick takes (weights, {"kv"}, the last tick's ids, tokens, positions,
    tables) and its prefill (weights, {"kv"}, tokens, lengths, tables); no
    slot is kept for it and its spans carry none. Granite's take the rows'
    slots as one argument more and a cache with "state"."""
    model = {"gpt": _gpt_model, "longcat": _lcf_engine,
             "granite": lambda: build(tiny_config())}[family]()
    registry = ModelRegistry(buckets=(1,))
    registry.register(family, model)
    engine = DecodeEngine(registry, family, block_len=4, decode_buckets=(2,),
                          prompt_buckets=(8,))
    v = registry.get(family)
    stateful = family == "granite"
    assert bool(engine.spec.state) == stateful
    assert engine.spec.state_slots == (3 if stateful else 0)
    for exe, more in ((engine.decode_exec(v, 2), 1),
                      (engine.prefill_exec(v, 8), 0)):
        (args, _) = exe.in_tree.unflatten(list(range(exe.in_tree.num_leaves)))
        assert len(args) == (6 if stateful else 5) + more
        assert set(args[1]) == ({"kv", "state"} if stateful else {"kv"})
    pool = engine.new_pool(MetricsRegistry())
    assert set(pool.cache) == ({"kv", "state"} if stateful else {"kv"})
    assert (pool._slots_g is not None) == stateful
    _serve(engine, v, pool, [[3, 1, 4], [1, 5]], 1)
    log = span_log.snapshot()
    prepare = [r for r in log if r["name"] == "dl4j/engine/tick.prepare"][-1]
    assert ("state_slots_live" in prepare["attrs"]) == stateful
    built = [r["attrs"] for r in log if r["name"] == "dl4j/engine/executable"]
    assert all(("state_bytes" in a) == stateful for a in built) and built
    assert pool.used_slots() == (2 if stateful else 0)


def test_a_stack_whose_layers_keep_state_but_page_nothing_is_refused():
    config = tiny_config(layer_types=["mamba"] * 4)
    registry = ModelRegistry(buckets=(1,))
    registry.register("ssm-only", build(config))
    with pytest.raises(ServingError, match="needs a layer that pages"):
        DecodeEngine(registry, "ssm-only", block_len=4)
    with pytest.raises(ValueError, match="mixer"):
        HybridSSMBlock(mixer="rwkv")
