"""The SambaY decoder-hybrid-decoder (`nn/layers/sambay.py`) that serves
Phi-4-mini-flash-reasoning, at a small size on the CPU against the
benchmark's plain reference (`benchmarks/reference/phi4flash.py`) with the
same structure: 8 layers (Mamba, window, Mamba, window | Mamba, full, GMU,
cross), a window of 8 keys, pages of 4 slots.

The selective scan against the sequential recurrence, differential attention
against its formula, the stack's forward, and the served path: prefill then
ticks through `DecodeEngine`, prompts longer than the window, generation
across window and page boundaries; the prefill that runs the cross-decoder for
the last token alone; the window's ring bounded whatever the context; the one
shared pair of channels, written by the full layer alone; rows joining and
leaving; the records. No test reads a sampled token: logits are compared.

The weights are float32 here (XLA's CPU backend has no bfloat16 batch
product), so the program and the reference differ by the order of their
float32 sums alone: tolerances of 2e-5 relative to the largest value
compared, far under what leaving out any term of the equations gives."""
from __future__ import annotations

import importlib.util
import json
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.kernels import paged_attention as paged_mod
from deeplearning4j_tpu.kernels import ring_attention as ring_mod
from deeplearning4j_tpu.nn.layers import sambay
from deeplearning4j_tpu.nn.layers.sambay import (CrossDecoderBlock,
                                                 SambaYBlock, selective_scan,
                                                 selective_step)
from deeplearning4j_tpu.serving import ModelRegistry
from deeplearning4j_tpu.serving.decode import DecodeEngine, GenerationScheduler
from deeplearning4j_tpu.serving.decode.cache import CacheIO
from deeplearning4j_tpu.telemetry import Tracer, install_tracer

BENCH = Path(__file__).resolve().parents[1] / "benchmarks"
TOL = 2e-5          # of the largest value compared (module docstring)


def _load(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"p4f_test_{kind}_{name}", BENCH / kind / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


ref = _load("reference", "phi4flash")
models = _load("models", "phi4flash")
REAL = json.loads((BENCH / "configs" / "phi-4-mini-flash-reasoning.json")
                  .read_text())


def tiny_config(**changes):
    """The cell's configuration at a size a CPU test can run: 8 layers, 64
    wide, 4 differential heads of 8 on 2 key/value heads, a window of 8, a
    Mamba state of 4 with a dt rank of 4 in chunks of 4, 64 positions."""
    config = dict(
        REAL, hidden_size=64, num_attention_heads=8, num_key_value_heads=4,
        num_hidden_layers=8, intermediate_size=32, vocab_size=96,
        max_position_embeddings=64, sliding_window=8, mamba_d_state=4,
        mamba_dt_rank=4, mamba_chunk_size=4,
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


def serve(config, name="p4f", **engine):
    """(model, registry, engine) of a fresh registry."""
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


@pytest.fixture
def span_log():
    previous = telemetry.tracer()
    install_tracer(Tracer())
    sambay._scan_record.cache_clear()
    yield telemetry.tracer()
    install_tracer(previous)


# ---------------------------------------------------------------------------
# the scan and the mixers against the reference
# ---------------------------------------------------------------------------
def _sequential(x, dt, a, bm, cm):
    """The recurrence token by token, in float64 on the host."""
    x, dt, a, bm, cm = (np.asarray(z, np.float64) for z in (x, dt, a, bm, cm))
    b, t, e = x.shape
    state = np.zeros((b, e, a.shape[-1]))
    ys = np.zeros_like(x)
    for i in range(t):
        state = np.exp(dt[:, i, :, None] * a) * state \
            + (dt[:, i] * x[:, i])[..., None] * bm[:, i, None, :]
        ys[:, i] = np.sum(state * cm[:, i, None, :], -1)
    return ys, state


def _scan_inputs(tokens, seed=0, e=12, n=4):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(k[0], (2, tokens, e), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(k[1], (2, tokens, e), jnp.float32)
                         - 2.0)
    a = -jnp.exp(jax.random.uniform(k[2], (e, n), jnp.float32, 0.0, 2.7))
    bm = jax.random.normal(k[3], (2, tokens, n), jnp.float32)
    cm = jax.random.normal(k[4], (2, tokens, n), jnp.float32)
    return x, dt, a, bm, cm


@pytest.mark.parametrize("tokens,chunk", [(29, 4), (8, 8), (5, 8), (33, 16),
                                          (64, 32), (70, 256)])
def test_selective_scan_matches_the_sequential_recurrence(tokens, chunk):
    """Lengths that are no multiple of the chunk, one chunk, a chunk longer
    than the sequence: outputs and the state after the last token; a step
    of the recurrence continues the scan (the tick's arithmetic)."""
    x, dt, a, bm, cm = _scan_inputs(tokens)
    want_y, want_state = _sequential(x, dt, a, bm, cm)
    y, state = selective_scan(x, dt, a, bm, cm, chunk)
    close(y, want_y)
    close(state, want_state)
    y1, state1 = selective_step(state, x[:, 0], dt[:, 0], a, bm[:, 0],
                                cm[:, 0])
    more = lambda z: jnp.concatenate([z, z[:, :1]], 1)
    want_y1, want_state1 = _sequential(more(x), more(dt), a, more(bm),
                                       more(cm))
    close(y1, want_y1[:, -1])
    close(state1, want_state1)


def test_right_padding_leaves_the_state_after_the_last_real_token():
    """dt = 0 past 21 real tokens of 32: the state is the 21 tokens' alone,
    and the outputs of the real positions are theirs."""
    x, dt, a, bm, cm = _scan_inputs(32, seed=1)
    dt = jnp.where(jnp.arange(32)[None, :, None] < 21, dt, 0.0)
    y, padded = selective_scan(x, dt, a, bm, cm, 8)
    y21, alone = selective_scan(x[:, :21], dt[:, :21], a, bm[:, :21],
                                cm[:, :21], 8)
    close(padded, alone, 1e-6)
    close(y[:, :21], y21, 1e-6)


def _attention_formula(q, k, v, ok, lam):
    """(A1 - lam A2) v head by head, in float64: q [T, H, 2, Dh], k [S, Hkv,
    2, Dh], v [S, Hkv, 2Dh], ok [T, S]."""
    q, k, v = (np.asarray(z, np.float64) for z in (q, k, v))
    t, h, _, dh = q.shape
    out = np.zeros((t, h, v.shape[-1]))
    for i in range(h):
        g = i // (h // k.shape[1])
        maps = []
        for s in range(2):
            z = np.where(ok, q[:, i, s] @ k[:, g, s].T / math.sqrt(dh), -np.inf)
            w = np.exp(z - z.max(1, keepdims=True))
            maps.append(w / w.sum(1, keepdims=True))
        out[:, i] = (maps[0] - lam * maps[1]) @ v[:, g]
    return out


@pytest.mark.parametrize("window", [0, 5])
def test_differential_attention_matches_its_formula(window):
    """Over local keys (a prompt: causal, within the window) and through the
    block-diagonal query over merged keys (a tick's ring or view)."""
    w = SambaYBlock(n_heads=4, n_kv_heads=2, head_dim=8, window=window,
                    mlp_hidden=8).widths(64)
    r = np.random.default_rng(0)
    t = 13
    q = r.normal(size=(t, 4, 2, 8)).astype(np.float32)
    k = r.normal(size=(t, 2, 2, 8)).astype(np.float32)
    v = r.normal(size=(t, 2, 16)).astype(np.float32)
    at, key = np.arange(t)[:, None], np.arange(t)[None, :]
    ok = (key <= at) & ((at - key < window) if window else True)
    want = _attention_formula(q, k, v, ok, 0.37)
    got = sambay.diff_attend(jnp.asarray(q)[None], jnp.asarray(k).reshape(
        1, t, -1), jnp.asarray(v).reshape(1, t, -1), jnp.asarray(ok)[None],
        0.37, w, jnp.float32)
    close(got[0], want)
    rows = sambay.diff_attend_rows(
        jnp.asarray(q), jnp.broadcast_to(k.reshape(1, t, -1), (t, t, 32)),
        jnp.broadcast_to(v.reshape(1, t, -1), (t, t, 32)), jnp.asarray(ok),
        0.37, w, jnp.float32)
    close(rows, want)


@pytest.mark.parametrize("layer,tokens", [(1, 21), (2, 21), (2, 5), (1, 3)],
                         ids=["mamba-21", "window-21", "window-5", "mamba-3"])
def test_self_decoder_layers_match_the_reference(layer, tokens):
    config = tiny_config()
    m = ref.dims(config)
    model = build(config)
    x = jax.random.normal(jax.random.PRNGKey(0), (tokens, 64), jnp.float32)
    p = model.params[layer]
    if layer == 1:
        want, _ = ref._mamba(p, x, m=m, precision="float32")
    else:
        want, _, _ = ref._attention(p, x, jnp.float32(layer - 1), m=m,
                                    precision="float32", window=m.window)
    got, _ = model.layers[layer].apply(p, {}, x[None])
    close(got[0], want)


def test_whole_stack_forward_matches_the_reference():
    """The self-decoder, the cross-decoder with its memory and shared keys,
    the final LayerNorm, the head that holds the table's values."""
    config = tiny_config()
    model = build(config)
    seq = np.random.default_rng(1).integers(0, 96, 40).tolist()
    x = np.zeros((1, 64, 1), np.float32)
    x[0, :40, 0] = seq
    h = model._forward(model.params, model.state, jnp.asarray(x), False,
                       None, upto=len(model.layers) - 1)[0]
    got = np.asarray(model.layers[-1].preout(model.params[-1], {}, h))[0]
    close(got[9:39], ref.served_logits(config, model.params, seq, 10, 30))
    np.testing.assert_array_equal(np.asarray(model.params[-1]["W"]),
                                  np.asarray(model.params[0]["W"]).T)


# ---------------------------------------------------------------------------
# served: per-sequence state, window rings and the shared pages
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


PROMPTS = (5, 19, 12)       # in buckets 8, 32, 16; two longer than the window


def test_prefill_then_ticks_match_the_reference_across_window_and_pages(
        served):
    """Rows of 5, 19 and 12 tokens (window 8, pages of 4), each prefilled in
    a bucket it does not fill, then 14 ticks together (bucket 4: one pad row):
    every ring wraps and every row crosses pages; each logit that chose a
    token against the reference's full causal forward. The geometry: ONE
    pair of channels of 2 x 16 lanes, for the 2 layers that read it; 5
    stateful layers (3 Mamba, 2 window)."""
    config, model, registry, engine = served
    spec = engine.spec
    assert (spec.channels, spec.width, spec.max_context) == (2, 32, 64)
    assert len(spec.state) == 5 and spec.state_slots == 5
    assert (engine.window, engine.shared_readers, engine.prefill_last) == (
        8, 2, True)
    pool, v = engine.new_pool(), registry.get("p4f")
    r = np.random.default_rng(0)
    prompts = [r.integers(0, 96, n).tolist() for n in PROMPTS]
    seqs, out, tables = _serve(engine, v, pool, prompts, 14)
    assert _worst_error(config, model, prompts, seqs, out) <= TOL
    assert pool.used_slots() == 3
    for t in tables:
        pool.release(t)
    assert pool.used_slots() == pool.used_blocks() == 0


def test_the_prefill_computes_the_cross_decoder_for_the_last_token_alone(
        served, span_log):
    """The YOCO skip: the prefill hands the head each prompt's last real
    token alone (the cross-decoder's output is one position wide), and its
    logits are those of a full pass over every layer; the span says
    `cross_tokens` 1 a sequence."""
    config, model, registry, engine = served
    pool, v = engine.new_pool(), registry.get("p4f")
    prompt = np.random.default_rng(6).integers(0, 96, 13).tolist()
    got = engine.run_prefill(v, pool, prompt, pool.alloc(4))
    x = np.zeros((1, 64, 1), np.float32)
    x[0, :13, 0] = prompt
    full = model._forward(model.params, model.state, jnp.asarray(x), False,
                          None, upto=len(model.layers) - 1)[0]
    close(got, model.layers[-1].preout(model.params[-1], {}, full)[0, 12])
    block = model.layers[-3]
    assert isinstance(block, CrossDecoderBlock)
    step = block.decode_prefill_step(CacheIO(engine.spec))
    cache = pool.cache
    hidden = jnp.zeros((1, 16, 64), jnp.float32)
    pos = jnp.arange(16, dtype=jnp.int32)[None]
    y = jax.eval_shape(
        step, model.params[-3], hidden, cache["kv"], None, jnp.int32(0),
        jnp.zeros((1, 16), jnp.int32), pos % 4, pos, jnp.asarray([13]),
        cache["state"][-1], jnp.zeros((1,), jnp.int32))[0]
    assert y.shape == (1, 1, 64)
    prepares = [r["attrs"] for r in span_log.snapshot()
                if r["name"] == "dl4j/engine/prefill.prepare"]
    assert prepares == [dict(bucket=16, tokens=13, cross_tokens=1)]


def test_only_the_full_layer_writes_the_shared_channels(served):
    """After a prefill of 11 tokens the sequence's pages of the one channel
    pair hold the full layer's keys and values (the reference's layer 5)
    and nothing else wrote there."""
    config, model, registry, engine = served
    m = ref.dims(config)
    pool, v = engine.new_pool(), registry.get("p4f")
    prompt = np.random.default_rng(7).integers(0, 96, 11).tolist()
    table = pool.alloc(3)
    engine.run_prefill(v, pool, prompt, table)
    x = jnp.asarray(model.params[0]["W"])[jnp.asarray(prompt)]
    layers = list(model.params[1:-3]) + list(model.params[-3]["layers"])
    for layer, (p, kind) in enumerate(list(zip(layers, ref.kinds(m)))[:5]):
        x = (ref._mamba(p, x, m=m, precision="float32")[0] if kind == "mamba"
             else ref._attention(p, x, jnp.float32(layer), m=m,
                                 precision="float32", window=m.window)[0])
    _, k, vals = ref._attention(layers[5], x, jnp.float32(5), m=m,
                                precision="float32", window=0)
    view = lambda c: np.asarray(CacheIO(engine.spec).gather(
        pool.cache["kv"], None, jnp.asarray([table]), c)).reshape(-1, 32)[:11]
    close(view(0), k)
    close(view(1), vals)


def test_a_windows_ring_is_bounded_whatever_the_context():
    """The window layers keep W slots a sequence whatever the context the
    stack serves; the pages of the shared pair grow with it."""
    small = serve(tiny_config(), "ctx64")[2].spec
    large = serve(tiny_config(max_position_embeddings=256), "ctx256")[2].spec
    rings = lambda spec: [{name: (shape, dtype) for name, shape, dtype in layer}
                          for layer in spec.state
                          if any(name == "k" for name, _, _ in layer)]
    assert rings(small) == rings(large) and len(rings(small)) == 2
    assert rings(small)[0]["k"] == (("slots", 8, 32), "float32")
    assert large.table_width == 4 * small.table_width


def test_rows_joining_and_leaving_leave_the_staying_row_bit_exact(served):
    """A row's ticks with two neighbours, one that joins after its second
    tick and one that leaves: bit for bit what it reads alone in the same
    bucket (rows share nothing: not a page, not a slot, not a ring)."""
    config, model, registry, engine = served
    v = registry.get("p4f")
    r = np.random.default_rng(3)
    mine, other, late = (r.integers(0, 96, n).tolist() for n in (11, 7, 14))

    def run(neighbours):
        pool = engine.new_pool()
        rows = [[list(mine), pool.alloc(engine.spec.blocks_for(30))]]
        z = engine.run_prefill(v, pool, mine, rows[0][1])
        if neighbours:
            rows.append([list(other), pool.alloc(engine.spec.blocks_for(30))])
            engine.run_prefill(v, pool, other, rows[1][1])
        kept = [z]
        for step in range(10):
            if neighbours and step == 2:        # one joins ...
                rows.append([list(late),
                             pool.alloc(engine.spec.blocks_for(30))])
                engine.run_prefill(v, pool, late, rows[-1][1])
            if neighbours and step == 6:        # ... one leaves
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


def test_the_scheduler_serves_it_and_gives_everything_back():
    """Through `GenerationScheduler`, as `/generate` does: greedy tokens that
    are the reference's argmax, and every block and slot free at the end."""
    config = tiny_config()
    model = build(config)
    registry = ModelRegistry(buckets=(1,))
    registry.register("sched", model)
    s = GenerationScheduler(registry, "sched", block_len=4,
                            decode_buckets=(1, 2))
    try:
        prompt = [5, 6, 7, 8, 9, 10, 11, 12, 13, 14]
        got = s.submit(prompt, max_tokens=12, timeout=300)["tokens"]
    finally:
        s.stop()
    assert s.pool.used_blocks() == s.pool.used_slots() == 0
    z = np.asarray(ref.served_logits(config, model.params, prompt + got, 10,
                                      12))
    assert np.argmax(z, axis=1).tolist() == got


# ---------------------------------------------------------------------------
# the tick through the kernel, and the records
# ---------------------------------------------------------------------------
def test_the_tick_through_the_kernel_agrees_with_the_tick_through_the_view(
        monkeypatch):
    """The tick with the kernels (the cross-decoder's `diff_paged` and the
    window layers' `ring_kernel`, interpreted, as the TPU would compile
    them) and with the views (`diff_gather`, `ring_gather`), over the same
    prefilled pages and rings: the same logits, and the same pages and
    rings written."""
    monkeypatch.setattr(
        paged_mod, "paged_diff_attention",
        lambda *a, interpret, **kw: paged_mod._diff_call(
            *a, kw["n_kv_heads"], kw["sm_scale"], True))
    monkeypatch.setattr(
        ring_mod, "ring_diff_attention",
        lambda *a, interpret, **kw: ring_mod._ring_call(
            *a, kw["n_heads"], kw["n_kv_heads"], kw["sm_scale"], True))
    config = tiny_config()
    out = {}
    for attention, window in (("diff_paged", "ring_kernel"),
                              ("diff_gather", "ring_gather")):
        monkeypatch.setattr(CrossDecoderBlock, "decode_attention",
                            lambda self, phase, spec, a=attention:
                            a if phase == "tick" else None)
        monkeypatch.setattr(SambaYBlock, "decode_window_attention",
                            lambda self, phase, width, a=window:
                            a if phase == "tick" and self.mixer == "window"
                            else None)
        model, registry, engine = serve(config, attention)
        assert (engine.attention, engine.window_attention) == (attention,
                                                               window)
        pool, v = engine.new_pool(), registry.get(attention)
        r = np.random.default_rng(2)
        # the first row's ring fills during the ticks; the second's wraps
        prompts = [r.integers(0, 96, n).tolist() for n in (5, 14)]
        _, logits, tables = _serve(engine, v, pool, prompts, 5)
        rings = [np.asarray(leaf) for layer in pool.cache["state"]
                 for name, leaf in sorted(layer.items()) if name in ("k", "v")]
        out[attention] = (np.stack(logits), np.asarray(pool.cache["kv"]),
                          rings)
    np.testing.assert_allclose(out["diff_paged"][0], out["diff_gather"][0],
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(out["diff_paged"][1], out["diff_gather"][1],
                               rtol=1e-5, atol=1e-6)
    assert len(out["diff_paged"][2]) == 4       # two window layers' k and v
    for got, want in zip(out["diff_paged"][2], out["diff_gather"][2]):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_records_the_paths_the_window_and_the_scan(span_log):
    config = tiny_config()
    model, registry, engine = serve(config, "records")
    pool, v = engine.new_pool(), registry.get("records")
    _serve(engine, v, pool, [[3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5], [9, 2, 6]], 3)
    log = span_log.snapshot()
    named = lambda name: [r["attrs"] for r in log if r["name"] == name]
    built = {(a["phase"], a["bucket"]): a
             for a in named("dl4j/engine/executable")}
    assert set(built) == {("prefill", 16), ("prefill", 8), ("tick", 2)}
    assert built[("tick", 2)]["attention"] == "diff_gather"    # the CPU
    for a in built.values():
        assert (a["window"], a["shared_readers"]) == (8, 2)
        assert a["state_bytes"] == engine.spec.state_nbytes()
    # the ring slots the rows read: min(position + 1, 8) a row
    windows = [a["window_live"] for a in named("dl4j/engine/tick.prepare")]
    assert windows == [8 + 4, 8 + 5, 8 + 6]
    scans = named("dl4j/layers/ssm_scan")
    assert scans and all(s["kind"] == "selective" for s in scans)
    assert {(s["tokens"], s["chunk"]) for s in scans} >= {(16, 4), (8, 4)}
    assert all(s["state_bytes"] == 4 * s["batch"] * 128 * 4 for s in scans)


def test_records_the_window_path_and_the_ring_kernel_once_a_call_shape(
        span_log, monkeypatch):
    """The tick's executable names the path its window layers took: the
    rings gathered on the CPU, the kernel where the layers answer it (here
    interpreted); the prefill has no such choice. The kernel's instant is
    written once for the call shape its two window layers share."""
    config = tiny_config()
    prompts = [[3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5], [9, 2, 6], [2, 7, 1]]
    _, registry, engine = serve(config, "gathered")
    _serve(engine, registry.get("gathered"), engine.new_pool(), prompts, 1)
    monkeypatch.setattr(
        ring_mod, "ring_diff_attention",
        lambda *a, interpret, **kw: ring_mod._ring_call(
            *a, kw["n_heads"], kw["n_kv_heads"], kw["sm_scale"], True))
    monkeypatch.setattr(SambaYBlock, "decode_window_attention",
                        lambda self, phase, width:
                        "ring_kernel" if phase == "tick"
                        and self.mixer == "window" else None)
    ring_mod._planned_ring.cache_clear()
    ring_mod._ring_call.clear_cache()
    _, registry, engine = serve(config, "ringed")
    pool = engine.new_pool()
    _serve(engine, registry.get("ringed"), pool, prompts, 3)
    log = span_log.snapshot()
    named = lambda name: [r["attrs"] for r in log if r["name"] == name]
    built = {(a["model"], a["phase"]): a
             for a in named("dl4j/engine/executable")}
    assert built[("gathered", "tick")]["window_attention"] == "ring_gather"
    assert built[("ringed", "tick")]["window_attention"] == "ring_kernel"
    assert not any("window_attention" in a for (_, phase), a in built.items()
                   if phase == "prefill")
    rings = named("dl4j/kernels/ring_attention")
    assert len(rings) == 1
    assert {key: rings[0][key] for key in (
        "rows", "slots", "window", "chunk", "piece", "chunks_a_row",
        "steps_a_call", "n_heads", "n_kv_heads", "width")} == {
            "rows": 4, "slots": engine.spec.state_slots, "window": 8,
            "chunk": 8, "piece": 8, "chunks_a_row": 1, "steps_a_call": 4,
            "n_heads": 4, "n_kv_heads": 2, "width": 32}
    assert rings[0]["vmem_bytes"] > 0


def test_refusals():
    with pytest.raises(ValueError, match="mixer"):
        SambaYBlock(mixer="rwkv")
    with pytest.raises(ValueError, match="even"):
        CrossDecoderBlock(layers=3)
    with pytest.raises(ValueError, match="diff_paged"):
        CrossDecoderBlock(n_heads=4, n_kv_heads=2, head_dim=8,
                          mlp_hidden=8).decode_tick_step(None, "paged_kernel")
    with pytest.raises(ValueError, match="ring_kernel"):
        SambaYBlock(mixer="window").decode_tick_step(None, None, "gather")
