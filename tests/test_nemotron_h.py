"""The Nemotron-H layers (`nn/layers/nemotron_h.py`) and what they brought to
the shared code, at a small size on the CPU against the benchmark's plain
reference (`benchmarks/reference/nemotron_h.py`): Mamba-2 with G groups
(the chunked scan against the sequential recurrence, the one-group case
as it was, the norm within each group), the sigmoid router normalised over
all its picks against a hand computation, relu^2 experts in a latent and
the four shares of the routed experts adding up to the uncut layer, each
layer kind and the whole stack against the reference, and the served path:
prefill in a larger bucket then ticks through `DecodeEngine` over pages and
per-sequence state, only the expert layers counting picks.

The weights are float32 here (XLA's CPU backend has no bfloat16 batch
product), so the program and the reference differ by the order of their
float32 sums alone: TOL 2e-5 relative to the largest value compared, far
under what leaving out any term of the equations gives (the faults shown
below read thousands of times over it)."""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.nn.layers import hybrid_ssm
from deeplearning4j_tpu.nn.layers.hybrid_ssm import ssm_scan, ssm_step
from deeplearning4j_tpu.nn.layers.nemotron_h import NemotronHBlock
from deeplearning4j_tpu.nn.layers.shortcut_moe import SparseExpertsLayer
from deeplearning4j_tpu.serving import ModelRegistry
from deeplearning4j_tpu.serving.decode import DecodeEngine
from deeplearning4j_tpu.telemetry import Tracer, install_tracer

BENCH = Path(__file__).resolve().parents[1] / "benchmarks"
TOL = 2e-5          # of the largest value compared (module docstring)


def _load(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"nemotron_test_{kind}_{name}", BENCH / kind / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


ref = _load("reference", "nemotron_h")
models = _load("models", "nemotron_h")
REAL = json.loads(
    (BENCH / "configs" / "nemotron-3-super-120b-a12b.json").read_text())


def tiny_config(held=(0, 16), routed=32, **changes):
    """The cell's configuration at a size a CPU test can run: 5 layers
    M E M * E, 8 Mamba heads of 8 in 4 groups with a state of 16 in chunks
    of 8, 4 query heads on 2 key/value heads of 16, 32 routed experts of
    which `held` live here, 6 picks, experts 32 wide in a latent of 32, a
    shared expert of 48."""
    config = dict(
        REAL, name="tiny-n3s", hidden_size=64, num_hidden_layers=5,
        hybrid_override_pattern="MEM*E", expand=1, mamba_num_heads=8,
        mamba_head_dim=8, ssm_state_size=16, n_groups=4, chunk_size=8,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        moe_intermediate_size=32, moe_latent_size=32,
        moe_shared_expert_intermediate_size=48,
        n_routed_experts=held[1] - held[0], num_experts_per_tok=6,
        vocab_size=96, max_position_embeddings=64,
        published=dict(REAL["published"], n_routed_experts=routed),
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


def error(got, want):
    got, want = np.asarray(got), np.asarray(want)
    return np.abs(got - want).max() / np.abs(want).max()


# ---------------------------------------------------------------------------
# Mamba-2 with groups
# ---------------------------------------------------------------------------
def _sequential(xs, dt, a, bm, cm):
    """The recurrence token by token in float64 on the host; bm, cm
    [B, T, G, N]: head h reads group h // (H / G)."""
    xs, dt, a, bm, cm = (np.asarray(z, np.float64) for z in (xs, dt, a, bm, cm))
    b, t, h, p = xs.shape
    per_head = lambda z: np.repeat(z, h // z.shape[2], axis=2)
    bm, cm = per_head(bm), per_head(cm)
    state = np.zeros((b, h, p, bm.shape[-1]))
    ys = np.zeros_like(xs)
    for i in range(t):
        keep = np.exp(dt[:, i] * a)[..., None, None]
        state = keep * state + (dt[:, i, :, None] * xs[:, i])[..., None] \
            * bm[:, i, :, None, :]
        ys[:, i] = np.sum(state * cm[:, i, :, None, :], -1)
    return ys, state


def _inputs(tokens, groups, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    xs = jax.random.normal(k[0], (2, tokens, 8, 4), jnp.float32)
    dt = jax.nn.softplus(
        jax.random.normal(k[1], (2, tokens, 8), jnp.float32) - 2.0)
    a = -jnp.exp(jax.random.uniform(k[2], (8,), jnp.float32, 0.0, 2.7))
    bm = jax.random.normal(k[3], (2, tokens, groups, 6), jnp.float32)
    cm = jax.random.normal(k[4], (2, tokens, groups, 6), jnp.float32)
    return xs, dt, a, bm, cm


@pytest.mark.parametrize("tokens,chunk,groups", [
    (29, 8, 4), (8, 8, 2), (33, 16, 8), (5, 8, 4), (40, 16, 1)])
def test_grouped_scan_matches_the_sequential_recurrence(tokens, chunk, groups):
    """G groups of B and C, lengths that are no multiple of the chunk, one
    chunk, a chunk longer than the sequence: the outputs, the state after
    the last token, and a step of the recurrence (the tick's) after it."""
    xs, dt, a, bm, cm = _inputs(tokens, groups, seed=tokens)
    want_y, want_state = _sequential(xs, dt, a, bm, cm)
    y, state = ssm_scan(xs, dt, a, bm, cm, chunk)
    close(y, want_y)
    close(state, want_state)
    y1, state1 = ssm_step(state, xs[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0])
    more = lambda z: jnp.concatenate([z, z[:, :1]], 1)
    want_y1, want_state1 = _sequential(more(xs), more(dt), a, more(bm),
                                       more(cm))
    close(y1, want_y1[:, -1])
    close(state1, want_state1)


def test_one_group_as_groups_is_the_one_group_scan():
    """B and C of one group given as [B, T, 1, N] fold into the batch and
    read what today's [B, T, N] path reads; a step likewise."""
    xs, dt, a, bm, cm = _inputs(21, 1, seed=9)
    grouped = ssm_scan(xs, dt, a, bm, cm, 8)
    plain = ssm_scan(xs, dt, a, bm[:, :, 0], cm[:, :, 0], 8)
    for g, p in zip(grouped, plain):
        close(g, p, 1e-6)
    state = plain[1]
    close(ssm_step(state, xs[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0])[1],
          ssm_step(state, xs[:, 0], dt[:, 0], a, bm[:, 0, 0], cm[:, 0, 0])[1],
          1e-6)


def test_the_scan_instant_records_the_groups():
    previous = telemetry.tracer()
    install_tracer(Tracer())
    hybrid_ssm._scan_record.cache_clear()
    try:
        ssm_scan(*_inputs(16, 4), 8)
        scans = [r["attrs"] for r in telemetry.tracer().snapshot()
                 if r["name"] == "dl4j/layers/ssm_scan"]
    finally:
        install_tracer(previous)
        hybrid_ssm._scan_record.cache_clear()
    assert scans == [dict(batch=2, tokens=16, chunk=8, chunks=2, heads=8,
                          head_dim=4, state=6, groups=4,
                          state_bytes=4 * 2 * 8 * 4 * 6)]


# ---------------------------------------------------------------------------
# each part against the reference
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("layer,tokens", [(1, 37), (2, 37), (4, 37), (4, 6),
                                          (1, 5)],
                         ids=["mamba-37", "moe-37", "attention-37",
                              "attention-6", "mamba-5"])
def test_each_part_matches_the_reference(layer, tokens):
    config = tiny_config()
    m = ref.dims(config)
    model = build(config)
    assert [b.mixer for b in model.layers[1:-2]] == list(m.parts) == [
        "mamba", "moe", "mamba", "attention", "moe"]
    x = jax.random.normal(jax.random.PRNGKey(0), (tokens, m.d), jnp.float32)
    want = ref.layer(model.params[layer], x, m.parts[layer - 1], m)
    got, _ = model.layers[layer].apply(model.params[layer], {}, x[None])
    close(got[0], want)


def test_the_norm_is_taken_within_each_group():
    """(y + D xs) * silu(z), normed within each of the 4 groups of 16
    channels, one gain of 64, W_out: by hand. The norm over all H*P
    (Granite's, one group) reads orders of magnitude over the tolerance."""
    model = build(tiny_config())
    blk, p = model.layers[1], model.params[1]["mixer"]
    k = jax.random.split(jax.random.PRNGKey(2), 3)
    y, xs = (jax.random.normal(key, (1, 24, 8, 8), jnp.float32)
             for key in k[:2])
    z = jax.random.normal(k[2], (1, 24, 64), jnp.float32)
    got = blk._gate_out(p, y, xs, z)
    f64 = lambda a: np.asarray(a, np.float64)
    g = (f64(y) + f64(p["D"])[:, None] * f64(xs)).reshape(1, 24, 64) \
        * f64(jax.nn.silu(z))
    g = g.reshape(1, 24, 4, 16)
    g = (g / np.sqrt(np.mean(g ** 2, -1, keepdims=True) + blk.eps)
         ).reshape(1, 24, 64) * f64(p["norm"])
    close(got, g @ f64(p["W_out"]))
    one = NemotronHBlock(**{**blk.__dict__, "ssm_groups": 1})
    assert error(one._gate_out(p, y, xs, z), got) > 1000 * TOL


def test_whole_stack_forward_matches_the_reference():
    """Five layers of three kinds, the final norm, the untied head."""
    config = tiny_config()
    model = build(config)
    seq = np.random.default_rng(1).integers(0, 96, 50).tolist()
    x = np.zeros((1, 64, 1), np.float32)
    x[0, :50, 0] = seq
    h = model._forward(model.params, model.state, jnp.asarray(x), False,
                       None, upto=len(model.layers) - 1)[0]
    got = np.asarray(model.layers[-1].preout(model.params[-1], {}, h))[0]
    close(got[9:49], ref.served_logits(config, model.params, seq, 10, 40))
    assert model.params[-1]["W"].shape == (64, 96)


# ---------------------------------------------------------------------------
# LatentMoE: the sigmoid router, relu^2 experts in the latent, the share
# ---------------------------------------------------------------------------
def test_sigmoid_router_against_a_hand_computation():
    """Picks by sigmoid score + correction bias; weights the picked scores
    over their sum, times the scaling: the bias moves the picks, not the
    weights."""
    d, routes, k = 16, 12, 4
    key = jax.random.split(jax.random.PRNGKey(4), 3)
    u = jax.random.normal(key[0], (9, d), jnp.float32)
    w_r = jax.random.normal(key[1], (d, routes), jnp.float32) * 0.3
    bias = 0.3 * jax.random.normal(key[2], (routes,), jnp.float32)
    layer = SparseExpertsLayer(n_experts=routes, top_k=k, expert_hidden=8,
                               scoring="sigmoid", routed_scaling=5.0)
    ids, w = layer.route({"router_W": w_r, "router_bias": bias}, u)
    s = 1.0 / (1.0 + np.exp(-(np.asarray(u, np.float64)
                              @ np.asarray(w_r, np.float64))))
    want_ids = np.argsort(-(s + np.asarray(bias)), axis=1)[:, :k]
    np.testing.assert_array_equal(np.asarray(ids), want_ids)
    top = np.take_along_axis(s, want_ids, 1)
    close(w, 5.0 * top / top.sum(1, keepdims=True))
    close(jnp.sum(w, -1), np.full(9, 5.0))
    no_bias = layer.route({"router_W": w_r, "router_bias": 0 * bias}, u)[0]
    assert not np.array_equal(np.asarray(no_bias), want_ids)
    assert "router_bias" in layer.init_params(jax.random.PRNGKey(0), None,
                                              width=d)


def test_the_weights_sum_over_all_picks_held_or_not():
    """Held 16 of 32: a token whose picks fall partly on absent experts
    weighs its held ones as the whole layer does, so the held weights add
    up to under the scaling, and the counts say how many picks were
    held."""
    config = tiny_config()
    m = ref.dims(config)
    model = build(config)
    p = model.params[2]["moe"]
    layer = model.layers[2].experts()
    assert (layer.scoring, layer.expert_activation, layer.latent,
            layer.routed_scaling) == ("sigmoid", "relu2", 32, 5.0)
    u = jax.random.normal(jax.random.PRNGKey(5), (40, m.d), jnp.float32)
    ids, w = layer.route(p, u)
    close(jnp.sum(w, -1), np.full(40, 5.0))
    held = np.where(np.asarray(ids) < 16, np.asarray(w), 0.0).sum(1)
    assert held.min() >= 0.0 and held.max() <= 5.0 + 1e-5
    assert held.mean() < 4.0
    _, counts = layer.mix(p, u[None])
    assert counts.tolist()[0] == 40 * 6
    assert counts.tolist()[2] == int((np.asarray(ids) < 16).sum())
    assert set(p) == {"router_W", "router_bias", "W_down", "W_up",
                      "expert_W_u", "expert_W_d", "shared_W_u", "shared_W_d"}


def test_four_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """32 routed experts over 4 chips of 8: each share's layer gives S +
    E_i W_up (the shared expert, which every chip computes alike, and its
    own experts' part through the latent's up-projection). W_up is linear,
    so the four outputs added up, less the shared expert's three times
    counted over, are the uncut reference layer."""
    whole = tiny_config(held=(0, 32))
    m = ref.dims(whole)
    p = build(whole).params[2]
    x = jax.random.normal(jax.random.PRNGKey(6), (30, m.d), jnp.float32)
    uncut = ref.layer(p, x, "moe", m) - x
    u = ref._norm(x, p["n"], m.eps)
    shared = ref._relu2(u, p["moe"]["shared_W_u"], p["moe"]["shared_W_d"],
                        "float32")
    parts, held = [], 0
    for lo in range(0, 32, 8):
        mine = dict(p["moe"], **{k: v[lo:lo + 8] for k, v in p["moe"].items()
                                 if k.startswith("expert_")})
        layer = SparseExpertsLayer(
            n_experts=32, top_k=6, expert_hidden=32, shared_hidden=48,
            held_experts=[lo, lo + 8], scoring="sigmoid",
            expert_activation="relu2",
            latent=32, routed_scaling=5.0)
        got, counts = layer.mix(mine, u[None])
        share = tiny_config(held=(lo, lo + 8))
        close(got[0], ref.layer(dict(p, moe=mine), x, "moe",
                                ref.dims(share)) - x)
        parts.append(got[0])
        held += int(counts[2])
    close(sum(parts) - 3 * shared, uncut)
    assert held == 30 * 6               # every pick is on one share


# ---------------------------------------------------------------------------
# served: pages, per-sequence state and expert layers through one engine
# ---------------------------------------------------------------------------
def serve(config, name="n3s", **engine):
    model = build(config)
    registry = ModelRegistry(buckets=(1,))
    registry.register(name, model)
    engine = dict(dict(block_len=4, decode_buckets=(1, 2, 4),
                       prompt_buckets=(8, 16, 32)), **engine)
    return model, registry, DecodeEngine(registry, name, **engine)


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
    return max(error(z, ref.served_logits(config, model.params, s, len(p),
                                          len(s) - len(p) + 1))
               for p, s, z in zip(prompts, seqs, out))


@pytest.fixture
def span_log():
    previous = telemetry.tracer()
    install_tracer(Tracer())
    yield telemetry.tracer()
    install_tracer(previous)


def test_prefill_in_a_larger_bucket_then_ticks_match_the_reference(span_log):
    """Rows of 5, 19 and 12 tokens, each prefilled in a bucket it does not
    fill, then six ticks together (bucket 4: one pad row on the trash
    slot): every logit that chose a token against the reference's full
    causal forward. The geometry: one paging layer of 2 channels of 2 x 16,
    two stateful layers whose convolution holds H*P + 2GN inputs, a slot a
    row of the largest tick and the trash slot; only the two expert layers
    count picks."""
    config = tiny_config()
    model, registry, engine = serve(config)
    spec = engine.spec
    assert (spec.channels, spec.width, spec.max_context) == (2, 32, 64)
    assert len(spec.state) == 2 and spec.state_slots == 5
    assert spec.state_shapes()[0] == {
        "ssm": ((5, 8, 8, 16), jnp.dtype("float32")),
        "conv": ((3, 5, 64 + 2 * 4 * 16), jnp.dtype("float32"))}
    assert engine.experts == "cond"                 # the CPU
    pool, v = engine.new_pool(), registry.get("n3s")
    r = np.random.default_rng(0)
    prompts = [r.integers(0, 96, n).tolist() for n in (5, 19, 12)]
    seqs, out, tables = _serve(engine, v, pool, prompts, 6)
    assert _worst_error(config, model, prompts, seqs, out) <= TOL
    assert pool.used_slots() == 3
    for t in tables:
        pool.release(t)
    assert pool.used_slots() == pool.used_blocks() == 0
    fetch = [r["attrs"] for r in span_log.snapshot()
             if r["name"] == "dl4j/engine/tick.fetch"][-1]
    assert fetch["moe_layers"] == 2 and fetch["moe_picks"] == 2 * 3 * 6
    assert 0 < fetch["moe_held"] < fetch["moe_picks"]


def test_one_group_read_for_eight_fails_that_comparison(monkeypatch):
    """The fault the groups invite: every head reading group 0's B and C.
    The served comparison then reads thousands of times its tolerance."""
    split = NemotronHBlock._split

    def group_zero(self, p, xbc, dt):
        xs, bm, cm, dt, a = split(self, p, xbc, dt)
        first = lambda z: jnp.broadcast_to(z[..., :1, :], z.shape)
        return xs, first(bm), first(cm), dt, a

    monkeypatch.setattr(NemotronHBlock, "_split", group_zero)
    config = tiny_config()
    model, registry, engine = serve(config, "faulty")
    pool, v = engine.new_pool(), registry.get("faulty")
    r = np.random.default_rng(0)
    prompts = [r.integers(0, 96, n).tolist() for n in (5, 19)]
    seqs, out, _ = _serve(engine, v, pool, prompts, 3)
    assert _worst_error(config, model, prompts, seqs, out) > 1000 * TOL


def test_the_paths_each_part_answers(monkeypatch):
    """Only an expert layer has an experts' path; only the attention layer
    pages and has an attention path; only a Mamba layer keeps state. On
    the TPU a tick's experts take the relu^2 kernel at the cell's widths
    (1,024-wide latent, experts 2,688 wide, bfloat16)."""
    m = ref.dims(REAL)
    kw = dict(ssm_heads=m.ssm_heads, ssm_head_dim=m.ssm_head,
              ssm_state=m.ssm_state, ssm_groups=m.groups, n_heads=m.heads,
              n_kv_heads=m.kv_heads, head_dim=m.head, n_experts=m.routed,
              top_k=m.top_k, expert_hidden=m.expert_ffn,
              shared_hidden=m.shared_ffn, latent=m.latent,
              held_experts=[0, 128], dtype="bfloat16")
    mamba, attention, moe = (NemotronHBlock(mixer=k, **kw)
                             for k in ("mamba", "attention", "moe"))
    assert [b.decode_cache(4096) for b in (mamba, attention, moe)] == [
        (0, 0), (2, 256), (0, 0)]
    assert mamba.decode_state(4096)["conv"][0] == (3, "slots", 8192 + 2048)
    assert attention.decode_state(4096) is moe.decode_state(4096) is None
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert moe.decode_experts("tick", 4096) == "grouped_kernel"
    assert moe.decode_experts("prefill", 4096) == "cond"
    assert mamba.decode_experts("tick", 4096) is None
    assert attention.decode_experts("tick", 4096) is None
    with pytest.raises(ValueError, match="mixer"):
        NemotronHBlock(mixer="mlp")
