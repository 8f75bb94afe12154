"""The held experts of a decode tick through one Pallas kernel a layer
(`kernels/grouped_experts.py`), on the CPU through the Pallas interpreter,
against the conditionals it replaces on the TPU (`SparseExpertsLayer.
_held_sum`'s "cond" path, its oracle): at experts shaped as Granite 4.0-H's
(4096 x 768: whole experts a grid step) and as LongCat-Flash's (6144 x 2048:
tiles of the hidden width) but narrower, for every row count a tick bucket
of the two cells holds, and every expert hit, none, a few, dead rows; the
relu^2 form of two matrices (Nemotron-H's experts in their latent) at up to
128 rows, past twice a tick's slots. Then which blocks the kernel fetches,
which path each block's tick takes, and a served tick of each family
through either path.

The weights are float32 (XLA's CPU backend has no bfloat16 batch product)
and both sides sum in float32 in the same order but for a tiled expert's
`W_d` part, which the kernel adds a tile at a time: RTOL 1e-5 of the largest
value compared, far under one expert's part (a row's weight is 0.1-1)."""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import InputType, telemetry
from deeplearning4j_tpu.kernels import grouped_experts as ge
from deeplearning4j_tpu.nn.layers.hybrid_ssm import HybridSSMBlock
from deeplearning4j_tpu.nn.layers.nemotron_h import NemotronHBlock
from deeplearning4j_tpu.nn.layers.shortcut_moe import (ShortcutMoEBlock,
                                                       SparseExpertsLayer)
from deeplearning4j_tpu.serving.decode.cache import KvCacheSpec, make_cache
from deeplearning4j_tpu.serving.decode.engine import (build_decode_fn,
                                                      cache_geometry)
from deeplearning4j_tpu.serving.registry import _snapshot_params

BENCH = Path(__file__).resolve().parents[1] / "benchmarks"
RTOL = 1e-5

# d, h, held experts, the weight blocks' VMEM budget (a smaller one makes
# the LongCat-shaped experts take tiles of 128 of their 512, as 6144 x 2048
# take 512 of 2048 in the real one)
SHAPES = {"granite": (256, 384, 6, ge._WEIGHT_VMEM,
                      dict(n_experts=12, top_k=3)),
          "longcat": (384, 512, 4, 2 * 3 * 384 * 128 * 4,
                      dict(n_experts=32, n_identity=16, top_k=4))}


@pytest.fixture
def shape(request, monkeypatch):
    d, h, e, budget, kw = SHAPES[request.param]
    monkeypatch.setattr(ge, "_WEIGHT_VMEM", budget)
    ge._planned.cache_clear()
    yield d, h, e, kw
    ge._planned.cache_clear()


def _experts(d, h, e, seed=0, gated=True):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    w = lambda key, s: jax.random.normal(key, s, jnp.float32) * s[-2] ** -0.5
    p = {"expert_W_u": w(k[1], (e, d, h)), "expert_W_d": w(k[2], (e, h, d))}
    if gated:
        p["expert_W_g"] = w(k[0], (e, d, h))
    return p


def _routing(rows, e, hit, seed):
    """took [rows, e]: each expert in `hit` picked by some of the rows (the
    last row dead: it picks nothing and weighs 0); w [rows, e] 0.1-1 where
    taken; loads [e]."""
    r = np.random.default_rng(seed)
    took = np.zeros((rows, e), bool)
    for x in hit:
        took[r.choice(rows, max(1, rows // 3)), x] = True
    if rows > 1:
        took[-1] = False
    w = np.where(took, r.uniform(0.1, 1.0, (rows, e)), 0.0)
    return (jnp.asarray(took), jnp.asarray(w, jnp.float32),
            jnp.asarray(took.sum(0), jnp.int32))


@pytest.mark.parametrize("shape", ["granite", "longcat"], indirect=True)
@pytest.mark.parametrize("rows", [1, 7, 32, 64])
def test_kernel_matches_the_conditionals(shape, rows):
    d, h, e, kw = shape
    layer = SparseExpertsLayer(expert_hidden=h, held_experts=[0, e], **kw)
    assert rows <= 2 * layer.rows_per_expert(rows)      # a tick's branch
    p = _experts(d, h, e)
    u = jax.random.normal(jax.random.PRNGKey(rows), (rows, d), jnp.float32)
    tiles = ge.experts_plan(rows, d, h, e, 4).tiles_an_expert
    assert tiles == (1 if h == 384 else 4)
    kernel = jax.jit(lambda u, w, loads: ge.grouped_experts(
        u, w, loads, p["expert_W_g"], p["expert_W_u"], p["expert_W_d"],
        interpret=True))
    for hit in (range(e), (), (1,), (0, e - 1), (2, 3)):
        took, w, loads = _routing(rows, e, hit, seed=rows + len(hit))
        want = layer._held_sum(p, u, w, took, loads, "cond")
        got = kernel(u, w, loads)
        assert got.shape == (rows, d) and got.dtype == jnp.float32
        scale = max(float(jnp.max(jnp.abs(want))), 1e-30)
        assert float(jnp.max(jnp.abs(got - want))) <= RTOL * scale, hit
        if not len(hit):
            assert not np.any(np.asarray(got))
        if rows > 1:        # the dead row adds nothing
            assert not np.any(np.asarray(got[-1]))


@pytest.mark.parametrize("rows", [1, 7, 64, 128])
def test_relu2_kernel_matches_the_conditionals(rows):
    """Two matrices an expert, no gate: `relu(u W_u)^2 W_d`, whole experts a
    grid step at 256 x 384 (as Nemotron-H's 1,024 x 2,688), 16 held of 64
    with 6 picks, up to 128 rows: more than twice a tick's slots, where a
    tick still takes the kernel."""
    d, h, e = 256, 384, 16
    layer = SparseExpertsLayer(n_experts=64, top_k=6, expert_hidden=h,
                               held_experts=[0, e],
                               expert_activation="relu2")
    assert layer.decode_experts("tick", d) == "cond"           # the CPU
    p = _experts(d, h, e, gated=False)
    assert ge.experts_plan(rows, d, h, e, 4, 2).tiles_an_expert == 1
    u = jax.random.normal(jax.random.PRNGKey(rows), (rows, d), jnp.float32)
    kernel = jax.jit(lambda u, w, loads: ge.grouped_experts(
        u, w, loads, None, p["expert_W_u"], p["expert_W_d"], interpret=True))
    for hit in (range(e), (), (3,), (0, e - 1, 7)):
        took, w, loads = _routing(rows, e, hit, seed=rows + len(hit))
        want = layer._held_sum(p, u, w, took, loads, "cond")
        got = kernel(u, w, loads)
        scale = max(float(jnp.max(jnp.abs(want))), 1e-30)
        assert float(jnp.max(jnp.abs(got - want))) <= RTOL * scale, hit
        by_hand = sum(w[:, x:x + 1] * jnp.square(jax.nn.relu(
            u @ p["expert_W_u"][x])) @ p["expert_W_d"][x] for x in hit)
        assert float(jnp.max(jnp.abs(got - by_hand))) <= RTOL * scale
        if rows > 1:
            assert not np.any(np.asarray(got[-1]))


def _fetched(loads, tiles):
    """The blocks the pipeline fetches: a step's block where it differs
    from the step before's, the first step's always."""
    src = np.asarray(ge.experts_sources(jnp.asarray(loads, jnp.int32),
                                        tiles)).reshape(-1, 2)
    return [tuple(b) for i, b in enumerate(src)
            if i == 0 or tuple(b) != tuple(src[i - 1])]


@pytest.mark.parametrize("loads", [
    [3, 1, 2, 5], [0, 0, 4, 0, 1, 0], [2, 0, 0, 0], [0, 0, 0, 7],
    [0, 1, 0, 1, 0, 1], [0, 0, 0, 0]])
@pytest.mark.parametrize("tiles", [1, 4])
def test_the_kernel_fetches_only_the_hit_experts(loads, tiles):
    fetched = _fetched(loads, tiles)
    hit = [e for e, n in enumerate(loads) if n]
    if not hit:                         # one tile, and nothing computed
        assert fetched == [(0, 0)]
        return
    # every tile of every hit expert once, in order, and nothing else
    assert fetched == [(e, j) for e in hit for j in range(tiles)]


def test_plan_tiles_and_vmem_at_the_cells_shapes():
    g4h = ge.experts_plan(64, 4096, 768, 36, 2)
    assert (g4h.tile, g4h.tiles_an_expert, g4h.steps_a_call) == (768, 1, 36)
    lcf = ge.experts_plan(32, 6144, 2048, 16, 2)
    assert (lcf.tile, lcf.tiles_an_expert, lcf.steps_a_call) == (512, 4, 64)
    for plan, d in ((g4h, 4096), (lcf, 6144)):
        assert 2 * 3 * d * plan.tile * 2 <= ge._WEIGHT_VMEM
        assert plan.vmem_bytes < plan.vmem_limit_bytes < 64 << 20
    assert ge.experts_plan(1, 4096, 768, 36, 2).rows_padded == 16
    assert ge.experts_plan(8, 64, 32, 4) is None          # no lane tiles
    assert not ge.grouped_experts_supported(4096, 768, "float16")
    assert ge.grouped_experts_supported(4096, 768, "bfloat16")
    # Nemotron-H: relu^2 experts of two matrices, 1,024 x 2,688, 128 rows
    n3s = ge.experts_plan(128, 1024, 2688, 128, 2, matrices=2)
    assert (n3s.tile, n3s.tiles_an_expert, n3s.steps_a_call) == (2688, 1, 128)
    assert 2 * 2 * 1024 * n3s.tile * 2 <= ge._WEIGHT_VMEM
    assert n3s.vmem_bytes < n3s.vmem_limit_bytes < 64 << 20
    assert ge.grouped_experts_supported(1024, 2688, "bfloat16")
    assert ge.grouped_experts_supported(1024, 2688, "bfloat16", matrices=2)


@pytest.mark.parametrize("block", [
    HybridSSMBlock(n_model=256, expert_hidden=384, n_experts=8, top_k=2),
    HybridSSMBlock(n_model=256, mixer="attention", expert_hidden=384,
                   n_experts=8, top_k=2, dtype="bfloat16"),
    ShortcutMoEBlock(n_model=256, expert_hidden=384, n_experts=8,
                     n_identity=4, top_k=2),
    NemotronHBlock(n_model=512, mixer="moe", expert_hidden=384, latent=256,
                   n_experts=8, top_k=2, dtype="bfloat16")],
    ids=["mamba", "gqa", "latent", "nemotron-moe"])
def test_the_tick_takes_the_kernel_only_on_the_tpu(block, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert block.decode_experts("tick", 256) == "grouped_kernel"
    assert block.decode_experts("prefill", 256) == "cond"
    odd = type(block)(**{**block.__dict__, "expert_hidden": 96})
    assert odd.decode_experts("tick", 256) == "cond"         # no lane tiles
    half = type(block)(**{**block.__dict__, "dtype": "float16"})
    assert half.decode_experts("tick", 256) == "cond"
    monkeypatch.setenv("DL4J_TPU_DISABLE_PALLAS", "1")
    assert block.decode_experts("tick", 256) == "cond"
    monkeypatch.delenv("DL4J_TPU_DISABLE_PALLAS")
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert block.decode_experts("tick", 256) == "cond"


def test_apply_keeps_the_conditionals_on_the_tpu(monkeypatch):
    """`apply` and `mix` without a path take the conditionals whatever the
    backend: the kernel has no gradient and is chosen for a tick alone."""
    def refuse(*a, **kw):
        raise AssertionError("the kernel was called")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(ge, "grouped_experts", refuse)
    layer = SparseExpertsLayer(n_experts=8, top_k=2, expert_hidden=128)
    p = layer.init_params(jax.random.PRNGKey(0), InputType.recurrent(128, 4))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 4, 128), jnp.float32)
    y, _ = layer.apply(p, {}, x)
    assert y.shape == (2, 4, 128)
    with pytest.raises(AssertionError, match="kernel was called"):
        layer.mix(p, x, None, "grouped_kernel")
    with pytest.raises(ValueError, match="experts must be one of"):
        layer.mix(p, x, None, "dense")


# ---------------------------------------------------------------------------
# a served tick of each family, through either path
# ---------------------------------------------------------------------------
def _load(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"grouped_test_{kind}_{name}", BENCH / kind / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


CONFIG_OF = {"granite_moe_hybrid": "granite-4.0-h-small",
             "longcat_flash": "longcat-flash-chat",
             "nemotron_h": "nemotron-3-super-120b-a12b"}


def _tiny(family):
    """The cell's configuration at a CPU test's size, experts in whole lane
    tiles: 128 wide, experts 128 wide (in a latent of 128), two layers
    (three for Nemotron-H: Mamba, attention, experts), float32."""
    real = json.loads((BENCH / "configs" / f"{CONFIG_OF[family]}.json")
                      .read_text())
    f32 = dict(real["precision"], weights="float32", registry="fp32",
               kv_dtype="fp32", reference="float32")
    if family == "nemotron_h":
        return dict(
            real, hidden_size=256, num_hidden_layers=3,
            hybrid_override_pattern="M*E", expand=1, mamba_num_heads=8,
            mamba_head_dim=32, ssm_state_size=16, n_groups=2, chunk_size=8,
            num_attention_heads=4, num_key_value_heads=2, head_dim=32,
            moe_intermediate_size=128, moe_latent_size=128,
            moe_shared_expert_intermediate_size=128, n_routed_experts=4,
            num_experts_per_tok=3, vocab_size=96, max_position_embeddings=64,
            published=dict(real["published"], n_routed_experts=8),
            deployment=dict(real["deployment"], held_experts=[0, 4]),
            precision=f32)
    if family == "granite_moe_hybrid":
        return dict(
            real, hidden_size=128, num_hidden_layers=2,
            layer_types=["mamba", "attention"], mamba_n_heads=8,
            mamba_d_head=32, mamba_d_state=16, mamba_chunk_size=8,
            num_attention_heads=4, num_key_value_heads=2,
            intermediate_size=128, shared_intermediate_size=128,
            num_local_experts=4, num_experts_per_tok=3, vocab_size=96,
            max_position_embeddings=64,
            published=dict(real["published"], num_local_experts=8),
            deployment=dict(real["deployment"], held_experts=[0, 4]),
            precision=f32)
    return dict(
        real, vocab_size=96, hidden_size=128, ffn_hidden_size=128,
        expert_ffn_hidden_size=128, num_layers=2, num_attention_heads=4,
        kv_lora_rank=16, q_lora_rank=32, qk_rope_head_dim=8, v_head_dim=16,
        qk_nope_head_dim=16, n_routed_experts=4, zero_expert_num=8,
        moe_topk=4, max_position_embeddings=64,
        published=dict(real["published"], n_routed_experts=16),
        deployment=dict(real["deployment"], held_experts=[0, 4]),
        precision=f32)


@pytest.fixture
def kernel_interpreted(monkeypatch):
    """"grouped_kernel" always means the COMPILED kernel; here it runs
    through the Pallas interpreter instead."""
    kernel = ge.grouped_experts
    monkeypatch.setattr(ge, "grouped_experts",
                        lambda *a, interpret, **kw: kernel(
                            *a, interpret=True, **kw))


@pytest.mark.parametrize("family", ["granite_moe_hybrid", "longcat_flash",
                                    "nemotron_h"])
def test_a_tick_through_the_kernel_is_the_tick_through_the_conditionals(
        family, kernel_interpreted):
    """Three live rows and a pad row in one tick over a random cache: the
    same logits, the same counts (experts hit, loads), the same cache."""
    ref, models = _load("reference", family), _load("models", family)
    model = models.build(_tiny(family), 3, ref, train=False)
    channels, width, context, state = cache_geometry(model)
    spec = KvCacheSpec(channels=channels, width=width, block_len=4,
                       num_blocks=17, max_context=context, state=state,
                       state_slots=5 if state else 0)
    snapshot = _snapshot_params(model, "fp32")
    ticks = {x: jax.jit(build_decode_fn(model, snapshot, spec, experts=x))
             for x in ("grouped_kernel", "cond")}
    r = np.random.default_rng(5)
    cache = jax.tree_util.tree_map(
        lambda a: jnp.asarray(r.normal(size=a.shape) * 0.1, a.dtype),
        make_cache(spec))
    tables = jnp.asarray([[1, 2, 3, 4] + [0] * 12, [5, 6, 0, 0] + [0] * 12,
                          [7] + [0] * 15, [0] * 16], jnp.int32)
    args = (jnp.asarray([3, 9, 40, 0], jnp.int32),
            jnp.asarray([14, 6, 2, 0], jnp.int32), tables)
    if state:
        args += (jnp.asarray([1, 2, 3, 0], jnp.int32),)
    got = {x: t(snapshot.data, cache, *args) for x, t in ticks.items()}
    a, b = got["grouped_kernel"], got["cond"]
    want = np.asarray(b[1][:3])
    assert np.abs(np.asarray(a[1][:3]) - want).max() \
        <= RTOL * np.abs(want).max()
    np.testing.assert_array_equal(np.asarray(a[2]), np.asarray(b[2]))
    assert int(np.asarray(a[2])[:, 3].sum()) > 0       # some expert was hit
    for x, y in zip(jax.tree_util.tree_leaves(a[0]),
                    jax.tree_util.tree_leaves(b[0])):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=RTOL, atol=1e-6)


def test_the_kernel_leaves_its_record_once_a_call_shape():
    previous = telemetry.tracer()
    telemetry.install_tracer(telemetry.Tracer())
    ge._planned.cache_clear()
    try:
        p = _experts(128, 256, 3)
        u = jnp.ones((5, 128), jnp.float32)
        took, w, loads = _routing(5, 3, (0, 2), seed=1)
        for gate in (p["expert_W_g"], p["expert_W_g"], None, None):
            ge.grouped_experts(u, w, loads, gate, p["expert_W_u"],
                               p["expert_W_d"], interpret=True)
        records = [e["attrs"] for e in telemetry.tracer().snapshot()
                   if e["name"] == "dl4j/kernels/grouped_experts"]
    finally:
        telemetry.install_tracer(previous)
        ge._planned.cache_clear()
    assert len(records) == 2            # one a call shape: gated, relu^2
    for rec, form in zip(records, (("swiglu", 3), ("relu2", 2))):
        assert (rec["experts"], rec["rows"], rec["d"], rec["h"], rec["tile"],
                rec["steps_a_call"], rec["rows_padded"], rec["dtype"]) == (
            3, 5, 128, 256, 256, 3, 8, "float32")
        assert (rec["activation"], rec["matrices"]) == form
        assert rec["vmem_bytes"] < rec["vmem_limit_bytes"]
    assert records[1]["vmem_bytes"] < records[0]["vmem_bytes"]
