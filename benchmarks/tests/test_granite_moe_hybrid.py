"""Tests of what the `granite-4.0-h-small` configuration brings to the
benchmark, on the CPU:

    python -m pytest benchmarks/tests/test_granite_moe_hybrid.py -q

The configuration's file against the `model-configs` catalog's row and its
cuts, the parameter count recounted from the reference's leaves,
`harness/flops_granite_moe_hybrid` against hand figures, the new readers on a
hand-made span log, and the cell's files found by name through `run_cell` at a
tiny override. (The block, the share and the served path against the reference
are in `tests/test_hybrid_ssm.py`, inside tier-1.)
"""
from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
sys.path[:0] = [str(REPO), str(BENCH)]

from harness import flops_granite_moe_hybrid as g4h, spanlog  # noqa: E402
from test_benchmark import _load_run  # noqa: E402
from test_span_metrics import Log, _facts  # noqa: E402

bench_run = _load_run(BENCH)
CONFIG = json.loads((BENCH / "configs" / "granite-4.0-h-small.json").read_text())
CELL_NAME = "granite-4.0-h-small.generate-chat64"
CELL = json.loads((BENCH / "workloads" / f"{CELL_NAME}.json").read_text())
# the source's config.json as the `model-configs` catalog's row has it
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.0078125,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 4096,
    "intermediate_size": 768,
    "layer_types": (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4,
    "logits_scaling": 16, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 128,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 10,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 72, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "shared_intermediate_size": 1536,
    "tie_word_embeddings": True, "vocab_size": 100352}
MAMBA, ATTENTION, EXPERT = 102_236_160, 41_943_040, 9_437_184   # matrices


def test_configuration_file_states_the_published_sizes_and_its_cuts():
    reduced = set(CONFIG["reduced"])
    assert reduced == {"num_hidden_layers", "num_local_experts", "vocab_size",
                       "max_position_embeddings"}
    for key, value in PUBLISHED.items():
        if key in reduced:      # the published value stands beside the cut
            assert CONFIG["published"][key] == value != CONFIG[key]
        else:
            assert CONFIG[key] == value, key
    assert set(CONFIG["published"]) == reduced
    # the floors of a cut: a whole period, 8 experts, an eighth of the vocabulary
    served = CONFIG["layer_types"][:CONFIG["num_hidden_layers"]]
    assert served == PUBLISHED["layer_types"][:10]
    assert (served.count("mamba"), served.count("attention")) == (9, 1)
    assert CONFIG["num_local_experts"] >= 8
    assert CONFIG["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    d = CONFIG["deployment"]
    assert d["held_experts"] == [0, CONFIG["num_local_experts"]]
    assert d["chips_sharing_a_layer"] * CONFIG["num_local_experts"] == 72
    assert d["vocabulary_slices"] * CONFIG["vocab_size"] == 100352
    assert d["pipeline_stages"] * CONFIG["num_hidden_layers"] == 40
    assert d["chips"] == d["pipeline_stages"] * d["chips_sharing_a_layer"] == 8
    assert CONFIG["precision"]["registry"] == CONFIG["precision"]["kv_dtype"] == "bf16"
    b = json.loads((REPO / "BENCHMARK.json").read_text())
    entry = next(c for c in b["configs"] if c["name"] == CONFIG["name"])
    assert entry["source"] == CONFIG["source"]
    assert set(entry["reduced"]) == reduced
    cell = next(w for w in b["workloads"] if w["name"] == CELL_NAME)
    assert cell["why"] == CELL["why"] and len(cell["why"]) <= 200
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG["name"], "generate-chat64", 1)


def test_the_cell_is_the_issues_traffic():
    t, s = CELL["traffic"], CELL["serve"]
    assert t["clients"] == 64 == max(s["decode_buckets"])
    assert s["decode_buckets"] == [64]        # the cell file says why one
    assert t["prompt_tokens"] == {"dist": "loguniform", "lo": 64, "hi": 1024}
    assert t["max_tokens"] == {"dist": "uniform", "lo": 64, "hi": 256}
    assert (t["temperature"], t["ramp_seconds"],
            t["request_timeout_seconds"]) == (0.0, 8.0, 120.0)
    assert s["prompt_buckets"] == [256, 512, 1024]
    assert t["prompt_tokens"]["hi"] + t["max_tokens"]["hi"] \
        <= CONFIG["max_position_embeddings"]


def test_parameter_count_is_what_the_file_and_the_reference_say():
    """4.76B parameters with the head tied, 4.96B as held (the second copy
    of the table), counted from the shapes the reference would make (nothing
    is made) and by hand from the widths."""
    import jax
    ref = bench_run.load(BENCH, "reference", CONFIG["family"])
    shapes = jax.eval_shape(lambda: ref.init_params(CONFIG, 0))
    leaves = jax.tree_util.tree_leaves(shapes)
    assert all(str(a.dtype) == "bfloat16" for a in leaves)
    held = sum(a.size for a in leaves)
    table = 50176 * 4096
    p = CONFIG["parameters"]
    assert held == p["total_as_held"] == p["total_with_the_head_tied"] + table
    assert shapes[-1]["W"].shape == (4096, 50176) and table == p["token_table"]
    # a Mamba-2 mixer: W_in, W_out, the convolution, 3 scalars a head, the gain
    mamba = MAMBA + 4 * 8448 + 8448 + 3 * 128 + 8192
    assert g4h.mamba_weights(CONFIG) == MAMBA == 4096 * 16768 + 8192 * 4096
    assert mamba == p["a_mamba_mixer"] == 102_286_976
    assert g4h.attention_weights(CONFIG) == ATTENTION == p["an_attention_mixer"]
    shared, router = 3 * 4096 * 1536, 4096 * 72
    assert (shared, router, EXPERT) == (
        p["the_shared_expert"], p["the_router"], p["an_expert"])
    rest = shared + router + 36 * EXPERT + 2 * 4096       # and the two norms
    assert mamba + rest == p["a_mamba_layer_with_36_experts"] == 461_203_072
    assert ATTENTION + rest == p["the_attention_layer_with_36_experts"]
    tied = 9 * (mamba + rest) + ATTENTION + rest + table + 4096
    assert tied == p["total_with_the_head_tied"] == 4_757_211_776
    assert 2 * held > 0.25 * 16e9           # over the floor by the weights alone
    # the whole model by the same count: 32.2B
    whole = 36 * (mamba + rest + 36 * EXPERT) + 4 * (ATTENTION + rest
                                                      + 36 * EXPERT) \
        + 100352 * 4096 + 4096
    assert round(whole / 1e9, 1) == 32.2


def test_flops_and_bytes_against_hand_figures():
    outside = 9 * MAMBA + ATTENTION + 10 * (3 * 4096 * 1536 + 4096 * 72)
    assert g4h.outside_experts_weights(CONFIG) == outside
    # the recurrence: 5 operations a state element, and the 4 taps
    scan = 5 * 128 * 64 * 128 + 2 * 4 * 8448
    assert g4h.dense_flops_per_token(CONFIG) == 2 * outside + 9 * scan
    assert g4h.attention_flops(CONFIG, 1) == 4 * 32 * 128
    assert g4h.expert_pair_flops(CONFIG) == 6 * 4096 * 768
    assert g4h.head_flops_per_token(CONFIG) == 2 * 4096 * 50176
    # one prompt of 3 tokens, 1 token sampled, 2 pairs on held experts
    assert g4h.serve_flops(CONFIG, [3], [1], held_pairs=2) == (
        3 * g4h.dense_flops_per_token(CONFIG) + 6 * g4h.attention_flops(CONFIG, 1)
        + g4h.head_flops_per_token(CONFIG) + 2 * 6 * 4096 * 768)
    # what a layer keeps for a sequence: 4.19 MB of state, 101 kB of inputs
    assert g4h.state_elements(CONFIG) == 128 * 64 * 128 + 3 * 8448
    # a tick of 2 rows that hit 5 (layer, expert) pairs over 30 live pages
    assert g4h.tick_bytes(CONFIG, 2, 5, 30) == (
        2 * (outside + 4096 * 50176 + 5 * 3 * 4096 * 768)
        + 4 * 2 * 2 * 9 * (128 * 64 * 128 + 3 * 8448)
        + 2 * 30 * 16 * 2 * 1024)
    # the issue's figures for a full tick: 2.72 GB of weights outside the
    # experts, 6.79 GB of experts, 2 x 2.42 GB of state (64 rows)
    gb = lambda n: round(n / 1e9, 2)
    assert gb(g4h.tick_bytes(CONFIG, 0, 0, 0)) == 2.72
    assert gb(g4h.tick_bytes(CONFIG, 0, 360, 0)
              - g4h.tick_bytes(CONFIG, 0, 0, 0)) == 6.79
    state = g4h.tick_bytes(CONFIG, 64, 0, 0) - g4h.tick_bytes(CONFIG, 0, 0, 0)
    assert gb(state / 2) == 2.47            # 2.42 GB of H, 0.06 GB of inputs
    assert round(g4h.tick_bytes(CONFIG, 64, 360, 64 * 40) / 819e9 * 1e3,
                 1) == 17.9                 # ms at the chip's bandwidth


# ---------------------------------------------------------------------------
# the new readers on a hand-made log
# ---------------------------------------------------------------------------
def _g4h_log():
    """Ticks 1-6 as `test_span_metrics._serve_log` lays them out: a tick is
    dispatch 10 ms + fetch 50 ms; every tick's spans carry the counts of 10
    expert layers, its live rows and pages; every admission a prefill."""
    log = Log()
    for t, k, admit in ((0, 1, 1), (100, 2, None), (200, 3, 2),
                        (300, 4, None), (400, 5, 3), (520, 6, 4)):
        log.loop(t, k, admit=None if admit is None else
                 {"prefill": admit, "queue_wait_s": 0.01})
    admits = [r for r in log.records if r["name"] == spanlog.ADMIT]
    for a, (bucket, tokens) in zip(admits, ((256, 200), (512, 300),
                                            (1024, 600), (256, 100))):
        log.add("dl4j/engine/prefill.prepare", a["t0"] / 1e6,
                a["t0"] / 1e6 + 1, a["id"], bucket=bucket, tokens=tokens)
    for rec in log.records:
        if rec["name"] == "dl4j/engine/tick.fetch":
            rec["attrs"].update(moe_layers=10, moe_picks=640, moe_identity=0,
                                moe_held=320, moe_held_hit=350,
                                moe_held_load_max=150)
        if rec["name"] == "dl4j/engine/tick.prepare":
            rec["attrs"].update(pages_live=2000, pages_table=8192,
                                state_slots_live=64)
        if rec["name"] == "dl4j/engine/prefill.fetch":
            rec["attrs"].update(moe_layers=10, moe_picks=5120, moe_identity=0,
                                moe_held=2560, moe_held_hit=360,
                                moe_held_load_max=1000)
    return log.records


def _reader(name):
    return bench_run.load(BENCH, "layer_metrics", name).compute


def _env(facts, **kw):
    return SimpleNamespace(**dict(dict(
        facts=facts, trace=None, config=CONFIG,
        peak={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 819e9}), **kw))


def test_tick_hbm_share_reader(monkeypatch):
    log = _g4h_log()
    monkeypatch.setattr(spanlog, "records", lambda: log)
    # ticks 3-5, each 60 ms of dispatch + fetch, the same bytes
    want = g4h.tick_bytes(CONFIG, 64, 350, 2000) / 819e9 / 0.060 * 100.0
    assert _reader("tick_hbm_share.g4h")(_env(_facts())) == pytest.approx(want)
    assert _reader("tick_hbm_share.g4h")(_env(_facts(), peak=None)) is None
    # a program whose spans carry no slots (the parent commit, a GPT stack)
    bare = [dict(r, attrs={k: v for k, v in r["attrs"].items()
                           if k != "state_slots_live"}) for r in log]
    monkeypatch.setattr(spanlog, "records", lambda: bare)
    assert _reader("tick_hbm_share.g4h")(_env(_facts())) is None
    for records in ([], None):
        monkeypatch.setattr(spanlog, "records", lambda: records)
        assert _reader("tick_hbm_share.g4h")(_env(_facts())) is None


def test_prefill_pad_share_and_load_readers(monkeypatch):
    log = _g4h_log()
    monkeypatch.setattr(spanlog, "records", lambda: log)
    # admissions 2-3: buckets 512 + 1024 for 300 + 600 tokens
    assert _reader("ssm_prefill_pad_share")(_env(_facts())) == pytest.approx(
        100.0 * (1536 - 900) / 1536)
    # 36 held experts: 36 x (1000 / 10 layers) / (2560 / 10)
    assert _reader("moe_held_load_max_over_mean.g4h")(
        _env(_facts())) == pytest.approx(36 * 1000 / 2560)
    assert _reader("moe_held_experts_hit")(_env(_facts())) == pytest.approx(35.0)
    bare = [r for r in log if r["name"] != "dl4j/engine/prefill.prepare"]
    monkeypatch.setattr(spanlog, "records", lambda: bare)
    assert _reader("ssm_prefill_pad_share")(_env(_facts())) is None
    monkeypatch.setattr(spanlog, "records", lambda: None)
    assert _reader("ssm_prefill_pad_share")(_env(_facts())) is None
    assert _reader("ssm_prefill_pad_share")(_env({})) is None


def test_mfu_reader_adds_the_experts_part_from_the_counted_pairs(monkeypatch):
    log = _g4h_log()
    monkeypatch.setattr(spanlog, "records", lambda: log)
    clients = [{"span_s": 2.0, "prompt_lens": [3], "generated": [1]}]
    env = _env(dict(_facts(), clients=clients, window_s=4.0))
    held = 3 * 320 + 2 * 2560
    want = (g4h.serve_flops(CONFIG, [3], [1]) / 2.0
            + held * g4h.expert_pair_flops(CONFIG) / 4.0) / 1e12 * 100.0
    assert _reader("mfu.g4h")(env) == pytest.approx(want)
    env.peak = None
    assert _reader("mfu.g4h")(env) is None


def test_every_g4h_metric_has_a_reader_and_lists_the_cell_alone():
    b = json.loads((REPO / "BENCHMARK.json").read_text())
    mine = [m for m in b["per_layer"] if CELL_NAME in m.get("workloads", ())]
    assert len(mine) == 18 and all(m["workloads"] == [CELL_NAME] for m in mine)
    for m in mine:
        name = m["name"]
        assert name.endswith(".g4h") or name == "ssm_prefill_pad_share"
        files = [BENCH / "layer_metrics" / f"{n}.py"
                 for n in (name, name.rpartition(".")[0])]
        assert any(f.is_file() for f in files), name
    for m in b["end_to_end"]:
        if m["name"].startswith("generate_"):
            assert m["workloads"][-1] == CELL_NAME


# ---------------------------------------------------------------------------
# the cell's files, found by name, at a tiny override
# ---------------------------------------------------------------------------
def test_cell_files_are_found_by_name_and_run_at_a_tiny_size(tmp_path,
                                                            monkeypatch):
    """A copy of benchmarks/ with the configuration and the cell overridden
    to a tiny size (float32: XLA's CPU backend has no bfloat16 batch
    product): `run_cell` finds the family's model builder and reference, the
    `generate` driver and every reader by their names."""
    from harness import xplane
    monkeypatch.setattr(xplane, "DEVICE_PLANE", "/host:CPU")
    monkeypatch.setattr(xplane, "OPS_LINE", "tf_XLAPjRtCpuClient")
    bench = tmp_path / "benchmarks"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    tiny = dict(
        CONFIG, hidden_size=64, num_hidden_layers=3,
        layer_types=["mamba", "attention", "mamba"], mamba_n_heads=8,
        mamba_d_head=16, mamba_d_state=16, mamba_chunk_size=8,
        num_attention_heads=4, num_key_value_heads=2, intermediate_size=32,
        shared_intermediate_size=48, num_local_experts=4,
        num_experts_per_tok=3, vocab_size=96, max_position_embeddings=64,
        published=dict(CONFIG["published"], num_local_experts=8),
        deployment=dict(CONFIG["deployment"], held_experts=[0, 4]),
        precision=dict(CONFIG["precision"], weights="float32",
                       registry="fp32", kv_dtype="fp32", reference="float32"))
    cell = dict(
        CELL, traffic=dict(
            CELL["traffic"], clients=3,
            prompt_tokens={"dist": "loguniform", "lo": 5, "hi": 40},
            max_tokens={"dist": "uniform", "lo": 2, "hi": 8},
            ramp_seconds=0.5, request_timeout_seconds=60.0),
        serve={"registry_buckets": [1], "decode_buckets": [1, 2, 4],
               "prompt_buckets": [16, 64]},
        trace_seconds=0.3,
        check={"sample_requests": 3, "limits": {"served_logit_gap": 1e-5,
                                                "logit_rel_err": 1e-4}})
    (bench / "configs" / f"{CONFIG['name']}.json").write_text(json.dumps(tiny))
    (bench / "workloads" / f"{CELL_NAME}.json").write_text(json.dumps(cell))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    run = _load_run(bench)
    rc, line = run.run_cell(CELL_NAME, 2147483659, 0.5, True, bench=bench,
                            repo=tmp_path, check_device=False,
                            t_start=time.perf_counter())
    assert rc == 0
    out = json.loads(line)
    assert out["correct"] is True and out["failed"] == 0 < out["attempted"]
    for c in out["checks"].values():
        assert c["value"] <= c["limit"]
    b = json.loads((REPO / "BENCHMARK.json").read_text())
    listed = {m["name"] for m in b["per_layer"] if CELL_NAME in m["workloads"]}
    # a CPU has no peak: the two shares of one are left out
    assert listed - set(out["metrics"]) == {"mfu.g4h", "tick_hbm_share.g4h"}
    got = out["metrics"]
    assert 0 < got["moe_held_experts_hit.g4h"]["value"] <= 4
    assert got["moe_held_load_max_over_mean.g4h"]["value"] >= 1
    assert 0 < got["ssm_prefill_pad_share"]["value"] < 100
    assert 1.0 <= got["decode_rows_per_tick.g4h"]["value"] <= 4.0
