"""Tests of what the `longcat-flash-chat` configuration brings to the
benchmark, on the CPU:

    python -m pytest benchmarks/tests/test_longcat_flash.py -q

The configuration's file against the published sizes, the cell's files found
by name through `run_cell` at a tiny override, `harness/flops_longcat_flash`
against hand counts, and the three `moe_*` readers on a hand-made span log.
(The block, the share and the served path against the reference are in
`tests/test_shortcut_moe.py`, inside tier-1.)
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

from harness import flops_longcat_flash as lcf, spanlog  # noqa: E402
from test_benchmark import _load_run  # noqa: E402
from test_span_metrics import Log, _facts  # noqa: E402

bench_run = _load_run(BENCH)
CONFIG = json.loads((BENCH / "configs" / "longcat-flash-chat.json").read_text())
CELL = json.loads(
    (BENCH / "workloads" / "longcat-flash-chat.generate-write.json").read_text())
# the source's config.json (the `model-configs` catalog's row), key by key
PUBLISHED = {
    "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
    "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
    "num_layers": 28, "num_attention_heads": 64, "kv_lora_rank": 512,
    "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
    "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
    "n_routed_experts": 512, "max_position_embeddings": 131072,
    "rms_norm_eps": 1e-05, "rope_theta": 10000000, "attention_method": "MLA",
    "zero_expert_num": 256, "zero_expert_type": "identity", "moe_topk": 12}


def test_configuration_file_states_the_published_sizes_and_its_cuts():
    reduced = set(CONFIG["reduced"])
    assert reduced == {"num_layers", "n_routed_experts", "vocab_size",
                       "max_position_embeddings"}
    for key, value in PUBLISHED.items():
        if key in reduced:      # the published value stands beside the cut
            assert CONFIG["published"][key] == value != CONFIG[key]
        else:
            assert CONFIG[key] == value, key
    assert set(CONFIG["published"]) == reduced
    # the floors of a cut: 4 layers, 8 experts, an eighth of the vocabulary
    assert CONFIG["num_layers"] >= 4 and CONFIG["n_routed_experts"] >= 8
    assert CONFIG["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    d = CONFIG["deployment"]
    assert d["held_experts"] == [0, CONFIG["n_routed_experts"]]
    assert d["chips_sharing_a_layer"] * CONFIG["n_routed_experts"] == 512
    assert d["vocabulary_slices"] * CONFIG["vocab_size"] == 131072
    assert CONFIG["precision"]["registry"] == CONFIG["precision"]["kv_dtype"] == "bf16"
    b = json.loads((REPO / "BENCHMARK.json").read_text())
    entry = next(c for c in b["configs"] if c["name"] == CONFIG["name"])
    assert entry["source"] == CONFIG["source"]


def test_parameter_count_is_what_the_file_and_the_reference_say():
    """5.17B parameters, counted from the shapes the reference would make
    (nothing is made) and by hand from the widths."""
    import jax
    ref = bench_run.load(BENCH, "reference", CONFIG["family"])
    shapes = jax.eval_shape(lambda: ref.init_params(CONFIG, 0))
    leaves = jax.tree_util.tree_leaves(shapes)
    total = sum(a.size for a in leaves)
    assert all(str(a.dtype) == "bfloat16" for a in leaves)
    assert total == CONFIG["parameters"]["total"]
    assert abs(total / 5.17e9 - 1) < 0.01
    mla = (6144 * 1536 + 1536 * 64 * 192 + 6144 * 576 + 512 * 64 * 256
           + 8192 * 6144)
    assert lcf.mla_weights(CONFIG) == mla == 90_570_752
    outside = 2 * (mla + 1536 + 512) + 2 * 3 * 6144 * 12288 + 6144 * 768 \
        + 768 + 4 * 6144
    assert outside == CONFIG["parameters"]["a_layer_outside_the_experts"]
    assert total == 4 * (outside + 16 * 3 * 6144 * 2048) + 2 * 16384 * 6144 + 6144
    assert 2 * total > 10.3e9            # over 25% of the chip by weights


def test_flops_against_hand_figures():
    dense = lcf.dense_flops_per_token(CONFIG)
    assert dense == 2 * 4 * (2 * 90_570_752 + 6 * 6144 * 12288 + 6144 * 768)
    assert round((dense + lcf.head_flops_per_token(CONFIG)) / 1e9, 1) == 5.3
    assert lcf.attention_flops(CONFIG, 1) == 2 * 64 * (192 + 128) * 8
    assert lcf.expert_pair_flops(CONFIG) == 6 * 6144 * 2048
    # one prompt of 3 tokens, 1 token sampled, 2 pairs on held experts: 3
    # passes of the blocks at contexts 1, 2, 3, one head, two expert FFNs
    assert lcf.serve_flops(CONFIG, [3], [1], held_pairs=2) == (
        3 * dense + 6 * lcf.attention_flops(CONFIG, 1)
        + lcf.head_flops_per_token(CONFIG) + 2 * 6 * 6144 * 2048)
    # a tick of 2 rows at contexts 100 and 300 that hit 5 (layer, expert)
    # pairs: every weight outside the experts, 5 experts, 400 cached tokens
    outside = 4 * (2 * 90_570_752 + 6 * 6144 * 12288 + 6144 * 768) \
        + 6144 * 16384
    assert lcf.tick_bytes(CONFIG, [100, 300], 5) == (
        2 * (outside + 5 * 3 * 6144 * 2048) + 2 * 400 * 8 * 576)
    assert round(lcf.tick_bytes(CONFIG, [], 0) / 1e9, 1) == 5.3


# ---------------------------------------------------------------------------
# the readers over the new counts
# ---------------------------------------------------------------------------
def _moe_log():
    """Ticks 1-6 as `test_span_metrics._serve_log` lays them out; every
    engine fetch span carries counts of 4 expert layers."""
    log = Log()
    tick = dict(moe_layers=4, moe_picks=96, moe_identity=30, moe_held=3,
                moe_held_hit=3, moe_held_load_max=3)
    prefill = dict(moe_layers=4, moe_picks=4800, moe_identity=1700,
                   moe_held=100, moe_held_hit=60, moe_held_load_max=40)
    for t, k, admit in ((0, 1, 1), (100, 2, None), (200, 3, 2),
                        (300, 4, None), (400, 5, 3), (520, 6, 4)):
        log.loop(t, k, admit=None if admit is None else
                 {"prefill": admit, "queue_wait_s": 0.01})
    for rec in log.records:
        if rec["name"] == "dl4j/engine/tick.fetch":
            rec["attrs"].update(tick)
        if rec["name"] == "dl4j/engine/prefill.fetch":
            rec["attrs"].update(prefill)
    # tick 4 hit one expert more in each layer; prefill 3 routed nothing here
    by = lambda name, n: [r for r in log.records if r["name"] == name][n]
    by("dl4j/engine/tick.fetch", 3)["attrs"].update(moe_held=7, moe_held_hit=7)
    by("dl4j/engine/prefill.fetch", 2)["attrs"].update(
        moe_held=0, moe_held_hit=0, moe_held_load_max=0)
    return log.records


def _reader(name):
    return bench_run.load(BENCH, "layer_metrics", name).compute


@pytest.mark.parametrize("name,want", [
    # ticks 3-5 and prefills 2-3: (3 x 30 + 2 x 1700) / (3 x 96 + 2 x 4800)
    ("moe_identity_pick_share", 100.0 * 3490 / 9888),
    ("moe_held_experts_hit", (3 + 7 + 3) / 3 / 4),
    # prefill 2 alone has a pick on a held expert: 16 x 40 / 100
    ("moe_held_load_max_over_mean", 6.4)])
def test_moe_reader_on_a_hand_made_log(monkeypatch, name, want):
    log = _moe_log()
    env = lambda facts: SimpleNamespace(facts=facts, trace=None, config=CONFIG,
                                        peak=None)
    monkeypatch.setattr(spanlog, "records", lambda: log)
    assert _reader(name)(env(_facts())) == pytest.approx(want)
    # a program whose spans carry no counts (the parent commit, a GPT stack)
    bare = [dict(r, attrs={k: v for k, v in r["attrs"].items()
                           if not k.startswith("moe_")}) for r in log]
    monkeypatch.setattr(spanlog, "records", lambda: bare)
    assert _reader(name)(env(_facts())) is None
    # an empty log, no log at all, a run that scraped no counters
    for records in ([], None):
        monkeypatch.setattr(spanlog, "records", lambda: records)
        assert _reader(name)(env(_facts())) is None
    monkeypatch.setattr(spanlog, "records", lambda: log)
    assert _reader(name)(env({})) is None


def test_mfu_reader_adds_the_experts_part_from_the_counted_pairs(monkeypatch):
    log = _moe_log()
    monkeypatch.setattr(spanlog, "records", lambda: log)
    clients = [{"span_s": 2.0, "prompt_lens": [3], "generated": [1]}]
    facts = dict(_facts(), clients=clients, window_s=4.0)
    env = SimpleNamespace(facts=facts, config=CONFIG, trace=None,
                          peak={"bf16_flops_per_s": 1e12})
    held = 3 + 7 + 3 + 100 + 0
    want = (lcf.serve_flops(CONFIG, [3], [1]) / 2.0
            + held * lcf.expert_pair_flops(CONFIG) / 4.0) / 1e12 * 100.0
    assert _reader("mfu.lcf")(env) == pytest.approx(want)
    env.peak = None
    assert _reader("mfu.lcf")(env) is None
    monkeypatch.setattr(spanlog, "records", lambda: [])
    env.peak = {"bf16_flops_per_s": 1e12}
    assert _reader("mfu.lcf")(env) is None


# ---------------------------------------------------------------------------
# the cell's files, found by name, at a tiny override
# ---------------------------------------------------------------------------
def test_cell_files_are_found_by_name_and_run_at_a_tiny_size(tmp_path,
                                                            monkeypatch):
    """A copy of benchmarks/ with the configuration and the cell overridden
    to a tiny size (float32: XLA's CPU backend has no bfloat16 batch
    product): `run_cell` finds the family's model builder and reference, the
    `generate` driver and the `.lcf` and `moe_*` readers by their names."""
    from harness import xplane
    monkeypatch.setattr(xplane, "DEVICE_PLANE", "/host:CPU")
    monkeypatch.setattr(xplane, "OPS_LINE", "tf_XLAPjRtCpuClient")
    bench = tmp_path / "benchmarks"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    tiny = dict(
        CONFIG, vocab_size=96, hidden_size=64, ffn_hidden_size=128,
        expert_ffn_hidden_size=32, num_layers=2, num_attention_heads=4,
        kv_lora_rank=16, q_lora_rank=32, qk_rope_head_dim=8, v_head_dim=16,
        qk_nope_head_dim=16, n_routed_experts=4, zero_expert_num=8,
        moe_topk=4, max_position_embeddings=64,
        published=dict(CONFIG["published"], n_routed_experts=16),
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
        check={"sample_requests": 3, "limits": {"served_logit_gap": 1e-3,
                                                "logit_rel_err": 1e-3}})
    (bench / "configs" / f"{CONFIG['name']}.json").write_text(json.dumps(tiny))
    (bench / "workloads" / f"{CELL['name']}.json").write_text(json.dumps(cell))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    run = _load_run(bench)
    rc, line = run.run_cell(CELL["name"], 2147483659, 0.5, True, bench=bench,
                            repo=tmp_path, check_device=False,
                            t_start=time.perf_counter())
    assert rc == 0
    out = json.loads(line)
    assert out["correct"] is True and out["failed"] == 0 < out["attempted"]
    for c in out["checks"].values():
        assert c["value"] <= c["limit"]
    b = json.loads((REPO / "BENCHMARK.json").read_text())
    listed = {m["name"] for m in b["per_layer"] if CELL["name"] in m["workloads"]}
    assert listed - set(out["metrics"]) == {"mfu.lcf"}      # a CPU has no peak
    got = out["metrics"]
    assert 0 < got["moe_identity_pick_share"]["value"] < 100
    assert 0 < got["moe_held_experts_hit"]["value"] <= 4
    assert got["moe_held_load_max_over_mean"]["value"] >= 1
    assert 1.0 <= got["decode_rows_per_tick.lcf"]["value"] <= 4.0
