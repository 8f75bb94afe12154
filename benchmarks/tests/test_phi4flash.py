"""Tests of what the `phi-4-mini-flash-reasoning` configuration brings to the
benchmark, on the CPU:

    python -m pytest benchmarks/tests/test_phi4flash.py -q

The configuration's file against the `model-configs` catalog's row and its
one cut, the parameter count recounted from the reference's leaves,
`harness/flops_phi4flash` against hand counts at a small shape, the new
readers on a hand-made span log and trace, and the cell's files found by name
through `run_cell` at a tiny override. (The layers and the served path
against the reference are in `tests/test_phi4flash.py`, inside tier-1.)
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

from harness import flops_phi4flash as p4f, spanlog, xplane  # noqa: E402
from test_benchmark import _load_run  # noqa: E402
from test_span_metrics import Log, _facts  # noqa: E402

bench_run = _load_run(BENCH)
CONFIG = json.loads((BENCH / "configs" / "phi-4-mini-flash-reasoning.json")
                    .read_text())
CELL_NAME = "phi-4-mini-flash-reasoning.generate-reason64"
CELL = json.loads((BENCH / "workloads" / f"{CELL_NAME}.json").read_text())
# the source's config.json as the `model-configs` catalog's row has it
PUBLISHED = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
    "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 200064}
MLP = 3 * 2560 * 10240                          # fc1 [gate | up], fc2
LAYER = {"mamba": 119_895_040, "attention": 98_314_624, "gmu": 104_867_840,
         "cross": 91_761_024}


def test_configuration_file_states_the_published_sizes_and_its_one_cut():
    reduced = set(CONFIG["reduced"])
    assert reduced == {"max_position_embeddings"}
    for key, value in PUBLISHED.items():
        if key in reduced:      # the published value stands beside the cut
            assert CONFIG["published"][key] == value != CONFIG[key]
        else:
            assert CONFIG[key] == value, key
    assert set(CONFIG["published"]) == reduced
    assert CONFIG["deployment"]["chips"] == 1
    assert CONFIG["precision"]["registry"] == CONFIG["precision"]["kv_dtype"] \
        == "bf16"
    for key in ("mamba_sizes", "differential_attention", "head_pairing",
                "memory", "window"):
        assert key in CONFIG["assumed"]
    b = json.loads((REPO / "BENCHMARK.json").read_text())
    entry = next(c for c in b["configs"] if c["name"] == CONFIG["name"])
    assert entry["source"] == CONFIG["source"]
    assert set(entry["reduced"]) == reduced
    cell = next(w for w in b["workloads"] if w["name"] == CELL_NAME)
    assert cell["why"] == CELL["why"] and len(cell["why"]) <= 200
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG["name"], "generate-reason64", 1)


def test_the_cell_is_the_traffic_its_file_states():
    t, s = CELL["traffic"], CELL["serve"]
    assert t["clients"] == 64 == max(s["decode_buckets"])
    assert s["decode_buckets"] == [64]
    assert t["prompt_tokens"] == {"dist": "loguniform", "lo": 128, "hi": 1024}
    assert t["max_tokens"] == {"dist": "uniform", "lo": 512, "hi": 2048}
    assert (t["temperature"], t["ramp_seconds"],
            t["request_timeout_seconds"]) == (0.0, 15.0, 120.0)
    assert s["prompt_buckets"] == [256, 512, 1024]
    assert t["prompt_tokens"]["hi"] + t["max_tokens"]["hi"] \
        <= CONFIG["max_position_embeddings"] == 4096


def test_parameter_count_is_what_the_file_and_the_reference_say():
    """3.85B parameters with the head tied, 4.36B as held (the second copy
    of the table), counted from the shapes the reference would make (nothing
    is made) and by hand from the widths."""
    import jax
    ref = bench_run.load(BENCH, "reference", CONFIG["family"])
    shapes = jax.eval_shape(lambda: ref.init_params(CONFIG, 0))
    leaves = jax.tree_util.tree_leaves(shapes)
    assert all(str(a.dtype) == "bfloat16" for a in leaves)
    p = CONFIG["parameters"]
    table = 200064 * 2560
    assert sum(a.size for a in leaves) == p["total_as_held"] \
        == p["total_with_the_head_tied"] + table
    assert shapes[-1]["W"].shape == (2560, 200064) and table == p["token_table"]
    size = lambda tree: sum(a.size for a in jax.tree_util.tree_leaves(tree))
    norms = 4 * 2560
    # Mamba: W_in, conv taps + bias, W_x, W_dt + bias, A, D, W_out
    mamba = (2560 * 10240 + 5 * 5120 + 5120 * 192 + 160 * 5120 + 5120
             + 5120 * 16 + 5120 + 5120 * 2560)
    attention = 2 * 2560 * 2560 + 2 * 2560 * 1280 + 4 * 64 + 128
    gmu, cross = 2 * 2560 * 5120, 2 * 2560 * 2560 + 4 * 64 + 128
    for kind, mix in (("mamba", mamba), ("attention", attention),
                      ("gmu", gmu), ("cross", cross)):
        assert mix + MLP + norms == LAYER[kind] == p[{
            "mamba": "a_mamba_layer",
            "attention": "an_attention_layer_window_or_full",
            "gmu": "a_gmu_layer", "cross": "a_cross_attention_layer"}[kind]]
    assert size(shapes[1]) == LAYER["mamba"] and size(shapes[2]) == LAYER[
        "attention"]
    cross_block = shapes[17]["layers"]
    assert [size(x) for x in cross_block[:4]] == [
        LAYER["mamba"], LAYER["attention"], LAYER["gmu"], LAYER["cross"]]
    layers = 9 * LAYER["mamba"] + 9 * LAYER["attention"] + 7 * (
        LAYER["gmu"] + LAYER["cross"])
    assert layers == p["the_32_layers"] == 3_340_289_024
    assert layers + table + 2 * 2560 == p["total_with_the_head_tied"]
    # weights plus the cache the cell keeps come to about 69% of the chip
    assert 2 * p["total_as_held"] > 0.25 * 16e9


def test_flops_and_bytes_against_hand_counts_at_a_small_shape():
    """8 layers, width 64 (4 differential heads of 8 on 2 key/value heads),
    state 4, rank 4, window 8, MLP 32, vocabulary 96."""
    small = dict(CONFIG, hidden_size=64, num_attention_heads=8,
                 num_key_value_heads=4, num_hidden_layers=8,
                 intermediate_size=32, vocab_size=96, sliding_window=8,
                 mamba_d_state=4, mamba_dt_rank=4)
    mamba = 64 * 256 + 128 * 12 + 4 * 128 + 128 * 64
    window, kv = 2 * 64 * 64 + 2 * 64 * 32, 2 * 64 * 32
    query_out, gmu, mlp = 2 * 64 * 64, 2 * 64 * 128, 3 * 64 * 32
    assert p4f.matrices(small) == {"mamba": mamba, "window": window,
                                   "full_kv": kv, "query_out": query_out,
                                   "gmu": gmu, "mlp": mlp}
    assert p4f.counts(small) == {"mamba": 3, "window": 2, "gmu": 1,
                                 "cross": 1, "readers": 2}
    weights = 3 * mamba + 2 * window + kv + 2 * query_out + gmu + 8 * mlp
    assert p4f.weights(small) == weights
    scan = 5 * 128 * 4 + 2 * 4 * 128
    selfs = 2 * (3 * mamba + 2 * window + kv + 5 * mlp) + 3 * scan
    cross = 2 * (2 * query_out + gmu + 3 * mlp)
    assert p4f.self_flops_per_token(small) == selfs
    assert p4f.cross_flops_per_token(small) == cross
    one_key = 8 * 4 * 8
    assert p4f.attention_flops(small, 1) == one_key
    # a prompt of 10 tokens, 3 sampled (2 fed back: positions 10, 11); the
    # window layers' queries at 0..11 read 1..8, then 8, 8, 8, 8 keys; the
    # readers' the last prompt token 10 keys, then 11 and 12
    head = 2 * 64 * 96
    window_keys = sum(range(1, 9)) + 4 * 8
    want = (10 * selfs + cross + 2 * (selfs + cross) + 3 * head
            + 2 * one_key * window_keys + 2 * one_key * (10 + 11 + 12))
    assert p4f.serve_flops(small, [10], [3]) == want
    # a tick of 2 rows over 5 live pages of 16 and 12 live ring slots
    state = 2 * 2 * 3 * (128 * 4 + 3 * 128)
    assert p4f.tick_bytes(small, 2, 5, 12) == (
        2 * (weights + 64 * 96) + 5 * 16 * 2 * 32 * 2 * 2
        + 12 * 2 * 32 * 2 * 2 + 4 * state)
    assert p4f.kernel_bytes(small, 5) == 5 * 16 * 2 * 32 * 2


def test_the_cells_tick_bytes_against_hand_figures():
    """64 rows at a mean context of 1,150: the weights 7.70 GB (the 32
    layers' 6.68 and the head's 1.02), the shared pages 3.0 GB over 8
    readers, the rings 1.34 GB, the state 0.45 GB read and written."""
    gb = lambda n: round(n / 1e9, 2)
    base = p4f.tick_bytes(CONFIG, 0, 0, 0)
    assert gb(base) == gb(2 * (p4f.weights(CONFIG) + 2560 * 200064)) == 7.70
    pages = 64 * (1150 // 16 + 1)
    assert gb(p4f.tick_bytes(CONFIG, 0, pages, 0) - base) == 3.02
    assert gb(p4f.tick_bytes(CONFIG, 0, 0, 64 * 512) - base) == 1.34
    assert gb(p4f.tick_bytes(CONFIG, 64, 0, 0) - base) == 0.45


# ---------------------------------------------------------------------------
# the new readers on a hand-made log and trace
# ---------------------------------------------------------------------------
def _p4f_log(window=True):
    """Ticks 1-6 as `test_span_metrics._serve_log` lays them out (dispatch
    10 ms + fetch 50 ms a tick); every tick's prepare span carries its live
    rows, pages and ring slots."""
    log = Log()
    for t, k, admit in ((0, 1, 1), (100, 2, None), (200, 3, 2),
                        (300, 4, None), (400, 5, 3), (520, 6, 4)):
        log.loop(t, k, admit=None if admit is None else
                 {"prefill": admit, "queue_wait_s": 0.01})
    for rec in log.records:
        if rec["name"] == "dl4j/engine/tick.prepare":
            rec["attrs"].update(pages_live=4600, pages_table=16384,
                                state_slots_live=64)
            if window:
                rec["attrs"]["window_live"] = 30000
    return log.records


def _reader(name):
    return bench_run.load(BENCH, "layer_metrics", name).compute


def _env(facts, **kw):
    return SimpleNamespace(**dict(dict(
        facts=facts, trace=None, config=CONFIG, xplane=xplane,
        peak={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 819e9}), **kw))


def test_tick_hbm_share_reader(monkeypatch):
    log = _p4f_log()
    monkeypatch.setattr(spanlog, "records", lambda: log)
    want = p4f.tick_bytes(CONFIG, 64, 4600, 30000) / 819e9 / 0.060 * 100.0
    assert _reader("tick_hbm_share.p4f")(_env(_facts())) == pytest.approx(want)
    assert _reader("tick_hbm_share.p4f")(_env(_facts(), peak=None)) is None
    # a program whose spans carry no ring slots (the parent commit)
    bare = _p4f_log(window=False)
    monkeypatch.setattr(spanlog, "records", lambda: bare)
    assert _reader("tick_hbm_share.p4f")(_env(_facts())) is None
    for records in ([], None):
        monkeypatch.setattr(spanlog, "records", lambda: records)
        assert _reader("tick_hbm_share.p4f")(_env(_facts())) is None


def test_kernel_roofline_reader(monkeypatch):
    """24 calls of the kernel in the traced window, 0.6 ms each, over the
    window ticks' mean of 4,600 live pages (0.46 ms of HBM time a call)."""
    log = _p4f_log()
    monkeypatch.setattr(spanlog, "records", lambda: log)
    trace = xplane.Trace(devices={"/device:TPU:0": [
        ("paged_diff_attention.3", 1_000_000 + i * 1_000_000, 600_000)
        for i in range(24)] + [("fusion.1", 500, 100)]})
    want = 24 * p4f.kernel_bytes(CONFIG, 4600) / 819e9 / (24 * 6e-4) * 100.0
    read = _reader("paged_diff_attention_roofline")
    assert read(_env(_facts(), trace=trace)) == pytest.approx(want)
    assert 0 < want < 100
    # no call of the kernel in the trace (the parent; a CPU run): nothing
    other = xplane.Trace(devices={"/device:TPU:0": [("fusion.1", 500, 100)]})
    assert read(_env(_facts(), trace=other)) is None
    assert read(_env(_facts())) is None


def test_mfu_reader(monkeypatch):
    clients = [{"span_s": 2.0, "prompt_lens": [300], "generated": [600]}]
    env = _env(dict(_facts(), clients=clients, window_s=4.0))
    want = p4f.serve_flops(CONFIG, [300], [600]) / 2.0 / 1e12 * 100.0
    assert _reader("mfu.p4f")(env) == pytest.approx(want)
    env.peak = None
    assert _reader("mfu.p4f")(env) is None


def test_every_p4f_metric_has_a_reader_and_lists_the_cell_alone():
    b = json.loads((REPO / "BENCHMARK.json").read_text())
    mine = [m for m in b["per_layer"] if m.get("workloads") == [CELL_NAME]]
    assert {m["name"] for m in mine} == {
        "mfu.p4f", "tick_hbm_share.p4f", "paged_diff_attention_roofline",
        "device_idle_share.p4f", "sched_loop_ms.p4f", "tick_fetch_ms.p4f",
        "prefill_ms.p4f", "decode_rows_per_tick.p4f", "first_token_p95_ms.p4f"}
    for m in mine:
        name = m["name"]
        files = [BENCH / "layer_metrics" / f"{n}.py"
                 for n in (name, name.rpartition(".")[0])]
        assert any(f.is_file() for f in files), name
    for m in b["end_to_end"] + b["per_layer"]:
        if m["name"].startswith(("generate_", "setup_")) and "workloads" in m:
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
    monkeypatch.setattr(xplane, "DEVICE_PLANE", "/host:CPU")
    monkeypatch.setattr(xplane, "OPS_LINE", "tf_XLAPjRtCpuClient")
    bench = tmp_path / "benchmarks"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    tiny = dict(
        CONFIG, hidden_size=64, num_attention_heads=8, num_key_value_heads=4,
        num_hidden_layers=8, intermediate_size=32, vocab_size=96,
        max_position_embeddings=64, sliding_window=8, mamba_d_state=4,
        mamba_dt_rank=4, mamba_chunk_size=4,
        precision=dict(CONFIG["precision"], weights="float32",
                       registry="fp32", kv_dtype="fp32", reference="float32"))
    cell = dict(
        CELL, traffic=dict(
            CELL["traffic"], clients=3,
            prompt_tokens={"dist": "loguniform", "lo": 5, "hi": 30},
            max_tokens={"dist": "uniform", "lo": 4, "hi": 20},
            ramp_seconds=0.5, request_timeout_seconds=60.0),
        serve={"registry_buckets": [1], "decode_buckets": [4],
               "prompt_buckets": [16, 32]},
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
    # a CPU has no peak and runs no kernel: those three are left out
    assert listed - set(out["metrics"]) == {
        "mfu.p4f", "tick_hbm_share.p4f", "paged_diff_attention_roofline"}
    assert 1.0 <= out["metrics"]["decode_rows_per_tick.p4f"]["value"] <= 3.0
