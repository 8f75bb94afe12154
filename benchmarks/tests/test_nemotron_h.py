"""Tests of what the `nemotron-3-super-120b-a12b` configuration brings to the
benchmark, on the CPU:

    python -m pytest benchmarks/tests/test_nemotron_h.py -q

The configuration's file against the `model-configs` catalog's row and its
cuts, the parameter count recounted from the reference's leaves,
`harness/flops_nemotron_h` against hand figures (a tick's 14.6 GB at 128
rows among them), the three new readers on a hand-made span log and trace
and with their counts absent, and the cell's files found by name through
`run_cell` at a tiny override. (The layers, the share and the served path
against the reference are in `tests/test_nemotron_h.py`, inside tier-1.)
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

from harness import flops_nemotron_h as n3s, spanlog, xplane  # noqa: E402
from test_benchmark import _load_run  # noqa: E402
from test_span_metrics import Log, _facts  # noqa: E402

bench_run = _load_run(BENCH)
CONFIG = json.loads((BENCH / "configs" / "nemotron-3-super-120b-a12b.json")
                    .read_text())
CELL_NAME = "nemotron-3-super-120b-a12b.generate-chat128"
CELL = json.loads((BENCH / "workloads" / f"{CELL_NAME}.json").read_text())
# the source's config.json as the `model-configs` catalog's row has it
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 4096,
    "hybrid_override_pattern": "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM"
    "*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 2688, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 128,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 2688, "moe_latent_size": 1024,
    "moe_shared_expert_intermediate_size": 5376,
    "moe_shared_expert_overlap": False, "mtp_hybrid_override_pattern": "*E",
    "n_group": 1, "n_groups": 8, "n_routed_experts": 512,
    "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 22,
    "num_hidden_layers": 88, "num_key_value_heads": 2,
    "num_logits_to_keep": 1, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072}
CUTS = {"num_hidden_layers": (11, 88), "n_routed_experts": (128, 512),
        "vocab_size": (32768, 131072),
        "max_position_embeddings": (2048, 262144)}
# a layer's parameters, from its widths
MAMBA, ATTENTION, MOE, EXPERT = 109_640_064, 35_655_680, 54_530_560, 5_505_024


def test_configuration_file_states_the_published_sizes_and_its_cuts():
    reduced = set(CONFIG["reduced"])
    assert reduced == set(CUTS)
    for key, (cut, published) in CUTS.items():
        assert (CONFIG[key], CONFIG["published"][key]) == (cut, published)
    assert set(CONFIG["published"]) == reduced
    for key, value in PUBLISHED.items():    # every key, uncut or cut
        assert (CONFIG["published"] if key in reduced else CONFIG)[
            key] == value, key
    # the floors of a cut: a whole period, 8 experts, an eighth of the vocabulary
    served = CONFIG["hybrid_override_pattern"][:CONFIG["num_hidden_layers"]]
    assert served == "MEMEMEM*EME"
    assert (served.count("M"), served.count("E"), served.count("*")) == (5, 5, 1)
    assert CONFIG["n_routed_experts"] >= 8
    assert CONFIG["vocab_size"] * 8 >= CUTS["vocab_size"][1]
    d = CONFIG["deployment"]
    assert d["held_experts"] == [0, 128]
    assert d["chips_sharing_a_layer"] * CONFIG["n_routed_experts"] == 512
    assert d["vocabulary_slices"] * CONFIG["vocab_size"] == 131072
    assert d["pipeline_stages"] * CONFIG["num_hidden_layers"] == 88
    assert d["chips"] == d["pipeline_stages"] * d["chips_sharing_a_layer"]
    assert CONFIG["precision"]["registry"] == CONFIG["precision"]["kv_dtype"] \
        == "bf16"
    b = json.loads((REPO / "BENCHMARK.json").read_text())
    entry = next(c for c in b["configs"] if c["name"] == CONFIG["name"])
    assert entry["source"] == CONFIG["source"]
    assert entry["file"] == f"benchmarks/configs/{CONFIG['name']}.json"
    assert set(entry["reduced"]) == reduced
    cell = next(w for w in b["workloads"] if w["name"] == CELL_NAME)
    assert cell["why"] == CELL["why"] and len(cell["why"]) <= 200
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG["name"], "generate-chat128", 1)


def test_the_cell_is_the_traffic_its_file_states():
    t, s = CELL["traffic"], CELL["serve"]
    assert t["clients"] == 128 == max(s["decode_buckets"])
    assert s["decode_buckets"] == [128]       # the cell file says why one
    assert t["prompt_tokens"] == {"dist": "loguniform", "lo": 128, "hi": 1024}
    assert t["max_tokens"] == {"dist": "uniform", "lo": 128, "hi": 512}
    assert (t["temperature"], t["ramp_seconds"],
            t["request_timeout_seconds"]) == (0.0, 10.0, 120.0)
    assert s["prompt_buckets"] == [256, 512, 1024]
    assert s["registry_buckets"] == [1]
    assert t["prompt_tokens"]["hi"] + t["max_tokens"]["hi"] \
        <= CONFIG["max_position_embeddings"]


def test_parameter_count_is_what_the_file_and_the_reference_say():
    """4.65B parameters, 9.30 GB of bfloat16, counted from the shapes the
    reference would make (nothing is made) and by hand from the widths."""
    import jax
    ref = bench_run.load(BENCH, "reference", CONFIG["family"])
    shapes = jax.eval_shape(lambda: ref.init_params(CONFIG, 0))
    leaves = jax.tree_util.tree_leaves(shapes)
    assert all(str(a.dtype) == "bfloat16" for a in leaves)
    p = CONFIG["parameters"]
    count = lambda tree: sum(a.size for a in jax.tree_util.tree_leaves(tree))
    layers = shapes[1:-2]
    assert [count(x) for x in layers] == [
        MAMBA if c == "M" else ATTENTION if c == "*" else MOE + 128 * EXPERT
        for c in "MEMEMEM*EME"]
    assert (MAMBA, ATTENTION, MOE, EXPERT) == (
        p["a_mamba_layer"], p["the_attention_layer"],
        p["a_moe_layer_outside_its_experts"], p["an_expert"])
    # W_in 4,096 x (8,192 + 10,240 + 128), the convolution, 3 scalars a
    # head, the gated norm, W_out, the pre-norm
    assert MAMBA == 4096 * 18560 + 4 * 10240 + 10240 + 3 * 128 + 8192 \
        + 8192 * 4096 + 4096
    assert n3s.mamba_weights(CONFIG) == 4096 * 18560 + 8192 * 4096
    assert ATTENTION == n3s.attention_weights(CONFIG) + 4096
    assert MOE == n3s.moe_weights(CONFIG) + 512 + 4096
    assert EXPERT == n3s.expert_bytes(CONFIG, 1) == 2 * 1024 * 2688
    held = count(shapes)
    assert held == p["total_as_held"] == 4_648_163_712
    assert 5 * MAMBA + ATTENTION + 5 * (MOE + 128 * EXPERT) \
        + 2 * p["token_table"] + 4096 == held
    assert round(2 * held / 1e9, 2) == 9.30
    assert 2 * held > 0.5 * 16.9e9          # over the floor by weights alone


def test_flops_and_bytes_against_hand_figures():
    outside = 5 * n3s.mamba_weights(CONFIG) + n3s.attention_weights(CONFIG) \
        + 5 * n3s.moe_weights(CONFIG)
    assert n3s.outside_experts_weights(CONFIG) == outside
    scan = 5 * 128 * 64 * 128 + 2 * 4 * 10240
    assert n3s.dense_flops_per_token(CONFIG) == 2 * outside + 5 * scan
    assert n3s.attention_flops(CONFIG, 1) == 4 * 32 * 128
    assert n3s.expert_pair_flops(CONFIG) == 4 * 1024 * 2688
    assert n3s.head_flops_per_token(CONFIG) == 2 * 4096 * 32768
    assert n3s.serve_flops(CONFIG, [3], [1], held_pairs=2) == (
        3 * n3s.dense_flops_per_token(CONFIG)
        + 6 * n3s.attention_flops(CONFIG, 1)
        + n3s.head_flops_per_token(CONFIG) + 2 * 4 * 1024 * 2688)
    # a layer keeps 4.19 MB of state and 123 kB of inputs for a sequence
    assert n3s.state_elements(CONFIG) == 128 * 64 * 128 + 3 * 10240


def test_a_full_tick_moves_14_6_gb():
    """128 rows: 99.6% of the 128 held experts hit in 5 layers, 7.02 GB;
    the state read and written, 5.53 GB; the Mamba weights 1.10 GB, the MoE
    layers' outside their experts 0.55, the head 0.27, the attention
    weights and 128 rows' pages of some 600 tokens 0.15: 14.6 GB, 17.8 ms at
    819 GB/s."""
    gb = lambda n: round(n / 1e9, 2)
    base = n3s.tick_bytes(CONFIG, 0, 0, 0)
    hit = 5 * 128 * (1 - (1 - 22 / 512) ** 128)
    assert round(hit / 5, 1) == 127.5
    assert gb(n3s.tick_bytes(CONFIG, 0, hit, 0) - base) == 7.02
    assert gb(n3s.tick_bytes(CONFIG, 128, 0, 0) - base) == 5.53
    assert gb(2 * 5 * n3s.mamba_weights(CONFIG)) == 1.10
    assert gb(2 * 5 * n3s.moe_weights(CONFIG)) == 0.55
    assert gb(2 * 4096 * 32768) == 0.27
    pages = 128 * 600 // 16
    whole = n3s.tick_bytes(CONFIG, 128, hit, pages)
    assert gb(whole - base) + gb(base) == pytest.approx(14.6, abs=0.05)
    assert gb(whole) == pytest.approx(14.6, abs=0.05)
    assert round(whole / 819e9 * 1e3, 1) == 17.8


# ---------------------------------------------------------------------------
# the new readers on a hand-made log and trace
# ---------------------------------------------------------------------------
def _n3s_log(counts=True):
    """Ticks 1-6 as `test_span_metrics._serve_log` lays them out: a tick is
    dispatch 10 ms + fetch 50 ms; every tick's spans carry the counts of 5
    expert layers (630 held experts hit), its live rows and pages."""
    log = Log()
    for t, k, admit in ((0, 1, 1), (100, 2, None), (200, 3, 2),
                        (300, 4, None), (400, 5, 3), (520, 6, 4)):
        log.loop(t, k, admit=None if admit is None else
                 {"prefill": admit, "queue_wait_s": 0.01})
    for rec in log.records:
        if rec["name"] == "dl4j/engine/tick.fetch" and counts:
            rec["attrs"].update(moe_layers=5, moe_picks=128 * 22 * 5,
                                moe_identity=0, moe_held=3500,
                                moe_held_hit=630, moe_held_load_max=60)
        if rec["name"] == "dl4j/engine/tick.prepare":
            rec["attrs"].update(pages_live=4800, pages_table=16384,
                                state_slots_live=128)
        if rec["name"] == "dl4j/engine/prefill.fetch" and counts:
            rec["attrs"].update(moe_layers=5, moe_picks=1024 * 22 * 5,
                                moe_identity=0, moe_held=28000,
                                moe_held_hit=640, moe_held_load_max=400)
    return log.records


def _reader(name):
    return bench_run.load(BENCH, "layer_metrics", name).compute


def _env(facts, **kw):
    return SimpleNamespace(**dict(dict(
        facts=facts, trace=None, config=CONFIG, xplane=xplane,
        peak={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 819e9}), **kw))


def test_tick_hbm_share_reader(monkeypatch):
    log = _n3s_log()
    monkeypatch.setattr(spanlog, "records", lambda: log)
    # ticks 3-5, each 60 ms of dispatch + fetch, the same bytes
    want = n3s.tick_bytes(CONFIG, 128, 630, 4800) / 819e9 / 0.060 * 100.0
    read = _reader("tick_hbm_share.n3s")
    assert read(_env(_facts())) == pytest.approx(want)
    assert read(_env(_facts(), peak=None)) is None
    # a program whose spans carry no expert counts (the parent commit)
    bare = _n3s_log(counts=False)
    monkeypatch.setattr(spanlog, "records", lambda: bare)
    assert read(_env(_facts())) is None
    for records in ([], None):
        monkeypatch.setattr(spanlog, "records", lambda: records)
        assert read(_env(_facts())) is None


def test_kernel_roofline_reader(monkeypatch):
    """15 calls of the kernel in the traced window, 1.8 ms each, over the
    window ticks' mean of 126 held experts hit a layer, two matrices of
    1,024 x 2,688 in bfloat16 each."""
    log = _n3s_log()
    monkeypatch.setattr(spanlog, "records", lambda: log)
    trace = xplane.Trace(devices={"/device:TPU:0": [
        ("grouped_experts.3", 1_000_000 + i * 3_000_000, 1_800_000)
        for i in range(15)] + [("fusion.1", 500, 100)]})
    want = 15 * 126 * 2 * 1024 * 2688 * 2 / 819e9 / (15 * 1.8e-3) * 100.0
    read = _reader("grouped_experts_roofline.n3s")
    assert read(_env(_facts(), trace=trace)) == pytest.approx(want)
    assert 0 < want < 100
    # no call of the kernel (experts under conditionals, a CPU run), no
    # trace, no counts: nothing
    other = xplane.Trace(devices={"/device:TPU:0": [("fusion.1", 500, 100)]})
    assert read(_env(_facts(), trace=other)) is None
    assert read(_env(_facts())) is None
    monkeypatch.setattr(spanlog, "records", lambda: _n3s_log(counts=False))
    assert read(_env(_facts(), trace=trace)) is None


def test_mfu_reader_adds_the_experts_part_from_the_counted_pairs(monkeypatch):
    log = _n3s_log()
    monkeypatch.setattr(spanlog, "records", lambda: log)
    clients = [{"span_s": 2.0, "prompt_lens": [300], "generated": [200]}]
    env = _env(dict(_facts(), clients=clients, window_s=4.0))
    held = 3 * 3500 + 2 * 28000     # ticks 3-5, admissions 2-3
    want = (n3s.serve_flops(CONFIG, [300], [200]) / 2.0
            + held * n3s.expert_pair_flops(CONFIG) / 4.0) / 1e12 * 100.0
    assert _reader("mfu.n3s")(env) == pytest.approx(want)
    env.peak = None
    assert _reader("mfu.n3s")(env) is None
    monkeypatch.setattr(spanlog, "records", lambda: _n3s_log(counts=False))
    assert _reader("mfu.n3s")(_env(dict(_facts(), clients=clients,
                                         window_s=4.0))) is None


def test_every_n3s_metric_has_a_reader_and_lists_the_cell_alone():
    b = json.loads((REPO / "BENCHMARK.json").read_text())
    mine = [m for m in b["per_layer"] if m.get("workloads") == [CELL_NAME]]
    assert {m["name"] for m in mine} == {
        "grouped_experts_roofline.n3s", "tick_hbm_share.n3s", "mfu.n3s",
        "device_idle_share.n3s", "decode_tick_ms.n3s", "prefill_ms.n3s",
        "tick_fetch_ms.n3s", "decode_rows_per_tick.n3s",
        "moe_held_experts_hit.n3s", "first_token_p95_ms.n3s",
        "queue_wait_p95_ms.n3s", "sched_loop_ms.n3s", "sched_host_ms.n3s",
        "tick_prepare_ms.n3s", "tick_dispatch_ms.n3s", "tick_sample_ms.n3s",
        "decode_tokens_per_s.n3s", "moe_held_load_max_over_mean.n3s",
        "ssm_prefill_pad_share.n3s"}
    p95 = {"first_token_p95_ms.n3s", "queue_wait_p95_ms.n3s"}
    for m in mine:
        assert m["moves"] == ("generate_latency_p95_ms" if m["name"] in p95
                              else "generate_tokens_per_s")
        name = m["name"]
        files = [BENCH / "layer_metrics" / f"{n}.py"
                 for n in (name, name.rpartition(".")[0])]
        assert any(f.is_file() for f in files), name
    for m in b["end_to_end"] + b["per_layer"]:   # a later cell may follow
        if m["name"].startswith(("generate_", "setup_")) and "workloads" in m:
            assert CELL_NAME in m["workloads"]
    old = next(m for m in b["per_layer"]
               if m["name"] == "grouped_experts_roofline")
    assert CELL_NAME not in old["workloads"]


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
        CONFIG, hidden_size=64, num_hidden_layers=5,
        hybrid_override_pattern="MEM*E", expand=1, mamba_num_heads=8,
        mamba_head_dim=8, ssm_state_size=16, n_groups=4, chunk_size=8,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        moe_intermediate_size=32, moe_latent_size=32,
        moe_shared_expert_intermediate_size=48, n_routed_experts=8,
        num_experts_per_tok=6, vocab_size=96, max_position_embeddings=64,
        published=dict(CONFIG["published"], n_routed_experts=16),
        deployment=dict(CONFIG["deployment"], held_experts=[0, 8]),
        precision=dict(CONFIG["precision"], weights="float32",
                       registry="fp32", kv_dtype="fp32", reference="float32"))
    cell = dict(
        CELL, traffic=dict(
            CELL["traffic"], clients=3,
            prompt_tokens={"dist": "loguniform", "lo": 5, "hi": 30},
            max_tokens={"dist": "uniform", "lo": 2, "hi": 8},
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
        "mfu.n3s", "tick_hbm_share.n3s", "grouped_experts_roofline.n3s"}
    got = out["metrics"]
    assert 0 < got["moe_held_experts_hit.n3s"]["value"] <= 8
    assert 1.0 <= got["decode_rows_per_tick.n3s"]["value"] <= 3.0
