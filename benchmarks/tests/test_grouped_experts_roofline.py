"""The per-layer metric `grouped_experts_roofline`
(`layer_metrics/grouped_experts_roofline.py`) on a hand-made span log and
trace, on the CPU:

    python -m pytest benchmarks/tests/test_grouped_experts_roofline.py -q

The window's ticks carry the expert layers' counts as the engine writes
them; the trace holds the kernel's calls under its device-op name. Both
expert cells' configurations; nothing where the trace holds no call or the
spans no count (the parent commit, whose experts run under conditionals).
"""
from __future__ import annotations

import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
sys.path[:0] = [str(REPO), str(BENCH)]

from harness import spanlog, xplane  # noqa: E402
from test_benchmark import _load_run  # noqa: E402
from test_span_metrics import Log, _facts  # noqa: E402

bench_run = _load_run(BENCH)
CONFIGS = {name: json.loads((BENCH / "configs" / f"{name}.json").read_text())
           for name in ("granite-4.0-h-small", "longcat-flash-chat")}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _log(layers, hit):
    """Ticks 1-6 (the window holds 3-5) whose fetch spans carry the counts
    of `layers` expert layers, `hit` (layer, held expert) pairs a tick but
    tick 4, which hit `layers` more."""
    log = Log()
    for t, k in ((0, 1), (100, 2), (200, 3), (300, 4), (400, 5), (500, 6)):
        log.loop(t, k)
    for rec in log.records:
        if rec["name"] == "dl4j/engine/tick.fetch":
            rec["attrs"].update(moe_layers=layers, moe_picks=640,
                                moe_identity=0, moe_held=320,
                                moe_held_hit=hit, moe_held_load_max=40)
    ticks = [r for r in log.records if r["name"] == "dl4j/engine/tick.fetch"]
    ticks[3]["attrs"]["moe_held_hit"] = hit + layers
    return log.records


def _trace(calls, seconds):
    return xplane.Trace(devices={"/device:TPU:0": [
        ("grouped_experts.7", 1_000_000 + i * 1_000_000, int(seconds * 1e9))
        for i in range(calls)] + [("fusion.1", 500, 100)]})


def _read(env):
    return bench_run.load(BENCH, "layer_metrics",
                          "grouped_experts_roofline").compute(env)


@pytest.mark.parametrize("name,layers,hit,expert,call_s", [
    # 10 layers of 35 hit, a tick 36 more: 35.33 a layer; 18.9 MB an expert
    ("granite-4.0-h-small", 10, 350, 3 * 4096 * 768 * 2, 0.9e-3),
    # 4 layers of 24 hit: 6.33 a layer; 75.5 MB an expert
    ("longcat-flash-chat", 4, 24, 3 * 6144 * 2048 * 2, 0.65e-3)])
def test_roofline_reader_on_a_recorded_span_log(monkeypatch, name, layers, hit,
                                                expert, call_s):
    log = _log(layers, hit)
    monkeypatch.setattr(spanlog, "records", lambda: log)
    per_layer = (3 * hit + layers) / 3 / layers
    env = SimpleNamespace(facts=_facts(prefills=(0, 0)),
                          trace=_trace(40, call_s),
                          config=CONFIGS[name], xplane=xplane, peak=PEAK)
    want = 100.0 * 40 * per_layer * expert / 819e9 / (40 * call_s)
    got = _read(env)
    assert got == pytest.approx(want)
    assert 80 < got < 100           # never over the bytes' time
    # no call in the trace (the parent: conditionals), no trace, no peak
    env.trace = xplane.Trace(devices={"/device:TPU:0": [("cond.3", 0, 9)]})
    assert _read(env) is None
    for trace, peak in ((None, PEAK), (_trace(40, call_s), None)):
        assert _read(SimpleNamespace(**dict(vars(env), trace=trace,
                                            peak=peak))) is None
    # spans without the counts, no span log at all
    env.trace = _trace(40, call_s)
    bare = [dict(r, attrs={k: v for k, v in r["attrs"].items()
                           if not k.startswith("moe_")}) for r in log]
    for records in (bare, [], None):
        monkeypatch.setattr(spanlog, "records", lambda: records)
        assert _read(env) is None


def test_the_metric_lists_the_two_expert_cells():
    b = json.loads((REPO / "BENCHMARK.json").read_text())
    m, = [m for m in b["per_layer"] if m["name"] == "grouped_experts_roofline"]
    assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) == (
        "%", "higher", "device_trace", "kernels", "generate_tokens_per_s")
    assert m["workloads"] == ["longcat-flash-chat.generate-write",
                              "granite-4.0-h-small.generate-chat64"]
