"""The per-layer metric `ring_diff_attention_roofline`
(`layer_metrics/ring_diff_attention_roofline.py`) on a hand-made span log and
trace, on the CPU:

    python -m pytest benchmarks/tests/test_ring_diff_attention_roofline.py -q

The window's ticks carry their live ring slots as the engine writes them; the
trace holds the kernel's calls under its device-op name beside the shared
pages' `paged_diff_attention`, which the reader must not count. Nothing where
the trace holds no call (the parent commit, whose window layers read their
rings through XLA) or the spans no ring slots.
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
CELL = "phi-4-mini-flash-reasoning.generate-reason64"
CONFIG = json.loads((BENCH / "configs" / "phi-4-mini-flash-reasoning.json")
                    .read_text())
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _log(window_live=26549):
    """Ticks 1-6 (the window holds 3-5) whose prepare spans carry the live
    ring slots, `window_live` a tick but tick 4, which has 64 more."""
    log = Log()
    for t, k, admit in ((0, 1, 1), (100, 2, None), (200, 3, 2),
                        (300, 4, None), (400, 5, 3), (520, 6, 4)):
        log.loop(t, k, admit=None if admit is None else
                 {"prefill": admit, "queue_wait_s": 0.01})
    prepares = [r for r in log.records
                if r["name"] == "dl4j/engine/tick.prepare"]
    for rec in prepares:
        rec["attrs"].update(pages_live=4142, state_slots_live=64)
        if window_live is not None:
            rec["attrs"]["window_live"] = window_live
    if window_live is not None:
        prepares[3]["attrs"]["window_live"] = window_live + 64
    return log.records


def _trace(calls, seconds):
    """`calls` of the ring kernel of `seconds` each, and as many of the
    shared pages' kernel (twice as long), a fusion beside them."""
    ops = [(name, 1_000_000 + i * 3_000_000 + at, int(t * 1e9))
           for i in range(calls)
           for name, at, t in (("ring_diff_attention.12", 0, seconds),
                               ("paged_diff_attention.3", 1_000_000,
                                2 * seconds))]
    return xplane.Trace(devices={"/device:TPU:0": ops + [("fusion.1", 500,
                                                          100)]})


def _env(**kw):
    return SimpleNamespace(**dict(dict(
        facts=_facts(), trace=None, config=CONFIG, xplane=xplane, peak=PEAK),
        **kw))


def _read(env):
    return bench_run.load(BENCH, "layer_metrics",
                          "ring_diff_attention_roofline").compute(env)


def test_the_share_is_the_live_slots_bytes_over_the_calls_time(monkeypatch):
    """24 calls of 0.2 ms over the window ticks' mean of 26,549 + 64/3 live
    slots of 1,280 bfloat16 keys and as many values: about 83%."""
    log = _log()
    monkeypatch.setattr(spanlog, "records", lambda: log)
    live = 26549 + 64 / 3                   # ticks 3-5 are the window's
    want = 24 * live * 2 * 1280 * 2 / 819e9 / (24 * 2e-4) * 100.0
    got = _read(_env(trace=_trace(24, 2e-4)))
    assert got == pytest.approx(want)
    assert 80 < got < 85


def test_nothing_without_a_call_a_count_a_trace_or_a_peak(monkeypatch):
    log = _log()
    monkeypatch.setattr(spanlog, "records", lambda: log)
    paged_only = xplane.Trace(devices={"/device:TPU:0": [
        ("paged_diff_attention.3", 1_000_000, 400_000)]})
    assert _read(_env(trace=paged_only)) is None        # the parent commit
    assert _read(_env()) is None                        # no trace
    assert _read(_env(trace=_trace(4, 2e-4), peak=None)) is None
    bare = _log(window_live=None)
    monkeypatch.setattr(spanlog, "records", lambda: bare)
    assert _read(_env(trace=_trace(4, 2e-4))) is None   # no ring slots
    for records in ([], None):
        monkeypatch.setattr(spanlog, "records", lambda: records)
        assert _read(_env(trace=_trace(4, 2e-4))) is None


def test_the_shared_pages_kernel_reads_none_of_the_ring_calls():
    """By name each kernel counts its own calls alone."""
    stats = xplane.kernel_stats(_trace(6, 2e-4), ["paged_diff_attention"])
    assert stats["paged_diff_attention"] == (pytest.approx(6 * 4e-4), 6.0)
    stats = xplane.kernel_stats(_trace(6, 2e-4), ["ring_diff_attention"])
    assert stats["ring_diff_attention"] == (pytest.approx(6 * 2e-4), 6.0)


def test_its_benchmark_entry_lists_cell_5_alone():
    b = json.loads((REPO / "BENCHMARK.json").read_text())
    entry = next(m for m in b["per_layer"]
                 if m["name"] == "ring_diff_attention_roofline")
    assert entry == {
        "name": "ring_diff_attention_roofline", "unit": "%",
        "better": "higher", "source": "device_trace", "layer": "kernels",
        "moves": "generate_tokens_per_s", "workloads": [CELL]}
