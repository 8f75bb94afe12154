"""Tests of the per-layer metrics that read the program's span log
(`harness/spanlog.py` and its ten readers), on the CPU:

    python -m pytest benchmarks/tests -q

Each reader on a hand-made log (the window by ordinals, self time, None
where the ring has lost the window's start), and a traced run of each tiny
cell through `run_cell`, which has to report all ten names.
"""
from __future__ import annotations

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
sys.path[:0] = [str(REPO), str(BENCH)]

from harness import spanlog, xplane  # noqa: E402
from test_benchmark import _load_run, _run, sandbox  # noqa: E402,F401 - the tiny cells

bench_run = _load_run(BENCH)

MS = 1_000_000
SERVE = ["sched_loop_ms", "sched_host_ms", "tick_prepare_ms",
         "tick_dispatch_ms", "tick_fetch_ms", "tick_sample_ms",
         "decode_rows_per_tick", "queue_wait_p95_ms", "first_token_p95_ms"]


class Log:
    """A span log written by hand: `add` returns the new span's id."""

    def __init__(self):
        self.records = []

    def add(self, name, t0, t1, parent=None, **attrs):
        rec = {"seq": len(self.records), "ph": "X", "name": name,
               "t0": int(t0 * MS), "t1": int(t1 * MS), "id": len(self.records) + 1,
               "parent": parent, "trace_id": None, "attrs": attrs}
        self.records.append(rec)
        return rec["id"]

    def loop(self, t, tick, *, rows=2, admit=None):
        """One scheduler loop of 100 ms at `t`: an optional admission of
        10 ms (its fetch 4), then a tick of 80 (prepare 5, dispatch 10,
        fetch 50, sample 3; 12 its own)."""
        loop = self.add(spanlog.LOOP, t, t + 100, waiting=0, running=rows)
        if admit is not None:
            a = self.add(spanlog.ADMIT, t + 2, t + 12, loop, **admit)
            self.add("dl4j/engine/prefill.fetch", t + 6, t + 10, a, bytes=4)
        k = self.add(spanlog.TICK, t + 15, t + 95, loop, tick=tick, rows=rows,
                     bucket=2, requests=[])
        self.add("dl4j/sched/reserve", t + 15, t + 16, k, evicted=0)
        self.add("dl4j/engine/tick.prepare", t + 20, t + 25, k, bucket=2)
        self.add("dl4j/engine/tick.dispatch", t + 25, t + 35, k)
        self.add("dl4j/engine/tick.fetch", t + 35, t + 85, k, bytes=8)
        self.add(spanlog.SAMPLE, t + 90, t + 93, k, finished=0)
        return loop


def _facts(ticks=(2, 5), prefills=(1, 3)):
    return {"counters_before": {"decode_count": float(ticks[0]),
                                "prefill_count": float(prefills[0])},
            "counters_after": {"decode_count": float(ticks[1]),
                               "prefill_count": float(prefills[1])}}


def _serve_log():
    """Ticks 1-6 in loops at 0, 100, ... ms; the window holds ticks 3-5 and
    admissions 2-3; tick 5 has one row and its loop an admission."""
    log = Log()
    log.loop(0, 1, admit={"prefill": 1, "queue_wait_s": 9.0,
                          "first_token_s": 9.0})
    log.loop(100, 2)
    log.loop(200, 3, admit={"prefill": 2, "queue_wait_s": 0.010,
                            "first_token_s": 0.020})
    log.loop(300, 4)
    # a re-admission after an eviction: no first token of its own
    log.loop(400, 5, rows=1, admit={"prefill": 3, "queue_wait_s": 0.030})
    log.add("dl4j/sched/idle", 500, 520)
    log.loop(520, 6, admit={"prefill": 4, "queue_wait_s": 9.0,
                            "first_token_s": 9.0})
    # the driver's feeding again, after the window: no scheduler parent
    log.add("dl4j/engine/tick.fetch", 700, 900, None, bytes=8)
    return log.records


def _reader(name):
    return bench_run.load(BENCH, "layer_metrics", name).compute


def _env(facts):
    return SimpleNamespace(facts=facts, trace=None)


@pytest.mark.parametrize("name,want", [
    ("sched_loop_ms", 100.0),
    # loops 3 and 5 hold an admission whose fetch (4) is not host time
    ("sched_host_ms", (46.0 + 50.0 + 46.0) / 3),
    ("tick_prepare_ms", 5.0), ("tick_dispatch_ms", 10.0),
    ("tick_fetch_ms", 50.0), ("tick_sample_ms", 3.0),
    ("decode_rows_per_tick", 5.0 / 3),
    ("queue_wait_p95_ms", 10.0 + 0.95 * 20.0),
    ("first_token_p95_ms", 20.0)])
def test_serving_reader_on_a_hand_made_log(monkeypatch, name, want):
    log = _serve_log()
    monkeypatch.setattr(spanlog, "records", lambda: log)
    assert _reader(name)(_env(_facts())) == pytest.approx(want)
    # the ring has lost the window's first tick (and all before it)
    lost = [r for r in log if r["attrs"].get("tick", 9) > 3]
    monkeypatch.setattr(spanlog, "records", lambda: lost)
    assert _reader(name)(_env(_facts())) is None
    # a program that keeps no log, and a run that scraped no counters
    monkeypatch.setattr(spanlog, "records", lambda: None)
    assert _reader(name)(_env(_facts())) is None
    monkeypatch.setattr(spanlog, "records", lambda: log)
    assert _reader(name)(_env({})) is None


def test_serving_window_is_chosen_by_ordinals_alone():
    log = _serve_log()
    w = spanlog.serve_window(log, _facts())
    assert [t["attrs"]["tick"] for t in w.ticks] == [3, 4, 5]
    assert [a["attrs"]["prefill"] for a in w.admits] == [2, 3]
    assert [s["t0"] for s in w.loops] == [200 * MS, 300 * MS, 400 * MS]
    # a window without a tick, one whose admission the ring has lost, and
    # one that ends after the log does
    assert spanlog.serve_window(log, _facts(ticks=(4, 4))) is None
    no_admit = [r for r in log if r["attrs"].get("prefill") != 2]
    assert spanlog.serve_window(no_admit, _facts()) is None
    assert spanlog.serve_window(log, _facts(ticks=(2, 9))) is None
    # no prefill inside the window: the tick metrics stand, the tails do not
    quiet = _facts(ticks=(3, 4), prefills=(2, 2))
    assert spanlog.sched_loop_ms(log, quiet) == pytest.approx(100.0)
    assert spanlog.admit_p95_ms(log, quiet, "queue_wait_s") is None


def test_self_time_is_the_duration_less_what_the_children_cover():
    log = Log()
    root = log.add("root", 0, 100)
    log.add("a", 10, 30, root)
    log.add("b", 20, 50, root)          # overlaps a: the union counts once
    log.add("c", 90, 120, root)         # runs past its parent: clipped
    idx = spanlog.Index(log.records)
    span = idx.spans[0]
    assert spanlog.less_ms(span, idx.kids(span)) == pytest.approx(
        100 - 40 - 10)
    assert spanlog.less_ms(span, []) == pytest.approx(100.0)
    assert [d["name"] for d in idx.descendants(span)] == ["a", "b", "c"]
    assert spanlog.p95([1.0]) == 1.0 and spanlog.p95([]) is None
    assert spanlog.mean([]) is None


def _fit_log(n):
    """n steps of 20 ms, 50 ms apart; every 5th waits 12 ms in its
    listeners, the others 1 ms."""
    log = Log()
    for i in range(n):
        t = 50 * i
        s = log.add(spanlog.FIT_STEP, t, t + 20, iteration=i + 1)
        log.add("host/batch_prep", t, t + 1, s)
        log.add("device/dispatch", t + 2, t + 6, s, kind="train_step")
        wait = 12 if (i + 1) % 5 == 0 else 1
        log.add(spanlog.FIT_LISTENERS, t + 20 - wait, t + 20, s)
    return log.records


def test_fit_reader_on_a_hand_made_log(monkeypatch):
    log = _fit_log(12)
    monkeypatch.setattr(spanlog, "records", lambda: log)
    reader = _reader("fit_host_ms")
    # steps 6-10 are the window, 11-12 the traced steps after it
    facts = {"steps": 5, "trace_steps": 2}
    steps = spanlog.fit_steps(log, facts)
    assert [s["attrs"]["iteration"] for s in steps] == [6, 7, 8, 9, 10]
    assert reader(_env(facts)) == pytest.approx((4 * 19.0 + 8.0) / 5)
    # an untraced run's facts: the window is the log's last steps
    assert [s["attrs"]["iteration"]
            for s in spanlog.fit_steps(log, {"steps": 3})] == [10, 11, 12]
    # the ring holds fewer steps than the window and the trace made
    assert reader(_env({"steps": 11, "trace_steps": 2})) is None
    assert reader(_env({})) is None
    monkeypatch.setattr(spanlog, "records", lambda: None)
    assert reader(_env(facts)) is None


def test_a_program_without_a_log_reads_as_none(monkeypatch):
    from deeplearning4j_tpu import telemetry
    assert spanlog.records() is not None
    monkeypatch.delattr(telemetry, "tracer")
    assert spanlog.records() is None


def test_traced_tiny_cells_report_all_ten_names(sandbox, monkeypatch):  # noqa: F811
    # the CPU's operations are on its client threads' lines of the host plane
    monkeypatch.setattr(xplane, "DEVICE_PLANE", "/host:CPU")
    monkeypatch.setattr(xplane, "OPS_LINE", "tf_XLAPjRtCpuClient")
    out = _run(sandbox, "tiny.generate", trace=True)
    assert out["correct"] is True and out["failed"] == 0
    got = out["metrics"]
    assert set(SERVE) <= set(got)
    assert {"decode_tick_ms", "prefill_ms",
            "decode_tokens_per_s"} <= set(got)       # what was there stays
    # both time the same ticks: the scheduler's clock around run_tick, and
    # the three spans inside run_tick (on this CPU the threads' switches
    # fall between them too)
    parts = sum(got[n]["value"] for n in ("tick_prepare_ms",
                                          "tick_dispatch_ms", "tick_fetch_ms"))
    tick = got["decode_tick_ms"]["value"]
    assert 0.8 * tick <= parts <= tick
    assert parts + got["tick_sample_ms"]["value"] <= got["sched_loop_ms"]["value"]
    assert 0 < got["sched_host_ms"]["value"] < got["sched_loop_ms"]["value"]
    assert 1.0 <= got["decode_rows_per_tick"]["value"] <= 4.0
    assert 0 < got["queue_wait_p95_ms"]["value"] <= got["first_token_p95_ms"]["value"]

    out = _run(sandbox, "tiny.fit", trace=True)
    assert out["correct"] is True
    assert 0 < out["metrics"]["fit_host_ms"]["value"]
    assert out["metrics"]["fit_host_ms"]["unit"] == "ms"
    assert "fit_step_p95_ms" in out["metrics"]
