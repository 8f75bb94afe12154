"""Tests of the per-layer metrics of set-up (`harness/spanlog_setup.py` and
its six readers), on the CPU:

    python -m pytest benchmarks/tests/test_setup_metrics.py -q

Each reader on a hand-made log (a nested trace counted once, a compile less
the loads inside it, the set-up of a serving and of a training window, None
where the ring has lost record 0 or the program writes no such span), and a
traced run of each tiny cell through `run_cell`, which has to report the
names that list it, with the two sums of set-up inside its `setup_s`.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
sys.path[:0] = [str(REPO), str(BENCH)]

from harness import spanlog, spanlog_setup, xplane  # noqa: E402
from test_benchmark import sandbox  # noqa: E402,F401 - the tiny cells
from test_span_metrics import Log, _facts, _fit_log  # noqa: E402

READERS = ["setup_init_s", "setup_fwd_aot_s", "setup_decode_aot_s",
           "setup_xla_trace_s", "setup_xla_compile_s", "setup_xla_load_s"]
FIT_SIDE = ["setup_init_s", "setup_xla_trace_s", "setup_xla_compile_s",
            "setup_xla_load_s"]
MS = 1e-3


def _reader(name):
    return bench_load(name).compute


def bench_load(name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        f"setup_{name}", BENCH / "layer_metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read(monkeypatch, log, facts):
    monkeypatch.setattr(spanlog, "records", lambda: log)
    env = SimpleNamespace(facts=facts)
    return {name: _reader(name)(env) for name in READERS}


def _on(log, tid, name, t0, t1, parent=None, **attrs):
    sid = log.add(name, t0, t1, parent, **attrs)
    log.records[-1]["tid"] = tid
    return sid


def _set_up(log, tid=1):
    """Set-up on the main thread (ms): init 0-100 holding a trace 10-40
    with an inner trace 20-30, a lower 40-50 and a compile 50-90 (miss);
    the stateless forward's build 100-200: trace 100-120, lower 120-130,
    compile 130-190 that loaded 150-180; a tick's build 200-300: compile
    210-290 that loaded 220-250. Another thread compiles 100-150."""
    init = _on(log, tid, spanlog_setup.INIT, 0, 100, given=0, layers=4)
    _on(log, tid, "xla/trace", 10, 40, init, fun="outer")
    _on(log, tid, "xla/trace", 20, 30, init, fun="inner")
    _on(log, tid, "xla/lower", 40, 50, init)
    _on(log, tid, "xla/compile", 50, 90, init, cache="miss")
    fwd = _on(log, tid, spanlog_setup.REGISTRY_COMPILE, 100, 200,
              plane="fwd", label="1")
    _on(log, tid, "xla/trace", 100, 120, fwd)
    _on(log, tid, "xla/lower", 120, 130, fwd)
    hit = _on(log, tid, "xla/compile", 130, 190, fwd, cache="hit")
    _on(log, tid, "xla/cache_load", 150, 180, hit)
    tick = _on(log, tid, spanlog_setup.REGISTRY_COMPILE, 200, 300,
               plane="decode", label="decode-b2")
    hit = _on(log, tid, "xla/compile", 210, 290, tick, cache="hit")
    _on(log, tid, "xla/cache_load", 220, 250, hit)
    _on(log, tid + 1, "xla/compile", 100, 150, cache="off")


WANT = {"setup_init_s": 100 * MS, "setup_fwd_aot_s": 100 * MS,
        "setup_decode_aot_s": 100 * MS,
        # 10-50 once, not 10-40 and 20-30 both; 100-130
        "setup_xla_trace_s": 70 * MS,
        # 40 + (60 - 30) + (80 - 30) on the main thread, 50 on the other
        "setup_xla_compile_s": 170 * MS,
        "setup_xla_load_s": 60 * MS}


def _serve_log():
    """The set-up, then loops of ticks 1-6 from 1,000 ms; the window holds
    ticks 3-5, so it opens with the loop at 1,200. A compile that straddles
    the opening and one inside the window are not set-up."""
    log = Log()
    _set_up(log)
    for i in range(6):
        log.loop(1000 + 100 * i, i + 1,
                 admit={"prefill": i + 1} if i in (1, 2) else None)
    _on(log, 1, "xla/compile", 1190, 1210, cache="miss")
    _on(log, 1, "xla/compile", 1250, 1260, cache="miss")
    return log.records


def test_each_reader_on_a_hand_made_serving_log(monkeypatch):
    got = _read(monkeypatch, _serve_log(), _facts(ticks=(2, 5),
                                                  prefills=(1, 3)))
    assert got == pytest.approx(WANT)


def test_each_reader_on_a_hand_made_training_log(monkeypatch):
    """The window is `spanlog.fit_steps`' first step; a compile inside a
    warm step before it is set-up too."""
    log = Log()
    _set_up(log)
    for r in log.records:
        r["t0"], r["t1"] = r["t0"] - 400_000_000, r["t1"] - 400_000_000
    steps = _fit_log(12)
    for r in steps:
        r["seq"] += len(log.records)
        r["id"] += len(log.records)
        r["parent"] = r["parent"] and r["parent"] + len(log.records)
    log.records += steps
    _on(log, 1, "xla/compile", 2, 10, cache="miss")          # step 1
    _on(log, 1, "xla/compile", 302, 310, cache="miss")      # step 7
    got = _read(monkeypatch, log.records, {"steps": 5, "trace_steps": 2})
    assert got["setup_xla_compile_s"] == pytest.approx(178 * MS)
    assert {k: got[k] for k in FIT_SIDE if k != "setup_xla_compile_s"} == (
        pytest.approx({k: WANT[k] for k in FIT_SIDE
                       if k != "setup_xla_compile_s"}))


def test_none_where_the_ring_lost_record_0_or_no_such_span(monkeypatch):
    log = _serve_log()
    facts = _facts(ticks=(2, 5), prefills=(1, 3))
    assert set(_read(monkeypatch, log[1:], facts).values()) == {None}
    assert set(_read(monkeypatch, log, {}).values()) == {None}
    assert set(_read(monkeypatch, None, facts).values()) == {None}
    # a program before PR 38: compiles as instants, no set-up spans
    parent = [dict(r, ph="i") if r["name"].startswith("xla/") else r
              for r in log if not r["name"].startswith("dl4j/nn")
              and not r["name"].startswith("dl4j/registry")]
    assert set(_read(monkeypatch, parent, facts).values()) == {None}
    # a cold run loads nothing: 0, not None
    cold = [r for r in log if r["name"] != "xla/cache_load"]
    assert _read(monkeypatch, cold, facts)["setup_xla_load_s"] == 0.0


@pytest.fixture(scope="module")
def setup_sandbox(sandbox):  # noqa: F811
    """The tiny cells' copy of BENCHMARK.json, with `tiny.fit` listed on
    the four set-up entries that a training cell reports."""
    run, bench, root = sandbox
    path = root / "BENCHMARK.json"
    b = json.loads(path.read_text())
    for m in b["per_layer"]:
        if m["name"] in FIT_SIDE and "tiny.fit" not in m["workloads"]:
            m["workloads"].append("tiny.fit")
    path.write_text(json.dumps(b))
    return sandbox


def _traced(setup_sandbox, monkeypatch, workload):
    """A traced run in a fresh span log; (result line, setup_s)."""
    from deeplearning4j_tpu.telemetry import Tracer, install_tracer

    run, bench, root = setup_sandbox
    opened = {}
    load = run.load

    def spy(bench_, kind, name):
        mod = load(bench_, kind, name)
        if kind != "drivers":
            return mod
        return SimpleNamespace(run=lambda ctx: opened.setdefault(
            "res", mod.run(ctx)))

    monkeypatch.setattr(run, "load", spy)
    monkeypatch.setattr(xplane, "DEVICE_PLANE", "/host:CPU")
    monkeypatch.setattr(xplane, "OPS_LINE", "tf_XLAPjRtCpuClient")
    prev = install_tracer(Tracer())
    try:
        t_start = time.perf_counter()
        rc, line = run.run_cell(workload, 7, 0.5, True, bench=bench,
                                repo=root, check_device=False,
                                t_start=t_start)
    finally:
        install_tracer(prev)
    assert rc == 0
    return json.loads(line), opened["res"]["t_open"] - t_start


@pytest.mark.parametrize("workload,names", [
    ("tiny.generate", READERS), ("tiny.fit", FIT_SIDE)])
def test_traced_tiny_cells_report_set_up_inside_setup_s(
        setup_sandbox, monkeypatch, workload, names):
    out, setup_s = _traced(setup_sandbox, monkeypatch, workload)
    assert out["correct"] is True
    got = {n: out["metrics"][n]["value"] for n in READERS
           if n in out["metrics"]}
    assert set(got) == set(names)
    assert all(v >= 0 and out["metrics"][n]["unit"] == "s"
               for n, v in got.items())
    assert got["setup_init_s"] > 0 and got["setup_xla_compile_s"] > 0
    program = sum(got.get(n, 0.0) for n in (
        "setup_init_s", "setup_fwd_aot_s", "setup_decode_aot_s"))
    xla = sum(got[n] for n in ("setup_xla_trace_s", "setup_xla_compile_s",
                               "setup_xla_load_s"))
    assert 0 < program <= setup_s and 0 < xla <= setup_s
    if workload == "tiny.generate":
        assert got["setup_decode_aot_s"] > 0 and got["setup_fwd_aot_s"] > 0
