"""The span log (ISSUE 26): one always-on, process-wide ring that the fit
and scheduler threads write, also into the profiler's trace.

  * the ring overwrites the oldest and counts it; nesting gives parent
    ids per thread and never across threads; `enabled = False` records
    nothing;
  * a tiny LM under `GenerationScheduler`: every `dl4j/sched/tick` has
    children that fit inside it, `tick` ordinals equal
    `dl4j_decode_phase_seconds_count{phase="decode"}`, one `first_token`
    a request, ending where the sampling after its prefill ends;
  * `fit` of both model families writes `dl4j/fit/step` with its children;
  * the same names are in the xplane under `jax.profiler.start_trace`;
  * the compile path (`xla/trace`, `xla/lower`, `xla/compile` with what the
    persistent cache said, `xla/cache_load` inside a hit) lies under the
    span that asked for it; `dl4j/nn/init` and `dl4j/registry/compile`
    time set-up;
  * the jitted train step, prefill and tick carry their named scope.
"""
import glob
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu import (Adam, DataSet, DenseLayer,
                                EmbeddingSequenceLayer, InputType,
                                MultiLayerNetwork, NeuralNetConfiguration,
                                OutputLayer, RnnOutputLayer, Sgd,
                                TransformerBlock, telemetry)
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.serving.decode.engine import (DecodeEngine,
                                                      build_decode_fn,
                                                      build_prefill_fn)
from deeplearning4j_tpu.serving.decode.scheduler import GenerationScheduler
from deeplearning4j_tpu.serving.registry import ModelRegistry
from deeplearning4j_tpu.telemetry import (MetricsRegistry, TraceContext,
                                          Tracer, install_tracer, tracer)


@pytest.fixture
def log():
    """A fresh process-wide span log for one test."""
    prev = install_tracer(Tracer())
    yield tracer()
    install_tracer(prev)


def _lm(seed=0, vocab=32, width=16, t=32, blocks=2):
    b = (NeuralNetConfiguration.builder().seed(seed).updater(Adam(1e-3))
         .list().layer(EmbeddingSequenceLayer(n_in=vocab, n_out=width)))
    for _ in range(blocks):
        b = b.layer(TransformerBlock(n_heads=4))
    conf = (b.layer(RnnOutputLayer(n_out=vocab, activation="softmax",
                                   loss="mcxent"))
            .set_input_type(InputType.recurrent(1, t)).build())
    return MultiLayerNetwork(conf).init()


def _mlp():
    conf = (NeuralNetConfiguration.builder().seed(7).updater(Sgd(0.1)).list()
            .layer(DenseLayer(n_out=16, activation="relu"))
            .layer(OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(8)).build())
    return MultiLayerNetwork(conf).init()


def _graph():
    b = (NeuralNetConfiguration.builder().seed(3).updater(Sgd(0.1))
         .graph_builder())
    b.add_inputs("in")
    b.add_layer("d", DenseLayer(n_out=16, activation="relu"), "in")
    b.add_layer("out", OutputLayer(n_out=3, activation="softmax",
                                   loss="mcxent"), "d")
    b.set_outputs("out")
    b.set_input_types(InputType.feed_forward(8))
    return ComputationGraph(b.build()).init()


def _batch(n=16):
    rng = np.random.default_rng(0)
    x = rng.normal(size=(n, 8)).astype(np.float32)
    return DataSet(x, np.eye(3, dtype=np.float32)[rng.integers(0, 3, n)])


def _spans(log, name=None):
    return [e for e in log.snapshot()
            if e["ph"] == "X" and (name is None or e["name"] == name)]


# ---------------------------------------------------------------------------
# the ring
# ---------------------------------------------------------------------------

def test_ring_overwrites_the_oldest_and_counts_it():
    tr = Tracer(capacity=8)
    for i in range(20):
        with tr.span("s", i=i):
            pass
    snap = tr.snapshot()
    assert [e["attrs"]["i"] for e in snap] == list(range(12, 20))
    assert [e["seq"] for e in snap] == list(range(12, 20))
    assert len(tr) == 8 and tr.total_written() == 20
    assert tr.dropped_events == 12


def test_nesting_gives_parent_ids_per_thread_not_across(log):
    ready, release = threading.Event(), threading.Event()
    ids = {}

    def other():
        with telemetry.span("other/outer") as o:
            with telemetry.span("other/inner") as i:
                ids["other"] = (o.id, i.id)
                ready.set()
                release.wait(10)

    t = threading.Thread(target=other, name="span-log-other")
    with telemetry.span("main/outer") as outer:
        t.start()
        assert ready.wait(10)
        # another thread's open spans are not this thread's parents
        assert log.current_span() == outer.id
        with telemetry.span("main/inner") as inner:
            pass
        sid = log.emit("main/explicit", 5, 9, answer=42)
        release.set()
        t.join(10)
    assert not t.is_alive()
    got = {e["name"]: e for e in _spans(log)}
    assert got["main/outer"]["parent"] is None
    assert got["main/inner"]["parent"] == outer.id and inner.id != outer.id
    assert got["main/explicit"]["id"] == sid
    assert got["main/explicit"]["parent"] == outer.id
    assert (got["main/explicit"]["t0"], got["main/explicit"]["t1"]) == (5, 9)
    assert got["other/outer"]["parent"] is None
    assert got["other/inner"]["parent"] == ids["other"][0]
    assert got["other/inner"]["thread"] == "span-log-other"
    assert got["main/inner"]["thread"] != "span-log-other"
    assert log.current_span() is None
    ids_seen = [e["id"] for e in _spans(log)]
    assert len(set(ids_seen)) == len(ids_seen)


def test_disabled_log_records_nothing(log):
    log.enabled = False
    with telemetry.span("quiet", x=1) as sp:
        with telemetry.span("quiet/inner"):
            pass
    log.instant("quiet/instant")
    log.counter("quiet/counter", n=1)
    log.emit("quiet/explicit", 0, 1)
    TraceContext.begin().emit("quiet/request", 0.0, 0.1)
    assert len(log) == 0 and log.snapshot() == []
    assert sp.seconds >= 0.0           # a span still times its block
    log.enabled = True
    with telemetry.span("loud"):
        pass
    assert [e["name"] for e in log.snapshot()] == ["loud"]


def test_session_span_writes_the_one_log_and_its_histogram(log):
    with telemetry.enabled() as sess:
        assert sess.tracer is log
        with sess.span("host/batch_prep"):
            pass
        with telemetry.span("device/dispatch", kind="x"):
            pass
        totals = sess.span_totals()
    assert set(totals) == {"host/batch_prep", "device/dispatch"}
    assert [e["name"] for e in _spans(log)] == ["host/batch_prep",
                                                "device/dispatch"]
    with telemetry.span("after"):       # no session: the log still records
        pass
    assert _spans(log, "after")


# ---------------------------------------------------------------------------
# the scheduler and engine spans
# ---------------------------------------------------------------------------

@pytest.fixture
def generated(log):
    """Four requests through a scheduler of 2-row ticks; (log, metrics,
    trace ids)."""
    metrics = MetricsRegistry()
    registry = ModelRegistry(buckets=(1,), metrics=metrics)
    registry.register("gen", _lm())
    sched = GenerationScheduler(registry, "gen", block_len=4,
                                decode_buckets=(1, 2), metrics=metrics)
    ctxs = [TraceContext.begin() for _ in range(4)]
    try:
        threads = [threading.Thread(
            target=sched.submit, args=([1, 2, 3 + i],),
            kwargs={"max_tokens": 5, "ctx": c, "timeout": 120})
            for i, c in enumerate(ctxs)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not any(t.is_alive() for t in threads)
    finally:
        sched.stop()
    return log, metrics, [c.trace_id for c in ctxs]


def test_every_tick_holds_its_children(generated):
    log, metrics, trace_ids = generated
    spans = _spans(log)
    by_parent = {}
    for s in spans:
        by_parent.setdefault(s["parent"], []).append(s)
    ticks = [s for s in spans if s["name"] == "dl4j/sched/tick"]
    assert ticks
    phases = metrics.get("dl4j_decode_phase_seconds")
    # the ordinals are the phase histogram's counts
    assert [t["attrs"]["tick"] for t in ticks] == list(
        range(1, phases.count(model="gen", phase="decode") + 1))
    loops = {s["id"] for s in spans if s["name"] == "dl4j/sched/loop"}
    for t in ticks:
        kids = by_parent[t["id"]]
        assert [k["name"] for k in kids] == [
            "dl4j/sched/reserve", "dl4j/engine/tick.prepare",
            "dl4j/engine/tick.dispatch", "dl4j/engine/tick.fetch",
            "dl4j/sched/sample"]
        # the tick's span is its retirement: the wait and the sampling lie
        # inside it, what started the tick was timed before and hung under
        # it there (a tick may be started a loop before it is retired)
        started, inside = kids[:3], kids[3:]
        assert all(t["t0"] <= k["t0"] <= k["t1"] <= t["t1"] for k in inside)
        assert all(k["t0"] <= k["t1"] <= t["t0"] for k in started)
        assert [k["t0"] for k in started] == sorted(k["t0"] for k in started)
        assert sum(k["t1"] - k["t0"] for k in inside) <= t["t1"] - t["t0"]
        assert t["parent"] in loops
        assert t["attrs"]["overlapped"] in (0, 1)
        assert 0 <= t["attrs"]["device_ids"] <= t["attrs"]["rows"]
        assert t["attrs"]["wasted_rows"] == 0
        assert 1 <= t["attrs"]["rows"] <= t["attrs"]["bucket"] <= 2
        assert len(t["attrs"]["requests"]) == t["attrs"]["rows"]
        assert set(t["attrs"]["requests"]) <= set(trace_ids)
    # every request decodes in some tick: found by membership
    for tid in trace_ids:
        assert sum(tid in t["attrs"]["requests"] for t in ticks) == 4
    fetch = [s for s in spans if s["name"] == "dl4j/engine/tick.fetch"]
    assert all(s["attrs"]["bytes"] > 0 for s in fetch)


def test_admissions_carry_the_requests_first_token(generated):
    log, metrics, trace_ids = generated
    spans = _spans(log)
    admits = [s for s in spans if s["name"] == "dl4j/sched/admit"]
    phases = metrics.get("dl4j_decode_phase_seconds")
    assert [a["attrs"]["prefill"] for a in admits] == list(
        range(1, phases.count(model="gen", phase="prefill") + 1))
    assert len(admits) == 4
    for a in admits:
        kids = [s for s in spans if s["parent"] == a["id"]]
        assert [k["name"] for k in kids] == [
            "dl4j/sched/reserve", "dl4j/engine/prefill.prepare",
            "dl4j/engine/prefill.dispatch", "dl4j/engine/prefill.fetch",
            "dl4j/sched/sample"]
        assert a["attrs"]["prompt_len"] == 3
        assert 0.0 <= a["attrs"]["queue_wait_s"] <= a["attrs"]["first_token_s"]
    # per request: one first_token, from its submit to the end of the
    # sampling that follows its prefill
    sample_ends = sorted(k["t1"] for a in admits for k in spans
                         if k["parent"] == a["id"]
                         and k["name"] == "dl4j/sched/sample")
    first_ends = []
    for tid in trace_ids:
        mine = {}
        for s in spans:
            if s["trace_id"] == tid:
                mine.setdefault(s["name"], []).append(s)
        assert {n: len(v) for n, v in mine.items()} == {
            "queue_wait": 1, "prefill": 1, "first_token": 1, "scatter": 1}
        first, wait = mine["first_token"][0], mine["queue_wait"][0]
        assert first["parent"] == f"{tid}.0"
        assert first["t0"] == wait["t0"] and first["t1"] > wait["t1"]
        first_ends.append(first["t1"])
    assert sorted(first_ends) == sample_ends
    firsts = metrics.get("dl4j_decode_first_token_seconds")
    assert firsts.count(model="gen") == 4
    assert firsts.sum(model="gen") == pytest.approx(
        sum(a["attrs"]["first_token_s"] for a in admits))


def test_loops_and_idle_tile_the_scheduler_thread(generated):
    log, metrics, _ = generated
    spans = _spans(log)
    top = [s for s in spans if s["name"] in ("dl4j/sched/loop",
                                             "dl4j/sched/idle")]
    assert all(s["parent"] is None for s in top)
    assert len({s["thread"] for s in top}) == 1
    assert top[0]["thread"] == "dl4j-decode-sched-gen"
    top.sort(key=lambda s: s["t0"])
    assert all(a["t1"] <= b["t0"] for a, b in zip(top, top[1:]))
    loops = [s for s in top if s["name"] == "dl4j/sched/loop"]
    assert all({"waiting", "running"} <= set(s["attrs"]) for s in loops)
    text = metrics.prometheus_text()
    for family in ("dl4j_decode_queue_wait_seconds",
                   "dl4j_decode_first_token_seconds",
                   "dl4j_decode_tick_rows", "dl4j_decode_host_seconds"):
        assert family in text
    host = metrics.get("dl4j_decode_host_seconds")
    assert host.count(model="gen", phase="loop") == len(loops)
    for phase in ("tick", "admit", "reserve", "sample", "tick.prepare",
                  "tick.dispatch", "tick.fetch", "prefill.fetch"):
        assert host.count(model="gen", phase=phase) > 0, phase
    rows = metrics.get("dl4j_decode_tick_rows")
    ticks = [s for s in spans if s["name"] == "dl4j/sched/tick"]
    assert rows.sum(model="gen") == sum(t["attrs"]["rows"] for t in ticks)


def test_a_version_is_met_once_in_a_run_of_many_ticks(generated):
    """`dl4j/engine/version`: once per version object the engine meets,
    inside the first call that brings it, never per call; the ticks'
    `prepare` spans carry what they carried."""
    log, _, _ = generated
    prepares = _spans(log, "dl4j/engine/tick.prepare")
    prefills = _spans(log, "dl4j/engine/prefill.prepare")
    assert len(prepares) >= 8 and len(prefills) == 4
    (met,) = [e for e in log.snapshot()
              if e["ph"] == "i" and e["name"] == "dl4j/engine/version"]
    assert met["attrs"] == {"model": "gen", "version": 1, "computed": 0,
                            "leaves": 4 + 2 * 16}
    assert met["parent"] == min(prefills, key=lambda s: s["t0"])["id"]
    for p in prepares:
        assert {"bucket", "pages_live", "pages_table"} <= set(p["attrs"])
        assert 1 <= p["attrs"]["pages_live"] <= p["attrs"]["pages_table"]


def test_the_default_ring_holds_a_traced_serving_run(generated):
    """What the scheduler writes a loop (admissions at this run's high
    share included), at 100 loops a second for the 90 s between a
    benchmark run's first request and its readers' turn, fits the
    default ring: the readers need the window's first tick. Set-up (the
    executables built on first use, and the compile path under them) is
    written once, not a loop."""
    log, _, _ = generated
    events = [e for e in log.snapshot() if not e["name"].startswith(
        ("xla/", "dl4j/registry/compile", "dl4j/nn/init"))]
    loops = _spans(log, "dl4j/sched/loop")
    per_loop = len(events) / len(loops)
    assert 6 <= per_loop <= 25, per_loop
    assert per_loop * 100 * 90 <= Tracer().capacity


def test_engine_spans_outside_the_scheduler_have_no_tick_parent(log):
    registry = ModelRegistry(buckets=(1,))
    registry.register("gen", _lm())
    eng = DecodeEngine(registry, "gen", block_len=4, decode_buckets=(1,))
    pool, v = eng.new_pool(), registry.get("gen")
    blocks = pool.alloc(eng.spec.blocks_for(5))
    with telemetry.span("driver/feed_again") as outer:
        eng.run_prefill(v, pool, [1, 2, 3], blocks)
        eng.run_tick(v, pool, [4], [3], [blocks], bucket=1)
    names = [s["name"] for s in _spans(log) if s["parent"] == outer.id]
    assert names == ["dl4j/engine/prefill.prepare",
                     "dl4j/engine/prefill.dispatch",
                     "dl4j/engine/prefill.fetch",
                     "dl4j/engine/tick.prepare", "dl4j/engine/tick.dispatch",
                     "dl4j/engine/tick.fetch"]
    assert not _spans(log, "dl4j/sched/tick")


def test_every_executable_built_leaves_its_memory_record(log):
    """`dl4j/engine/executable`: once per executable built, never per
    call, what the program holds beside the donated arena."""
    registry = ModelRegistry(buckets=(1,))
    registry.register("gen", _lm())
    eng = DecodeEngine(registry, "gen", block_len=4, decode_buckets=(1, 2))
    pool, v = eng.new_pool(), registry.get("gen")
    blocks = pool.alloc(eng.spec.blocks_for(6))
    eng.run_prefill(v, pool, [1, 2, 3], blocks)
    for pos in (3, 4):
        eng.run_tick(v, pool, [4], [pos], [blocks], bucket=1)
    eng.decode_exec(v, 2)
    records = [e["attrs"] for e in log.snapshot()
               if e["ph"] == "i" and e["name"] == "dl4j/engine/executable"]
    assert [(r["phase"], r["bucket"]) for r in records] == [
        ("prefill", 8), ("tick", 1), ("tick", 2)]
    for r in records:
        assert r["model"] == "gen"
        assert r["arena_bytes"] == eng.spec.arena_nbytes()
        assert r["temp_bytes"] >= 0 and r["alias_bytes"] >= 0


def test_tick_prepare_counts_the_live_pages_beside_the_tables(log):
    """`pages_live` / `pages_table` on `dl4j/engine/tick.prepare`: the pages
    the rows' lengths span (what the paged kernel reads; a pad row spans
    one) of the pages the tables name (what the gathered view reads)."""
    registry = ModelRegistry(buckets=(1,))
    registry.register("gen", _lm())
    eng = DecodeEngine(registry, "gen", block_len=4, decode_buckets=(1, 4))
    pool, v = eng.new_pool(), registry.get("gen")
    tables = [pool.alloc(eng.spec.blocks_for(n)) for n in (10, 6)]
    for prompt, table in zip(([1, 2, 3, 4, 5, 6, 7, 8, 9], [1, 2, 3, 4]),
                             tables):
        eng.run_prefill(v, pool, prompt, table)
    eng.run_tick(v, pool, [4, 5], [9, 4], tables, bucket=4)
    eng.run_tick(v, pool, [6], [5], tables[1:], bucket=1)
    got = [s["attrs"] for s in _spans(log, "dl4j/engine/tick.prepare")]
    width = eng.spec.table_width
    assert width == 8
    # positions 9 and 4 span 3 and 2 pages of 4 slots; two pad rows, 1 each
    assert [(a["bucket"], a["pages_live"], a["pages_table"]) for a in got] == [
        (4, 3 + 2 + 1 + 1, 4 * width), (1, 2, width)]


def test_the_paged_kernel_leaves_its_record_once_per_call_shape(log):
    """`dl4j/kernels/paged_attention`: written while a kernel is built,
    never while one runs."""
    import functools

    from deeplearning4j_tpu.kernels import paged_attention as paged_mod

    r = np.random.default_rng(0)
    kv = jnp.asarray(r.normal(size=(4, 9, 8, 128)), jnp.float32)
    q = jnp.asarray(r.normal(size=(2, 128)), jnp.float32)
    tables = jnp.asarray([[3, 5, 7, 0, 0, 0], [2, 0, 0, 0, 0, 0]], jnp.int32)
    lengths = jnp.asarray([20, 7], jnp.int32)
    paged_mod._planned.cache_clear()
    try:
        run = jax.jit(functools.partial(paged_mod.paged_decode_attention,
                                        n_heads=2, interpret=True))
        for rows in (2, 2, 1):                  # a shape twice, then a new one
            for channel in (0, 2):
                run(q[:rows], kv, jnp.int32(channel), tables[:rows],
                    lengths[:rows])
        recs = [e for e in log.snapshot()
                if e["name"] == "dl4j/kernels/paged_attention"]
    finally:
        paged_mod._planned.cache_clear()
    assert [r["attrs"]["rows"] for r in recs] == [2, 1]
    a = recs[0]["attrs"]
    assert recs[0]["ph"] == "i"
    assert (a["table_width"], a["block_len"], a["n_heads"], a["width"],
            a["num_blocks"]) == (6, 8, 2, 128, 9)
    plan = paged_mod.paged_plan(2, 6, 8, 2, 128)
    assert (a["pages_a_chunk"], a["chunks_a_row"], a["steps_a_call"]) == (
        6, 1, 2) == plan[:3]
    assert a["vmem_bytes"] == plan.vmem_bytes


# ---------------------------------------------------------------------------
# fit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("make", [_mlp, _graph], ids=["multilayer", "graph"])
def test_fit_writes_a_step_span_with_its_children(log, make):
    model, ds, reads = make(), _batch(), []

    class Reads:
        def iteration_done(self, model, iteration):
            reads.append((iteration, float(model.score())))

    model.listeners.append(Reads())
    for _ in range(3):
        model.fit(ds)
    steps = _spans(log, "dl4j/fit/step")
    assert [s["attrs"]["iteration"] for s in steps] == [1, 2, 3]
    assert [r[0] for r in reads] == [1, 2, 3]
    for s in steps:
        kids = [k for k in _spans(log) if k["parent"] == s["id"]]
        assert [k["name"] for k in kids] == [
            "host/batch_prep", "device/dispatch", "dl4j/fit/listeners"]
        assert kids[1]["attrs"] == {"kind": "train_step"}
        assert sum(k["t1"] - k["t0"] for k in kids) <= s["t1"] - s["t0"]


# ---------------------------------------------------------------------------
# the profiler's trace, compiles, named scopes
# ---------------------------------------------------------------------------

def test_the_same_names_are_in_the_profilers_trace(log, tmp_path):
    from jax.profiler import ProfileData

    model, ds = _mlp(), _batch()
    model.fit(ds)                                   # compile outside
    registry = ModelRegistry(buckets=(1,))
    registry.register("gen", _lm())
    sched = GenerationScheduler(registry, "gen", block_len=4,
                                decode_buckets=(1,))
    try:
        sched.submit([1, 2, 3], max_tokens=2, timeout=120)   # warm
        jax.profiler.start_trace(str(tmp_path))
        try:
            model.fit(ds)
            sched.submit([1, 2, 3], max_tokens=3, timeout=120)
        finally:
            jax.profiler.stop_trace()
    finally:
        sched.stop()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    host = next(p for p in ProfileData.from_file(path).planes
                if p.name == "/host:CPU")
    traced = {}
    for line in host.lines:
        for e in line.events:
            if e.name.startswith(("dl4j/", "host/", "device/")):
                traced.setdefault(e.name, []).append(e.duration_ns)
    want = {"dl4j/fit/step", "dl4j/fit/listeners", "host/batch_prep",
            "device/dispatch", "dl4j/sched/loop", "dl4j/sched/admit",
            "dl4j/sched/tick", "dl4j/sched/reserve", "dl4j/sched/sample",
            "dl4j/engine/prefill.prepare", "dl4j/engine/prefill.dispatch",
            "dl4j/engine/prefill.fetch", "dl4j/engine/tick.prepare",
            "dl4j/engine/tick.dispatch", "dl4j/engine/tick.fetch"}
    assert want <= set(traced)
    assert want <= {s["name"] for s in _spans(log)}
    # spans with explicit timestamps are the log's alone
    assert "first_token" not in traced
    # one interval, two clocks: the traced tick is the logged tick
    logged = [s["t1"] - s["t0"] for s in _spans(log, "dl4j/sched/tick")][-2:]
    for a, b in zip(sorted(traced["dl4j/sched/tick"]), sorted(logged)):
        assert abs(a - b) < 1_000_000


def test_compile_event_names_the_span_it_happened_under(log):
    """The compile path as spans under the step that asked for it: trace,
    lower and backend compile (with the function's name and what the
    persistent cache said); the second call writes none."""
    fn = jax.jit(lambda x: x * 3.0 + 1.0)
    x = np.ones(7, np.float32)
    with telemetry.span("outer"):
        with telemetry.span("compiles/here") as here:
            fn(x).block_until_ready()
        marks = len(log.snapshot())
        with telemetry.span("compiles/not_here"):
            fn(x).block_until_ready()
    snap = log.snapshot()
    compiles = [e for e in snap if e["name"] == "xla/compile"]
    assert compiles and all(e["ph"] == "X" for e in compiles)
    assert {e["parent"] for e in compiles} == {here.id}
    for e in compiles:
        assert e["attrs"]["fun"] and e["attrs"]["seconds"] > 0
        assert e["attrs"]["cache"] in ("hit", "miss", "off")
        assert here.t0 <= e["t0"] <= e["t1"] <= here.t1
    for name in ("xla/trace", "xla/lower"):
        found = [e for e in snap if e["name"] == name]
        assert found and {e["parent"] for e in found} == {here.id}
        assert all(e["ph"] == "X" and e["attrs"]["fun"] for e in found)
    assert not [e for e in snap[marks:] if e["name"].startswith("xla/")]


def _compile_events(cache_events, load_s=None, fun="jit_step"):
    """Fire a backend compile's events in jax's order
    (`compiler.compile_or_get_cached` inside the compile's timing): the
    cache's events, the load where it hit, then the compile's duration."""
    from jax import monitoring
    t0 = time.perf_counter()
    for event in cache_events:
        time.sleep(0.002)
        monitoring.record_event(f"/jax/compilation_cache/{event}")
    if load_s is not None:
        time.sleep(load_s)
        monitoring.record_event_duration_secs(
            "/jax/compilation_cache/compile_time_saved_sec", 1.0)
        monitoring.record_event_duration_secs(
            "/jax/compilation_cache/cache_retrieval_time_sec", load_s)
    time.sleep(0.002)
    monitoring.record_event_duration_secs(
        "/jax/core/compile/backend_compile_duration",
        time.perf_counter() - t0, fun_name=fun)


def test_a_cache_load_is_a_hit_inside_its_compile_not_a_compile(log):
    prev = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", "/no/such/cache")
    try:
        with telemetry.span("loads/here") as here:
            _compile_events(["compile_requests_use_cache", "cache_hits"],
                            load_s=0.01, fun="jit_hit")
            _compile_events(["compile_requests_use_cache", "cache_misses"],
                            fun="jit_miss")
        jax.config.update("jax_compilation_cache_dir", None)
        _compile_events(["compile_requests_use_cache"], fun="jit_off")
        log.enabled = False
        _compile_events(["compile_requests_use_cache", "cache_hits"],
                        load_s=0.001, fun="jit_quiet")
        log.enabled = True
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
    compiles = _spans(log, "xla/compile")
    assert [(c["attrs"]["fun"], c["attrs"]["cache"]) for c in compiles] == [
        ("jit_hit", "hit"), ("jit_miss", "miss"), ("jit_off", "off")]
    assert [c["parent"] for c in compiles] == [here.id, here.id, None]
    (load,) = _spans(log, "xla/cache_load")
    hit = compiles[0]
    assert load["parent"] == hit["id"]
    assert hit["t0"] <= load["t0"] < load["t1"] <= hit["t1"]
    assert load["attrs"]["seconds"] == pytest.approx(0.01)


def test_init_is_a_span_that_says_whether_the_parameters_were_given(log):
    made = _mlp()
    MultiLayerNetwork(made.conf).init(params=made.params)
    _graph()
    inits = _spans(log, "dl4j/nn/init")
    assert [(s["attrs"]["given"], s["attrs"]["layers"], s["attrs"]["leaves"])
            for s in inits] == [(0, 2, 4), (1, 2, 4), (0, 2, 4)]


def test_every_registry_build_is_a_span_with_its_plane_and_label(log):
    """`dl4j/registry/compile` once per executable the registry builds:
    the stateless forward a bucket, each prefill and tick; an executable
    found in the cache writes none. The engine's record of an executable
    and the compile path lie inside its build."""
    registry = ModelRegistry(buckets=(1, 2))
    registry.register("gen", _lm())
    eng = DecodeEngine(registry, "gen", block_len=4, decode_buckets=(1, 2),
                       prompt_buckets=(8, 16))
    v = registry.get("gen")
    for tb in eng.prompt_buckets:
        eng.prefill_exec(v, tb)
    for b in eng.decode_buckets:
        eng.decode_exec(v, b)
    eng.decode_exec(v, 2)
    builds = _spans(log, "dl4j/registry/compile")
    assert [(s["attrs"]["model"], s["attrs"]["plane"], s["attrs"]["label"])
            for s in builds] == [
        ("gen", "fwd", "1"), ("gen", "fwd", "2"),
        ("gen", "decode", "prefill-t8"), ("gen", "decode", "prefill-t16"),
        ("gen", "decode", "decode-b1"), ("gen", "decode", "decode-b2")]
    decode_ids = {s["id"] for s in builds[2:]}
    records = [e for e in log.snapshot()
               if e["name"] == "dl4j/engine/executable"]
    assert sorted(e["parent"] for e in records) == sorted(decode_ids)
    under = {e["parent"] for e in _spans(log, "xla/compile")}
    assert {s["id"] for s in builds} <= under


def _lowered_text(what):
    if what == "train_step":
        model, ds = _mlp(), _batch()
        x, y, fmask, lmask = ds.device_tuple()
        return jax.jit(model.train_step_fn).lower(
            model.params, model.state, model.updater_state,
            jnp.asarray(0, jnp.int32), x, y, jax.random.PRNGKey(0), fmask,
            lmask)
    registry = ModelRegistry(buckets=(1,))
    registry.register("gen", _lm())
    eng = DecodeEngine(registry, "gen", block_len=4, decode_buckets=(2,))
    v, pool = registry.get("gen"), eng.new_pool()
    i32 = lambda *s: jnp.zeros(s, jnp.int32)
    w = eng.spec.table_width
    if what == "prefill":
        return jax.jit(build_prefill_fn(v.model, v.snapshot, eng.spec)).lower(
            v.snapshot.data, pool.cache, i32(1, 8), i32(1) + 3, i32(1, w))
    return jax.jit(build_decode_fn(v.model, v.snapshot, eng.spec)).lower(
        v.snapshot.data, pool.cache, i32(2), i32(2), i32(2, w))


@pytest.mark.parametrize("what", ["train_step", "prefill", "tick"])
def test_jitted_steps_carry_their_scope_and_name(what):
    text = _lowered_text(what).as_text(debug_info=True)
    assert f"module @jit_dl4j_{what} " in text
    assert f"dl4j/{what}/" in text
