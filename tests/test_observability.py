"""ISSUE 17 — end-to-end request tracing, flight recorder, SLO surface.

Covers the tentpole acceptance criteria:

  * one `/generate` request under continuous batching yields ONE
    connected trace — HTTP root -> queue_wait -> prefill ->
    first_token -> scatter, and >=3 scheduler ticks that list the
    request — asserted by walking the span parent-child links and the
    ticks' membership;
  * an injected non-finite step trips the guard and produces a
    flight-recorder dump carrying the failing step's score, the
    collective-sequence hash and the 64 preceding events;
  * the write paths stay bounded and off-lock: the Tracer ring under
    N concurrent threads keeps EXACTLY the newest `capacity` events and
    counts the overwritten ones (no torn events),
    and FlightRecorder.record takes no lock at all (proven under the
    sanitizer's lock-order shims).

Plus the satellites: trace_id in every structured error body + the
X-DL4J-Trace response header, per-counter named Perfetto rows (the tid-0
pinning fix), and the /debug/flightrecord endpoint.
"""
import json
import math
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from deeplearning4j_tpu import (Adam, DataSet, DenseLayer,
                                EmbeddingSequenceLayer, InputType,
                                MultiLayerNetwork, NeuralNetConfiguration,
                                OutputLayer, RnnOutputLayer,
                                TransformerBlock)
from deeplearning4j_tpu.fault.guard import GuardPolicy, TrainingGuard
from deeplearning4j_tpu.telemetry import enabled
from deeplearning4j_tpu.telemetry.recorder import (FlightRecorder,
                                                   flight_recorder, install)
from deeplearning4j_tpu.telemetry.trace_context import (DEFAULT_SLO_TARGETS,
                                                        SloSurface,
                                                        TraceContext)
from deeplearning4j_tpu.telemetry.tracing import (_COUNTER_TID_BASE, Tracer,
                                                   install as install_tracer,
                                                   tracer)

pytestmark = pytest.mark.sanitize(
    allow_threads=("dl4j-decode-sched-", "dl4j-serving-http",
                   "dl4j-serving-batcher-"))


@pytest.fixture
def fresh_recorder():
    """Isolate the process-wide flight recorder per test."""
    prev = install(FlightRecorder(capacity=256))
    yield flight_recorder()
    install(prev)


def _mlp(n_in=8, n_out=4, hidden=16, seed=7):
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .updater(Adam(1e-2)).list()
            .layer(DenseLayer(n_out=hidden, activation="relu"))
            .layer(OutputLayer(n_out=n_out, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(n_in)).build())
    return MultiLayerNetwork(conf).init()


def _lm(seed=0, vocab=32, width=16, t=32, blocks=2):
    b = (NeuralNetConfiguration.builder().seed(seed).updater(Adam(1e-3))
         .list().layer(EmbeddingSequenceLayer(n_in=vocab, n_out=width)))
    for _ in range(blocks):
        b = b.layer(TransformerBlock(n_heads=4))
    conf = (b.layer(RnnOutputLayer(n_out=vocab, activation="softmax",
                                   loss="mcxent"))
            .set_input_type(InputType.recurrent(1, t)).build())
    return MultiLayerNetwork(conf).init()


def _http(method, url, body=None, headers=None, timeout=120):
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(url, data=data, method=method,
                                 headers={"Content-Type":
                                          "application/json",
                                          **(headers or {})})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, dict(r.headers), json.loads(r.read())


# ---------------------------------------------------------------------------
# TraceContext / SloSurface units
# ---------------------------------------------------------------------------

def test_trace_context_parent_links():
    with enabled() as sess:
        ctx = TraceContext.begin(tier="interactive")
        assert ctx.span_id == f"{ctx.trace_id}.0"
        with ctx.span("child_a", foo=1):
            pass
        sid_b = ctx.emit("child_b", 0.0, 0.1)
        sid_c = ctx.emit("grandchild", 0.1, 0.2, parent=sid_b)
        ctx.emit_root("http/test", code=200)
        evts = {e["args"]["span_id"]: e for e in sess.tracer.events()
                if e.get("ph") == "X"
                and e.get("args", {}).get("trace_id") == ctx.trace_id}
        assert len(evts) == 4
        root = evts[ctx.span_id]
        assert root["args"]["parent_id"] is None
        assert root["args"]["tier"] == "interactive"
        assert evts[sid_b]["args"]["parent_id"] == ctx.span_id
        assert evts[sid_c]["args"]["parent_id"] == sid_b
        # ids unique and monotonic within the trace
        assert sid_b != sid_c and sid_b.startswith(ctx.trace_id + ".")


def test_trace_context_records_without_a_session():
    """The span log is always on: a context records whether or not a
    telemetry session is active, and nothing once the log is disabled."""
    prev = install_tracer(Tracer(capacity=16))
    try:
        ctx = TraceContext.begin()
        sid = ctx.emit("child", 0.0, 0.1)
        assert sid.startswith(ctx.trace_id)
        ctx.emit_root("root")
        got = {e["name"]: e for e in tracer().snapshot()}
        assert got["child"]["id"] == sid
        assert got["child"]["parent"] == got["root"]["id"] == ctx.span_id
        assert got["child"]["t1"] - got["child"]["t0"] == 100_000_000
        assert got["root"]["parent"] is None
        assert got["root"]["trace_id"] == ctx.trace_id
        tracer().enabled = False
        assert ctx.emit("more", 0.0, 0.1).startswith(ctx.trace_id)
        ctx.emit_root("more")
        assert len(tracer()) == 2
        assert ctx.elapsed() >= 0.0
    finally:
        install_tracer(prev)


def test_slo_surface_burn_accounting():
    with enabled() as sess:
        slo = SloSurface(sess.registry, error_budget=0.01)
        assert slo.targets == DEFAULT_SLO_TARGETS
        slo.observe("interactive", 0.01)       # within 0.25s target
        slo.observe("interactive", 1.0)        # breach
        slo.observe("undeclared", 99.0)        # histogram only
        assert slo.burn_rate("interactive") == pytest.approx(50.0)
        assert slo.burn_rate("undeclared") == 0.0
        s = slo.summary()
        assert s["interactive"]["breaches"] == 1
        assert s["interactive"]["requests"] == 2
        assert "undeclared" not in s
        slo.declare("bulk", 10.0)
        slo.observe("bulk", 0.5)
        assert slo.summary()["bulk"]["breaches"] == 0
        text = sess.registry.prometheus_text()
        assert "dl4j_slo_latency_seconds" in text
        assert "dl4j_slo_burn_rate" in text


# ---------------------------------------------------------------------------
# Tracer: named counter rows + saturation under concurrency
# ---------------------------------------------------------------------------

def test_counter_tracks_get_named_rows():
    tr = Tracer()
    tr.counter("kv_blocks", free=3, used=5)
    tr.counter("queue_depth", depth=2)
    tr.counter("kv_blocks", free=2, used=6)
    counters = [e for e in tr.events() if e["ph"] == "C"]
    tids = {e["name"]: e["tid"] for e in counters}
    # distinct synthetic rows, never the tid-0 process row
    assert tids["kv_blocks"] != tids["queue_depth"]
    assert all(t >= _COUNTER_TID_BASE for t in tids.values())
    assert len({e["tid"] for e in counters
                if e["name"] == "kv_blocks"}) == 1
    names = {e["tid"]: e["args"]["name"] for e in tr.events()
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert names[tids["kv_blocks"]] == "counter:kv_blocks"
    assert names[tids["queue_depth"]] == "counter:queue_depth"


def test_tracer_saturation_exact_drop_accounting():
    n_threads, per_thread, capacity = 8, 200, 301
    tr = Tracer(capacity=capacity)
    barrier = threading.Barrier(n_threads)

    def writer(i):
        barrier.wait()
        for k in range(per_thread):
            tr.instant(f"w{i}", k=k)

    threads = [threading.Thread(target=writer, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(tr) == capacity
    assert tr.dropped_events == n_threads * per_thread - capacity
    # the newest `capacity` events survive, none torn
    snap = tr.snapshot()
    assert [e["seq"] for e in snap] == list(
        range(n_threads * per_thread - capacity, n_threads * per_thread))
    for e in tr.events():
        if e["ph"] == "i":
            assert {"name", "ts", "pid", "tid"} <= set(e)
            assert "k" in e["args"]
    assert tr.chrome_trace()["otherData"]["dropped_events"] == \
        tr.dropped_events


# ---------------------------------------------------------------------------
# FlightRecorder: ring semantics + off-lock writes
# ---------------------------------------------------------------------------

def test_flight_recorder_ring_and_dump(tmp_path):
    rec = FlightRecorder(capacity=8)
    for i in range(12):
        rec.record("k", v=i)
    snap = rec.snapshot()
    assert [e["v"] for e in snap] == list(range(4, 12))   # oldest dropped
    assert rec.total_written() == 12
    assert rec.dropped() == 4
    assert rec.snapshot(last=2)[-1]["v"] == 11
    path = tmp_path / "dump.json"
    doc = rec.dump("guard/test", path=str(path), extra={"score": 1.5})
    assert rec.last_dump is doc
    assert doc["reason"] == "guard/test" and doc["score"] == 1.5
    assert doc["dropped_by_wraparound"] == 4
    on_disk = json.loads(path.read_text())
    assert on_disk["total_events"] == 12
    assert len(on_disk["events"]) == 8


def test_flight_recorder_disabled_is_free():
    rec = FlightRecorder(capacity=8, enabled=False)
    rec.record("k", v=1)
    assert rec.snapshot() == [] and rec.total_written() == 0


@pytest.mark.sanitize(lock_order=True)
def test_flight_recorder_writes_off_lock():
    """Concurrent writers with NO lock: the sanitizer's lock-order shims
    are active, so any lock taken on the record path would be observed;
    the assertions prove no torn tuples survive either way."""
    rec = FlightRecorder(capacity=64)
    n_threads, per_thread = 8, 500
    barrier = threading.Barrier(n_threads)

    def writer(i):
        barrier.wait()
        for k in range(per_thread):
            rec.record("w", thread=i, k=k)

    threads = [threading.Thread(target=writer, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    # reader races the writers: every observed event must be complete
    for _ in range(50):
        for e in rec.snapshot():
            assert {"seq", "ts", "thread", "kind", "k"} <= set(e)
    for t in threads:
        t.join()
    assert rec.total_written() == n_threads * per_thread
    assert rec.dropped() == n_threads * per_thread - 64
    seqs = [e["seq"] for e in rec.snapshot()]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)


# ---------------------------------------------------------------------------
# HTTP end to end: the connected-trace acceptance test
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def served():
    from deeplearning4j_tpu.serving.registry import ModelRegistry
    from deeplearning4j_tpu.serving.server import InferenceServer

    with enabled() as sess:
        registry = ModelRegistry(buckets=(1,), metrics=sess.registry)
        srv = InferenceServer(registry, batching=False, port=0)
        srv.start()
        try:
            registry.register("gen", _lm())
            srv.enable_generation("gen", block_len=4, decode_buckets=(1, 2))
            yield srv, sess
        finally:
            srv.stop()


def test_generate_yields_one_connected_trace(served, fresh_recorder):
    srv, sess = served
    url = f"http://127.0.0.1:{srv.port}/v1/models/gen/generate"
    code, headers, out = _http(
        "POST", url, {"prompt": [1, 2, 3], "max_tokens": 6},
        headers={"X-DL4J-SLO-Tier": "interactive"})
    assert code == 200 and len(out["tokens"]) == 6
    trace_id = headers["X-DL4J-Trace"]
    evts = [e for e in sess.tracer.events()
            if e.get("ph") == "X"
            and e.get("args", {}).get("trace_id") == trace_id]
    by_name = {}
    for e in evts:
        by_name.setdefault(e["name"], []).append(e)
    root = by_name["http/generate"][0]["args"]
    assert root["parent_id"] is None and root["tier"] == "interactive"
    rid = root["span_id"]
    # the request's whole lifecycle hangs off the one root span
    for stage in ("queue_wait", "prefill", "first_token", "scatter"):
        assert len(by_name[stage]) == 1, stage
        assert by_name[stage][0]["args"]["parent_id"] == rid, stage
    # its decode ticks are the scheduler's tick spans that list it
    ticks = [e for e in sess.tracer.events()
             if e["name"] == "dl4j/sched/tick"
             and trace_id in e["args"].get("requests", ())]
    assert len(ticks) >= 3
    assert len({t["args"]["tick"] for t in ticks}) == len(ticks)
    # every span of the trace shares the trace_id and a unique span_id
    sids = [e["args"]["span_id"] for e in evts]
    assert len(set(sids)) == len(sids)
    # SLO surface observed the request under its header-declared tier
    assert srv.slo.summary()["interactive"]["requests"] >= 1
    # and the scheduler fed KV admission events into the flight recorder
    kinds = {e["kind"] for e in fresh_recorder.snapshot()}
    assert "decode/admit" in kinds


def test_error_body_carries_trace_id(served):
    srv, _ = served
    url = f"http://127.0.0.1:{srv.port}/v1/models/nope/generate"
    with pytest.raises(urllib.error.HTTPError) as ei:
        _http("POST", url, {"prompt": [1]})
    err = ei.value
    body = json.loads(err.read())
    assert err.code == 404 and "error" in body
    assert body["trace_id"] == err.headers["X-DL4J-Trace"]


def test_debug_flightrecord_endpoint(served, fresh_recorder):
    srv, _ = served
    fresh_recorder.record("test/ping", n=1)
    code, _, body = _http(
        "GET", f"http://127.0.0.1:{srv.port}/debug/flightrecord")
    assert code == 200 and body["enabled"]
    assert body["capacity"] == 256
    assert any(e["kind"] == "test/ping" for e in body["events"])


# ---------------------------------------------------------------------------
# Training plane: guard-trip dump with scores + collective hashes
# ---------------------------------------------------------------------------

def test_guard_trip_dumps_flightrecord(fresh_recorder, tmp_path):
    from deeplearning4j_tpu.parallel import ParallelTrainer, ShardingStrategy

    mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
    trainer = ParallelTrainer(_mlp(), mesh=mesh,
                              strategy=ShardingStrategy.ZERO1)
    guard = TrainingGuard(GuardPolicy.SKIP_BATCH,
                          flight_dump_dir=str(tmp_path))
    r = np.random.default_rng(0)
    x = r.normal(size=(64, 8)).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[r.integers(0, 4, 64)]
    for _ in range(40):     # 2 events/step: train/step + train/collectives
        trainer.fit(DataSet(x, y), guard=guard)
    bad = x.copy()
    bad[0, 0] = np.nan
    trainer.fit(DataSet(bad, y), guard=guard)
    assert guard.skipped_batches == 1
    doc = guard.last_flight_dump
    assert doc is not None and doc["reason"] == "guard/skip_batch"
    # the failing step's score and context made it into the dump
    assert math.isnan(doc["score"]) and doc["policy"] == "skip_batch"
    steps = [e for e in doc["events"] if e["kind"] == "train/step"]
    assert math.isnan(steps[-1]["score"]) and not steps[-1]["finite"]
    # ... with at least the 64 preceding events
    assert len(doc["events"]) >= 65
    assert doc["events"][-1]["seq"] - doc["events"][0]["seq"] >= 64
    # collective-sequence digests ride alongside the scores
    col = [e for e in doc["events"] if e["kind"] == "train/collectives"]
    assert col and all(len(e["digest"]) == 16 for e in col)
    # the dump also landed on disk, atomically, and is valid JSON
    files = list(tmp_path.glob("flightrecord-skip_batch-*.json"))
    assert len(files) == 1 and "path" in doc
    assert json.loads(files[0].read_text())["reason"] == "guard/skip_batch"
    # guard-trip state is queryable for the NEXT dump too
    assert fresh_recorder.last_dump is doc


def test_guard_halt_and_circuit_breaker_dump(fresh_recorder):
    m = _mlp()
    guard = TrainingGuard(GuardPolicy.HALT)
    r = np.random.default_rng(1)
    x = r.normal(size=(16, 8)).astype(np.float32)
    x[0, 0] = np.nan
    y = np.eye(4, dtype=np.float32)[r.integers(0, 4, 16)]
    from deeplearning4j_tpu.fault.guard import NonFiniteScoreError
    with pytest.raises(NonFiniteScoreError):
        m.fit(DataSet(x, y), guard=guard)
    assert guard.last_flight_dump["reason"] == "guard/halt"


def test_superstep_window_events(fresh_recorder):
    m = _mlp(n_in=8)
    r = np.random.default_rng(2)
    x = r.normal(size=(64, 8)).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[r.integers(0, 4, 64)]
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    it = ListDataSetIterator([DataSet(x, y)], batch_size=16)
    m.fit(it, superstep=2)
    snap = fresh_recorder.snapshot()
    windows = [e for e in snap if e["kind"] == "train/window"]
    assert windows and all(e["n_steps"] >= 1 and e["dispatch_s"] >= 0
                           for e in windows)
    scores = [e for e in snap if e["kind"] == "train/window_scores"]
    assert scores and all(e["nonfinite"] == 0 for e in scores)
    assert all(e["lo"] <= e["hi"] for e in scores)


def test_recorder_disabled_planes_stay_silent(fresh_recorder):
    install(FlightRecorder(enabled=False))
    m = _mlp()
    guard = TrainingGuard(GuardPolicy.WARN)
    r = np.random.default_rng(3)
    x = r.normal(size=(16, 8)).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[r.integers(0, 4, 16)]
    m.fit(DataSet(x, y), guard=guard)
    assert flight_recorder().total_written() == 0
