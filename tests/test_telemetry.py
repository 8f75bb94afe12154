"""Telemetry subsystem: metrics registry, step tracing, XLA compile
watcher, resource watermarks, TelemetryListener wiring — plus the listener
satellite fixes (PerformanceListener warm-up window, export_scores
round-trip, warn_scan_replay coverage).

All file writes go through tmp_path (tier-1 safe, no network).
"""
import json
import math
import threading
import warnings

import numpy as np
import pytest

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.datasets.iterators import (ArrayDataSetIterator,
                                                   DataSet)
from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.updaters import Sgd
from deeplearning4j_tpu.optimize.listeners import (
    CollectScoresIterationListener, ComposableIterationListener,
    ParamAndGradientIterationListener, PerformanceListener,
    ScoreIterationListener, warn_scan_replay)
from deeplearning4j_tpu.telemetry import (MetricsRegistry, TelemetryListener,
                                          TelemetrySession, Tracer)
from deeplearning4j_tpu.telemetry.compile_watch import (
    RecompilationStormWarning)


def _mlp(n_in=8, n_out=3, seed=7):
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Sgd(0.1))
            .list()
            .layer(DenseLayer(n_out=16, activation="relu"))
            .layer(OutputLayer(n_out=n_out, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(n_in))
            .build())
    return MultiLayerNetwork(conf).init()


def _data(n=64, n_in=8, n_out=3, seed=0):
    r = np.random.default_rng(seed)
    x = r.normal(size=(n, n_in)).astype(np.float32)
    y = np.eye(n_out, dtype=np.float32)[r.integers(0, n_out, n)]
    return x, y


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------

def test_registry_counter_gauge():
    reg = MetricsRegistry()
    c = reg.counter("t_total", "help text")
    c.inc()
    c.inc(4)
    assert c.value() == 5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("t_gauge", "g", labels=("k",))
    g.set(2.5, k="a")
    g.set_max(1.0, k="a")   # below current -> keeps 2.5
    assert g.value(k="a") == 2.5
    g.set_max(7.0, k="a")
    assert g.value(k="a") == 7.0
    # same name returns the SAME family; type mismatch is an error
    assert reg.counter("t_total") is c
    with pytest.raises(ValueError):
        reg.gauge("t_total")


def test_registry_histogram_and_timer():
    reg = MetricsRegistry()
    h = reg.histogram("t_hist", "h", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 5.0, 50.0):
        h.observe(v)
    assert h.count() == 4
    assert h.sum() == pytest.approx(55.55)
    t = reg.timer("t_timer", "t")
    with t.time():
        pass
    assert t.count() == 1 and t.sum() >= 0.0


def test_prometheus_text_format():
    reg = MetricsRegistry()
    reg.counter("a_total", "the a", labels=("fn",)).inc(3, fn="x")
    reg.gauge("b_gauge", "the b").set(1.5)
    reg.histogram("c_seconds", "the c", buckets=(1.0,)).observe(0.5)
    txt = reg.prometheus_text()
    assert '# TYPE a_total counter' in txt
    assert 'a_total{fn="x"} 3' in txt
    assert 'b_gauge 1.5' in txt
    assert '# TYPE c_seconds histogram' in txt
    assert 'c_seconds_bucket{le="1"} 1' in txt
    assert 'c_seconds_bucket{le="+Inf"} 1' in txt
    assert 'c_seconds_count 1' in txt


def test_prometheus_text_survives_nan_and_inf():
    # a diverged run sets dl4j_score to NaN — the exporter must emit the
    # Prometheus NaN/+Inf literals, not crash
    reg = MetricsRegistry()
    reg.gauge("nan_gauge").set(float("nan"))
    reg.gauge("inf_gauge").set(float("inf"))
    txt = reg.prometheus_text()
    assert "nan_gauge NaN" in txt
    assert "inf_gauge +Inf" in txt


def test_registry_thread_safety():
    reg = MetricsRegistry()
    c = reg.counter("race_total")
    h = reg.histogram("race_hist")

    def work():
        for _ in range(1000):
            c.inc()
            h.observe(0.001)

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value() == 8000
    assert h.count() == 8000


def test_jsonl_export_roundtrip(tmp_path):
    reg = MetricsRegistry()
    reg.counter("j_total").inc(2)
    p = tmp_path / "metrics.jsonl"
    reg.export_jsonl(p)
    reg.counter("j_total").inc(1)
    reg.export_jsonl(p, extra={"tag": "w2"})
    lines = p.read_text(encoding="utf-8").splitlines()
    assert len(lines) == 2
    r1, r2 = (json.loads(l) for l in lines)
    assert r1["metrics"]["j_total"]["values"][""] == 2
    assert r2["metrics"]["j_total"]["values"][""] == 3
    assert r2["tag"] == "w2"


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------

def test_tracer_chrome_trace(tmp_path):
    tr = Tracer()
    with tr.span("outer", step=1) as outer:
        with tr.span("inner"):
            pass
    tr.instant("marker")
    p = tmp_path / "trace.json"
    tr.export_chrome_trace(p)
    doc = json.loads(p.read_text(encoding="utf-8"))
    evs = doc["traceEvents"]
    names = [e["name"] for e in evs]
    assert "outer" in names and "inner" in names and "marker" in names
    x = next(e for e in evs if e["name"] == "outer")
    assert x["ph"] == "X" and x["dur"] >= 0 and "ts" in x
    assert x["args"]["step"] == 1 and x["args"]["parent_id"] is None
    inner = next(e for e in evs if e["name"] == "inner")
    assert inner["args"]["parent_id"] == x["args"]["span_id"] == outer.id


def test_tracer_bounded_buffer():
    tr = Tracer(capacity=5)     # a ring: the newest 5 stay
    for i in range(20):
        tr.instant(f"e{i}")
    assert len(tr) == 5
    assert tr.dropped_events == 15
    assert [e["name"] for e in tr.snapshot()] == [f"e{i}"
                                                  for i in range(15, 20)]
    assert tr.chrome_trace()["otherData"]["dropped_events"] == 15


# ---------------------------------------------------------------------------
# Compile watcher
# ---------------------------------------------------------------------------

def test_compile_watcher_counts_and_storm():
    import jax
    import jax.numpy as jnp

    sess = TelemetrySession(storm_threshold=3)
    fn = jax.jit(lambda x: x * 2)
    with warnings.catch_warnings():
        warnings.simplefilter("error", RecompilationStormWarning)
        for n in (2, 3, 4):  # 3 distinct shapes = 3 compiles: no storm yet
            sess.compiles.call("f", fn, (jnp.ones(n),), {})
        sess.compiles.call("f", fn, (jnp.ones(2),), {})  # cached: no compile
    assert sess.compiles.count("f") == 3
    with pytest.warns(RecompilationStormWarning, match="recompilation storm"):
        sess.compiles.call("f", fn, (jnp.ones(5),), {})
    assert sess.compiles.count("f") == 4
    rep = sess.compiles.report()
    assert rep["f"]["count"] == 4 and rep["f"]["wall_s"] > 0
    assert sess.registry.get("dl4j_xla_compilations_total").value(
        function="f") == 4


# ---------------------------------------------------------------------------
# End-to-end: 3-epoch fit with TelemetryListener (acceptance criteria)
# ---------------------------------------------------------------------------

def test_three_epoch_fit_produces_all_artifacts(tmp_path):
    x, y = _data()
    net = _mlp()
    with telemetry.enabled(sync_per_step=True) as sess:
        net.set_listeners(TelemetryListener(session=sess, report_window=4))
        it = ArrayDataSetIterator(x, y, batch_size=16)
        net.fit(it, epochs=3)

        # 1. Prometheus dump with >= 6 metric families
        prom = tmp_path / "metrics.prom"
        sess.export_prometheus(prom)
        txt = prom.read_text(encoding="utf-8")
        families = [l.split()[2] for l in txt.splitlines()
                    if l.startswith("# TYPE")]
        assert len(families) >= 6, families
        assert "dl4j_iterations_total" in families
        assert "dl4j_xla_compilations_total" in families
        # 12 iterations, 192 samples over 3 epochs of 4 batches
        assert "dl4j_iterations_total 12" in txt
        assert "dl4j_samples_total 192" in txt
        assert "dl4j_epochs_total 3" in txt

        # 2. valid Chrome trace-event JSON with host-prep + device spans
        trace = tmp_path / "trace.json"
        sess.export_chrome_trace(trace)
        doc = json.loads(trace.read_text(encoding="utf-8"))
        names = {e["name"] for e in doc["traceEvents"]}
        assert "host/batch_prep" in names
        assert "device/dispatch" in names
        assert "device/sync" in names
        for e in doc["traceEvents"]:
            if e["ph"] == "X":
                assert e["dur"] >= 0 and "ts" in e and "pid" in e

        # 3. compile watcher: uniform batches = exactly ONE train-step
        # compilation across all 3 epochs
        rep = sess.compiles.report()
        assert rep["nn/train_step"]["count"] == 1, rep

        # JSONL exporter on the live registry
        jl = tmp_path / "metrics.jsonl"
        sess.export_jsonl(jl)
        rec = json.loads(jl.read_text(encoding="utf-8").splitlines()[0])
        assert rec["metrics"]["dl4j_iterations_total"]["values"][""] == 12
    assert telemetry.active() is None


def test_shape_churn_fires_storm_warning():
    x, y = _data(n=48)
    net = _mlp()
    with telemetry.enabled(storm_threshold=3):
        with pytest.warns(RecompilationStormWarning,
                          match="nn/train_step.*compiled 4"):
            for b in (8, 9, 10, 11):  # four distinct batch signatures
                net.fit(DataSet(x[:b], y[:b]))


def test_fit_scan_path_counts_scan_compile():
    x, y = _data()
    net = _mlp()
    xs = np.stack([x[:16], x[16:32], x[32:48]])
    ys = np.stack([y[:16], y[16:32], y[32:48]])
    with telemetry.enabled() as sess:
        lis = TelemetryListener(session=sess)
        net.set_listeners(lis)
        with warnings.catch_warnings():
            # TelemetryListener reads no params: scan replay must NOT warn
            warnings.simplefilter("error")
            net.fit_scan_arrays(xs, ys, epochs=2)
        assert sess.compiles.report()["nn/scan_epoch"]["count"] == 1
        assert sess.registry.get("dl4j_iterations_total").value() == 6
        spans = sess.span_totals()
        assert spans.get("device/dispatch", 0) > 0
        assert "device/sync" in spans  # scan-score materialization


def test_computation_graph_telemetry():
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    b = (NeuralNetConfiguration.builder().seed(3).updater(Sgd(0.1))
         .graph_builder())
    b.add_inputs("in")
    b.add_layer("d", DenseLayer(n_out=16, activation="relu"), "in")
    b.add_layer("out", OutputLayer(n_out=3, activation="softmax",
                                   loss="mcxent"), "d")
    b.set_outputs("out")
    b.set_input_types(InputType.feed_forward(8))
    g = ComputationGraph(b.build()).init()
    x, y = _data()
    with telemetry.enabled(sync_per_step=True) as sess:
        g.set_listeners(TelemetryListener(session=sess))
        g.fit(ArrayDataSetIterator(x, y, batch_size=16), epochs=2)
        assert sess.compiles.report()["graph/train_step"]["count"] == 1
        names = {e["name"] for e in sess.tracer.events()}
        assert "host/batch_prep" in names and "device/dispatch" in names


def test_parallel_trainer_telemetry():
    import jax

    from deeplearning4j_tpu.parallel.mesh import make_mesh
    from deeplearning4j_tpu.parallel.trainer import (ParallelTrainer,
                                                     TrainingMode)

    x, y = _data(n=32)
    net = _mlp()
    mesh = make_mesh({"data": 2}, devices=jax.devices()[:2])
    with telemetry.enabled(sync_per_step=True, report_window=1) as sess:
        tr = ParallelTrainer(net, mesh=mesh, mode=TrainingMode.SYNC)
        for _ in range(3):
            tr.fit(DataSet(x, y))
        assert sess.compiles.report()["parallel/train_step"]["count"] == 1
        spans = sess.span_totals()
        assert spans.get("device/dispatch", 0) > 0
        assert spans.get("device/sync", 0) > 0
        # per-device watermark sampling happened (gauges exist; CPU
        # backends may expose no memory_stats, so only host is guaranteed)
        assert sess.registry.get("dl4j_host_rss_mb").value() > 0


def test_word2vec_telemetry_compile_count():
    from deeplearning4j_tpu.nlp.sentence_iterator import (
        CollectionSentenceIterator)
    from deeplearning4j_tpu.nlp.word2vec import Word2Vec

    sents = ["the quick brown fox jumps over the lazy dog",
             "the cat sat on the mat and the dog barked"] * 20
    w2v = Word2Vec(sentence_iterator=CollectionSentenceIterator(sents),
                   layer_size=8, window_size=2, negative=2,
                   min_word_frequency=1, epochs=2, batch_size=64, seed=3)
    with telemetry.enabled() as sess:
        w2v.fit()
        rep = sess.compiles.report()
        assert rep.get("word2vec/sgns_epoch", {}).get("count") == 1, rep
        assert sess.span_totals().get("device/dispatch", 0) > 0


def test_disabled_telemetry_is_inert():
    assert telemetry.active() is None
    x, y = _data(n=16)
    net = _mlp()
    net.fit(DataSet(x, y))     # instrumented paths run with null spans
    assert telemetry.active() is None


# ---------------------------------------------------------------------------
# Satellite: PerformanceListener warm-up window + dt clamp
# ---------------------------------------------------------------------------

def test_performance_listener_counts_warmup_and_never_nan():
    x, y = _data(n=64)
    net = _mlp()
    perf = PerformanceListener(frequency=1)
    net.set_listeners(perf)
    net.fit(ArrayDataSetIterator(x, y, batch_size=16), epochs=1)
    # frequency=1 over 4 batches: FOUR records — the warm-up batch is
    # counted explicitly (the seed silently discarded it)
    assert len(perf.history) == 4
    assert perf.history[0].get("warmup") is True
    assert all(not rec.get("warmup") for rec in perf.history[1:])
    for rec in perf.history:
        assert math.isfinite(rec["samples_per_sec"])
        assert math.isfinite(rec["batches_per_sec"])
        assert rec["samples_per_sec"] > 0


def test_performance_listener_clamps_zero_dt():
    perf = PerformanceListener(frequency=1)

    class M:
        last_batch_size = 8

        def score(self):
            return 0.0

    # back-to-back calls in the same perf_counter tick must yield finite
    # (clamped), positive rates — the seed emitted NaN for dt == 0
    perf.iteration_done(M(), 1)
    perf.iteration_done(M(), 2)
    assert all(math.isfinite(r["samples_per_sec"]) for r in perf.history)


# ---------------------------------------------------------------------------
# Satellite: export_scores round-trip
# ---------------------------------------------------------------------------

def test_collect_scores_export_roundtrip(tmp_path):
    lis = CollectScoresIterationListener()
    lis.scores = [(1, 0.75), (2, 0.5), (3, 0.25)]
    p = tmp_path / "scores.csv"
    lis.export_scores(p)
    raw = p.read_bytes()
    assert b"\r\n" not in raw          # unix newlines on every platform
    raw.decode("utf-8")                # decodes as the declared encoding
    back = CollectScoresIterationListener.load_scores(p)
    assert back == [(1, 0.75), (2, 0.5), (3, 0.25)]
    with pytest.raises(ValueError):
        bad = tmp_path / "bad.csv"
        bad.write_text("nope\n", encoding="utf-8")
        CollectScoresIterationListener.load_scores(bad)


# ---------------------------------------------------------------------------
# Satellite: warn_scan_replay coverage
# ---------------------------------------------------------------------------

def test_warn_scan_replay_fires_for_nested_composable_trees():
    nested = ComposableIterationListener(
        ScoreIterationListener(1),
        ComposableIterationListener(ParamAndGradientIterationListener()))
    with pytest.warns(UserWarning,
                      match="ParamAndGradientIterationListener"):
        warn_scan_replay([nested])


def test_warn_scan_replay_silent_for_plain_score_listeners():
    listeners = [ScoreIterationListener(1),
                 CollectScoresIterationListener(),
                 PerformanceListener(),
                 ComposableIterationListener(ScoreIterationListener(5))]
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        warn_scan_replay(listeners)  # must not raise


def test_warn_scan_replay_silent_for_telemetry_listener():
    with telemetry.enabled() as sess:
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            warn_scan_replay([TelemetryListener(session=sess)])
