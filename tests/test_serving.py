"""Production inference plane tests (serving/): registry + hot-swap,
AOT-compiled buckets, quantized paths, dynamic batching, HTTP semantics,
and swap-under-concurrent-load guarantees."""
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu import (DenseLayer, InputType, MultiLayerNetwork,
                                NeuralNetConfiguration, OutputLayer, Sgd,
                                ModelSerializer, telemetry)
from deeplearning4j_tpu.serving import (BatcherClosedError, DynamicBatcher,
                                        InferenceServer, ModelRegistry,
                                        ServingError, UnknownModelError,
                                        cast_tree, quantize_tree)

# graftlint runtime sanitizer (ISSUE 9): every test runs under the
# thread-leak watchdog + order-asserting lock shims on the serving
# plane's locks — a leaked batcher/HTTP worker or an inverted lock
# acquisition fails the test at teardown. The module-scoped `served`
# fixture's batcher is allowlisted: it starts lazily inside the first
# test that predicts through it and legitimately lives until module
# teardown (srv.stop() joins it there).
pytestmark = pytest.mark.sanitize(
    allow_threads=("dl4j-serving-batcher-tiny",))

N_IN, N_OUT = 6, 3


def tiny_net(seed=0, hidden=16):
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Sgd(0.1))
            .list()
            .layer(DenseLayer(n_out=hidden, activation="relu"))
            .layer(OutputLayer(n_out=N_OUT, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(N_IN)).build())
    return MultiLayerNetwork(conf).init()


def rows(n, seed=0):
    return np.random.default_rng(seed).normal(
        size=(n, N_IN)).astype(np.float32)


# ---------------------------------------------------------------------------
# Registry: registration, precision paths, checkpoint sources
# ---------------------------------------------------------------------------
def test_register_and_predict_matches_model_output():
    net = tiny_net()
    reg = ModelRegistry(buckets=(1, 4))
    v = reg.register("m", net)
    assert v.version == 1 and v.precision == "fp32"
    assert v.buckets == (1, 4) and v.example_shape == (N_IN,)
    x = rows(3)
    out, version = reg.predict("m", x)
    assert version == 1 and out.shape == (3, N_OUT)
    np.testing.assert_allclose(out, np.asarray(net.output(x)),
                               rtol=1e-5, atol=1e-6)


def test_predict_chunks_oversize_requests():
    net = tiny_net()
    reg = ModelRegistry(buckets=(1, 4))
    reg.register("m", net)
    x = rows(11)   # > largest bucket: 2 full chunks of 4 + ragged 3
    out, _ = reg.predict("m", x)
    assert out.shape == (11, N_OUT)
    np.testing.assert_allclose(out, np.asarray(net.output(x)),
                               rtol=1e-5, atol=1e-6)


def test_single_example_convenience_and_validation():
    reg = ModelRegistry(buckets=(1,))
    reg.register("m", tiny_net())
    out, _ = reg.predict("m", rows(1)[0])     # 1-D single example
    assert out.shape == (1, N_OUT)
    with pytest.raises(ServingError):
        reg.predict("m", np.zeros((2, N_IN + 1), np.float32))
    with pytest.raises(ServingError):
        reg.predict("m", np.zeros((0, N_IN), np.float32))
    with pytest.raises(UnknownModelError):
        reg.predict("nope", rows(1))


def test_quantized_and_bf16_paths_close_to_fp32():
    net = tiny_net(hidden=32)
    x = rows(4, seed=3)
    ref = np.asarray(net.output(x))
    reg = ModelRegistry(buckets=(4,))
    reg.register("q8", net, precision="int8")
    reg.register("b16", net, precision="bf16")
    out8, _ = reg.predict("q8", x)
    outb, _ = reg.predict("b16", x)
    assert out8.dtype == np.float32 and outb.dtype == np.float32
    np.testing.assert_allclose(out8, ref, atol=5e-2)
    np.testing.assert_allclose(outb, ref, atol=2e-2)
    # int8 actually quantized something (weight matrices, not biases)
    assert reg.get("q8").snapshot.n_quantized >= 2
    assert reg.get("q8").param_bytes < reg.get("b16").param_bytes


def test_quantize_tree_unit():
    tree = {"w": np.random.default_rng(0).normal(size=(64, 32)).astype(
        np.float32), "b": np.ones(32, np.float32)}
    qt = quantize_tree(tree, min_elems=64)
    assert qt.n_quantized == 1                  # bias passes through
    rebuilt = qt.rebuild(qt.data)
    err = np.max(np.abs(np.asarray(rebuilt["w"]) - tree["w"]))
    assert err <= np.max(np.abs(tree["w"])) / 127 + 1e-6
    np.testing.assert_array_equal(np.asarray(rebuilt["b"]), tree["b"])
    cast = cast_tree(tree, "bfloat16")
    assert str(np.asarray(cast["w"]).dtype) == "bfloat16"


def test_register_from_verified_zip_and_directory(tmp_path):
    import zipfile

    from deeplearning4j_tpu.fault.atomic import CorruptCheckpointError

    net = tiny_net(seed=5)
    path = str(tmp_path / "model.zip")
    ModelSerializer.write_model(net, path)
    reg = ModelRegistry(buckets=(2,))
    reg.register("zip", path)
    out, _ = reg.predict("zip", rows(2))
    np.testing.assert_allclose(out, np.asarray(net.output(rows(2))),
                               rtol=1e-5, atol=1e-6)

    # corrupt zip (bit-rotted entry, manifest intact) -> sha256
    # verification failure, never silently-wrong params
    bad = str(tmp_path / "bad.zip")
    with zipfile.ZipFile(path) as zin, \
            zipfile.ZipFile(bad, "w") as zout:
        for name in zin.namelist():
            data = zin.read(name)
            if name == "coefficients.npz":
                data = data[:-1] + bytes([data[-1] ^ 0xFF])
            zout.writestr(name, data)
    with pytest.raises(CorruptCheckpointError):
        reg.register("bad", bad)

    # checkpoint DIRECTORY: newest committed ckpt wins; corrupt newest
    # falls back to the older good one
    d = tmp_path / "ckpts"
    d.mkdir()
    old = tiny_net(seed=6)
    old.iteration_count = 3
    ModelSerializer.write_model(old, str(d / "ckpt_000000003.zip"))
    (d / "ckpt_000000009.zip").write_bytes(b"PK\x03\x04garbage")
    reg.register("dir", str(d))
    out, _ = reg.predict("dir", rows(2))
    np.testing.assert_allclose(out, np.asarray(old.output(rows(2))),
                               rtol=1e-5, atol=1e-6)


def test_unknown_precision_and_bad_source_rejected(tmp_path):
    reg = ModelRegistry()
    with pytest.raises(ServingError):
        ModelRegistry(precision="fp8")
    with pytest.raises(ServingError):
        reg.register("m", tiny_net(), precision="fp64")
    with pytest.raises(ServingError):
        reg.register("m", str(tmp_path / "missing.zip"))
    with pytest.raises(ServingError):
        reg.register("m", str(tmp_path))   # empty dir: no committed ckpt


# ---------------------------------------------------------------------------
# Hot-swap + compile accounting
# ---------------------------------------------------------------------------
def test_swap_bumps_version_and_reuses_executables():
    with telemetry.enabled() as sess:
        reg = ModelRegistry(buckets=(1, 4), metrics=sess.registry)
        net_a, net_b = tiny_net(seed=1), tiny_net(seed=2)
        reg.register("m", net_a)
        out_a, v_a = reg.predict("m", rows(2))
        reg.swap("m", net_b)
        out_b, v_b = reg.predict("m", rows(2))
        assert (v_a, v_b) == (1, 2)
        assert not np.allclose(out_a, out_b)   # new params actually serve
        np.testing.assert_allclose(out_b, np.asarray(net_b.output(rows(2))),
                                   rtol=1e-5, atol=1e-6)
        # same architecture -> executables reused: ONE compile per bucket
        # across register + swap
        rep = sess.compiles.report()
        for b in (1, 4):
            assert rep[f"serving/m:b{b}"]["count"] == 1, rep
        # ensure() never replaces an existing version
        assert reg.ensure("m", net_a).version == 2


def test_swap_compile_failure_rejected_live_version_untouched():
    """ISSUE 20 regression: a candidate whose AOT compile fails must be
    rejected with a structured AotCompileError that leaves the live
    version AND the shared executable cache bit-for-bit untouched — a bad
    checkpoint cannot take down serving."""
    from deeplearning4j_tpu.serving import AotCompileError

    reg = ModelRegistry(buckets=(1, 4))
    net = tiny_net(seed=1)
    v1 = reg.register("m", net)
    x = rows(2, seed=5)
    expected, _ = reg.predict("m", x)
    entry = reg._entries["m"]
    cache_before = dict(entry.compiled)
    compiles = reg.metrics.counter("dl4j_serving_compiles_total",
                                   labels=("model", "bucket"))
    n_compiles = sum(compiles.values().values())

    # different architecture -> cache miss -> the poisoned forward is
    # actually traced (a same-arch candidate would reuse executables and
    # never hit the compiler)
    bad = tiny_net(seed=2, hidden=8)

    def boom(*args, **kw):
        raise ValueError("injected trace failure")

    bad.predict_fn = boom
    with pytest.raises(AotCompileError) as ei:
        reg.swap("m", bad)
    err = ei.value
    assert err.model == "m" and isinstance(err.cause, ValueError)
    assert "injected trace failure" in str(err)

    # live version, outputs, executable cache, compile accounting: all
    # exactly as before the failed swap
    assert reg.get("m") is v1
    out, version = reg.predict("m", x)
    assert version == v1.version
    np.testing.assert_array_equal(out, expected)
    assert entry.compiled == cache_before
    assert sum(compiles.values().values()) == n_compiles
    # and the registry still accepts a GOOD swap afterwards
    assert reg.swap("m", tiny_net(seed=3)).version == v1.version + 1


def test_compile_counter_metric_exported():
    with telemetry.enabled() as sess:
        reg = ModelRegistry(buckets=(2,), metrics=sess.registry)
        reg.register("m", tiny_net())
        text = sess.registry.prometheus_text()
        assert 'dl4j_serving_compiles_total{model="m",bucket="2"} 1' in text
        assert "dl4j_serving_model_version" in text


def test_predict_during_swap_no_errors_versions_monotonic():
    """Many threads hammer predict while swaps land mid-flight: no
    errors, no torn outputs (every response equals one version's exact
    output), versions observed monotonically per thread."""
    reg = ModelRegistry(buckets=(1, 4))
    nets = [tiny_net(seed=s) for s in range(4)]
    reg.register("m", nets[0])
    server = InferenceServer(reg, batching=True, max_wait_us=500)
    x = rows(1, seed=42)
    expected = {i + 1: np.asarray(n.output(x)) for i, n in enumerate(nets)}
    errors, torn, nonmono = [], [], []
    stop = threading.Event()

    def client():
        last = 0
        while not stop.is_set():
            try:
                out, version, _ = server.predict("m", x)
            except Exception as e:
                errors.append(f"{type(e).__name__}: {e}")
                return
            if version < last:
                nonmono.append((last, version))
            last = version
            if not np.allclose(out, expected[version], atol=1e-4):
                torn.append(version)

    threads = [threading.Thread(target=client) for _ in range(8)]
    for t in threads:
        t.start()
    for net in nets[1:]:
        time.sleep(0.05)
        reg.swap("m", net)
    time.sleep(0.05)
    stop.set()
    for t in threads:
        t.join(timeout=30)
    server.stop()
    assert not errors and not torn and not nonmono
    assert reg.get("m").version == 4


def test_int8_swap_reuses_executables_and_cache_is_bounded():
    """Quantization scales are runtime args, so a re-quantized
    same-architecture int8 swap reuses executables (one compile per
    bucket, swaps included); cycling ARCHITECTURES keeps at most the
    newest two signatures' executables."""
    with telemetry.enabled() as sess:
        reg = ModelRegistry(buckets=(1, 4), metrics=sess.registry)
        reg.register("m", tiny_net(seed=1), precision="int8")
        reg.swap("m", tiny_net(seed=2), precision="int8")
        rep = sess.compiles.report()
        for b in (1, 4):
            assert rep[f"serving/m:b{b}"]["count"] == 1, rep
        out, v = reg.predict("m", rows(2))
        assert v == 2
        np.testing.assert_allclose(
            out, np.asarray(tiny_net(seed=2).output(rows(2))), atol=5e-2)
        # three distinct architectures -> executable cache stays bounded
        # to the newest two signatures
        for h in (8, 24, 40):
            reg.swap("m", tiny_net(hidden=h))
        entry = reg._entries["m"]
        assert len(entry.sig_history) == 2
        assert len(entry.compiled) == 2 * 2   # 2 sigs x 2 buckets


def test_oversize_request_routes_direct_when_batcher_capped():
    """A request larger than the batcher's max_batch (but within the
    compiled buckets) must be served on the direct path, not bounced
    with a 400 (review regression)."""
    reg = ModelRegistry(buckets=(1, 4, 8))
    net = tiny_net()
    reg.register("m", net)
    srv = InferenceServer(reg, batching=True, max_wait_us=500, max_batch=4)
    x = rows(6)                     # > max_batch 4, <= largest bucket 8
    out, _, path = srv.predict("m", x)
    assert path == "direct" and out.shape == (6, N_OUT)
    np.testing.assert_allclose(out, np.asarray(net.output(x)),
                               rtol=1e-5, atol=1e-6)
    out, _, path = srv.predict("m", rows(2))
    assert path == "batched"
    srv.stop()
    # engine predicts after stop() fail loudly instead of leaking a
    # fresh batcher worker (review regression)
    with pytest.raises(BatcherClosedError):
        srv.predict("m", rows(1))


# ---------------------------------------------------------------------------
# DynamicBatcher units
# ---------------------------------------------------------------------------
def _echo_runner(calls=None):
    def runner(x, bucket):
        assert x.shape[0] == bucket   # padded to the bucket contract
        if calls is not None:
            calls.append((x.shape[0], bucket))
        return x * 2.0, 7
    return runner


def test_batcher_full_batch_flush_coalesces():
    calls = []
    b = DynamicBatcher(_echo_runner(calls), bucket_for=lambda r: 4,
                       max_batch=4, max_wait_us=2_000_000, name="t")
    outs = [None] * 4
    def go(i):
        outs[i], _ = b.submit(np.full((1, 2), i, np.float32))
    ts = [threading.Thread(target=go, args=(i,)) for i in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=30)
    b.stop()
    # 4 rows with a 2s max-wait: flushed by FULL BATCH well before the
    # deadline, in one coalesced forward
    assert len(calls) == 1 and calls[0] == (4, 4)
    for i in range(4):
        np.testing.assert_allclose(outs[i], np.full((1, 2), 2.0 * i))


def test_batcher_max_wait_timeout_flush():
    b = DynamicBatcher(_echo_runner(), bucket_for=lambda r: 4,
                       max_batch=4, max_wait_us=30_000, name="t")
    t0 = time.perf_counter()
    out, version = b.submit(np.ones((1, 2), np.float32))
    dt = time.perf_counter() - t0
    b.stop()
    # a lone request flushes at the max-wait deadline, NOT the full batch
    assert version == 7 and out.shape == (1, 2)
    assert dt < 5.0                      # nowhere near the submit timeout


def test_batcher_error_isolation():
    boom = {"on": False}

    def runner(x, bucket):
        if boom["on"]:
            raise RuntimeError("forward exploded")
        return x * 2.0, 1

    b = DynamicBatcher(runner, bucket_for=lambda r: 2, max_batch=2,
                       max_wait_us=1000, name="t")
    # oversize request fails ALONE on the caller's thread, pre-queue
    with pytest.raises(ServingError):
        b.submit(np.ones((3, 2), np.float32))
    # a failing forward fails that batch's requests with the server fault
    boom["on"] = True
    with pytest.raises(RuntimeError, match="forward exploded"):
        b.submit(np.ones((1, 2), np.float32))
    # ...and the batcher keeps serving afterwards
    boom["on"] = False
    out, _ = b.submit(np.ones((1, 2), np.float32))
    np.testing.assert_allclose(out, 2.0)
    b.stop()


def test_batcher_multi_row_requests_scatter_correctly():
    b = DynamicBatcher(_echo_runner(), bucket_for=lambda r: 8,
                       max_batch=8, max_wait_us=50_000, name="t")
    outs = {}
    def go(i, n):
        outs[i], _ = b.submit(np.full((n, 2), i, np.float32))
    ts = [threading.Thread(target=go, args=(i, n))
          for i, n in enumerate((3, 2, 3))]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=30)
    b.stop()
    for i, n in enumerate((3, 2, 3)):
        assert outs[i].shape == (n, 2)
        np.testing.assert_allclose(outs[i], 2.0 * i)


def test_batcher_survives_submit_storm():
    """Hammer the lock-free queue from many threads: the worker must
    never die to a deque-mutation race (review regression — a dead
    worker turns every batched request into a 30s timeout)."""
    b = DynamicBatcher(_echo_runner(), bucket_for=lambda r: 8,
                       max_batch=8, max_wait_us=200, name="t")
    errors = []

    def client(i):
        for k in range(60):
            try:
                out, _ = b.submit(np.full((1, 2), i, np.float32),
                                  timeout=20)
                assert float(out[0, 0]) == 2.0 * i
            except Exception as e:
                errors.append(f"{type(e).__name__}: {e}")
                return

    ts = [threading.Thread(target=client, args=(i,)) for i in range(16)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=60)
    alive = b._worker.is_alive()
    b.stop()
    assert not errors and alive


def test_server_max_batch_above_largest_bucket_is_clamped():
    """max_batch greater than the largest compiled bucket must not let
    coalesced flushes exceed the bucket set and fail whole batches
    (review regression, repro'd with 3x20-row concurrent predicts)."""
    reg = ModelRegistry(buckets=(1, 8, 32))
    net = tiny_net()
    reg.register("m", net)
    srv = InferenceServer(reg, batching=True, max_wait_us=20_000,
                          max_batch=64)
    outs, errs = {}, []

    def go(i):
        try:
            outs[i] = srv.predict("m", rows(20, seed=i))
        except Exception as e:
            errs.append(f"{type(e).__name__}: {e}")

    ts = [threading.Thread(target=go, args=(i,)) for i in range(3)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=60)
    srv.stop()
    assert not errs
    for i in range(3):
        out, _, _ = outs[i]
        np.testing.assert_allclose(
            out, np.asarray(net.output(rows(20, seed=i))),
            rtol=1e-5, atol=1e-6)


def test_a_burst_of_connects_is_queued_not_dropped():
    """128 clients connecting at once before one is accepted: every connect
    completes at once (the listening socket queues them), where a queue of
    socketserver's default 5 leaves all but a few waiting on TCP's
    retransmission (1 s, then 3, 7 ...)."""
    import socket
    srv = InferenceServer(ModelRegistry())          # bound, not serving
    socks = []
    try:
        for _ in range(128):        # a connect left waiting raises here
            socks.append(socket.create_connection((srv.host, srv.port),
                                                  timeout=0.5))
    finally:
        for s in socks:
            s.close()
        srv._httpd.server_close()


def test_batcher_stop_drains_then_rejects():
    b = DynamicBatcher(_echo_runner(), bucket_for=lambda r: 2,
                       max_batch=2, max_wait_us=1000, name="t")
    out, _ = b.submit(np.ones((1, 2), np.float32))
    b.stop()
    with pytest.raises(BatcherClosedError):
        b.submit(np.ones((1, 2), np.float32))


# ---------------------------------------------------------------------------
# InferenceServer HTTP plane
# ---------------------------------------------------------------------------
def _http(method, url, body=None, timeout=30):
    req = urllib.request.Request(
        url, None if body is None else json.dumps(body).encode(),
        {"Content-Type": "application/json"}, method=method)
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        ct = resp.headers.get("Content-Type", "")
        data = resp.read()
        return resp.status, (json.loads(data) if "json" in ct
                             else data.decode())


def _http_err(method, url, body=None):
    try:
        return _http(method, url, body)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


@pytest.fixture(scope="module")
def served():
    reg = ModelRegistry(buckets=(1, 4))
    net = tiny_net(seed=9)
    reg.register("tiny", net)
    srv = InferenceServer(reg, max_wait_us=500).start()
    yield srv, net
    srv.stop()


def test_http_predict_models_health_metrics(served):
    srv, net = served
    base = f"http://{srv.host}:{srv.port}"
    code, out = _http("GET", f"{base}/v1/models")
    assert code == 200 and out["models"][0]["name"] == "tiny"
    code, info = _http("GET", f"{base}/v1/models/tiny")
    assert info["buckets"] == [1, 4] and info["version"] >= 1
    x = rows(2, seed=1)
    code, out = _http("POST", f"{base}/v1/models/tiny/predict",
                      {"features": x.tolist()})
    assert code == 200 and out["batched"] is True
    np.testing.assert_allclose(np.asarray(out["output"], np.float32),
                               np.asarray(net.output(x)), atol=1e-4)
    code, out2 = _http("POST", f"{base}/v1/models/tiny/predict",
                       {"features": x.tolist(), "batched": False})
    assert code == 200 and out2["batched"] is False
    code, health = _http("GET", f"{base}/healthz")
    assert code == 200 and health["status"] == "ok" \
        and "tiny" in health["models"]
    code, text = _http("GET", f"{base}/metrics")
    for family in ("dl4j_serving_requests_total",
                   "dl4j_serving_latency_seconds",
                   "dl4j_serving_batch_size",
                   "dl4j_serving_queue_wait_seconds",
                   "dl4j_serving_compiles_total"):
        assert family in text, f"{family} missing from /metrics"


def test_http_swap_endpoint(served, tmp_path):
    srv, _ = served
    base = f"http://{srv.host}:{srv.port}"
    swapped = tiny_net(seed=11)
    ckpt = str(tmp_path / "swap.zip")
    ModelSerializer.write_model(swapped, ckpt)
    before = srv.registry.get("tiny").version
    code, info = _http("POST", f"{base}/v1/models/tiny/swap",
                       {"source": ckpt})
    assert code == 200 and info["version"] == before + 1
    x = rows(2, seed=2)
    code, out = _http("POST", f"{base}/v1/models/tiny/predict",
                      {"features": x.tolist()})
    assert out["version"] == before + 1
    np.testing.assert_allclose(np.asarray(out["output"], np.float32),
                               np.asarray(swapped.output(x)), atol=1e-4)


def test_http_error_semantics(served):
    srv, _ = served
    base = f"http://{srv.host}:{srv.port}"
    # malformed JSON -> 400 with a structured body
    req = urllib.request.Request(
        f"{base}/v1/models/tiny/predict", b"{not json",
        {"Content-Type": "application/json"}, method="POST")
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(req, timeout=30)
    assert ei.value.code == 400
    assert "malformed JSON" in json.loads(ei.value.read())["error"]
    # missing key -> 400
    code, body = _http_err("POST", f"{base}/v1/models/tiny/predict", {})
    assert code == 400 and "features" in body["error"]
    # bad shape -> 400
    code, body = _http_err("POST", f"{base}/v1/models/tiny/predict",
                           {"features": [[1.0] * (N_IN + 2)]})
    assert code == 400 and "error" in body
    # empty body -> 400
    code, body = _http_err("POST", f"{base}/v1/models/tiny/predict", None)
    assert code == 400
    # unknown model -> 404; unknown path -> 404
    code, _b = _http_err("POST", f"{base}/v1/models/ghost/predict",
                         {"features": [[0.0] * N_IN]})
    assert code == 404
    code, _b = _http_err("GET", f"{base}/v2/bogus")
    assert code == 404
    # swap from a nonexistent source -> 400 (client mistake, not a 500)
    code, body = _http_err("POST", f"{base}/v1/models/tiny/swap",
                           {"source": "/nope/missing.zip"})
    assert code == 400 and "does not exist" in body["error"]
    # malformed swap parameters -> 400, not 500 (review regression)
    code, body = _http_err("POST", f"{base}/v1/models/tiny/swap",
                           {"source": "/tmp/x.zip", "buckets": ["a"]})
    assert code == 400 and "invalid swap parameters" in body["error"]


def test_http_keepalive_survives_error_then_success(served):
    """An error reply must not desynchronize a persistent connection:
    the server closes errored connections, so a fresh request after an
    unread-body 404 still works (review regression)."""
    import http.client

    srv, net = served
    conn = http.client.HTTPConnection(srv.host, srv.port, timeout=30)
    body = json.dumps({"features": rows(1).tolist()})
    conn.request("POST", "/v1/bogus/path", body,
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 404
    assert resp.headers.get("Connection", "").lower() == "close"
    resp.read()
    # http.client transparently reconnects on a closed keep-alive socket
    conn.request("POST", "/v1/models/tiny/predict", body,
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200
    out = json.loads(resp.read())
    assert np.asarray(out["output"]).shape == (1, N_OUT)
    conn.close()


# ---------------------------------------------------------------------------
# Legacy Keras backend server semantics (no keras needed: these paths
# fail before any model is touched)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def legacy():
    from deeplearning4j_tpu.modelimport.server import KerasBackendServer
    srv = KerasBackendServer().start()
    yield srv
    srv.stop()


def test_legacy_server_malformed_json_is_400(legacy):
    base = f"http://{legacy.host}:{legacy.port}"
    req = urllib.request.Request(base + "/output", b"{oops",
                                 {"Content-Type": "application/json"},
                                 method="POST")
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(req, timeout=30)
    assert ei.value.code == 400
    body = json.loads(ei.value.read())
    assert "malformed JSON" in body["error"]


def test_legacy_server_missing_keys_is_400(legacy):
    base = f"http://{legacy.host}:{legacy.port}"
    for path, body in (("/output", {"features": [[1.0]]}),
                       ("/output", {"model": "/tmp/x.h5"}),
                       ("/fit", {"model": "/tmp/x.h5"})):
        code, out = _http_err("POST", base + path, body)
        assert code == 400, (path, body, code)
        assert "error" in out


def test_legacy_server_unknown_path_404_and_server_fault_500(legacy):
    base = f"http://{legacy.host}:{legacy.port}"
    code, _ = _http_err("POST", base + "/bogus", {})
    assert code == 404
    # a genuine server fault stays 500: break the entry point itself
    entry = legacy.entry_point
    orig = entry.output
    entry.output = lambda *a, **k: (_ for _ in ()).throw(
        MemoryError("server fault"))
    try:
        code, body = _http_err("POST", base + "/output",
                               {"model": "m", "features": [[1.0]]})
        assert code == 500 and "MemoryError" in body["error"]
    finally:
        entry.output = orig


def test_legacy_output_routes_through_registry(tmp_path):
    """/output serves via the ModelRegistry: loaded+compiled once, and
    concurrent requests don't serialize behind a global forward lock."""
    from deeplearning4j_tpu.modelimport.server import (
        DeepLearning4jEntryPoint)

    reg = ModelRegistry(buckets=(1, 4))
    entry = DeepLearning4jEntryPoint(registry=reg)
    net = tiny_net(seed=13)
    path = str(tmp_path / "native.zip")
    ModelSerializer.write_model(net, path)
    # seed the cache the way _load would (skip the keras import path —
    # the registry accepts any model object)
    entry._models[path] = net
    out = entry.output(path, rows(2).tolist())
    assert path in reg and out.shape == (2, N_OUT)
    v1 = reg.get(path).version
    entry.output(path, rows(2).tolist())
    assert reg.get(path).version == v1      # no reload/re-register


def test_legacy_output_accepts_shape_varying_sequences():
    """The legacy /output contract accepts variable trailing shapes
    (e.g. variable-length sequences); registered fixed buckets serve the
    stable shape and off-shape requests fall back to direct net.output()
    (review regression)."""
    from deeplearning4j_tpu import (GravesLSTM, RnnOutputLayer)
    from deeplearning4j_tpu.modelimport.server import (
        DeepLearning4jEntryPoint)

    conf = (NeuralNetConfiguration.builder().seed(0).updater(Sgd(0.1))
            .list()
            .layer(GravesLSTM(n_out=8))
            .layer(RnnOutputLayer(n_out=2, activation="softmax",
                                  loss="mcxent"))
            .set_input_type(InputType.recurrent(4)).build())
    net = MultiLayerNetwork(conf).init()
    entry = DeepLearning4jEntryPoint(registry=ModelRegistry(buckets=(1, 2)))
    entry._models["rnn"] = net
    r = np.random.default_rng(0)
    x5 = r.normal(size=(2, 5, 4)).astype(np.float32)
    x9 = r.normal(size=(2, 9, 4)).astype(np.float32)
    out5 = entry.output("rnn", x5.tolist())   # registers shape (5, 4)
    out9 = entry.output("rnn", x9.tolist())   # off-shape: direct path
    np.testing.assert_allclose(out5, np.asarray(net.output(x5)),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(out9, np.asarray(net.output(x9)),
                               rtol=1e-4, atol=1e-5)


