"""Tests of the benchmark itself, on the CPU at tiny sizes:

    python -m pytest benchmarks/tests -q

They drive everything of a run but the look for a chip, through cells,
configurations and a per-layer metric dropped into a temporary copy of
`benchmarks/` — which is also the proof that each can be added as a file.
"""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
sys.path[:0] = [str(REPO), str(BENCH)]

from harness import compare, flops, xplane  # noqa: E402

TINY = {
    "name": "tiny", "family": "gpt2", "source": "test",
    "n_embd": 32, "n_layer": 2, "n_head": 2, "n_positions": 64, "n_ctx": 64,
    "vocab_size": 120, "reduced": [],
    "assumed": {"n_inner": 128, "padded_vocab_size": 128},
    "precision": {"states": "float32", "compute_dtype": None,
                  "registry": "fp32", "kv_dtype": "fp32", "control": "float8",
                  "reference": "default"},
    "updater": {"adam": {"learning_rate": 3e-4, "beta1": 0.9, "beta2": 0.999,
                         "epsilon": 1e-8}},
}
TINY_FIT = {
    "name": "tiny.fit", "config": "tiny", "driver": "fit", "chips": 1,
    "why": "test",
    "traffic": {"batch": 4, "seq_len": 64, "ring": 2, "score_every": 5},
    "trace_seconds": 0.3,
    "check": {"steps": 3, "reference_block_rows": 2,
              "limits": {"loss_gap": 1e-4, "grad_norm_gap": 1e-3,
                         "update_norm_gap": 1e-2}},
}
TINY_GEN = {
    "name": "tiny.generate", "config": "tiny", "driver": "generate",
    "chips": 1, "why": "test",
    "traffic": {"clients": 3,
                "prompt_tokens": {"dist": "loguniform", "lo": 5, "hi": 40},
                "max_tokens": {"dist": "uniform", "lo": 2, "hi": 8},
                "temperature": 0.0, "ramp_seconds": 0.5,
                "request_timeout_seconds": 60.0},
    "serve": {"registry_buckets": [1], "decode_buckets": [1, 2, 4],
              "prompt_buckets": [16, 64]},
    "trace_seconds": 0.3,
    "check": {"sample_requests": 3, "limits": {"served_logit_gap": 1e-3,
                                               "logit_rel_err": 1e-3}},
}
# the program's own lower-precision paths, as configurations of their own
LOWER = {"tiny-bf16": {"registry": "bf16"}, "tiny-kvint8": {"kv_dtype": "int8"}}
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def sandbox(tmp_path_factory):
    """A copy of benchmarks/ with a tiny configuration, two tiny cells and one
    more per-layer metric dropped in: files added, none edited."""
    root = tmp_path_factory.mktemp("checkout")
    bench = root / "benchmarks"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    (bench / "configs" / "tiny.json").write_text(json.dumps(TINY))
    (bench / "workloads" / "tiny.fit.json").write_text(json.dumps(TINY_FIT))
    (bench / "workloads" / "tiny.generate.json").write_text(json.dumps(TINY_GEN))
    for name, change in LOWER.items():
        (bench / "configs" / f"{name}.json").write_text(json.dumps(dict(
            TINY, name=name, precision=dict(TINY["precision"], **change))))
        (bench / "workloads" / f"{name}.generate.json").write_text(json.dumps(
            dict(TINY_GEN, name=f"{name}.generate", config=name)))
    (bench / "layer_metrics" / "steps_seen.py").write_text(
        "def compute(env):\n    return env.facts.get('steps')\n")
    b = json.loads((REPO / "BENCHMARK.json").read_text())
    for m in b["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append("tiny.fit" if m["name"] == "train_step_ms"
                                  else "tiny.generate")
    for m in b["per_layer"]:
        m["workloads"].append("tiny.fit" if m["moves"] == "train_step_ms"
                              else "tiny.generate")
    b["per_layer"].append({"name": "steps_seen", "unit": "steps",
                           "better": "higher", "source": "program_counter",
                           "layer": "entry points", "moves": "train_step_ms",
                           "workloads": ["tiny.fit"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    spec_run = _load_run(bench)
    return spec_run, bench, root


def _load_run(bench):
    import importlib.util
    spec = importlib.util.spec_from_file_location("bench_run_copy",
                                                  bench / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(sandbox, workload, seed=5, seconds=0.5, trace=False):
    run, bench, root = sandbox
    import time
    rc, line = run.run_cell(workload, seed, seconds, trace, bench=bench,
                            repo=root, check_device=False,
                            t_start=time.perf_counter())
    assert rc == 0
    return json.loads(line)


@pytest.mark.parametrize("workload,metrics", [
    ("tiny.fit", {"train_step_ms", "setup_s"}),
    ("tiny.generate", {"generate_tokens_per_s", "generate_latency_p95_ms",
                       "setup_s"})])
def test_driver_prints_the_contract_line(sandbox, workload, metrics):
    out = _run(sandbox, workload)
    assert LINE_KEYS <= set(out) and list(out)[-1] == "checks"
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == metrics
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert set(out["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    for c in out["checks"].values():
        assert c["value"] <= c["limit"]


def test_traced_run_reports_found_metrics_and_a_dropped_in_one(sandbox,
                                                               monkeypatch):
    # the CPU's operations are on its client threads' lines of the host plane
    monkeypatch.setattr(xplane, "DEVICE_PLANE", "/host:CPU")
    monkeypatch.setattr(xplane, "OPS_LINE", "tf_XLAPjRtCpuClient")
    out = _run(sandbox, "tiny.fit", trace=True)
    assert out["correct"] is True
    assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
    assert out["breakdown"]["device_ops"] and out["breakdown"]["idle_gaps"]
    # the metric dropped in as a file, and the host-clock and trace readers
    assert {"steps_seen", "fit_step_p95_ms",
            "device_idle_share.train"} <= set(out["metrics"])
    # no flash kernel runs on the CPU and a CPU has no peaks: the readers
    # find nothing to read and are left out, never 0
    assert "flash_roofline" not in out["metrics"]
    assert "mfu.train" not in out["metrics"]


def test_command_refuses_a_machine_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", "gpt2-124m.fit",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_xplane_reduction_on_a_hand_made_trace():
    ms = 1_000_000
    tr = xplane.Trace(
        devices={"/device:TPU:0": [
            ("fusion.1", 0 * ms, 10 * ms), ("fusion.2", 5 * ms, 10 * ms),
            ("copy.4", 50 * ms, 1 * ms), ("copy.5", 52 * ms, 1 * ms),
            ("jvp_flash_fwd__.3", 20 * ms, 4 * ms),
            (xplane.op_name("%transpose_jvp_flash_bwd_dq__.7 = (bf16[96,1024,"
                            "64]) custom-call(bf16[96,1024,64] %x)"),
             30 * ms, 6 * ms),
            ("transpose_jvp_flash_bwd_dkv__.1", 40 * ms, 5 * ms),
            ("late", 99 * ms, 10 * ms)]},
        host=[(xplane.WINDOW_SPAN, 0, 100 * ms),
              ("bench/fit", 14 * ms, 8 * ms),
              ("bench/score_read", 24 * ms, 6 * ms)])
    busy, window = xplane.busy_seconds(tr)
    # union: [0,15] [20,24] [30,36] [40,45] [50,51] [52,53] [99,100]: 33 of 100
    assert window == pytest.approx(0.1) and busy == pytest.approx(0.033)
    k = xplane.kernel_stats(tr, flops.FLASH_KERNELS)
    assert k == {"flash_fwd": (pytest.approx(0.004), 1),
                 "flash_bwd_dq": (pytest.approx(0.006), 1),
                 "flash_bwd_dkv": (pytest.approx(0.005), 1)}
    assert xplane.top_ops(tr, 2) == [["fusion.1", pytest.approx(0.01)],
                                     ["fusion.2", pytest.approx(0.01)]]
    assert ["copy", pytest.approx(0.002)] in xplane.top_ops(tr)
    gaps = dict(xplane.idle_gaps(tr))
    assert gaps["bench/fit"] == pytest.approx(0.005)          # gap 15..20
    assert gaps["bench/score_read"] == pytest.approx(0.006)   # gap 24..30
    assert gaps["unattributed"] == pytest.approx(0.004 + 0.005 + 0.001 + 0.046)
    assert xplane.kernel_stats(tr, ("lstm_fwd",)) == {}


def test_flops_against_hand_figures():
    cfg = json.loads((BENCH / "configs" / "gpt2-124m.json").read_text())
    per_token = flops.lm_train_flops_per_token(cfg, 1024)
    assert per_token == 3 * (2 * (12 * 12 * 768 ** 2 + 768 * 50304)
                             + 2 * 1024 * 768 * 12)
    assert round(per_token / 1e9, 2) == 0.80
    assert flops.flash_step_flops(96, 1024, 64) == 6 * 2 * 1024 ** 2 * 64 * 96 / 2
    assert round(flops.flash_step_flops(96, 1024, 64) / 1e9, 1) == 38.7
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = flops.roofline_seconds(
        flops.flash_kernel_flops("flash_fwd", 96, 1024, 64),
        flops.flash_kernel_bytes("flash_fwd", 96, 1024, 64), peak)
    assert bound == "compute" and t == pytest.approx(12.885e9 / 2 / 197e12 * 2,
                                                     rel=1e-3)
    # one prompt of 3 tokens, 1 token sampled: 3 block passes at contexts
    # 1, 2, 3 and one head
    assert flops.lm_serve_flops(cfg, [3], [1]) == (
        3 * flops.lm_block_flops_per_token(cfg)
        + 6 * flops.lm_attention_flops(cfg, 1)
        + flops.lm_head_flops_per_token(cfg))


def _tiny_model_and_reference(train):
    import importlib.util
    def load(kind):
        spec = importlib.util.spec_from_file_location(
            f"t_{kind}", BENCH / kind / "gpt2.py")
        m = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(m)
        return m
    ref, models = load("reference"), load("models")
    return models.build(TINY, 11, ref, train=train), ref


def test_reference_matches_the_programs_loss_and_gradients():
    import jax
    import jax.numpy as jnp
    model, ref = _tiny_model_and_reference(train=True)
    rng = np.random.default_rng(0)
    tok = rng.integers(0, 120, (4, 64)).astype(np.int32)
    tgt = np.roll(tok, -1, axis=1)
    y = jax.nn.one_hot(tgt, 128, dtype=jnp.float32)
    (score, _), grads = jax.value_and_grad(model._loss_fn, has_aux=True)(
        model.params, model.state, jnp.asarray(tok[..., None]), y, None)
    params = ref.init_params(TINY, 11)
    with jax.default_matmul_precision("highest"):
        want, wgrads = jax.value_and_grad(ref.loss_sum)(params, tok, tgt, 2)
    assert float(score) == pytest.approx(float(want) / tok.size, rel=1e-5)
    for g, w in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(wgrads)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w) / tok.size,
                                   rtol=2e-3, atol=1e-7)


def test_prefill_plus_ticks_match_the_reference_full_forward():
    from deeplearning4j_tpu.serving import ModelRegistry
    from deeplearning4j_tpu.serving.decode.engine import DecodeEngine
    model, ref = _tiny_model_and_reference(train=False)
    reg = ModelRegistry()
    reg.register("tiny", model, buckets=(1,))
    eng = DecodeEngine(reg, "tiny", decode_buckets=(1,),
                       prompt_buckets=(16, 64))
    v, pool = reg.get("tiny"), eng.new_pool()
    seq = np.random.default_rng(1).integers(0, 120, 30).tolist()
    n, g = 20, 10
    blocks = pool.alloc(eng.spec.blocks_for(n + g))
    got = [eng.run_prefill(v, pool, seq[:n], blocks)]
    for i in range(g - 1):
        got.append(eng.run_tick(v, pool, [seq[n + i]], [n + i], [blocks],
                                bucket=1)[0])
    want = ref.served_logits(TINY, ref.init_params(TINY, 11), seq, n, g)
    np.testing.assert_allclose(np.stack(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


# ---- the timed path broken underneath: `correct` has to come out false ----
def test_fault_a_step_that_returns_its_state_unchanged(sandbox, monkeypatch):
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    real = MultiLayerNetwork._fit_batch

    def frozen(self, ds):
        before = (self.params, self.state, self.updater_state)
        self.params, self.updater_state = _copies(before[0]), _copies(before[2])
        real(self, ds)                       # the step runs, its state is dropped
        self.params, self.state, self.updater_state = before
    monkeypatch.setattr(MultiLayerNetwork, "_fit_batch", frozen)
    out = _run(sandbox, "tiny.fit")
    assert out["correct"] is False
    assert out["checks"]["update_norm_gap"]["value"] == pytest.approx(1.0)


def _copies(tree):
    import jax
    import jax.numpy as jnp
    return jax.tree_util.tree_map(jnp.copy, tree)


def test_fault_half_of_the_batch_left_out(sandbox, monkeypatch):
    from deeplearning4j_tpu import DataSet
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    real = MultiLayerNetwork._fit_batch

    def half(self, ds):
        x, y, _, _ = ds.device_tuple()
        n = x.shape[0] // 2
        real(self, DataSet(x[:n], y[:n]))    # the mean is over the rest
    monkeypatch.setattr(MultiLayerNetwork, "_fit_batch", half)
    out = _run(sandbox, "tiny.fit")
    assert out["correct"] is False
    assert out["checks"]["grad_norm_gap"]["value"] > 10 * TINY_FIT[
        "check"]["limits"]["grad_norm_gap"]


def test_fault_a_token_altered_where_it_is_produced(sandbox, monkeypatch):
    from deeplearning4j_tpu.serving.decode.scheduler import GenerationScheduler
    real = GenerationScheduler._sample

    def altered(self, seq, logits):
        tok = real(self, seq, logits)
        return (tok + 1) % 120 if len(seq.ctx) % 7 == 0 else tok
    monkeypatch.setattr(GenerationScheduler, "_sample", altered)
    out = _run(sandbox, "tiny.generate")
    assert out["correct"] is False
    assert out["checks"]["served_logit_gap"]["value"] > 0.01


@pytest.mark.parametrize("control", ["float8", "half_batch"])
def test_reference_controls_fail_at_test_size(sandbox, control):
    """PERF.md's controls of the training cell at a size a test run can hold:
    the reference in the precision below the configuration's, and with half of
    the batch left out, put in the program's place and judged by `verdict`."""
    from types import SimpleNamespace
    run, bench, _ = sandbox
    ctx = SimpleNamespace(cell=TINY_FIT, config=TINY, seed=3, compare=compare,
                          reference=run.load(bench, "reference", "gpt2"))
    checks = run.load(bench, "drivers", "fit").reference_controls(ctx)[control]
    assert compare.verdict(checks) is False
    assert dict((n, v) for n, v, _ in checks)["grad_norm_gap"] > 1e-2


@pytest.mark.parametrize("config", sorted(LOWER))
def test_the_programs_lower_precision_paths_fail_at_test_size(sandbox, config):
    """The serving cell's controls: the program's registry precision bf16 and
    its int8 KV arena, the cell otherwise as it stands."""
    out = _run(sandbox, f"{config}.generate")
    assert out["correct"] is False
    assert out["checks"]["logit_rel_err"]["value"] > 3e-3
    assert out["failed"] == 0


def test_client_rates_and_logit_error_on_hand_figures(sandbox):
    run, bench, _ = sandbox
    gen = run.load(bench, "drivers", "generate")
    rec = lambda c, sent, done, n, ok=True: {
        "c": c, "sent": sent, "done": done, "ok": ok, "prompt": [0] * 7,
        "tokens": [1] * n if ok else []}
    rates = gen.client_rates([rec(0, 1.0, 3.0, 10), rec(0, 3.0, 6.0, 20),
                              rec(1, 2.0, 4.0, 8, ok=False)], clients=3)
    assert rates == [{"span_s": 5.0, "prompt_lens": [7, 7], "generated": [10, 20]},
                     {"span_s": 2.0, "prompt_lens": [], "generated": []}]
    r = np.array([[1.0, -1.0], [2.0, 0.0]])
    assert compare.logit_rel_err([r], [r]) == 0.0
    assert compare.logit_rel_err([r + [[0.1, 0.1], [0.0, 0.2]]], [r]) == (
        pytest.approx((0.06 / 4.0) ** 0.5))


def test_benchmark_json_names_units_and_workload_lists():
    b = json.loads((REPO / "BENCHMARK.json").read_text())
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    cells = {w["name"] for w in b["workloads"]}
    configs = {c["name"] for c in b["configs"]}
    for c in b["configs"]:
        assert name.match(c["name"]) and (REPO / c["file"]).is_file()
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert json.loads((REPO / c["file"]).read_text())["reduced"] == c["reduced"]
    for w in b["workloads"]:
        assert name.match(w["name"]) and name.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        cell = json.loads((BENCH / "workloads" / f"{w['name']}.json").read_text())
        assert cell["config"] == w["config"] and cell["chips"] == w["chips"]
        assert cell["why"] == w["why"]
        assert all(v is not None for v in cell["check"]["limits"].values())
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    for m in b["end_to_end"] + b["per_layer"]:
        assert name.match(m["name"]) and unit.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["name"] == "setup_s" or set(m["workloads"]) <= cells
    for m in b["end_to_end"]:
        assert 0 < m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert m["moves"] in e2e and m["workloads"]
        assert any((BENCH / "layer_metrics" / f"{n}.py").is_file()
                   for n in (m["name"], m["name"].rpartition(".")[0]))
    # a full check of 24 cells at this length fits the contract's 43,200 s
    assert (2 + 14 * 24) * (b["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
