#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, no network, data from a seed. Drives the two main paths through
the entry points a user calls, at the full width of models the repo ships:

  device    platform/kind/count + versions; anything but a TPU is exit 2
  kernels   the three Pallas kernels, forward and backward, compiled by
            libtpu (interpret=False passed explicitly) against their oracles
  train     zoo.resnet50() as shipped, batch 256: ComputationGraph.fit steps
            and one fit_scan_arrays window
  lm        GPT-2 124M sizes built with the public builder: fit steps at
            T=1024 on the flash kernel, then ModelSerializer zip ->
            ModelRegistry -> InferenceServer -> POST /generate over HTTP,
            prefill+decode logits checked against the full forward
  char-rnn  zoo.char_rnn (2 x 200, b64, seq 128) on the fused LSTM kernel
  mesh      (>= 4 devices) ResNet-50 data-parallel and the LM under
            zero1_tp (2, 2) with flash under shard_map

Phases run in order and stop at the first that fails. The last two stdout
lines are JSON objects: first the per-phase summary {"phases": [...],
"cache_dir": ...}, then — last, and with exactly these keys —
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.
Exit codes: 0 all phases passed; 1 a phase failed; 2 no TPU.

Every phase function takes `sizes` and `interpret`; tests call them on the
CPU at tiny sizes with interpret=True (Pallas interpreter, no Mosaic
assertions). `main()` always runs FULL with interpret=False and refuses to
start without a TPU — no flag or variable changes that.
"""
from __future__ import annotations

import gc
import json
import re
import sys
import tempfile
import threading
import time
import urllib.request
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

MOSAIC_CALL = "tpu_custom_call"
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   _BACKEND_COMPILE)


class SmokeFailure(AssertionError):
    """A phase ran and its check did not hold."""


class NoChip(RuntimeError):
    """JAX found no TPU; the smoke has nothing to prove."""


@dataclass(frozen=True)
class Sizes:
    # kernels: (B, T, D, dtype, causal) / (N, C, dtype) / (T, B, F, H)
    flash_shapes: Tuple = ((8, 1024, 64, "bfloat16", True),
                           (4, 1024, 64, "float32", True),
                           (2, 100, 64, "bfloat16", False),
                           # tilings the GPT-2 shape does not choose: a
                           # ragged causal tail, Dh 128 at four chunks
                           (8, 1000, 64, "bfloat16", True),
                           (2, 2048, 128, "bfloat16", False))
    bn_shapes: Tuple = ((256, 2048, "bfloat16"), (512, 512, "bfloat16"),
                        (64, 100, "float32"))
    lstm_shapes: Tuple = ((64, 64, 77, 200), (64, 64, 200, 200))
    # train: zoo.resnet50 kwargs, batch, per-batch fit steps, scan window
    resnet: Dict = field(default_factory=dict)   # {} = as shipped
    image: int = 224
    n_classes: int = 1000
    train_batch: int = 256
    fit_steps: int = 4
    scan_steps: int = 3
    # lm: published GPT-2 124M, vocabulary padded 50,257 -> 50,304 (x128)
    vocab: int = 50304
    width: int = 768
    heads: int = 12
    blocks: int = 12
    context: int = 1024
    lm_batch: int = 4            # one-hot f32 labels [B, T, V]: 206 MB a row
    lm_steps: int = 3
    prompt_len: int = 500        # + gen_tokens >= 512: a real context
    gen_tokens: int = 24
    check_ticks: int = 8
    # char-rnn: zoo.char_rnn at the reference example's size
    rnn_vocab: int = 77
    rnn_hidden: int = 200
    rnn_batch: int = 64
    rnn_seq: int = 128
    rnn_tbptt: int = 64
    rnn_steps: int = 3


FULL = Sizes()


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------
def _say(msg: str):
    print(f"[chip_smoke] {msg}", flush=True)


def mosaic_kernels(lowered_text: str) -> List[str]:
    """Names of the Mosaic kernels in a lowered module's text, in order —
    [] when no Pallas kernel was compiled into it."""
    if MOSAIC_CALL not in lowered_text:
        return []
    return re.findall(r'kernel_name = "([^"]+)"', lowered_text)


def _require_kernels(what: str, text: str, names, interpret: bool) -> List[str]:
    """State which implementation a lowered step holds; on the chip fail
    unless every kernel in `names` is a Mosaic custom call in it."""
    found = mosaic_kernels(text)
    if interpret:
        _say(f"{what}: CPU rehearsal, no Mosaic call expected")
        return found
    _say(f"{what}: Mosaic kernels in lowered step: "
         f"{sorted(set(found)) or 'none (XLA/reference path)'}")
    missing = [n for n in names if n not in found]
    if missing:
        raise SmokeFailure(
            f"{what}: lowered step lacks Mosaic kernel(s) {missing} — the "
            "layer selected another implementation")
    return found


def _rel_err(got, want) -> float:
    """max|got-want| / max|want| in f32: one number per tensor, insensitive
    to near-zero entries."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def _check_close(what: str, got, want, tol: float):
    errs = [_rel_err(g, w) for g, w in zip(got, want)]
    _say(f"{what}: max rel err {max(errs):.2e} (tol {tol:.0e})")
    if not all(np.isfinite(e) and e <= tol for e in errs):
        raise SmokeFailure(f"{what}: rel errs {errs} exceed {tol}")
    return max(errs)


def _hbm_peaks() -> Optional[Dict]:
    """Device 0's high-water marks, process-wide and monotonic. On this
    runtime live buffers count under `peak_bytes_in_use` and the compiled
    programs' temporaries under `peak_bytes_reserved`; peak HBM is about
    their sum."""
    import jax
    stats = jax.devices()[0].memory_stats()
    if not stats:
        return None
    return {k: int(stats.get(k, 0))
            for k in ("peak_bytes_in_use", "peak_bytes_reserved")}


def _falling(what: str, losses):
    _say(f"{what}: losses {[round(float(l), 4) for l in losses]}")
    if not all(np.isfinite(l) for l in losses):
        raise SmokeFailure(f"{what}: non-finite loss {losses}")
    if not losses[-1] < losses[0]:
        raise SmokeFailure(f"{what}: loss did not fall on a repeated batch "
                           f"({losses[0]} -> {losses[-1]})")


def _timed_steps(fit_one, score, n: int):
    """n fit steps, each synced by reading the score; returns (losses,
    first-call seconds, median later-step seconds)."""
    losses, times = [], []
    for _ in range(n):
        t0 = time.perf_counter()
        fit_one()
        losses.append(float(score()))
        times.append(time.perf_counter() - t0)
    later = sorted(times[1:]) or times
    return losses, times[0], later[len(later) // 2]


def _one_hot(idx: np.ndarray, n: int) -> np.ndarray:
    out = np.zeros(idx.shape + (n,), np.float32)
    np.put_along_axis(out, idx[..., None], 1.0, axis=-1)
    return out


# ---------------------------------------------------------------------------
# phase: device
# ---------------------------------------------------------------------------
def device_info() -> Dict:
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": jax.device_count()}


def result_line(ok: bool) -> str:
    """The last stdout line: one JSON object with exactly the keys "ok" and
    "device", the device exactly {"platform", "kind", "count"} as JAX reports
    it. The driver parses this line and refuses anything more or less."""
    return json.dumps({"ok": bool(ok), "device": device_info()})


def phase_device() -> Dict:
    from importlib import metadata

    import jax
    import jaxlib

    from deeplearning4j_tpu.kernels import pallas_supported
    from deeplearning4j_tpu.native import native_available

    info = device_info()
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "absent"
    _say(f"platform={info['platform']} device_kind={info['kind']} "
         f"device_count={info['count']} jax={jax.__version__} "
         f"jaxlib={jaxlib.__version__} libtpu={libtpu}")
    if info["platform"] != "tpu":
        raise NoChip(f"no TPU chip: jax.devices()[0].platform is "
                     f"'{info['platform']}' — chip_smoke.py proves nothing "
                     "off the chip")
    if not pallas_supported():
        raise SmokeFailure("DL4J_TPU_DISABLE_PALLAS is set: the kernel tier "
                           "is switched off, unset it")
    native = native_available()
    _say(f"native_available()={native}")
    return {"versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                         "libtpu": libtpu}, "native": native}


# ---------------------------------------------------------------------------
# phase: kernels
# ---------------------------------------------------------------------------
def phase_kernels(sizes: Sizes = FULL, interpret: bool = False) -> Dict:
    """Each Pallas kernel, forward and backward, against its oracle computed
    under default_matmul_precision("highest")."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.kernels.attention import (attention_reference,
                                                      flash_attention)
    from deeplearning4j_tpu.kernels.bn_relu import (bn_relu_reference,
                                                    fused_bn_relu)
    from deeplearning4j_tpu.kernels.lstm import fused_lstm_sequence
    from deeplearning4j_tpu.nn.layers.recurrent import _lstm_cell

    r = np.random.default_rng(0)
    worst = {}

    def run(what, kernel_fn, oracle_fn, args, names, tol):
        """value+grad of a scalar mix of the outputs, kernel vs oracle."""
        def scalar(fn):
            def f(*a):
                outs = jax.tree_util.tree_leaves(fn(*a))
                return sum(jnp.sum(o.astype(jnp.float32)
                                   * jnp.cos(jnp.arange(o.size, dtype=jnp.float32)
                                             ).reshape(o.shape))
                           for o in outs), outs
            return jax.jit(jax.value_and_grad(f, argnums=tuple(
                range(len(args))), has_aux=True))
        kfn = scalar(kernel_fn)
        _require_kernels(what, kfn.lower(*args).as_text(), names, interpret)
        (_, k_out), k_grad = kfn(*args)
        with jax.default_matmul_precision("highest"):
            (_, o_out), o_grad = scalar(oracle_fn)(*args)
        worst[what] = max(
            _check_close(f"{what} fwd", k_out, o_out, tol),
            _check_close(f"{what} bwd", k_grad, o_grad, tol))

    # Tolerances are max-abs error over the tensor's max-abs value. 2e-2
    # for every kernel with a matmul or a bf16 tensor: stored bf16 elements
    # carry 2^-8 rounding, and in-kernel f32 dots run as bf16 MXU passes,
    # so against a "highest" oracle they all measure 4e-3..6e-3 on the v5e
    # (CHANGES.md, PR 21) — a wrong mask, scale or gate is 1e-1 or more.
    # f32 bn_relu has neither and must agree to 1e-5 (measured 1.9e-7).
    matmul_tol = 2e-2
    for B, T, D, dt, causal in sizes.flash_shapes:
        q, k, v = (jnp.asarray(r.normal(size=(B, T, D)), dt)
                   for _ in range(3))
        run(f"flash[{B},{T},{D}] {dt} causal={causal}",
            lambda q, k, v: flash_attention(q, k, v, causal,
                                            interpret=interpret),
            lambda q, k, v: attention_reference(q, k, v, causal),
            (q, k, v), ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"),
            tol=matmul_tol)

    for N, C, dt in sizes.bn_shapes:
        x = jnp.asarray(r.normal(size=(N, C)) * 2.0 + 0.5, dt)
        g = jnp.asarray(r.uniform(0.5, 1.5, C), jnp.float32)
        b = jnp.asarray(r.normal(size=C) * 0.1, jnp.float32)
        run(f"bn_relu[{N},{C}] {dt}",
            lambda x, g, b: fused_bn_relu(x, g, b, interpret=interpret)[0],
            lambda x, g, b: bn_relu_reference(x, g, b)[0],
            (x, g, b), ("bn_relu_fwd", "bn_relu_bwd"),
            tol=matmul_tol if dt == "bfloat16" else 1e-5)

    def lstm_oracle(x, W, b, peep, h0, c0):
        step = lambda c, x_t: _lstm_cell(W, b, peep, h0.shape[-1], c, x_t,
                                         None, 1.0, jax.nn.sigmoid, jnp.tanh)
        (hT, cT), hs = jax.lax.scan(step, (h0, c0), x)
        return hs, hT, cT

    for T, B, F, H in sizes.lstm_shapes:
        f32 = lambda a: jnp.asarray(a, jnp.float32)
        args = (f32(r.normal(size=(T, B, F))),
                f32(r.normal(size=(F + H, 4 * H)) / np.sqrt(F + H)),
                f32(r.normal(size=4 * H) * 0.1),
                f32(r.normal(size=3 * H) * 0.1),
                f32(r.normal(size=(B, H)) * 0.1),
                f32(r.normal(size=(B, H)) * 0.1))
        run(f"lstm[T{T},B{B},F{F},H{H}] float32",
            lambda *a: fused_lstm_sequence(*a, 1.0, interpret),
            lstm_oracle, args, ("lstm_fwd", "lstm_bwd"), tol=matmul_tol)
    return {"impl": "pallas-interpret" if interpret else "pallas-mosaic",
            "max_rel_err": {k: float(f"{v:.3g}") for k, v in worst.items()}}


# ---------------------------------------------------------------------------
# phase: train (ResNet-50)
# ---------------------------------------------------------------------------
def _resnet(sizes: Sizes):
    from deeplearning4j_tpu.models.zoo import resnet50
    from deeplearning4j_tpu.nn.updaters import Adam

    return resnet50(n_classes=sizes.n_classes, image=sizes.image,
                    updater=Adam(1e-3, state_dtype="bfloat16"),
                    **sizes.resnet).init()


def _resnet_batch(sizes: Sizes):
    r = np.random.default_rng(0)
    x = r.normal(size=(sizes.train_batch, sizes.image, sizes.image, 3)
                 ).astype(np.float32)
    y = _one_hot(r.integers(0, sizes.n_classes, sizes.train_batch),
                 sizes.n_classes)
    return x, y


def phase_train(sizes: Sizes = FULL, interpret: bool = False) -> Dict:
    """zoo.resnet50 through ComputationGraph.fit per batch, then one
    fit_scan_arrays window (the whole window in one dispatch)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu import DataSet

    model = _resnet(sizes)
    x, y = _resnet_batch(sizes)
    ds = DataSet(x, y)
    losses, first_s, step_s = _timed_steps(
        lambda: model.fit(ds), model.score, sizes.fit_steps)
    _falling("resnet50 fit", losses)
    xd = jax.device_put(x.astype(jnp.bfloat16))
    xs = jnp.broadcast_to(xd, (sizes.scan_steps,) + xd.shape)
    ys = jnp.broadcast_to(jax.device_put(y), (sizes.scan_steps,) + y.shape)
    t0 = time.perf_counter()
    model.fit_scan_arrays(xs, ys)
    scan_loss = float(model.score())
    scan_first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    model.fit_scan_arrays(xs, ys)
    scan_loss2 = float(model.score())
    scan_s = time.perf_counter() - t0
    _falling("resnet50 fit -> scan windows", [losses[0], scan_loss,
                                               scan_loss2])
    out = {"impl": "BatchNormalization: XLA fused custom-vjp on 4-D "
                   "activations (the Pallas bn_relu serves 2-D inputs only)",
           "batch": sizes.train_batch, "losses": losses,
           "first_step_s": round(first_s, 2), "step_s": round(step_s, 4),
           "scan_first_s": round(scan_first_s, 2),
           "scan_step_s": round(scan_s / sizes.scan_steps, 4),
           "hbm": _hbm_peaks()}
    _say(f"resnet50 b{sizes.train_batch}: first fit {first_s:.1f}s, later "
         f"{step_s * 1e3:.0f} ms/step; scan window first {scan_first_s:.1f}s"
         f", then {scan_s / sizes.scan_steps * 1e3:.0f} ms/step; "
         f"memory_stats {out['hbm']}")
    return out


# ---------------------------------------------------------------------------
# phase: lm (GPT-2 124M sizes): train on the flash kernel, then serve
# ---------------------------------------------------------------------------
def _lm(sizes: Sizes):
    from deeplearning4j_tpu import (Adam, EmbeddingSequenceLayer, InputType,
                                    MultiLayerNetwork,
                                    NeuralNetConfiguration, RnnOutputLayer,
                                    TransformerBlock)

    b = (NeuralNetConfiguration.builder().seed(7).updater(Adam(3e-4))
         .compute_dtype("bfloat16").list()
         .layer(EmbeddingSequenceLayer(n_in=sizes.vocab, n_out=sizes.width)))
    for _ in range(sizes.blocks):
        b = b.layer(TransformerBlock(n_heads=sizes.heads))   # ffn 4x, gelu
    conf = (b.layer(RnnOutputLayer(n_out=sizes.vocab, activation="softmax",
                                   loss="mcxent"))
            .set_input_type(InputType.recurrent(1, sizes.context)).build())
    return MultiLayerNetwork(conf).init()


def _lm_batch(sizes: Sizes):
    """int32 tokens [B, T, 1] (float tokens would be rounded by the bf16
    input cast above id 256) and one-hot f32 next-token labels [B, T, V]."""
    r = np.random.default_rng(1)
    idx = r.integers(0, sizes.vocab, (sizes.lm_batch, sizes.context))
    return (idx[..., None].astype(np.int32),
            _one_hot(np.roll(idx, -1, axis=1), sizes.vocab))


def phase_lm(sizes: Sizes = FULL, interpret: bool = False) -> Dict:
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu import DataSet

    model = _lm(sizes)
    ds = DataSet(*_lm_batch(sizes))
    xd, yd, _, _ = ds.device_tuple()
    # (a) train: flash="auto" must have put the kernel into the step, not
    # attention_reference
    text = model._train_step.__wrapped__.lower(
        model.params, model.state, model.updater_state,
        jnp.asarray(0, jnp.int32), xd, yd, jax.random.PRNGKey(0), None,
        None).as_text()
    found = _require_kernels(
        "lm train step", text,
        ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"), interpret)
    losses, first_s, step_s = _timed_steps(
        lambda: model.fit(ds), model.score, sizes.lm_steps)
    _falling("lm fit", losses)
    hbm = _hbm_peaks()
    _say(f"lm B={sizes.lm_batch} T={sizes.context} V={sizes.vocab}: first "
         f"fit {first_s:.1f}s, later {step_s * 1e3:.0f} ms/step; "
         f"memory_stats {hbm} (monotonic: the train phase ran first)")
    del ds, xd, yd, text
    gc.collect()
    serve = _serve_lm(model, sizes)
    return {"impl": {"train_attention":
                     "pallas flash (Mosaic, heads folded into batch)" if found
                     else "attention_reference (einsum)",
                     "decode_attention": serve.pop("decode_attention")},
            "batch": sizes.lm_batch, "losses": losses,
            "first_step_s": round(first_s, 2), "step_s": round(step_s, 4),
            "hbm": hbm, **serve}


def _post(url: str, body: Dict, timeout: float = 900.0) -> Dict:
    req = urllib.request.Request(
        url, json.dumps(body).encode(), {"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def _serve_lm(model, sizes: Sizes) -> Dict:
    """zip -> registry -> HTTP /generate -> /metrics -> stop, then the same
    prefill/tick executables called directly against the full forward."""
    from deeplearning4j_tpu.serving import InferenceServer, ModelRegistry
    from deeplearning4j_tpu.util.serializer import ModelSerializer

    name = "gpt2"
    r = np.random.default_rng(2)
    n, gen = sizes.prompt_len, sizes.gen_tokens
    with tempfile.TemporaryDirectory() as tmp:
        path = f"{tmp}/{name}.zip"
        t0 = time.perf_counter()
        ModelSerializer.write_model(model, path, save_updater=False)
        registry = ModelRegistry()
        registry.register(name, path, buckets=(1,))
        _say(f"serve: zip written and registered in "
             f"{time.perf_counter() - t0:.1f}s")
    srv = InferenceServer(registry).start()
    try:
        sched = srv.enable_generation(name)
        base = f"http://{srv.host}:{srv.port}"
        url = f"{base}/v1/models/{name}/generate"
        replies, errors = [], []
        prompts = [r.integers(0, sizes.vocab, m).tolist()
                   for m in (n, n + 3, n - 5, n - 2, n + 7)]

        def ask(prompt):
            try:
                replies.append(_post(url, {"prompt": prompt,
                                           "max_tokens": gen}))
            except Exception as e:      # noqa: BLE001 - counted, reported
                errors.append(f"{type(e).__name__}: {e}")

        t0 = time.perf_counter()
        ask(prompts[0])                         # compiles prefill + tick b1
        first_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        for pair in (prompts[1:3], prompts[3:5]):
            threads = [threading.Thread(target=ask, args=(p,))
                       for p in pair]           # concurrent: a batched tick
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=900)
        rest_s = time.perf_counter() - t0
        if errors or len(replies) != 5:
            raise SmokeFailure(f"/generate: {len(errors)} failed requests "
                               f"of 5: {errors[:2]}")
        for rep in replies:
            toks = rep["tokens"]
            if (len(toks) != gen or rep["finish_reason"] != "length"
                    or not all(0 <= t < sizes.vocab for t in toks)):
                raise SmokeFailure(f"/generate: bad reply {rep}")
        with urllib.request.urlopen(f"{base}/metrics", timeout=60) as resp:
            metrics = resp.read().decode()
        m = re.search(r'dl4j_decode_tokens_total\{[^}]*\} (\S+)', metrics)
        if not m or float(m.group(1)) < 5 * gen:
            raise SmokeFailure("/metrics: dl4j_decode_tokens_total missing "
                               f"or short of {5 * gen}")
        _say(f"serve: 5/5 /generate ok (prompts ~{n}, {gen} tokens each; "
             f"first incl. compiles {first_s:.1f}s, other four "
             f"{rest_s:.1f}s); dl4j_decode_tokens_total={m.group(1)}")

        # numerical check, same executables: prefill n tokens, then
        # teacher-forced ticks, against output() on the whole sequence
        eng, v = sched.engine, registry.get(name)
        ticks = sizes.check_ticks
        seq = r.integers(0, sizes.vocab, n + ticks)
        full = np.zeros((1, sizes.context, 1), np.int32)
        full[0, :n + ticks, 0] = seq
        probs = np.asarray(model.output(full), np.float32)[0]
        want = np.log(np.maximum(probs, 1e-30))       # log-softmax rows
        pool = eng.new_pool()
        blocks = pool.alloc(eng.spec.blocks_for(n + ticks))
        got = [eng.run_prefill(v, pool, seq[:n].tolist(), blocks)]
        for i in range(ticks - 1):
            got.append(eng.run_tick(v, pool, [int(seq[n + i])], [n + i],
                                    [blocks], bucket=1)[0])
        # and once more by a fresh prefill of the same tokens: the last
        # tick read its keys through the block table (the paged kernel on
        # the TPU, _gather's view elsewhere), the prefill uses its local
        # projections
        blocks2 = pool.alloc(eng.spec.blocks_for(n + ticks))
        again = eng.run_prefill(v, pool, seq[:n + ticks - 1].tolist(),
                                blocks2)
        pool.release(blocks + blocks2)

        def logp(z):
            z = z - z.max(-1, keepdims=True)
            return z - np.log(np.sum(np.exp(z), -1, keepdims=True))

        got = logp(np.stack(got))
        rows = want[n - 1:n - 1 + ticks]
        diff = float(np.max(np.abs(got - rows)))
        spread = float(np.std(rows))
        cache_diff = float(np.max(np.abs(got[-1] - logp(again))))
        # Tolerances are fractions of the rows' own spread, because a
        # wrong position, table or cache slot moves a row by that spread.
        # vs output(), a quarter: the full forward runs the bf16 compute
        # policy, the decode plane f32 weights at the MXU's default
        # (bf16-pass) precision (0.02 of the spread on the v5e at full
        # size, 0.10 at the tests' width 32). Tick vs prefill, a tenth:
        # the same f32 math re-rounded to bf16 along another summation
        # order (0.01 on the v5e, 1e-6 on the CPU).
        _say(f"serve: prefill+{ticks - 1} ticks vs output(): max |dlogp| "
             f"{diff:.3e} nat (tol 0.25 x logp std {spread:.3f}); last tick "
             f"vs fresh prefill {cache_diff:.3e} nat (tol 0.1 x)")
        if not diff <= 0.25 * spread:
            raise SmokeFailure(f"decode logp drift {diff} nat > 0.25 x "
                               f"{spread}")
        if not cache_diff <= 0.1 * spread:
            raise SmokeFailure(f"tick vs prefill differ by {cache_diff} nat "
                               f"> 0.1 x {spread}")
    finally:
        srv.stop()
    leaked = [t.name for t in threading.enumerate()
              if t.name.startswith(("dl4j-serving", "dl4j-decode"))]
    if leaked:
        raise SmokeFailure(f"server threads alive after stop(): {leaked}")
    return {"requests": 5, "failed": 0, "context": n + gen,
            "serve_first_s": round(first_s, 2),
            "decode_attention": eng.attention,
            "decode_max_dlogp": float(f"{diff:.3g}")}


# ---------------------------------------------------------------------------
# phase: char-rnn
# ---------------------------------------------------------------------------
def phase_char_rnn(sizes: Sizes = FULL, interpret: bool = False) -> Dict:
    """zoo.char_rnn through fit (TBPTT chunks); on the
    chip GravesLSTM._helper must have picked the fused kernel."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu import DataSet
    from deeplearning4j_tpu.models.zoo import char_rnn

    model = char_rnn(vocab_size=sizes.rnn_vocab, lstm_size=sizes.rnn_hidden,
                     seq_len=sizes.rnn_seq, tbptt=sizes.rnn_tbptt).init()
    r = np.random.default_rng(3)
    idx = r.integers(0, sizes.rnn_vocab, (sizes.rnn_batch, sizes.rnn_seq))
    x = _one_hot(idx, sizes.rnn_vocab)
    y = _one_hot(np.roll(idx, -1, axis=1), sizes.rnn_vocab)
    ds = DataSet(x, y)
    xd, yd, _, _ = ds.device_tuple()
    L = sizes.rnn_tbptt
    text = model._tbptt_step.__wrapped__.lower(
        model.params, model.state, model.updater_state,
        jnp.asarray(0, jnp.int32), xd[:, :L], yd[:, :L],
        jax.random.PRNGKey(0), None, None,
        model._zero_carries(sizes.rnn_batch, xd.dtype)).as_text()
    found = _require_kernels("char-rnn tbptt step", text,
                             ("lstm_fwd", "lstm_bwd"), interpret)
    losses, first_s, step_s = _timed_steps(
        lambda: model.fit(ds), model.score, sizes.rnn_steps)
    _falling("char-rnn fit", losses)
    _say(f"char-rnn b{sizes.rnn_batch} seq{sizes.rnn_seq}: first fit "
         f"{first_s:.1f}s, later {step_s * 1e3:.0f} ms/fit")
    return {"impl": "pallas fused LSTM (Mosaic)" if found
            else "lax.scan _lstm_cell",
            "losses": losses, "first_step_s": round(first_s, 2),
            "step_s": round(step_s, 4)}


# ---------------------------------------------------------------------------
# phase: mesh (four chips)
# ---------------------------------------------------------------------------
def _check_spread(what: str, trainer, devices, interpret: bool):
    import jax
    want = set(devices)
    for leaf in jax.tree_util.tree_leaves(trainer._params):
        if set(leaf.sharding.device_set) != want:
            raise SmokeFailure(f"{what}: a parameter lives on "
                               f"{leaf.sharding.device_set}, not all of "
                               f"{want}")
    used = []
    for d in devices:
        stats = d.memory_stats()
        used.append(None if not stats else int(stats["bytes_in_use"]))
    _say(f"{what}: bytes_in_use per device {used}")
    if not interpret and not all(used):
        raise SmokeFailure(f"{what}: a device holds no bytes: {used}")
    return used


def phase_mesh(sizes: Sizes = FULL, interpret: bool = False,
               ref_losses: Optional[Dict] = None) -> Dict:
    """ResNet-50 SYNC data-parallel on {"data": 4}; the LM under
    mesh_shape (2, 2) zero1_tp with flash under shard_map. Losses of the
    first two steps must match the one-chip phases' (same seed, same data,
    same global batch)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu import DataSet
    from deeplearning4j_tpu.parallel import (ParallelTrainer, TrainingMode,
                                             make_mesh)

    devices = jax.devices()[:4]
    out = {}

    def two_steps(what, trainer, ds, ref):
        losses = []
        for _ in range(2):
            trainer.fit(ds)
            losses.append(float(trainer.score()))
        _say(f"{what}: losses {losses} (one chip: {ref})")
        if not all(np.isfinite(l) for l in losses):
            raise SmokeFailure(f"{what}: non-finite loss {losses}")
        # 2e-2 relative: same math in bf16 with the batch reductions
        # split four ways — reassociation, nothing else
        if ref is not None and not np.allclose(losses, ref[:2], rtol=2e-2):
            raise SmokeFailure(f"{what}: losses {losses} differ from the "
                               f"one-chip run {ref[:2]} beyond 2e-2")
        return losses

    # (a) ResNet-50, data parallel
    model = _resnet(sizes)
    tr = ParallelTrainer(model, mesh=make_mesh({"data": 4}, devices=devices),
                         mode=TrainingMode.SYNC)
    losses = two_steps("mesh resnet50 dp4", tr, DataSet(*_resnet_batch(sizes)),
                       (ref_losses or {}).get("train"))
    out["resnet50_dp4"] = {
        "losses": losses,
        "bytes_in_use": _check_spread("mesh resnet50 dp4", tr, devices,
                                      interpret)}
    del tr, model
    gc.collect()

    # (b) the LM, ZeRO-1 x tensor parallel on (2, 2)
    model = _lm(sizes)
    ds = DataSet(*_lm_batch(sizes))
    tr = ParallelTrainer(model, mesh_shape=(2, 2), strategy="zero1_tp",
                         flash="spmd" if interpret else None)
    _say(f"mesh lm zero1_tp (2,2): flash_mode={tr.flash_mode!r}")
    if tr.flash_mode != "spmd":
        raise SmokeFailure("configure_flash_attention selected "
                           f"{tr.flash_mode!r}, not 'spmd'")
    xd, yd, fm, lm = tr._to_batch(ds)
    text = tr._step_fn.__wrapped__.lower(
        tr._params, tr._state, tr._opt, jnp.asarray(0, jnp.int32), xd, yd,
        jax.random.PRNGKey(0), fm, lm).as_text()
    _require_kernels("mesh lm zero1_tp step", text,
                     ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"),
                     interpret)
    losses = two_steps("mesh lm zero1_tp (2,2)", tr, ds,
                       (ref_losses or {}).get("lm"))
    out["lm_zero1_tp_2x2"] = {
        "flash_mode": tr.flash_mode, "losses": losses,
        "bytes_in_use": _check_spread("mesh lm zero1_tp", tr, devices,
                                      interpret)}
    return out


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------
class _CompileClock:
    """Sums JAX's own compile-path durations (trace, lowering, backend
    compile or cache retrieval) and counts persistent-cache hits and
    writes (JAX's `cache_misses` event fires when an entry is written,
    i.e. after a compile of at least a second that was not found) —
    jax.monitoring listeners, process-wide, so other threads' compiles
    (the decode scheduler's) are counted too. Nested traces are counted
    twice, so the sum can exceed the wall clock by a little."""

    def __init__(self):
        import jax
        self.compile_s = 0.0
        self.backend_s = 0.0
        self.hits = self.writes = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.compile_s += duration
            if event == _BACKEND_COMPILE:
                self.backend_s += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1

    def snapshot(self):
        return self.compile_s, self.backend_s, self.hits, self.writes


def main() -> int:
    from deeplearning4j_tpu.util.platform import enable_compilation_cache

    cache_dir = enable_compilation_cache()      # before the first compile
    clock = _CompileClock()
    _say(f"compile cache: {cache_dir}")
    phases: List[Dict] = []
    results: Dict[str, Dict] = {}

    def run(name, fn, *args, **kw) -> bool:
        c0, b0, h0, w0 = clock.snapshot()
        t0 = time.perf_counter()
        rec = {"phase": name, "result": "ok"}
        try:
            results[name] = rec["detail"] = fn(*args, **kw)
        except NoChip:
            raise
        except Exception as e:      # noqa: BLE001 - recorded, run stops
            import traceback
            traceback.print_exc()
            rec["result"] = f"failed: {type(e).__name__}: {e}"[:2000]
        wall = time.perf_counter() - t0
        c1, b1, h1, w1 = clock.snapshot()
        rec.update(compile_s=round(c1 - c0, 2),
                   backend_compile_s=round(b1 - b0, 2),
                   run_s=round(max(0.0, wall - (c1 - c0)), 2),
                   cache_hits=h1 - h0, cache_writes=w1 - w0)
        phases.append(rec)
        _say(f"phase {name}: {rec['result']} — compile {rec['compile_s']}s "
             f"(backend {rec['backend_compile_s']}s, cache hits "
             f"{rec['cache_hits']} writes {rec['cache_writes']}), run "
             f"{rec['run_s']}s")
        return rec["result"] == "ok"

    try:
        ok = run("device", phase_device)
    except NoChip as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 2
    import jax
    for name, fn in (("kernels", phase_kernels), ("train", phase_train),
                     ("lm", phase_lm), ("char-rnn", phase_char_rnn)):
        ok = ok and run(name, fn, FULL, False)
        gc.collect()
    if ok and jax.device_count() >= 4:
        ok = run("mesh", phase_mesh, FULL, False,
                 {"train": results["train"]["losses"],
                  "lm": results["lm"]["losses"]})
    elif ok:
        _say(f"phase mesh: did not run — it needs 4 devices and "
             f"jax.device_count() is {jax.device_count()}")
        phases.append({"phase": "mesh", "result": "not run: "
                       f"{jax.device_count()} device(s), needs 4"})
    print(json.dumps({"phases": phases, "cache_dir": cache_dir}))
    print(result_line(ok), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
