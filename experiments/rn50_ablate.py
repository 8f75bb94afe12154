"""ResNet-50 traffic-cutting ablation harness (round 4, VERDICT #1).

Measures the framework's OWN ComputationGraph train step (zoo.resnet50,
b256/224^2 bf16+f32-master, Adam) under candidate traffic-reduction levers:

  * window variants: scanned fresh-batch reads (current bench) vs a
    keys-only scan (pure device step time, no input re-reads)
  * activation remat: None | blocks | layer | full (jax.checkpoint)
  * stored-input dtype: f32 vs bf16 scan window
  * optimizer-state dtype (Adam m/v)

Run one variant per process (XLA flag sweeps need a fresh process):
    python -m experiments.rn50_ablate <variant> [--steps N] [--reps R]

Prints one JSON line per run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

# script lives in repo/experiments/; make the package importable without
# touching PYTHONPATH
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build(remat=None, updater=None, store=None):
    from deeplearning4j_tpu.models.zoo import resnet50
    return resnet50(remat=remat, updater=updater,
                    activation_store_dtype=store).init()


def data(batch, image, classes, dtype):
    r = np.random.default_rng(0)
    x = r.normal(size=(batch, image, image, 3)).astype(np.float32)
    y = np.eye(classes, dtype=np.float32)[r.integers(0, classes, batch)]
    if dtype == "bfloat16":
        import jax.numpy as jnp
        x = x.astype(jnp.bfloat16)
    return x, y


def bench_scan_window(model, x, y, steps, reps):
    """Current-bench shape: xs [T,...] scanned (fresh batch read per step),
    whole window one dispatch."""
    import jax
    import jax.numpy as jnp
    xs = jnp.broadcast_to(jax.device_put(x), (steps,) + x.shape)
    ys = jnp.broadcast_to(jax.device_put(y), (steps,) + y.shape)
    model.fit_scan_arrays(xs, ys)
    float(model.score())
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        model.fit_scan_arrays(xs, ys)
        float(model.score())
        times.append(time.perf_counter() - t0)
    return min(times) / steps


def bench_keys_only(model, x, y, steps, reps, compiler_options=None):
    """Pure device step time: one batch carried as a scan invariant, scan
    over rng keys only. Params still update each step (no constant
    folding); removes the per-step input HBM read and leaves one
    dispatch per window."""
    import jax
    import jax.numpy as jnp

    step_fn = model.train_step_fn
    in_name = model.conf.network_inputs[0]
    out_name = model.conf.network_outputs[0]
    x = jax.device_put(jnp.asarray(x))
    y = jax.device_put(jnp.asarray(y))

    def epoch(params, state, opt, step0, keys, x, y):
        def body(carry, k):
            params, state, opt, step = carry
            params, state, opt, score = step_fn(
                params, state, opt, step, {in_name: x}, {out_name: y}, k,
                None, None)
            return (params, state, opt, step + 1), score
        (params, state, opt, _), scores = jax.lax.scan(
            body, (params, state, opt, step0), keys)
        return params, state, opt, scores

    epoch = jax.jit(epoch, compiler_options=compiler_options)

    import jax.numpy as jnp
    p, s, o = model.params, model.state, model.updater_state
    keys = jax.random.split(jax.random.PRNGKey(0), steps)
    step0 = jnp.asarray(0, jnp.int32)
    p, s, o, scores = epoch(p, s, o, step0, keys, x, y)
    float(scores[-1])
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        p, s, o, scores = epoch(p, s, o, step0, keys, x, y)
        float(scores[-1])
        times.append(time.perf_counter() - t0)
    return min(times) / steps


VARIANTS = {}


def variant(name):
    def deco(fn):
        VARIANTS[name] = fn
        return fn
    return deco


@variant("base20")
def _base20(a):
    m = build()
    x, y = data(a.batch, a.image, a.classes, "float32")
    return bench_scan_window(m, x, y, 20, a.reps)


@variant("window_bf16")
def _window_bf16(a):
    m = build()
    x, y = data(a.batch, a.image, a.classes, "bfloat16")
    return bench_scan_window(m, x, y, a.steps, a.reps)


@variant("keys")
def _keys(a):
    m = build()
    x, y = data(a.batch, a.image, a.classes, "bfloat16")
    return bench_keys_only(m, x, y, a.steps, a.reps)


@variant("keys_remat_blocks")
def _keys_rb(a):
    m = build(remat="blocks")
    x, y = data(a.batch, a.image, a.classes, "bfloat16")
    return bench_keys_only(m, x, y, a.steps, a.reps)


@variant("keys_remat_layer")
def _keys_rl(a):
    m = build(remat="layer")
    x, y = data(a.batch, a.image, a.classes, "bfloat16")
    return bench_keys_only(m, x, y, a.steps, a.reps)


@variant("keys_remat_full")
def _keys_rf(a):
    m = build(remat="full")
    x, y = data(a.batch, a.image, a.classes, "bfloat16")
    return bench_keys_only(m, x, y, a.steps, a.reps)


@variant("keys_adam_bf16")
def _keys_adam16(a):
    from deeplearning4j_tpu.nn.updaters import Adam
    m = build(updater=Adam(1e-3, state_dtype="bfloat16"))
    x, y = data(a.batch, a.image, a.classes, "bfloat16")
    return bench_keys_only(m, x, y, a.steps, a.reps)


@variant("keys_store_f8")
def _keys_store_f8(a):
    m = build(store="float8_e4m3fn")
    x, y = data(a.batch, a.image, a.classes, "bfloat16")
    return bench_keys_only(m, x, y, a.steps, a.reps)


@variant("keys_vmem64")
def _keys_vmem64(a):
    m = build()
    x, y = data(a.batch, a.image, a.classes, "bfloat16")
    return bench_keys_only(m, x, y, a.steps, a.reps, compiler_options={
        "xla_tpu_scoped_vmem_limit_kib": "65536"})


@variant("keys_vmem96")
def _keys_vmem96(a):
    m = build()
    x, y = data(a.batch, a.image, a.classes, "bfloat16")
    return bench_keys_only(m, x, y, a.steps, a.reps, compiler_options={
        "xla_tpu_scoped_vmem_limit_kib": "98304"})


@variant("keys_lhs")
def _keys_lhs(a):
    m = build()
    x, y = data(a.batch, a.image, a.classes, "bfloat16")
    return bench_keys_only(m, x, y, a.steps, a.reps, compiler_options={
        "xla_tpu_enable_latency_hiding_scheduler": "true"})


@variant("keys_adam16_lhs")
def _keys_adam16_lhs(a):
    from deeplearning4j_tpu.nn.updaters import Adam
    m = build(updater=Adam(1e-3, state_dtype="bfloat16"))
    x, y = data(a.batch, a.image, a.classes, "bfloat16")
    return bench_keys_only(m, x, y, a.steps, a.reps, compiler_options={
        "xla_tpu_enable_latency_hiding_scheduler": "true"})


@variant("keys_f8_vmem64")
def _keys_f8_vmem64(a):
    m = build(store="float8_e4m3fn")
    x, y = data(a.batch, a.image, a.classes, "bfloat16")
    return bench_keys_only(m, x, y, a.steps, a.reps, compiler_options={
        "xla_tpu_scoped_vmem_limit_kib": "65536"})


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("variant", choices=sorted(VARIANTS))
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--image", type=int, default=224)
    ap.add_argument("--classes", type=int, default=1000)
    a = ap.parse_args()
    step_time = VARIANTS[a.variant](a)
    print(json.dumps({
        "variant": a.variant,
        "step_ms": round(step_time * 1e3, 2),
        "samples_per_sec": round(a.batch / step_time, 1),
        "steps": a.steps, "reps": a.reps,
    }))


if __name__ == "__main__":
    main()
