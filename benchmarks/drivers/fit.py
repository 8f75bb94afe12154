"""Driver `fit`: a trainer that calls `model.fit(DataSet)` step after step and
reads the score every Nth step, as a ScoreIterationListener(N) user does.

Traffic parameters (the cell file's "traffic"): batch, seq_len, ring (how many
device-resident batches are cycled), score_every. Token ids are uniform over
the published vocabulary, from the seed; labels are the next token, one-hot
float32 [B, T, V], because `mcxent` takes nothing else.

The window opens at a score read and closes at the first score read after
`--seconds`: every step in it is complete on the device when the clock stops.
"""
from __future__ import annotations

import gc
import time

import numpy as np


def make_tokens(config: dict, traffic: dict, seed: int):
    """(tokens, targets), int32 [ring, batch, seq_len]: the rows all differ."""
    rng = np.random.default_rng([int(seed), 1])
    tok = rng.integers(0, int(config["vocab_size"]),
                       (int(traffic["ring"]), int(traffic["batch"]),
                        int(traffic["seq_len"])), dtype=np.int32)
    return tok, np.roll(tok, -1, axis=2)


class _ScoreReads:
    """The program's ScoreIterationListener, with the time of every read
    kept and the read put under a span of its own in a traced window."""

    def __init__(self, every: int):
        from deeplearning4j_tpu.optimize.listeners import ScoreIterationListener
        self.times = []
        self.inner = ScoreIterationListener(
            every, printer=lambda s: self.times.append(time.perf_counter()))

    def iteration_done(self, model, iteration):
        import jax
        with jax.profiler.TraceAnnotation("bench/score_read"):
            self.inner.iteration_done(model, iteration)


def _steps_until(model, ring, reads, deadline) -> int:
    """fit until the first score read at or after `deadline`; steps made."""
    import jax
    n = 0
    while True:
        seen = len(reads.times)
        with jax.profiler.TraceAnnotation("bench/fit"):
            model.fit(ring[model.iteration_count % len(ring)])
        n += 1
        if len(reads.times) > seen and reads.times[-1] >= deadline:
            return n


def _gaps(cmp_, program: dict, want: dict) -> list:
    """The cell's three numbers, program (or control) against reference."""
    grad_gap, g_at = cmp_.norm_gap(program["grad_norms"], want["grad_norms"])
    upd_gap, u_at = cmp_.norm_gap(
        program["change_norms"], want["change_norms"],
        leave_out=cmp_.unmoved_leaves(want["grad_norms"]))
    print(f"[fit] worst leaves: gradient {want['names'][g_at]}, change "
          f"{want['names'][u_at]}", flush=True)
    return [("loss_gap", cmp_.loss_gap(program["losses"], want["losses"])),
            ("grad_norm_gap", grad_gap), ("update_norm_gap", upd_gap)]


def _reference_kw(cell: dict, config: dict) -> dict:
    return dict(steps=int(cell["check"]["steps"]),
                adam=config["updater"]["adam"],
                block_rows=int(cell["check"]["reference_block_rows"]))


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu import DataSet

    cell, config, ref = ctx.cell, ctx.config, ctx.reference
    traffic, check = cell["traffic"], cell["check"]
    every = int(traffic["score_every"])
    vocab_rows = int(config["assumed"]["padded_vocab_size"])
    adam = config["updater"]["adam"]

    model = ctx.model.build(config, ctx.seed, ref, train=True)
    tokens, targets = make_tokens(config, traffic, ctx.seed)
    one_hot = jax.jit(lambda t: jax.nn.one_hot(t, vocab_rows, dtype=jnp.float32))
    ring = [DataSet(jnp.asarray(tokens[i][..., None]), one_hot(targets[i]))
            for i in range(tokens.shape[0])]

    # the first steps, through the window's own call and feed; what the
    # reference will be held against is read here, before later steps
    # overwrite it
    program = {"losses": []}
    for s in range(int(check["steps"])):
        model.fit(ring[model.iteration_count % len(ring)])
        program["losses"].append(float(model.score()))
        if s == 0:
            # the gradient as Adam got it: its first moment is (1 - beta1) g
            program["grad_norms"] = [
                x / (1.0 - adam["beta1"]) for x in ref.leaf_norms(
                    tuple(u["m"] for u in model.updater_state))]
    program["change_norms"] = ref.diff_norms(
        model.params, ref.init_params(config, ctx.seed))
    print(f"[fit] first losses {program['losses']}", flush=True)

    reads = _ScoreReads(every)
    model.listeners.append(reads)
    _steps_until(model, ring, reads, 0.0)           # to a multiple of `every`
    _steps_until(model, ring, reads, 0.0)           # one whole warm group
    compiles = ctx.compiles.count

    t_open = reads.times[-1]
    first_read = len(reads.times)
    steps = _steps_until(model, ring, reads, t_open + ctx.seconds)
    t_close = reads.times[-1]
    window_compiles = ctx.compiles.count - compiles
    marks = [t_open] + reads.times[first_read:]
    facts = {
        "steps": steps, "window_s": t_close - t_open,
        "tokens_per_step": int(traffic["batch"]) * int(traffic["seq_len"]),
        "batch": int(traffic["batch"]), "seq_len": int(traffic["seq_len"]),
        "group_step_ms": [(b - a) * 1e3 / every
                          for a, b in zip(marks, marks[1:])],
        "compiles_in_window": window_compiles,
    }
    print(f"[fit] window {facts['window_s']:.3f}s, {steps} steps, "
          f"{window_compiles} compiles inside it; ms/step by group "
          f"{[round(g, 1) for g in facts['group_step_ms']]}", flush=True)

    trace_dir = None
    if ctx.trace:
        trace_dir = ctx.trace_dir()
        jax.profiler.start_trace(trace_dir)
        try:
            with jax.profiler.TraceAnnotation("bench/window"):
                t0 = time.perf_counter()
                facts["trace_steps"] = _steps_until(
                    model, ring, reads, t0 + float(cell["trace_seconds"]))
        finally:
            jax.profiler.stop_trace()

    memory = ctx.device.memory_stats()
    peak = ctx.device.memory_peak_bytes()
    model.listeners.remove(reads)
    del model, ring, one_hot
    gc.collect()

    t0 = time.perf_counter()
    want = ref.train_readings(config, ctx.seed, tokens, targets,
                              **_reference_kw(cell, config))
    print(f"[fit] reference {time.perf_counter() - t0:.1f}s, losses "
          f"{want['losses']}", flush=True)
    limits = check["limits"]
    checks = [(n, v, limits[n]) for n, v in _gaps(ctx.compare, program, want)]
    return {
        "attempted": steps, "failed": 0,
        "t_open": t_open,
        "end_to_end": {"train_step_ms": facts["window_s"] * 1e3 / steps},
        "facts": facts, "trace_dir": trace_dir, "checks": checks,
        "memory_peak_bytes": peak, "memory_stats": memory,
    }


def reference_controls(ctx) -> dict:
    """For calibrate.py and the tests, never a run of the benchmark: the
    reference put in the program's place, once in the precision below the
    configuration's and once with half of the batch left out. Each control's
    three numbers beside the cell's limits."""
    cell, config, ref = ctx.cell, ctx.config, ctx.reference
    tokens, targets = make_tokens(config, cell["traffic"], ctx.seed)
    kw = _reference_kw(cell, config)
    want = ref.train_readings(config, ctx.seed, tokens, targets, **kw)
    planted = {config["precision"]["control"]:
               {"precision": config["precision"]["control"]},
               "half_batch": {"rows_used": int(cell["traffic"]["batch"]) // 2}}
    limits = cell["check"]["limits"]
    return {name: [(n, v, limits[n]) for n, v in _gaps(
                ctx.compare,
                ref.train_readings(config, ctx.seed, tokens, targets, **kw, **how),
                want)]
            for name, how in planted.items()}
