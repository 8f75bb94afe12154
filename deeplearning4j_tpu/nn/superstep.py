"""Device-resident supersteps: one training loop at fit_scan speed.

An earlier installation's capture (BASELINE.md) found the per-batch
``fit()`` path of a small model bound by per-batch host dispatch where
the device-resident ``fit_scan`` path was not (not measured on the
current chip). The superstep removes those dispatches without forking
the API: ``fit(..., superstep=K)`` groups the iterator's batches into
on-device windows of K and runs each window as ONE jitted ``lax.scan``
of the train step, so the host pays one dispatch per K batches instead
of one per batch.

Per-batch API semantics are preserved:

  * **Bit-exactness.** The scan body threads the model's RNG key through
    the same ``jax.random.split`` chain the per-batch loop draws
    host-side, and the step counter increments inside the scan — a
    ``superstep=K`` fit produces bit-identical params, updater state and
    RNG to the ``superstep=1`` per-batch fit, for ANY window grouping
    (windows are a pure regrouping of the identical per-batch math).
  * **Ragged tails.** Windows never mix batch signatures: a ragged final
    batch (or a ``time_buckets`` signature change) simply closes the
    current window and opens a new one. ``pad_ragged=True`` keeps the
    whole epoch to one signature exactly as on the per-batch path.
  * **Listeners** replay at the superstep edge with the
    already-transferred per-window loss vector: every ``iteration_done``
    sees a HOST scalar in ``model._score``, so score-reading listeners
    cost no device sync (and per-iteration param histograms see
    end-of-window params — the same ``warn_scan_replay`` caveat as
    ``fit_scan``).
  * **TrainingGuard** checks the window's K losses at the superstep edge
    (``guard.check_scores``); skip_batch/rollback restore the
    pre-superstep snapshot, so a poisoned window never escapes.
  * **Checkpoints / SIGTERM** fire at superstep edges via
    ``FitCheckpointer.on_batches`` — the first boundary where model state
    and the recorded batch cursor agree. Resume composes with any K: a
    checkpoint at a non-window-aligned batch ordinal resumes bit-exactly
    because window grouping does not change the math.

Overlap: when neither a guard nor a checkpointer needs the model state at
window boundaries, the loop runs PIPELINED — the next window is drawn,
stacked and transferred (``datasets/pipeline.py`` staging) while the
current superstep computes on device, and the loss sync for window i
happens after window i+1 has been dispatched. The device never waits on
host batch assembly.

Gradient accumulation (ISSUE 12): ``fit(..., grad_accumulation=M)`` runs
M consecutive iterator microbatches per OPTIMIZER step — forward/backward
per microbatch, gradients summed in fp32 accumulators, ONE update on the
mean — so the effective batch is M·b with activation memory for b.
Composes with supersteps: a window holds K·M microbatches scanned as a
nested ``lax.scan`` (outer K optimizer steps, inner M microbatches), and
``superstep="auto"`` is now overlap-aware — the byte budget seeds K, then
``OverlapAutoK`` grows it from the measured dispatch/compute ratio.
Listeners, guard checks and ``iteration_count`` operate per optimizer
step; the checkpoint batch cursor keeps counting iterator microbatches
and only ever lands on optimizer-step boundaries (window edges).
"""
from __future__ import annotations

import logging
import time
from typing import Optional

import numpy as np

from ..telemetry.recorder import flight_recorder
from ..telemetry.runtime import span as _span

log = logging.getLogger("deeplearning4j_tpu")

__all__ = ["AUTO_WINDOW_BYTES", "AUTO_MAX_K", "AUTO_ADAPT_MAX_K",
           "AUTO_DISPATCH_SHARE", "EPOCH", "auto_superstep_k",
           "validate_superstep", "validate_grad_accumulation",
           "accum_skip_nonfinite", "build_superstep",
           "build_accum_superstep", "dispatch_accum_groups",
           "split_accum_groups", "steps_in", "OverlapAutoK",
           "SuperstepRunner"]

#: ``superstep="auto"`` sizes the window so its stacked device footprint
#: stays near this budget — big enough to amortize dispatch, small enough
#: that window staging never competes with model state for memory.
AUTO_WINDOW_BYTES = 64 << 20
AUTO_MAX_K = 32
#: overlap-aware ``superstep="auto"`` may GROW K past the byte-budget
#: seed while the measured dispatch share stays above target — bounded
#: here so the growth (one extra XLA compile per doubling) terminates.
AUTO_ADAPT_MAX_K = 256
#: hard byte ceiling for the GROWN window: adaptation may trade staging
#: memory for dispatch amortization up to this much (8x the seed
#: budget), never further — a dispatch-bound fit with large batches must
#: not double itself into staging OOM (2 windows are in flight under the
#: pipelined loop).
AUTO_ADAPT_WINDOW_BYTES = AUTO_WINDOW_BYTES * 8
#: target host-dispatch share of the window period for the overlap-aware
#: auto-K: below this, per-window dispatch overhead is noise; above it,
#: the window is too short to hide the host work and K doubles.
AUTO_DISPATCH_SHARE = 0.10
#: ``superstep="epoch"``: the window is bounded only by the epoch (and by
#: signature changes) — the fit_scan regime expressed through fit().
EPOCH = "epoch"


def auto_superstep_k(batch_bytes: int,
                     target_bytes: int = AUTO_WINDOW_BYTES,
                     max_k: int = AUTO_MAX_K) -> int:
    """Window length for ``superstep="auto"``: as many batches as fit the
    byte budget, clamped to [1, max_k]."""
    if batch_bytes <= 0:
        return int(max_k)
    return max(1, min(int(max_k), int(target_bytes // batch_bytes)))


def validate_superstep(superstep):
    """Normalize the ``superstep=`` knob: a positive int, "auto", or
    "epoch". Returns the normalized value (ints coerced)."""
    if superstep in ("auto", EPOCH):
        return superstep
    try:
        k = int(superstep)
    except (TypeError, ValueError):
        k = 0
    if k < 1 or (not isinstance(superstep, (int, np.integer))):
        raise ValueError(
            f"superstep={superstep!r} — expected a positive int (window "
            "length in batches; 1 = per-batch dispatch), 'auto' (size the "
            "window from batch bytes) or 'epoch' (one window per epoch)")
    return k


def build_superstep(step_fn):
    """The raw (unjitted) superstep: ``lax.scan`` of ``step_fn`` over a
    [K, batch, ...] window of stacked inputs.

    ``step_fn`` is a model's pure train step ``(params, state, opt, step,
    x, y, rng, fmask, lmask) -> (params, state, opt, score)`` — arrays for
    MultiLayerNetwork, dicts for ComputationGraph, and the ZeRO step from
    ``parallel/zero.py`` all share this signature, so one builder serves
    every family. Mask slots may be None pytrees; a None leaf passes
    through the scan untouched, so the body sees the same static absence
    the per-batch step does.

    The RNG is split INSIDE the scan with the exact chain the per-batch
    loop draws host-side (``rng, k = split(rng)`` per step), making
    superstep-K training bit-identical to K=1 — and keeping the split on
    device instead of paying 2K tiny host dispatches per window."""
    import jax

    def superstep(params, state, opt_state, step0, rng0, xs, ys, fm, lm):
        def body(carry, inp):
            params, state, opt, step, rng = carry
            x, y, f, l = inp
            rng, k = jax.random.split(rng)
            params, state, opt, score = step_fn(params, state, opt, step,
                                                x, y, k, f, l)
            return (params, state, opt, step + 1, rng), score

        (params, state, opt, _step, rng), scores = jax.lax.scan(
            body, (params, state, opt_state, step0, rng0), (xs, ys, fm, lm))
        return params, state, opt, rng, scores

    return superstep


def validate_grad_accumulation(m):
    """Normalize the ``grad_accumulation=`` knob: a positive int (number of
    microbatches accumulated per optimizer step; 1 = classic one batch =
    one step)."""
    try:
        mi = int(m)
    except (TypeError, ValueError):
        mi = 0
    if mi < 1 or (not isinstance(m, (int, np.integer))):
        raise ValueError(
            f"grad_accumulation={m!r} — expected a positive int: the number "
            "of consecutive iterator microbatches whose gradients accumulate "
            "into one optimizer step (1 = no accumulation)")
    return mi


def accum_skip_nonfinite(guard, m) -> bool:
    """True when the accumulated step must neutralize non-finite
    microbatches IN-TRACE: under ``GuardPolicy.SKIP_BATCH`` a bad
    microbatch loss zeroes only that microbatch's gradient and the mean
    renormalizes over the finite ones — the rest of the accumulated step
    survives (ISSUE 12 satellite). Other policies keep the per-step
    semantics: the NaN propagates into the step score and the guard
    handles the whole step (warn/rollback/halt)."""
    return (m > 1 and guard is not None
            and getattr(guard, "policy", None) == "skip_batch")


def build_accum_superstep(grad_fn, update_fn, skip_nonfinite: bool = False):
    """The raw (unjitted) ACCUMULATED superstep: a nested ``lax.scan`` over
    a [K, M, batch, ...] window — outer over K optimizer steps, inner over
    each step's M microbatches, the update applied once per outer step on
    the fp32 mean gradient.

    ``grad_fn(params, state, x, y, rng, fmask, lmask) -> (score, new_state,
    grads)`` is a family's gradient half (loss selection incl. remat and
    the minimize sign already folded in); ``update_fn(params, grads,
    opt_state, step) -> (params, opt_state)`` its update half (gradient
    normalization, per-layer lr, bias-lr rescale). Both model families and
    the ZeRO step (which owns its own reduction — see
    ``parallel/zero.py.make_zero_accum_superstep``) fit this split.

    Semantics:
      * Gradients accumulate by SUMMATION in float32 accumulators and the
        update sees their mean — in exact arithmetic identical to one
        batch of M·b rows (each microbatch loss is a mean over its rows),
        and grouping-invariant bitwise: any (K, M) regrouping of the same
        microbatch sequence produces identical bits, because the op
        sequence per microbatch is identical. Against a NATIVE M·b batch
        the only difference is XLA's reassociation of the batch reduction
        — allclose at f32-ulp, asserted in tests/test_accumulation.py.
      * The RNG split chain advances per MICROBATCH (each microbatch draws
        its own dropout key, exactly as the per-batch loop would for the
        same iterator batches); the step counter advances per OPTIMIZER
        step, so updater bias correction and lr schedules see optimizer
        steps, not microbatches.
      * M is read from the input shape — one traced builder serves every
        M (a ragged tail group of m < M microbatches compiles its own
        shape and renormalizes by m, like a smaller final batch).
      * ``skip_nonfinite`` (static): a non-finite microbatch loss
        contributes a ZERO gradient and drops out of the mean's
        denominator; the step score averages the finite microbatches only
        (NaN when every microbatch was bad, so the guard still catches a
        fully-poisoned step). The raw per-microbatch scores are returned
        alongside so the host can count the skips.
      * The per-microbatch score stack is accumulated in a CARRIED [M]
        buffer with an explicit int32 index rather than as a scan output:
        on a 2-D (data, model) mesh (ISSUE 14) GSPMD shards the
        scan-output stacking buffer over a mesh axis whose size divides
        M, and this XLA version then mis-types the partitioned scan
        update (s64 loop index vs s32 partition offset — a verifier
        error after SPMD partitioning). The hand-indexed buffer keeps
        the update's index arithmetic int32 and the buffer off the mesh;
        the values are identical, so grouping invariance is unaffected.

    Returns ``(params, state, opt, rng, scores[K], micro_scores[K, M])``.
    """
    import jax
    import jax.numpy as jnp

    def superstep(params, state, opt_state, step0, rng0, xs, ys, fm, lm):
        f32 = jnp.float32

        def opt_body(carry, inp):
            params, state, opt, step, rng = carry
            n_micro = jax.tree_util.tree_leaves(inp)[0].shape[0]

            def micro_body(mcarry, minp):
                state, rng, acc, n_ok, ssum, mbuf, mi = mcarry
                x, y, f, l = minp
                rng, k = jax.random.split(rng)
                score, new_state, grads = grad_fn(params, state, x, y, k,
                                                  f, l)
                if skip_nonfinite:
                    # where-select, never multiply: 0 * NaN is NaN, and a
                    # poisoned gradient/state must not touch the carry
                    ok = jnp.isfinite(score)
                    acc = jax.tree_util.tree_map(
                        lambda a, g: a + jnp.where(ok, g.astype(f32), 0.0),
                        acc, grads)
                    state = jax.tree_util.tree_map(
                        lambda o, n_: jnp.where(ok, n_, o), state,
                        new_state)
                    n_ok = n_ok + ok.astype(f32)
                    ssum = ssum + jnp.where(ok, score, 0.0)
                else:
                    acc = jax.tree_util.tree_map(
                        lambda a, g: a + g.astype(f32), acc, grads)
                    state = new_state
                    n_ok = n_ok + 1.0
                    ssum = ssum + score
                # carried, int32-indexed score buffer (NOT a scan output)
                # — see the docstring's 2-D-mesh partitioner note
                mbuf = jax.lax.dynamic_update_index_in_dim(
                    mbuf, score.astype(f32), mi, 0)
                return (state, rng, acc, n_ok, ssum, mbuf,
                        mi + jnp.int32(1)), None

            acc0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(jnp.shape(p), f32), params)
            (state, rng, acc, n_ok, ssum, mscores, _mi), _ = jax.lax.scan(
                micro_body, (state, rng, acc0, f32(0.0), f32(0.0),
                             jnp.zeros((n_micro,), f32), jnp.int32(0)),
                inp)
            denom = jnp.maximum(n_ok, 1.0)
            gmean = jax.tree_util.tree_map(
                lambda a, p: (a / denom).astype(jnp.result_type(p)),
                acc, params)
            params, opt = update_fn(params, gmean, opt, step)
            # all-microbatches-bad: 0/0 -> NaN, the step score the guard's
            # whole-step policies key on
            score = jnp.where(n_ok > 0, ssum / denom, jnp.nan)
            return (params, state, opt, step + 1, rng), (score, mscores)

        (params, state, opt, _step, rng), (scores, mscores) = jax.lax.scan(
            opt_body, (params, state, opt_state, step0, rng0),
            (xs, ys, fm, lm))
        return params, state, opt, rng, scores, mscores

    return superstep


def steps_in(n_micro: int, m: int) -> int:
    """Optimizer steps a window of `n_micro` microbatches trains under
    grad_accumulation=m: full M-groups plus one renormalized step for any
    remainder."""
    q, r = divmod(int(n_micro), int(m))
    return q + (1 if r else 0)


def dispatch_accum_groups(staged, n_micro: int, m: int, step0: int,
                          run_group):
    """Drive a staged window through the accumulated superstep one
    [K', M'] group at a time (see `split_accum_groups`): ``run_group(tree,
    step0)`` dispatches one group — rebinding its model's trees — and
    returns the group's (scores, micro_scores) device arrays. Returns the
    parts list in step order, the M>1 dispatch contract
    ``SuperstepRunner._finalize`` consumes. Shared by all three adapters
    so the group/step-counter arithmetic lives in one place."""
    parts, step = [], int(step0)
    for seg, q, _m_eff in split_accum_groups(staged, n_micro, m):
        parts.append(run_group(seg, step))
        step += q
    return parts


def split_accum_groups(staged, n_micro: int, m: int):
    """Split a staged [n_micro, batch, ...] window into accumulation
    groups: the full [q, M, batch, ...] part plus (when n_micro is not a
    multiple of M — an epoch tail or a signature change that closed the
    group early) a [1, r, batch, ...] remainder that trains as ONE
    optimizer step renormalized over its r microbatches. None leaves
    (absent masks) pass through. Returns [(tree, n_steps, m_eff), ...]."""
    import jax

    q, r = divmod(int(n_micro), int(m))

    def cut(lo, hi, k, mm):
        return jax.tree_util.tree_map(
            lambda a: (None if a is None else
                       a[lo:hi].reshape((k, mm) + a.shape[1:])),
            staged, is_leaf=lambda x: x is None)

    parts = []
    if q:
        parts.append((cut(0, q * m, q, m), q, m))
    if r:
        parts.append((cut(q * m, n_micro, 1, r), 1, r))
    return parts


class OverlapAutoK:
    """Overlap-aware ``superstep="auto"`` sizing (ISSUE 12): the byte
    budget only seeds K; from there K adapts to the MEASURED
    dispatch/compute ratio. Each full window reports (host seconds spent
    inside the dispatch call, wall seconds of the whole window period);
    EMAs smooth sandbox noise, and while the dispatch share of the period
    exceeds ``target_share`` K doubles — each growth costs one extra XLA
    compile, so growth is geometric and capped at ``max_k``. K never
    shrinks: a long window is at worst slightly stale for listeners,
    while thrash between two K values would pay compiles forever.
    Bit-exactness is unaffected — window grouping never changes the math
    (nn/superstep.py header)."""

    def __init__(self, k0: int, max_k: int = AUTO_ADAPT_MAX_K,
                 target_share: float = AUTO_DISPATCH_SHARE):
        self.k = max(1, int(k0))
        self.max_k = max(self.k, int(max_k))
        self.target_share = float(target_share)
        self._disp = 0.0
        self._period = 0.0

    def observe(self, dispatch_s: float, period_s: float) -> int:
        """Feed one full window's timings; returns the (possibly grown)
        K for the next window."""
        if period_s <= 0.0:
            return self.k
        if self._period == 0.0:
            self._disp, self._period = dispatch_s, period_s
        else:
            self._disp = 0.5 * dispatch_s + 0.5 * self._disp
            self._period = 0.5 * period_s + 0.5 * self._period
        if (self.k < self.max_k
                and self._disp / self._period > self.target_share):
            self.k = min(self.max_k, self.k * 2)
        return self.k


class SuperstepRunner:
    """The windowed inner fit loop, shared by MultiLayerNetwork.fit,
    ComputationGraph.fit and ParallelTrainer.fit.

    The model-specific pieces live in an *adapter* with five hooks:

      signature(ds)    hashable batch signature (windows never mix
                       signatures), or None to consume the batch without
                       training it (e.g. a batch that trims to zero rows
                       on the mesh)
      batch_nbytes(ds) bytes of one batch (``superstep="auto"`` sizing)
      stage(window)    stack the window's batches into [K, batch, ...]
                       device pytrees (datasets/pipeline.py staging)
      dispatch(staged, n, step0)
                       run the jitted superstep, rebinding the model's
                       params/state/updater/RNG in place; returns the
                       device loss vector(s) WITHOUT syncing: a [n] array
                       for grad_accumulation=1, else a list of
                       (scores[K], micro_scores[K, M]) group parts (see
                       split_accum_groups)
      on_window_end(window)
                       per-window bookkeeping (last_input/last_batch_size,
                       signature tracking, telemetry samples) — runs only
                       for KEPT windows, before the listener replay

    With ``grad_accumulation=M`` the window holds K·M MICROBATCHES (K
    optimizer steps); listeners/guard/counters operate per optimizer step
    while the checkpoint batch cursor keeps counting iterator
    microbatches, so window edges are always optimizer-step boundaries.

    One runner drives one fit() call; `skip()` positions the resume
    cursor before the first epoch.
    """

    def __init__(self, model, adapter, superstep, *, guard=None, ckpt=None,
                 grad_accumulation: int = 1):
        self.model = model
        self.adapter = adapter
        self.superstep = validate_superstep(superstep)
        self.guard = guard
        self.ckpt = ckpt
        self._m = validate_grad_accumulation(grad_accumulation)
        self._skip_nonfinite = accum_skip_nonfinite(guard, self._m)
        self._autok: Optional[OverlapAutoK] = None
        self._k: Optional[int] = (self.superstep
                                  if isinstance(self.superstep, int) else None)
        self._skip = 0
        self._pending = None   # drawn batch belonging to the next window
        self._untrained = 0    # consumed untrainable batches awaiting a
                               # window-edge cursor advance
        self._staged_memo = None   # single-slot (ids, staged, window refs)
        # Pipelining (stage window i+1 while window i computes, sync i's
        # losses after i+1 dispatched) is only safe when nothing host-side
        # consumes model state at window boundaries: a guard may roll the
        # window back, a checkpointer may save mid-loop — both need the
        # boundary finalized before the next dispatch.
        self._pipelined = guard is None and ckpt is None

    def skip(self, n: int):
        """Resume bookkeeping: draw and discard the first `n` batches (the
        prefix the interrupted run already trained) before windowing."""
        self._skip = max(0, int(n))

    # ------------------------------------------------------------------
    def _resolve_k(self, ds):
        if self._k is not None:
            return
        if self.superstep == "auto":
            # byte budget SEEDS K (staged window = K·M microbatches);
            # from there OverlapAutoK grows it from the measured
            # dispatch/compute ratio (ISSUE 12). Growth is bounded BOTH
            # by the step cap and by a byte ceiling: the grown window may
            # trade staging memory for dispatch amortization only up to
            # AUTO_ADAPT_WINDOW_BYTES, so large batches can't double
            # themselves into staging OOM
            nbytes = self.adapter.batch_nbytes(ds)
            micros = auto_superstep_k(nbytes)
            self._k = max(1, micros // self._m)
            byte_cap = max(self._k, int(
                AUTO_ADAPT_WINDOW_BYTES // max(1, nbytes * self._m)))
            self._autok = OverlapAutoK(
                self._k, max_k=min(AUTO_ADAPT_MAX_K, byte_cap))
            log.info("superstep='auto' seeded at K=%d optimizer steps "
                     "(batch ~%.2f MB x M=%d, window budget %d MB, "
                     "adaptive cap K<=%d); overlap-aware adaptation "
                     "active", self._k, nbytes / 1e6, self._m,
                     AUTO_WINDOW_BYTES >> 20, self._autok.max_k)
        else:   # EPOCH: bounded only by the epoch / signature changes
            self._k = 1 << 30

    def _steps_in(self, n_micro: int) -> int:
        return steps_in(n_micro, self._m)

    def _observe_auto(self, window, dispatch_s: float, period_s: float):
        """Feed a FULL window's measured timings to the overlap-aware
        auto-K policy (partial tail windows would understate the ratio) —
        and the window's step anatomy (dispatch/host shares per optimizer
        step) to the flight recorder, for EVERY window including tails."""
        rec = flight_recorder()
        if rec.enabled:
            rec.record("train/window", micro=len(window),
                       n_steps=self._steps_in(len(window)),
                       dispatch_s=round(dispatch_s, 6),
                       period_s=round(period_s, 6),
                       dispatch_share=round(
                           dispatch_s / period_s, 4) if period_s > 0 else None)
        if self._autok is None or len(window) != self._k * self._m:
            return
        new_k = self._autok.observe(dispatch_s, period_s)
        if new_k != self._k:
            log.info(
                "superstep='auto' growing K %d -> %d (measured dispatch "
                "share %.1f%% of window period, target %.0f%%)", self._k,
                new_k, 100.0 * self._autok._disp / self._autok._period,
                100.0 * self._autok.target_share)
            self._k = new_k

    def _collect(self, data):
        """Next window: up to K consecutive batches sharing one signature.
        A signature change (ragged tail, time-bucket switch) closes the
        window; the odd batch opens the next one."""
        guard = self.guard
        window, sig0 = [], None
        while True:
            if self._pending is not None:
                ds, self._pending = self._pending, None
            elif data.has_next():
                ds = (guard.next_batch(data) if guard is not None
                      else data.next())
            else:
                break
            if self._skip:
                self._skip -= 1
                continue
            sig = self.adapter.signature(ds)
            if sig is None:
                # consumed but untrainable (per-batch path does the same).
                # The batch cursor advances only at the NEXT window edge /
                # epoch end (_finalize folds this count in): advancing it
                # here, while earlier window batches are drawn but not yet
                # trained, would let a deferred-SIGTERM snapshot record a
                # cursor ahead of the trained state and lose a batch on
                # resume
                self._untrained += 1
                continue
            if sig0 is None:
                self._resolve_k(ds)
                sig0 = sig
            elif sig != sig0:
                self._pending = ds
                break
            window.append(ds)
            if len(window) >= self._k * self._m:
                break
        return window

    def _stage(self, window):
        """Stage a window, with a SINGLE-SLOT identity memo: the
        whole-epoch window regime (the fit_scan alias) re-presents the
        exact same batch objects every epoch, and re-staging them would
        re-pay a full-dataset device stack per epoch that the historic
        fit_scan staged once. The staged arrays are never donated by the
        superstep jit, so cross-epoch reuse is safe. K-window regimes and
        streaming iterators churn the one slot harmlessly (no growth, no
        stale hits — the key is the tuple of batch object identities,
        kept alive by the stored window refs)."""
        if not window:
            return None
        key = tuple(id(ds) for ds in window)
        memo = self._staged_memo
        if memo is not None and memo[0] == key:
            return memo[1]
        staged = self.adapter.stage(window)
        self._staged_memo = (key, staged, window)
        return staged

    # ------------------------------------------------------------------
    def run_epoch(self, data):
        if self._pipelined:
            self._run_pipelined(data)
        else:
            self._run_sequential(data)
        if self._untrained and self.ckpt is not None:
            # untrainable tail batches with no following window: flush the
            # cursor at the epoch edge (model state is final here, so the
            # cursor and trained state agree)
            self.ckpt.on_batches(self._untrained)
            self._untrained = 0

    def _run_sequential(self, data):
        """Guard/checkpoint mode: each window is finalized (losses synced,
        guard verdict applied, checkpoint cursor advanced) before the next
        window is dispatched — a rollback can never race a dispatch."""
        while True:
            t_win = time.perf_counter()
            with _span("host/batch_prep", kind="superstep_window"):
                window = self._collect(data)
                staged = self._stage(window)
            if not window:
                return
            snap = self._pre_window_snapshot()
            t_d = time.perf_counter()
            with _span("device/dispatch", kind="superstep"):
                scores = self.adapter.dispatch(staged, len(window),
                                               self.model.iteration_count)
            t_d = time.perf_counter() - t_d
            self._finalize(window, scores, snap)
            self._observe_auto(window, t_d, time.perf_counter() - t_win)

    def _run_pipelined(self, data):
        """No guard, no checkpointer: window i+1 is collected, stacked and
        transferred while window i computes on device. With no listeners
        attached, window i's finalize (loss sync) is additionally DEFERRED
        until window i+1 has been dispatched — the sync lands on a window
        that already finished while its successor was being staged, so the
        device never idles at a window boundary and the host never blocks
        on an in-flight computation (except the last window; the
        one-window lag also bounds staging memory to two windows). With
        listeners, finalize runs BEFORE the next dispatch so every replay
        observes exactly the end-of-its-own-window params — the documented
        warn_scan_replay contract, never a window ahead."""
        lag = not (getattr(self.model, "listeners", None) or [])
        step0 = self.model.iteration_count
        inflight = None   # (window, scores_dev) — one window of lag
        with _span("host/batch_prep", kind="superstep_window"):
            window = self._collect(data)
            staged = self._stage(window)
        t_prev = time.perf_counter()
        while window:
            t_d = time.perf_counter()
            with _span("device/dispatch", kind="superstep"):
                scores = self.adapter.dispatch(staged, len(window), step0)
            t_d = time.perf_counter() - t_d
            step0 += self._steps_in(len(window))
            cur = (window, scores)
            with _span("host/batch_prep", kind="superstep_window"):
                window = self._collect(data)
                staged = self._stage(window)
            if lag:
                if inflight is not None:
                    self._finalize(inflight[0], inflight[1], None)
                inflight = cur
            else:
                self._finalize(cur[0], cur[1], None)
            now = time.perf_counter()
            self._observe_auto(cur[0], t_d, now - t_prev)
            t_prev = now
        if inflight is not None:
            self._finalize(inflight[0], inflight[1], None)

    # ------------------------------------------------------------------
    def _pre_window_snapshot(self):
        g = self.guard
        if g is None or not g._needs_snapshot:
            return None
        # device-side copy BEFORE dispatch: the superstep donates the
        # model trees, so post-dispatch the originals are invalidated
        return g._snapshot(self.model)

    def _finalize(self, window, scores_dev, snap):
        model = self.model
        n_micro = len(window)
        with _span("device/sync", kind="superstep_scores"):
            if self._m == 1:
                host_scores = np.asarray(scores_dev)
                micro_scores = None
            else:
                # dispatch returned accumulation-group parts: per-step
                # scores concatenate in step order; raw per-microbatch
                # scores kept for skip accounting
                host_scores = np.concatenate(
                    [np.asarray(s).reshape(-1) for s, _ in scores_dev])
                micro_scores = [np.asarray(ms) for _, ms in scores_dev]
        n_steps = len(host_scores)
        kept = True
        rec = flight_recorder()
        if rec.enabled and self.guard is None and n_steps:
            # guarded fits record scores inside guard.check_scores; the
            # unguarded path feeds the same already-host-synced vector
            # here so a post-hoc dump still shows the loss trajectory
            finite = host_scores[np.isfinite(host_scores)]
            rec.record("train/window_scores", n=n_steps,
                       nonfinite=int(n_steps - finite.size),
                       last=float(host_scores[-1]),
                       lo=float(finite.min()) if finite.size else None,
                       hi=float(finite.max()) if finite.size else None)
        if self.guard is not None:
            # superstep-granular guard: a bad window is discarded WHOLE,
            # restoring the pre-superstep snapshot (params/updater/RNG/
            # counters) — fit_scan's epoch-granular contract at window
            # granularity. Under skip_nonfinite the accumulated step
            # already neutralized bad MICROBATCHES in-trace (finite step
            # score), so only fully-poisoned steps reach this policy.
            kept = self.guard.check_scores(model, host_scores, snap)
            if kept and micro_scores is not None and self._skip_nonfinite:
                bad = int(sum((~np.isfinite(ms)).sum()
                              for ms in micro_scores))
                if bad:
                    note = getattr(self.guard, "note_skipped_micros", None)
                    if note is not None:
                        note(model, bad)
        if kept:
            self.adapter.on_window_end(window)
            listeners = getattr(model, "listeners", None) or []
            if listeners:
                # replay at the superstep edge with the ALREADY-TRANSFERRED
                # loss vector: every iteration_done sees a HOST scalar, so
                # listeners reading model.score() re-sync nothing
                # (graftlint hot-loop-sync stays structurally quiet here).
                # Cadence contract: one iteration_done per OPTIMIZER step —
                # microbatches are not iterations
                for i in range(n_steps):
                    model._score = host_scores[i]
                    model.iteration_count += 1
                    for listener in listeners:
                        listener.iteration_done(model, model.iteration_count)
            else:
                model._score = host_scores[-1]
                model.iteration_count += n_steps
        if self.ckpt is not None:
            # cursor advances for kept AND discarded windows (the batches
            # were consumed either way — per-batch fit does the same),
            # plus any untrainable batches consumed during collection —
            # counted HERE, at the edge, so the cursor never runs ahead
            # of the trained state. The cursor counts MICROBATCHES (what
            # the iterator yields and what resume re-draws); edges are
            # optimizer-step boundaries by construction, so a saved
            # cursor never lands mid-accumulation
            self.ckpt.on_batches(n_micro + self._untrained)
            self._untrained = 0
