"""MultiLayerNetwork — the Sequential model.

Capability parity with `nn/multilayer/MultiLayerNetwork.java` (2590 LoC):
`init`, `fit(DataSetIterator)` (:947), `output`, `score`, `evaluate` (:2413),
per-layer params, masking, TBPTT hooks, listeners — redesigned TPU-first:

  * Params/state/updater-state are **pytrees** (tuple of per-layer dicts), not
    views into one flattened buffer (`MultiLayerNetwork.java:420-511`). A
    flattened view is still available (`params_flat`) because parameter
    averaging & serialization parity need it.
  * Forward+backward+update is ONE jitted pure function (`_train_step`): XLA
    sees the whole step and fuses layer math, loss, gradient normalization and
    the optimizer. The reference's Solver/updater object pipeline
    (`optimize/Solver.java:41`, `nn/updater/MultiLayerUpdater.java:115`)
    collapses into traced code.
  * Backward is `jax.grad` of the scalar score — the 700-line
    `calcBackpropGradients` (:1034) has no equivalent.
  * The host-side `fit` loop only moves numpy batches to device and runs
    listeners; with `AsyncDataSetIterator` prefetch this is the same
    double-buffered pipeline as the reference's (:950).
"""
from __future__ import annotations

import functools
import logging
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .conf import (BackpropType, MultiLayerConfiguration,
                   NeuralNetConfiguration, OptimizationAlgorithm)
from .conf.base import LayerConf, cast_floating
from .gradnorm import apply_gradient_normalization
from .remat import resolve_policy
from .layers.feedforward import BaseOutputLayerConf
from ..datasets.iterators import ArrayDataSetIterator, DataSet, DataSetIterator
from ..eval.evaluation import Evaluation
from ..telemetry.compile_watch import watch_compiles
from ..telemetry.runtime import (active as _tel_active,
                                 null_span as _null_span, span as _span)
from ..telemetry.tracing import named_step

log = logging.getLogger("deeplearning4j_tpu")

__all__ = ["MultiLayerNetwork"]


def _split_or_none(rng, n):
    return [None] * n if rng is None else list(jax.random.split(rng, n))


def _flat_leaves(p):
    """Leaves of a (possibly nested) param dict in sorted-key-path order —
    the deterministic layout params_flat/set_params_flat rely on (nested
    trees: bidirectional LSTM {"fwd": {...}, "bwd": {...}})."""
    if not isinstance(p, dict):
        return [p]
    out = []
    for k in sorted(p):
        out.extend(_flat_leaves(p[k]))
    return out


def _unflatten_like(p, vec, pos, to_array):
    """Rebuild a param tree shaped like `p` from vec[pos:]; returns
    (tree, new_pos)."""
    if not isinstance(p, dict):
        n = int(np.prod(p.shape))
        return to_array(vec[pos:pos + n], p), pos + n
    d = {}
    for k in sorted(p):
        d[k], pos = _unflatten_like(p[k], vec, pos, to_array)
    return d, pos


def _rescale_bias_updates(updates, scale):
    """Scale the bias entries of a (possibly nested) per-layer update dict
    — nested param trees (bidirectional LSTM {"fwd": ..., "bwd": ...})
    rescale their inner biases."""
    if not isinstance(updates, dict):
        return updates
    return {k: (v * scale if not isinstance(v, dict)
                and (k == "b" or "bias" in k)
                else _rescale_bias_updates(v, scale))
            for k, v in updates.items()}


def _fitting(layer, rng, it, given, i):
    """`given` if its leaves have the shapes `layer.init_params` would
    make (nothing is made to learn them), else ValueError."""
    shapes = lambda tree: jax.tree_util.tree_map(lambda a: tuple(a.shape),
                                                 tree)
    want = shapes(jax.eval_shape(lambda r: layer.init_params(r, it), rng))
    if shapes(given) != want:
        raise ValueError(
            f"init(params=...): layer {i} ({type(layer).__name__}) takes "
            f"{want}, got {shapes(given)}")
    return given


class MultiLayerNetwork:
    # attrs a TrainingGuard snapshot/restore covers (fault/guard.py):
    # everything a training step mutates, so a restored snapshot is
    # indistinguishable from the step never having run
    _fault_state_attrs = ("params", "state", "updater_state", "_rng",
                          "iteration_count", "epoch_count", "_score")

    def __init__(self, conf: MultiLayerConfiguration):
        self.conf = conf
        self.layers: List[LayerConf] = list(conf.layers)
        self.params: Optional[Tuple[Dict]] = None
        self.state: Optional[Tuple[Dict]] = None
        self.updater_state: Optional[Tuple] = None
        self.iteration_count = 0
        self.epoch_count = 0
        self.listeners = []
        self.last_batch_size = 0
        self._score = float("nan")
        self._rng = None
        self._input_types = None  # input type *to* each layer (post-preprocessor)
        self._rnn_carries = None
        self._pretrained = False
        # retrace telemetry: every distinct batch signature costs a full
        # XLA recompile of the train step (SURVEY §5 tracing; the
        # PerformanceListener-style ETL/iteration split would hide this)
        self._batch_signatures = set()
        self.recompile_count = 0

    # ------------------------------------------------------------------
    # Initialization
    # ------------------------------------------------------------------
    def init(self, seed: Optional[int] = None,
             params=None) -> "MultiLayerNetwork":
        """Make the parameters from the seed, or take `params` (one dict a
        layer, as `init` would make them) where the caller has them: no
        second set is then made, which a model that fills most of a chip
        has no room for. Their shapes are held to what the layers would
        have made; their dtypes are the caller's. The span `dl4j/nn/init`
        (`layers`, `leaves`, `given` 1 where `params` were passed) times
        it."""
        if params is not None and len(params) != len(self.layers):
            raise ValueError(f"init(params=...) got {len(params)} entries "
                             f"for {len(self.layers)} layers")
        with _span("dl4j/nn/init", layers=len(self.layers),
                   given=int(params is not None)) as span:
            self._init(seed, params)
            span.set(leaves=len(jax.tree_util.tree_leaves(self.params)))
        return self

    def _init(self, seed, given):
        from . import activations as _acts
        for layer in self.layers:
            if layer.activation is not None:  # fail fast on bad names
                _acts.get(layer.activation)
        seed = self.conf.conf.seed if seed is None else seed
        self._rng = jax.random.PRNGKey(seed)
        self._rng, init_rng = jax.random.split(self._rng)
        layer_rngs = jax.random.split(init_rng, max(1, len(self.layers)))

        # track input types through preprocessors for init
        it = self.conf.input_type
        self._input_types = []
        params, state = [], []
        for i, layer in enumerate(self.layers):
            if i in self.conf.preprocessors and it is not None:
                it = self.conf.preprocessors[i].output_type(it)
            if it is None:
                n_in = getattr(layer, "n_in", None)
                if layer.has_params and not n_in:
                    raise ValueError(
                        f"Layer {i} ({type(layer).__name__}) needs n_in or a "
                        "network input_type for shape inference")
                from .conf.input_type import InputType
                it = InputType.feed_forward(n_in or 0)
            self._input_types.append(it)
            if given is None:
                params.append(layer.init_params(layer_rngs[i], it))
            else:
                params.append(_fitting(layer, layer_rngs[i], it, given[i], i))
            state.append(layer.init_state(it))
            it = layer.output_type(it)

        self.params = tuple(params)
        self.state = tuple(state)
        self.updater_state = tuple(
            self._layer_updater(l).init(p) for l, p in zip(self.layers, params))

    def _layer_updater(self, layer: LayerConf):
        return layer.updater or self.conf.conf.updater

    @functools.cached_property
    def _compute_dtype(self):
        """jnp dtype for mixed-precision compute, or None when disabled."""
        cdt = self.conf.conf.compute_dtype
        if cdt is None or jnp.dtype(cdt) == jnp.dtype(self.conf.conf.dtype):
            return None
        return jnp.dtype(cdt)

    def _precision_remat_context(self):
        """FitCheckpointer context entries for the policies that shape the
        step's math/memory (ISSUE 18): resume warns when the restored
        run's values differ (compute_dtype changes the math; remat /
        remat_policy only the memory profile)."""
        c = self.conf.conf
        return {"compute_dtype": c.compute_dtype, "remat": c.remat,
                "remat_policy": c.remat_policy}

    # ------------------------------------------------------------------
    # Pure functional core (closed over static layer configs)
    # ------------------------------------------------------------------
    def _forward(self, params, state, x, train, rng, fmask=None, upto=None,
                 carries=None):
        """Returns (activations, new_state, mask, new_carries).

        `carries` (tuple, entry per layer, None for non-recurrent layers)
        threads RNN hidden state across TBPTT chunks / rnn_time_step calls."""
        n = len(self.layers) if upto is None else upto
        rngs = _split_or_none(rng, max(1, n))
        new_state = list(state)
        new_carries = list(carries) if carries is not None else [None] * len(self.layers)
        mask = fmask
        cdt = self._compute_dtype
        if cdt is not None and jnp.issubdtype(x.dtype, jnp.floating):
            x = x.astype(cdt)
        # per-layer activation remat ("blocks" ≡ "layer" for a sequential
        # net): checkpoint each hidden layer so only layer boundaries are
        # saved for backward ("full" is handled at the loss level)
        use_remat = (self.conf.conf.remat in ("layer", "blocks") and train
                     and carries is None and fmask is None)
        if (self.conf.conf.remat in ("layer", "blocks") and train
                and not use_remat):
            import warnings
            warnings.warn(
                f"remat={self.conf.conf.remat!r} is inactive for this "
                "step: per-layer checkpointing does not support mask "
                "arrays or TBPTT carries — training falls back to the "
                "save-everything path", stacklevel=3)
        for i in range(n):
            layer = self.layers[i]
            p_i = params[i]
            # Mixed precision: hidden layers compute in cdt (bf16 on the MXU);
            # output layers stay in the master dtype so softmax/loss are f32
            # (their matmul promotes bf16 activations back up).
            if cdt is not None and not isinstance(layer, BaseOutputLayerConf):
                p_i = cast_floating(p_i, cdt)
            if i in self.conf.preprocessors:
                x = self.conf.preprocessors[i].apply(x)
                mask = self.conf.preprocessors[i].apply_mask(mask)
            if carries is not None and getattr(layer, "is_recurrent", False):
                (x, new_carries[i]), new_state[i] = layer.apply(
                    p_i, state[i], x, train=train, rng=rngs[i],
                    mask=mask, carry=carries[i], return_carry=True)
            elif (use_remat and mask is None
                    and not isinstance(layer, BaseOutputLayerConf)):
                fn = lambda p_, s_, x_, r_, _l=layer: _l.apply(
                    p_, s_, x_, train=train, rng=r_, mask=None)
                # per-layer selective remat: the layer's (inherited)
                # policy decides what this boundary saves
                x, new_state[i] = jax.checkpoint(
                    fn, policy=resolve_policy(layer.remat_policy))(
                        p_i, state[i], x, rngs[i])
            else:
                x, new_state[i] = layer.apply(p_i, state[i], x,
                                              train=train, rng=rngs[i],
                                              mask=mask)
            mask = layer.output_mask(mask)
        return x, tuple(new_state), mask, tuple(new_carries)

    def _reg_score(self, params):
        reg = jnp.float32(0.0)
        for layer, p in zip(self.layers, params):
            if p:
                reg = reg + layer.reg_score(p)
        return reg

    def _loss_fn(self, params, state, x, y, rng, fmask=None, lmask=None,
                 train=True, carries=None):
        """Scalar score = mean per-example loss + regularization/batch
        (reference `BaseOutputLayer.computeScore` semantics)."""
        out_layer = self.layers[-1]
        if not isinstance(out_layer, BaseOutputLayerConf):
            raise ValueError("Last layer must be an output/loss layer for fit()")
        n = len(self.layers)
        if rng is not None:
            rng, out_rng = jax.random.split(rng)
        else:
            out_rng = None
        h, new_state, mask, new_carries = self._forward(
            params, state, x, train, rng, fmask=fmask, upto=n - 1,
            carries=carries)
        if (n - 1) in self.conf.preprocessors:
            h = self.conf.preprocessors[n - 1].apply(h)
            mask = self.conf.preprocessors[n - 1].apply_mask(mask)
        eff_lmask = lmask if lmask is not None else (
            mask if mask is not None else None)
        loss = out_layer.loss_score(params[-1], state[-1], h, y,
                                    train=train, rng=out_rng, mask=eff_lmask)
        # Regularization normalizes by REAL rows (any live mask entry), not
        # the padded batch size, so PadToBatchIterator's weight-zero rows
        # are a learning no-op (the loss itself is already a masked mean)
        batch = x.shape[0]
        if eff_lmask is not None:
            live = eff_lmask.astype(jnp.float32).reshape(
                (eff_lmask.shape[0], -1)).max(axis=1)
            batch = jnp.maximum(jnp.sum(live), 1.0)
        score = loss + self._reg_score(params) / batch
        # layer auxiliary losses from the state side-channel (MoE router
        # load balancing, nn/layers/moe.py) — train only: eval state holds
        # a stale aux from the last training batch
        if train:
            for layer, s in zip(self.layers, new_state):
                if hasattr(layer, "aux_score"):
                    score = score + layer.aux_score(s)
        return score, (new_state, new_carries)

    def _layer_lr(self, layer: LayerConf, step):
        """Scheduled, per-layer learning rate (None = updater default)."""
        sched = self.conf.conf.lr_schedule
        base = layer.learning_rate
        if sched is None:
            return base  # may be None -> updater default
        lr = sched(step)
        if base is not None and sched.base_lr:
            lr = lr * (base / sched.base_lr)
        return lr

    def apply_layer_updates(self, layers, params, grads, opt_state, step):
        """Apply per-layer updaters to a (sub)list of layers — the update
        half of the train step, shared with the pipeline trainer which
        updates one stage's layer slice at a time. Pure/traceable."""
        new_params, new_opt = [], []
        for layer, p, g, os in zip(layers, params, grads, opt_state):
            if not p or layer.frozen:
                new_params.append(p)
                new_opt.append(os)
                continue
            g = apply_gradient_normalization(
                layer.gradient_normalization,
                layer.gradient_normalization_threshold or 1.0, g)
            upd = self._layer_updater(layer)
            lr = self._layer_lr(layer, step)
            updates, os = upd.update(g, os, step, lr)
            if layer.bias_learning_rate is not None:
                # lr may be a traced scalar (schedule); avoid python
                # truthiness on it. Updater steps are linear in lr, so
                # rescaling bias updates by bias_lr/lr is exact.
                if lr is None:
                    eff = getattr(upd, "learning_rate", 1.0) or 1.0
                    scale = layer.bias_learning_rate / eff
                else:
                    scale = layer.bias_learning_rate / jnp.maximum(
                        jnp.asarray(lr, jnp.float32), 1e-30)
                updates = _rescale_bias_updates(updates, scale)
            # tree-wise subtract: params may be NESTED dicts (the
            # bidirectional LSTM's {"fwd": {...}, "bwd": {...}})
            new_params.append(jax.tree_util.tree_map(
                lambda a, u: a - u, p, updates))
            new_opt.append(os)
        return new_params, new_opt

    def _make_train_step(self):
        base_loss = self._loss_fn
        if self.conf.conf.remat == "full":
            # save only the step inputs; recompute the entire forward in
            # backward (jax.checkpoint over the whole loss)
            pol = resolve_policy(self.conf.conf.remat_policy)

            def loss_fn(params, state, x, y, rng, fmask=None, lmask=None,
                        carries=None):
                f = lambda p, s, x_, y_, r_: base_loss(
                    p, s, x_, y_, r_, fmask=fmask, lmask=lmask,
                    carries=carries)
                return jax.checkpoint(f, policy=pol)(params, state, x, y,
                                                     rng)
        else:
            loss_fn = base_loss

        def train_step(params, state, opt_state, step, x, y, rng, fmask,
                       lmask, carries=None):
            (score, (new_state, new_carries)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, state, x, y, rng,
                                       fmask=fmask, lmask=lmask,
                                       carries=carries)
            if not self.conf.conf.minimize:
                grads = jax.tree_util.tree_map(lambda g: -g, grads)
            new_params, new_opt = self.apply_layer_updates(
                self.layers, params, grads, opt_state, step)
            if carries is None:
                return tuple(new_params), new_state, tuple(new_opt), score
            # TBPTT chunk step: carries cross chunk boundaries as *inputs*, so
            # gradients naturally stop at the boundary (the reference's
            # rnnActivateUsingStoredState + truncated backprop,
            # MultiLayerNetwork.java:1119)
            return (tuple(new_params), new_state, tuple(new_opt), score,
                    new_carries)

        return named_step("train_step", train_step)

    @functools.cached_property
    def train_step_fn(self):
        """The raw (unjitted) pure training step — for callers that jit it
        themselves with custom shardings (parallel trainers, dryrun)."""
        return self._make_train_step()

    @functools.cached_property
    def grad_step_fn(self):
        """The GRADIENT half of the train step — ``(params, state, x, y,
        rng, fmask, lmask) -> (score, new_state, grads)`` with the loss
        selection (remat="full") and the minimize sign folded in. The
        accumulation superstep and the ZeRO step compose it with their own
        reduction/update schedule (nn/superstep.py, parallel/zero.py)."""
        base_loss = self._loss_fn
        if self.conf.conf.remat == "full":
            pol = resolve_policy(self.conf.conf.remat_policy)

            def loss_fn(params, state, x, y, rng, fmask=None, lmask=None):
                f = lambda p, s, x_, y_, r_: base_loss(
                    p, s, x_, y_, r_, fmask=fmask, lmask=lmask)
                return jax.checkpoint(f, policy=pol)(params, state, x, y,
                                                     rng)
        else:
            loss_fn = base_loss
        minimize = self.conf.conf.minimize

        def grad_step(params, state, x, y, rng, fmask, lmask):
            (score, (new_state, _)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, state, x, y, rng,
                                       fmask=fmask, lmask=lmask)
            if not minimize:
                grads = jax.tree_util.tree_map(lambda g: -g, grads)
            return score, new_state, grads

        return grad_step

    def apply_updates(self, params, grads, opt_state, step):
        """The UPDATE half on a full gradient tree — per-layer gradient
        normalization, scheduled/per-layer lr and bias-lr rescale — the
        counterpart of `grad_step_fn` for callers that schedule the
        gradient themselves (accumulated mean, ZeRO-reduced shards).
        Pure/traceable."""
        new_params, new_opt = self.apply_layer_updates(
            self.layers, params, grads, opt_state, step)
        return tuple(new_params), tuple(new_opt)

    def _accum_superstep_fn(self, skip_nonfinite: bool):
        """Jitted accumulated superstep (nn/superstep.py): nested scan over
        [K, M, batch, ...] windows, fp32 gradient accumulators, one update
        per outer step. Cached per skip flag only — K and M are read from
        the input shapes, so one jit serves every grouping (each distinct
        (K, M, signature) costs one XLA compile, like ragged tails)."""
        cache = self.__dict__.setdefault("_accum_superstep_cache", {})
        fn = cache.get(bool(skip_nonfinite))
        if fn is None:
            from .superstep import build_accum_superstep
            fn = cache[bool(skip_nonfinite)] = watch_compiles(
                jax.jit(build_accum_superstep(self.grad_step_fn,
                                              self.apply_updates,
                                              bool(skip_nonfinite)),
                        donate_argnums=(0, 1, 2)),
                "nn/accum_superstep")
        return fn

    @functools.cached_property
    def _train_step(self):
        return watch_compiles(
            jax.jit(self.train_step_fn, donate_argnums=(0, 1, 2)),
            "nn/train_step")

    @functools.cached_property
    def _superstep_fn(self):
        """Device-resident superstep: `lax.scan` of the train step over a
        [K, batch, ...] window, RNG chain threaded inside so superstep
        training is bit-identical to the per-batch loop (nn/superstep.py).
        One XLA compile per (K, batch signature)."""
        from .superstep import build_superstep
        return watch_compiles(
            jax.jit(build_superstep(self.train_step_fn),
                    donate_argnums=(0, 1, 2)),
            "nn/superstep")

    @functools.cached_property
    def predict_fn(self):
        """Raw (unjitted) pure inference step — for callers that jit it
        themselves with custom shardings (distributed evaluation plane)."""
        def predict(params, state, x, fmask):
            out, _, _, _ = self._forward(params, state, x, False, None,
                                         fmask=fmask)
            return out
        return predict

    @functools.cached_property
    def _predict_fn(self):
        return watch_compiles(jax.jit(self.predict_fn), "nn/predict")

    @functools.cached_property
    def _tbptt_step(self):
        return watch_compiles(
            jax.jit(self.train_step_fn, donate_argnums=(0, 1, 2)),
            "nn/tbptt_step")

    @functools.cached_property
    def _rnn_step_fn(self):
        """One-step stateful inference (reference rnnTimeStep,
        MultiLayerNetwork.java:2234): x is [B, 1, F] (or [B, F] upgraded),
        carries in/out."""
        def step(params, state, x, carries):
            out, _, _, new_carries = self._forward(params, state, x, False,
                                                   None, carries=carries)
            return out, new_carries
        return watch_compiles(jax.jit(step), "nn/rnn_step")

    @functools.cached_property
    def _score_fn(self):
        def score(params, state, x, y, fmask, lmask):
            s, _ = self._loss_fn(params, state, x, y, None, fmask=fmask,
                                 lmask=lmask, train=False)
            return s
        return watch_compiles(jax.jit(score), "nn/score")

    # ------------------------------------------------------------------
    # Public training API
    # ------------------------------------------------------------------
    def fit(self, data, labels=None, epochs: int = 1, *,
            superstep=1, grad_accumulation: int = 1,
            prefetch: bool = False, pad_ragged: bool = False,
            time_buckets=None, checkpoint_dir: Optional[str] = None,
            checkpoint_every: int = 0, resume: bool = False, guard=None):
        """fit(DataSetIterator), fit(DataSet), or fit(features, labels).

        `superstep=K` (iterator inputs) runs the SAME per-batch training
        through device-resident windows of K batches: one jitted
        `lax.scan` dispatch per window instead of one per batch, killing
        the per-batch host-dispatch floor while staying BIT-IDENTICAL to
        K=1 (see nn/superstep.py). K=1 (default) is the classic per-batch
        loop; "auto" sizes the window from batch bytes AND adapts K to the
        measured dispatch/compute ratio (overlap-aware); "epoch" windows
        the whole epoch (the fit_scan regime). Listeners, `guard` checks
        and checkpoint/SIGTERM saves fire at superstep edges with the
        per-window loss vector; ragged tails just close a window early.
        Falls back to per-batch dispatch (with a log line) for
        line-search optimizers and TBPTT configs.

        `grad_accumulation=M` (iterator inputs) accumulates M consecutive
        iterator microbatches into ONE optimizer step: forward/backward
        per microbatch, gradients summed in fp32 accumulators, one update
        on the mean — the effective batch is M·b at the activation memory
        of b. Equivalent to training on the concatenated M·b batch (exact
        arithmetic; bitwise up to XLA's reassociation of the batch
        reduction — see nn/superstep.build_accum_superstep). Composes
        with `superstep` (a window = K·M microbatches) and is
        grouping-invariant bitwise across K. Listeners/iteration_count/
        lr schedules advance per optimizer step; checkpoint cadence lands
        on optimizer-step boundaries; an epoch tail (or signature change)
        shorter than M trains as one step renormalized over its
        microbatches. Resume must use the SAME M (the checkpoint records
        it and resume warns on a mismatch). Line-search optimizers and
        TBPTT reject M>1 (silently changing the effective batch would be
        worse than an error).

        Input-pipeline knobs (iterator inputs only; see
        `datasets/pipeline.py`):
          pad_ragged    — pad ragged final batches to the fixed batch size
                          with weight-zero rows: ONE train-step compile per
                          fit instead of one per distinct batch shape, and
                          a provable learning no-op (loss and
                          regularization normalize by real rows).
          time_buckets  — with pad_ragged semantics, additionally pad the
                          time axis of sequence batches up to these bucket
                          lengths (at most len(buckets) signatures).
          prefetch      — stage `device_tuple()` on a background thread one
                          batch ahead so host->device transfer overlaps the
                          previous step's compute (donation-safe: batch
                          tensors are never donated).

        Fault-tolerance knobs (iterator inputs; see `fault/`):
          checkpoint_dir   — directory of crash-safe checkpoints (atomic
                             zip writes with sha256 manifests). A SIGTERM
                             during fit snapshots here before exit.
          checkpoint_every — save every N iterations (0 = only at fit end
                             and on SIGTERM).
          resume           — restore the newest verifiable checkpoint
                             first (params/updater/counters/RNG + the
                             iterator's shuffle epoch via `set_epoch`),
                             skip the already-trained prefix, and train
                             only what remains of `epochs` — a resumed
                             run matches an uninterrupted one.
          guard            — a fault.TrainingGuard: isfinite check on
                             every step's loss (warn/skip_batch/rollback/
                             halt) + bounded-backoff retry around
                             iterator.next() for transient data errors."""
        from .superstep import validate_grad_accumulation
        accum_m = validate_grad_accumulation(grad_accumulation)
        if self.params is None:
            self.init()
        if labels is not None:
            data = DataSet(np.asarray(data), np.asarray(labels))
        if isinstance(data, DataSet):
            if checkpoint_dir is not None or resume:
                raise ValueError(
                    "checkpoint_dir/resume need an iterator fit (the "
                    "checkpoint records epoch/batch progress); wrap the "
                    "DataSet in a ListDataSetIterator")
            if accum_m != 1:
                # silently training one b-row step where the caller asked
                # for an M·b effective batch would be a correctness trap
                raise ValueError(
                    f"grad_accumulation={accum_m} needs an iterator fit "
                    "(M consecutive microbatches form one optimizer "
                    "step); wrap the DataSet in a ListDataSetIterator or "
                    "split it with datasets.pipeline.split_microbatches")
            if superstep != 1:
                log.info("superstep=%r ignored for a single-DataSet fit "
                         "(one batch is one step); pass an iterator to "
                         "window batches", superstep)
            if guard is not None:
                guard.run_step(self, lambda: self._fit_batch(data))
            else:
                self._fit_batch(data)
            return self
        if not isinstance(data, DataSetIterator):
            raise TypeError(f"Cannot fit on {type(data)}")
        if self.conf.pretrain and not self._pretrained:
            self.pretrain(data)
            self._pretrained = True
        if not self.conf.backprop:
            if (checkpoint_dir is not None or resume or checkpoint_every
                    or guard is not None or accum_m != 1):
                raise ValueError(
                    "checkpoint_dir/checkpoint_every/resume/guard/"
                    "grad_accumulation need a backprop fit — this "
                    "configuration has backprop=False, so none of them "
                    "would take effect")
            return self
        from ..fault.resume import maybe_fit_checkpointer
        ckpt = maybe_fit_checkpointer(
            self, checkpoint_dir, checkpoint_every, resume,
            context={"grad_accumulation": accum_m,
                     **self._precision_remat_context()})
        skip, done_epochs = (0, 0) if ckpt is None else ckpt.resume_into(data)
        from ..datasets.pipeline import build_pipeline
        data, close = build_pipeline(data, pad_ragged=pad_ragged,
                                     prefetch=prefetch,
                                     time_buckets=time_buckets)
        runner = self._make_superstep_runner(superstep, guard, ckpt, accum_m)
        if runner is not None:
            runner.skip(skip)
            skip = 0
            if self.listeners:
                from ..optimize.listeners import warn_scan_replay
                warn_scan_replay(self.listeners)
        sigterm = (ckpt.sigterm_snapshot() if ckpt is not None
                   else _null_span())
        try:
            with sigterm:
                for _ in range(max(0, epochs - done_epochs)):
                    for listener in self.listeners:
                        if hasattr(listener, "on_epoch_start"):
                            listener.on_epoch_start(self)
                    data.reset()
                    if runner is not None:
                        runner.run_epoch(data)
                    else:
                        while data.has_next():
                            ds = (guard.next_batch(data) if guard is not None
                                  else data.next())
                            if skip:
                                # resume: this prefix of the epoch already
                                # trained before the interruption — drawing
                                # (and discarding) it keeps the iterator
                                # position identical to the uninterrupted run
                                skip -= 1
                                continue
                            if guard is not None:
                                guard.run_step(self,
                                               lambda b=ds: self._fit_batch(b))
                            else:
                                self._fit_batch(ds)
                            if ckpt is not None:
                                ckpt.on_batch()
                    for listener in self.listeners:
                        if hasattr(listener, "on_epoch_end"):
                            listener.on_epoch_end(self)
                    self.epoch_count += 1
                    if ckpt is not None:
                        ckpt.on_epoch()
                if ckpt is not None:
                    ckpt.on_fit_end()
        finally:
            close()
        return self

    def _make_superstep_runner(self, superstep, guard, ckpt, accum_m=1):
        """SuperstepRunner for this fit, or None for the per-batch loop
        (superstep=1 with grad_accumulation=1, line-search optimizers,
        TBPTT). grad_accumulation>1 always needs the windowed loop; on
        configs that can't window it raises instead of silently training
        with a different effective batch."""
        from .conf import OptimizationAlgorithm as OA
        from .superstep import (SuperstepRunner, accum_skip_nonfinite,
                                validate_superstep)

        k = validate_superstep(superstep)
        if k == 1 and accum_m == 1:
            return None
        reason = None
        if self.conf.conf.optimization_algo != OA.STOCHASTIC_GRADIENT_DESCENT:
            reason = ("line-search optimizers (CG/LBFGS) are per-batch "
                      "sequential")
        elif self.conf.backprop_type == BackpropType.TRUNCATED_BPTT:
            reason = ("TBPTT chunks each batch on host; use fit_scan for "
                      "device-resident TBPTT epochs")
        if reason is not None:
            if accum_m != 1:
                raise ValueError(
                    f"grad_accumulation={accum_m} is not supported for "
                    f"this configuration: {reason}")
            log.info("superstep=%r falls back to per-batch dispatch: %s",
                     superstep, reason)
            return None
        adapter = _NetworkSuperstepAdapter(
            self, m=accum_m,
            skip_nonfinite=accum_skip_nonfinite(guard, accum_m))
        return SuperstepRunner(self, adapter, k, guard=guard, ckpt=ckpt,
                               grad_accumulation=accum_m)

    # ------------------------------------------------------------------
    # Device-resident epoch training (one dispatch per epoch)
    # ------------------------------------------------------------------
    def fit_scan(self, data, epochs: int = 1, *, pad_ragged: bool = False,
                 checkpoint_dir: Optional[str] = None,
                 checkpoint_every: int = 0, resume: bool = False,
                 guard=None):
        """Device-resident epoch training — since the superstep refactor a
        THIN ALIAS for `fit(..., superstep="epoch")`: the whole epoch runs
        as one jitted `lax.scan` window, bit-identical to the per-batch
        loop (nn/superstep.py). Kept for API compatibility and for the two
        cases the unified loop routes specially: TBPTT configs (scanned
        over (series, chunk) via fit_scan_arrays — hidden state flows
        between a series' chunks and resets at series boundaries; a ragged
        final chunk is padded to the chunk length under a zero label-mask,
        exactly the reference's doTruncatedBPTT semantics) and line-search
        optimizers (per-batch sequential, delegated to the fit() loop).
        All batches must share shapes (use pad_ragged=True, a
        uniform-batch iterator, or drop the ragged tail)."""
        from .conf import OptimizationAlgorithm as OA

        if self.params is None:
            self.init()
        if self.conf.conf.optimization_algo != OA.STOCHASTIC_GRADIENT_DESCENT:
            # delegate to fit() so epoch listeners/epoch_count behave the
            # same on this path (and generic iterables survive multi-epoch)
            from ..datasets.iterators import ListDataSetIterator
            if isinstance(data, DataSet):
                data = ListDataSetIterator([data])
            elif not isinstance(data, DataSetIterator):
                data = ListDataSetIterator(list(data))
            return self.fit(data, epochs=epochs,
                            checkpoint_dir=checkpoint_dir,
                            checkpoint_every=checkpoint_every,
                            resume=resume, guard=guard)
        if isinstance(data, DataSet):
            batches = [data]
        elif isinstance(data, DataSetIterator):
            data.reset()
            batches = []
            while data.has_next():
                batches.append(data.next())
        else:
            batches = list(data)
        if not batches:
            return self
        if pad_ragged:
            from ..datasets.pipeline import pad_dataset
            target = max(b.num_examples() for b in batches)
            batches = [pad_dataset(b, target)[0] for b in batches]
        shapes = {tuple(np.asarray(b.features).shape) for b in batches}
        if len(shapes) != 1:
            raise ValueError(
                f"fit_scan needs uniform batch shapes, got {sorted(shapes)}; "
                "pad the ragged tail (pad_ragged=True — weight-zero rows, "
                "a learning no-op), drop it (ArrayDataSetIterator("
                "drop_last=True)), or use fit()")
        tbptt = (self.conf.backprop_type == BackpropType.TRUNCATED_BPTT
                 and np.asarray(batches[0].features).ndim >= 3)
        if not tbptt:
            # the unified loop: one superstep window per epoch
            from ..datasets.iterators import ListDataSetIterator
            return self.fit(ListDataSetIterator(batches), epochs=epochs,
                            superstep="epoch",
                            checkpoint_dir=checkpoint_dir,
                            checkpoint_every=checkpoint_every,
                            resume=resume, guard=guard)
        xs = np.stack([np.asarray(b.features) for b in batches])
        ys = np.stack([np.asarray(b.labels) for b in batches])

        def stack_masks(ms, name):
            have = [m is not None for m in ms]
            if not any(have):
                return None
            if not all(have):
                raise ValueError(
                    f"fit_scan needs {name} on every batch or on none "
                    f"(got a mix); mask the full dataset or use fit()")
            return np.stack([np.asarray(m) for m in ms])

        fmask = stack_masks([b.features_mask for b in batches],
                            "features_mask")
        lmask = stack_masks([b.labels_mask for b in batches], "labels_mask")

        return self.fit_scan_arrays(xs, ys, fmask, lmask, epochs=epochs,
                                    checkpoint_dir=checkpoint_dir,
                                    checkpoint_every=checkpoint_every,
                                    resume=resume, guard=guard)

    def fit_scan_arrays(self, xs, ys, fmask=None, lmask=None,
                        epochs: int = 1, *,
                        checkpoint_dir: Optional[str] = None,
                        checkpoint_every: int = 0, resume: bool = False,
                        guard=None):
        """fit_scan on pre-stacked [T, batch, ...] arrays. Pass
        device-resident arrays (jax.device_put once) to avoid re-paying the
        host->device transfer on every call.

        Listener caveat: iteration_done is replayed AFTER the scan with
        per-step scores, so every call sees the END-OF-WINDOW params —
        per-iteration param/update histograms are not faithful on this
        path (a warning fires for such listeners); use fit() for those."""
        from .conf import OptimizationAlgorithm as OA

        if self.params is None:
            self.init()
        if self.conf.conf.optimization_algo != OA.STOCHASTIC_GRADIENT_DESCENT:
            raise ValueError(
                "fit_scan_arrays supports SGD-updater training only; "
                "line-search optimizers (CG/LBFGS) are per-batch sequential "
                "— use fit()")
        tbptt = (self.conf.backprop_type == BackpropType.TRUNCATED_BPTT
                 and xs.ndim >= 4)
        firsts = None
        with _span("host/batch_prep"):
            xs_d, ys_d = jnp.asarray(xs), jnp.asarray(ys)
        fm_d = jnp.asarray(fmask) if fmask is not None else None
        lm_d = jnp.asarray(lmask) if lmask is not None else None
        if tbptt:
            # device-side chunking: keeps pre-transferred inputs resident
            L = self.conf.tbptt_fwd_length
            B, T_time = xs_d.shape[1], xs_d.shape[2]
            pad = (-T_time) % L
            if pad:
                if lm_d is None:
                    lm_d = jnp.ones(ys_d.shape[:3], jnp.float32)
                pad3 = lambda a: jnp.pad(a, [(0, 0), (0, 0), (0, pad)]
                                         + [(0, 0)] * (a.ndim - 3))
                xs_d, ys_d, lm_d = pad3(xs_d), pad3(ys_d), pad3(lm_d)
                if fm_d is not None:
                    fm_d = pad3(fm_d)
            nc = xs_d.shape[2] // L

            def chunked(a):
                # [S, B, nc*L, ...] -> [S*nc, B, L, ...]
                a = a.reshape((a.shape[0], a.shape[1], nc, L) + a.shape[3:])
                a = jnp.moveaxis(a, 2, 1)
                return a.reshape((a.shape[0] * nc, a.shape[2], L)
                                 + a.shape[4:])

            xs_d, ys_d = chunked(xs_d), chunked(ys_d)
            fm_d = chunked(fm_d) if fm_d is not None else None
            lm_d = chunked(lm_d) if lm_d is not None else None
            firsts = np.zeros(int(xs_d.shape[0]), np.float32)
            firsts[::nc] = 1.0
            carries0 = self._zero_carries(int(B), xs_d.dtype)
        key = (tuple(xs_d.shape), tuple(ys_d.shape), fm_d is not None,
               lm_d is not None, tbptt)
        cache = self.__dict__.setdefault("_scan_epoch_cache", {})
        epoch_fn = cache.get(key)
        if epoch_fn is None:
            epoch_fn = cache[key] = watch_compiles(
                self._make_scan_epoch(fm_d is not None, lm_d is not None,
                                      tbptt), "nn/scan_epoch")
        fs_d = jnp.asarray(firsts) if tbptt else None
        if self.listeners:
            from ..optimize.listeners import warn_scan_replay
            warn_scan_replay(self.listeners)
        from ..fault.resume import maybe_fit_checkpointer
        ckpt = maybe_fit_checkpointer(self, checkpoint_dir, checkpoint_every,
                                      resume,
                                      context=self._precision_remat_context())
        done_epochs = (ckpt.resume_into()[1] if ckpt is not None else 0)
        with (ckpt.sigterm_snapshot() if ckpt is not None else _null_span()):
            for _ in range(max(0, epochs - done_epochs)):
                for listener in self.listeners:
                    if hasattr(listener, "on_epoch_start"):
                        listener.on_epoch_start(self)
                # guard works at EPOCH granularity here (the whole epoch is
                # one dispatch): snapshot pre-epoch state (incl. rng) so a
                # non-finite epoch can be discarded wholesale
                snap = (guard._snapshot(self)
                        if guard is not None and guard._needs_snapshot
                        else None)
                self._rng, k = jax.random.split(self._rng)
                with _span("device/dispatch", kind="scan_epoch"):
                    (self.params, self.state, self.updater_state,
                     scores) = epoch_fn(
                        self.params, self.state, self.updater_state,
                        jnp.asarray(self.iteration_count, jnp.int32),
                        xs_d, ys_d, fm_d, lm_d, fs_d,
                        carries0 if tbptt else (), k)
                guard_scores = None
                if guard is not None:
                    with _span("device/sync", kind="guard_scores"):
                        guard_scores = np.asarray(scores)
                    if not guard.check_scores(self, guard_scores, snap):
                        # epoch discarded, pre-epoch state back — still
                        # balance on_epoch_start with on_epoch_end
                        for listener in self.listeners:
                            if hasattr(listener, "on_epoch_end"):
                                listener.on_epoch_end(self)
                        continue
                self.last_batch_size = int(xs_d.shape[1])
                self.last_input = xs_d[-1]   # last scanned batch (listeners)
                n_steps = int(xs_d.shape[0])
                if self.listeners:
                    if guard_scores is not None:
                        host_scores = guard_scores   # already synced
                    else:
                        with _span("device/sync", kind="scan_scores"):
                            host_scores = np.asarray(scores)
                    for i in range(n_steps):
                        self._score = host_scores[i]
                        self.iteration_count += 1
                        for listener in self.listeners:
                            listener.iteration_done(self,
                                                    self.iteration_count)
                else:
                    self._score = scores[-1]
                    self.iteration_count += n_steps
                for listener in self.listeners:
                    if hasattr(listener, "on_epoch_end"):
                        listener.on_epoch_end(self)
                self.epoch_count += 1
                if ckpt is not None:
                    ckpt.on_epoch()
                    ckpt.maybe_save()
            if ckpt is not None:
                ckpt.on_fit_end()
        return self

    def _make_scan_epoch(self, has_fmask, has_lmask, tbptt):
        step_fn = self.train_step_fn

        @jax.jit
        def epoch(params, state, opt_state, step0, xs, ys, fmask, lmask,
                  firsts, carries0, rng):
            keys = jax.random.split(rng, xs.shape[0])

            def body(carry, inp):
                params, state, opt, step, carries = carry
                x, y, fm, lm, first, k = inp
                if tbptt:
                    carries = jax.tree_util.tree_map(
                        lambda c: c * (1.0 - first), carries)
                    params, state, opt, score, carries = step_fn(
                        params, state, opt, step, x, y, k, fm, lm, carries)
                else:
                    params, state, opt, score = step_fn(
                        params, state, opt, step, x, y, k, fm, lm)
                return (params, state, opt, step + 1, carries), score

            inp = (xs, ys,
                   fmask if has_fmask else jnp.zeros((xs.shape[0],)),
                   lmask if has_lmask else jnp.zeros((xs.shape[0],)),
                   firsts if tbptt else jnp.zeros((xs.shape[0],)), keys)
            if not has_fmask or not has_lmask or not tbptt:
                # replace unused per-step slots with cheap dummies; the body
                # must see None for absent masks (static branch in loss)
                def body_wrap(carry, inp):
                    x, y, fm, lm, first, k = inp
                    return body(carry, (x, y,
                                        fm if has_fmask else None,
                                        lm if has_lmask else None,
                                        first, k))
                run_body = body_wrap
            else:
                run_body = body
            (params, state, opt, _step, _carries), scores = jax.lax.scan(
                run_body, (params, state, opt_state, step0, carries0), inp)
            return params, state, opt, scores

        return epoch

    @functools.cached_property
    def _line_solver(self):
        from ..optimize.solvers import LineSearchSolver
        return LineSearchSolver(
            self, self.conf.conf.optimization_algo,
            max_line_search_iterations=
            self.conf.conf.max_num_line_search_iterations)

    def _track_signature(self, x, y, fmask, lmask):
        self._track_signature_shapes(
            tuple(x.shape), tuple(np.shape(y)),
            None if fmask is None else tuple(fmask.shape),
            None if lmask is None else tuple(lmask.shape))

    def _track_signature_shapes(self, xs, ys, fs, ls):
        sig = (xs, ys, fs, ls)
        if sig not in self._batch_signatures:
            self._batch_signatures.add(sig)
            self.recompile_count += 1
            if self.recompile_count == 2:
                log.info(
                    "train step retracing for a second batch signature %s — "
                    "ragged final batches double compile time; use "
                    "fit(..., pad_ragged=True) (weight-zero padding, a "
                    "learning no-op) or ArrayDataSetIterator("
                    "drop_last=True)", sig)

    def _check_input_width(self, x):
        """Fail with a named error instead of a raw XLA shape error when the
        input shape doesn't match the configured InputType."""
        it = getattr(self.conf, "input_type", None)
        if it is None:
            return
        kind = getattr(it, "kind", None)
        if kind == "ff":
            if x.ndim >= 2 and x.shape[-1] != it.flat_size():
                raise ValueError(
                    f"input width {x.shape[-1]} != configured "
                    f"InputType.feed_forward({it.flat_size()})")
        elif kind == "rnn":
            if x.ndim == 3 and x.shape[-1] != it.size:
                raise ValueError(
                    f"input feature size {x.shape[-1]} != configured "
                    f"InputType.recurrent({it.size}, ...)")
            if x.ndim == 2:
                raise ValueError(
                    "recurrent network input must be 3-D [batch, time, "
                    f"features]; got 2-D {tuple(x.shape)} (use "
                    "rnn_time_step for single-step inference)")
        elif kind == "cnn":
            if x.ndim == 4 and tuple(x.shape[1:]) != (it.height, it.width,
                                                      it.channels):
                raise ValueError(
                    f"input shape {tuple(x.shape[1:])} != configured "
                    f"InputType.convolutional({it.height}, {it.width}, "
                    f"{it.channels}) (NHWC)")
        elif kind in ("cnn_flat", "cnn1d"):
            if x.ndim == 2 and x.shape[-1] != it.flat_size():
                raise ValueError(
                    f"input width {x.shape[-1]} != configured "
                    f"{kind} InputType flat size {it.flat_size()}")

    def _fit_batch(self, ds: DataSet):
        with _span("dl4j/fit/step") as fit_step:
            self._fit_step(ds)
            fit_step.set(iteration=self.iteration_count)

    def _fit_step(self, ds: DataSet):
        from .conf import OptimizationAlgorithm as OA

        tel = _tel_active()
        with _span("host/batch_prep"):
            x, y, fmask, lmask = ds.device_tuple()
            self._check_input_width(x)
        self.last_input = x   # reference setInput keeps the batch around;
        # listeners (e.g. ConvolutionalIterationListener) read it
        if (self.conf.backprop_type == BackpropType.TRUNCATED_BPTT
                and x.ndim == 3):
            # TBPTT traces per-chunk shapes; _fit_tbptt tracks those
            self._fit_tbptt(x, y, fmask, lmask)
            return
        self._track_signature(x, y, fmask, lmask)
        self._rng, step_rng = jax.random.split(self._rng)
        if self.conf.conf.optimization_algo != OA.STOCHASTIC_GRADIENT_DESCENT:
            # line-search path (Solver.java -> CG/LBFGS/line GD); the
            # updater chain is SGD-only, as in the reference's BaseOptimizer
            with _span("device/dispatch", kind="line_search"):
                self.params, self.state, score = self._line_solver.fit_batch(
                    self.params, self.state, x, y, step_rng, fmask, lmask)
        else:
            step = jnp.asarray(self.iteration_count, dtype=jnp.int32)
            with _span("device/dispatch", kind="train_step"):
                (self.params, self.state, self.updater_state,
                 score) = self._train_step(
                    self.params, self.state, self.updater_state, step, x, y,
                    step_rng, fmask, lmask)
        if tel is not None and tel.sync_per_step:
            with _span("device/sync"):
                jax.block_until_ready(score)
        self._score = score
        self.last_batch_size = int(x.shape[0])
        self.iteration_count += 1
        # a listener that reads the score blocks here until the device has
        # finished the step
        with _span("dl4j/fit/listeners"):
            for listener in self.listeners:
                listener.iteration_done(self, self.iteration_count)

    def _zero_carries(self, batch: int, dtype=jnp.float32):
        return tuple(
            layer.init_carry(batch, dtype)
            if getattr(layer, "is_recurrent", False) else None
            for layer in self.layers)

    def _fit_tbptt(self, x, y, fmask, lmask):
        """Truncated BPTT (reference `doTruncatedBPTT`,
        `MultiLayerNetwork.java:1119`): split the series into fwd-length
        chunks; hidden state flows forward between chunks, gradients do not."""
        tel = _tel_active()
        T = x.shape[1]
        L = self.conf.tbptt_fwd_length
        carries = self._zero_carries(int(x.shape[0]), x.dtype)
        for t0 in range(0, T, L):
            sl = slice(t0, min(t0 + L, T))
            # chunk signature computed arithmetically — no device slicing
            # just to read shapes
            n_t = sl.stop - t0
            chunk = lambda a: (None if a is None else
                               (a.shape[0], n_t) + tuple(a.shape[2:]))
            self._track_signature_shapes(
                chunk(x), chunk(y), chunk(fmask), chunk(lmask))
            self._rng, step_rng = jax.random.split(self._rng)
            step = jnp.asarray(self.iteration_count, dtype=jnp.int32)
            with _span("device/dispatch", kind="tbptt_chunk"):
                (self.params, self.state, self.updater_state, score,
                 carries) = self._tbptt_step(
                    self.params, self.state, self.updater_state, step,
                    x[:, sl], y[:, sl], step_rng,
                    None if fmask is None else fmask[:, sl],
                    None if lmask is None else lmask[:, sl], carries)
            if tel is not None and tel.sync_per_step:
                with _span("device/sync"):
                    jax.block_until_ready(score)
            self._score = score
            self.last_batch_size = int(x.shape[0])
            self.iteration_count += 1
            for listener in self.listeners:
                listener.iteration_done(self, self.iteration_count)

    # ------------------------------------------------------------------
    # Layerwise pretraining (reference `pretrain`, MultiLayerNetwork.java:161)
    # ------------------------------------------------------------------
    def pretrain(self, iterator: DataSetIterator, epochs: int = 1):
        """Greedy layerwise unsupervised pretraining of AE/RBM/VAE layers."""
        if self.params is None:
            self.init()
        for i, layer in enumerate(self.layers):
            if getattr(layer, "is_pretrainable", False):
                self.pretrain_layer(i, iterator, epochs)
        return self

    def pretrain_layer(self, i: int, iterator: DataSetIterator,
                       epochs: int = 1):
        layer = self.layers[i]
        if not getattr(layer, "is_pretrainable", False):
            return self
        if self.params is None:
            self.init()
        step_fn = self._make_pretrain_step(i)
        opt_i = self.updater_state[i]
        it_count = 0
        for _ in range(epochs):
            iterator.reset()
            while iterator.has_next():
                ds = iterator.next()
                self._rng, rng = jax.random.split(self._rng)
                new_pi, opt_i, score = step_fn(
                    self.params, self.state, opt_i,
                    jnp.asarray(it_count, jnp.int32),
                    jnp.asarray(ds.features), rng)
                params = list(self.params)
                params[i] = new_pi
                self.params = tuple(params)
                self._score = score
                it_count += 1
        opt = list(self.updater_state)
        opt[i] = opt_i
        self.updater_state = tuple(opt)
        return self

    def _make_pretrain_step(self, i: int):
        layer = self.layers[i]
        upd = self._layer_updater(layer)

        def pstep(params, state, opt_i, step, x, rng):
            rng_fwd, rng_p = jax.random.split(rng)
            h = x
            if i > 0:
                h, _, _, _ = self._forward(params, state, h, False, None,
                                           upto=i)
            # preprocessor feeding layer i (not applied by _forward(upto=i))
            if i in self.conf.preprocessors:
                h = self.conf.preprocessors[i].apply(h)
            score, grads = layer.pretrain_value_and_grad(params[i], h, rng_p)
            grads = apply_gradient_normalization(
                layer.gradient_normalization,
                layer.gradient_normalization_threshold or 1.0, grads)
            lr = self._layer_lr(layer, step)
            updates, opt_i = upd.update(grads, opt_i, step, lr)
            new_pi = {k: params[i][k] - updates[k] for k in params[i]}
            return new_pi, opt_i, score

        return watch_compiles(jax.jit(pstep), "nn/pretrain_step")

    # ------------------------------------------------------------------
    # Stateful RNN inference (reference rnnTimeStep / rnnClearPreviousState)
    # ------------------------------------------------------------------
    def rnn_time_step(self, x) -> jax.Array:
        """Feed one (or a few) timesteps, carrying hidden state across calls.
        x: [B, F] (single step) or [B, T, F]."""
        x = jnp.asarray(x)
        squeeze = x.ndim == 2
        if squeeze:
            x = x[:, None, :]
        if getattr(self, "_rnn_carries", None) is None:
            self._rnn_carries = self._zero_carries(int(x.shape[0]), x.dtype)
        out, self._rnn_carries = self._rnn_step_fn(self.params, self.state, x,
                                                   self._rnn_carries)
        return out[:, 0] if (squeeze and out.ndim == 3) else out

    def rnn_clear_previous_state(self):
        self._rnn_carries = None

    def rnn_get_previous_state(self, layer_idx: int):
        c = getattr(self, "_rnn_carries", None)
        return None if c is None else c[layer_idx]

    # ------------------------------------------------------------------
    # Inference / scoring
    # ------------------------------------------------------------------
    def output(self, x, train: bool = False, features_mask=None) -> jax.Array:
        if self.params is None:
            self.init()
        x = jnp.asarray(x)
        self._check_input_width(x)
        fm = None if features_mask is None else jnp.asarray(features_mask)
        return self._predict_fn(self.params, self.state, x, fm)

    def feed_forward(self, x) -> List[jax.Array]:
        """All layer activations (reference `feedForward`)."""
        x = jnp.asarray(x)
        acts = [x]
        mask = None
        for i, layer in enumerate(self.layers):
            if i in self.conf.preprocessors:
                x = self.conf.preprocessors[i].apply(x)
            x, _ = layer.apply(self.params[i], self.state[i], x,
                               train=False, rng=None, mask=mask)
            acts.append(x)
        return acts

    def predict(self, x) -> np.ndarray:
        """Argmax class predictions (reference `predict(INDArray)`)."""
        return np.asarray(jnp.argmax(self.output(x), axis=-1))

    def score(self, dataset: Optional[DataSet] = None) -> float:
        """Last minibatch score, or score of a given DataSet."""
        if dataset is None:
            return float(self._score)
        fm = None if dataset.features_mask is None else jnp.asarray(dataset.features_mask)
        lm = None if dataset.labels_mask is None else jnp.asarray(dataset.labels_mask)
        return float(self._score_fn(self.params, self.state,
                                    jnp.asarray(dataset.features),
                                    jnp.asarray(dataset.labels), fm, lm))

    def evaluate(self, iterator: DataSetIterator,
                 labels_list: Optional[Sequence[str]] = None,
                 top_n: int = 1) -> Evaluation:
        ev = Evaluation(labels=labels_list, top_n=top_n)
        iterator.reset()
        while iterator.has_next():
            ds = iterator.next()
            out = self.output(ds.features, features_mask=ds.features_mask)
            ev.eval(ds.labels, np.asarray(out), mask=ds.labels_mask)
        return ev

    @functools.cached_property
    def score_examples_fn(self):
        """Raw per-example scoring step (params, state, x, y, fmask, lmask,
        add_reg) -> [batch] — jitted by callers (see _score_examples_fn and
        the ParallelTrainer scoring plane)."""
        def per_example(params, state, x, y, fmask, lmask, add_reg):
            out_layer = self.layers[-1]
            n = len(self.layers)
            h, _, mask, _ = self._forward(params, state, x, False, None,
                                          fmask=fmask, upto=n - 1)
            if (n - 1) in self.conf.preprocessors:
                h = self.conf.preprocessors[n - 1].apply(h)
                mask = self.conf.preprocessors[n - 1].apply_mask(mask)
            eff_lmask = lmask if lmask is not None else mask
            per = out_layer.loss_per_example(params[-1], state[-1], h, y,
                                             mask=eff_lmask)
            if add_reg:
                per = per + self._reg_score(params)
            return per
        return per_example

    @functools.cached_property
    def _score_examples_fn(self):
        """add_reg static: at most two compiles (with/without reg terms)."""
        return watch_compiles(
            jax.jit(self.score_examples_fn, static_argnums=(6,)),
            "nn/score_examples")

    def score_examples(self, data, add_regularization_terms: bool = True
                       ) -> np.ndarray:
        """Per-example scores (loss values), NOT averaged over the batch —
        reference `MultiLayerNetwork.scoreExamples`
        (MultiLayerNetwork.java:1737 for iterators, :1754 for a DataSet).
        With `add_regularization_terms`, the full-network l1/l2 is added to
        each example's score, so row i equals `score(DataSet)` of that
        single example (the reference's documented equivalence). Accepts a
        DataSet or a DataSetIterator (scores concatenated in order)."""
        if self.params is None:
            self.init()
        if isinstance(data, DataSetIterator):
            data.reset()
            outs = []
            while data.has_next():
                outs.append(self.score_examples(data.next(),
                                                add_regularization_terms))
            return (np.concatenate(outs) if outs
                    else np.zeros(0, np.float32))
        if not isinstance(data, DataSet):
            raise TypeError(f"score_examples needs DataSet/iterator, got "
                            f"{type(data)}")
        fm = (None if data.features_mask is None
              else jnp.asarray(data.features_mask))
        lm = (None if data.labels_mask is None
              else jnp.asarray(data.labels_mask))
        per = self._score_examples_fn(self.params, self.state,
                                      jnp.asarray(data.features),
                                      jnp.asarray(data.labels), fm, lm,
                                      bool(add_regularization_terms))
        return np.asarray(per)

    def reconstruction_log_probability(self, x, num_samples: int = 5,
                                       seed: int = 0) -> np.ndarray:
        """Per-example importance-sampled reconstruction log-probability of a
        leading VariationalAutoencoder layer — the scoring quantity behind
        the reference's VAE anomaly-detection plane
        (`variational/VariationalAutoencoder.reconstructionLogProbability`,
        used by Spark's
        `BaseVaeReconstructionProbWithKeyFunctionAdapter.java:1`). The seed
        is explicit so distributed captures are reproducible."""
        from .layers.generative import VariationalAutoencoder
        if self.params is None:
            self.init()
        layer0 = self.layers[0]
        if not isinstance(layer0, VariationalAutoencoder):
            raise ValueError("reconstruction_log_probability requires the "
                             "first layer to be a VariationalAutoencoder "
                             f"(got {type(layer0).__name__})")
        fn = self._recon_logp_fn
        return np.asarray(fn(self.params[0], jnp.asarray(x),
                             jax.random.PRNGKey(seed), num_samples))

    @functools.cached_property
    def _recon_logp_fn(self):
        layer0 = self.layers[0]
        return watch_compiles(jax.jit(
            lambda p, x, rng, n: layer0.reconstruction_probability(
                p, x, rng, num_samples=n),
            static_argnums=(3,)), "nn/recon_logp")

    def reconstruction_probability(self, x, num_samples: int = 5,
                                   seed: int = 0) -> np.ndarray:
        return np.exp(self.reconstruction_log_probability(
            x, num_samples=num_samples, seed=seed))

    # ------------------------------------------------------------------
    # Introspection / param plumbing
    # ------------------------------------------------------------------
    @property
    def num_layers(self) -> int:
        return len(self.layers)

    def get_layer(self, i: int) -> LayerConf:
        return self.layers[i]

    def set_listeners(self, *listeners):
        self.listeners = list(listeners)
        return self

    def add_listeners(self, *listeners):
        self.listeners.extend(listeners)
        return self

    def num_params(self) -> int:
        return sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(self.params))

    def params_flat(self) -> np.ndarray:
        """Deterministic flattened view (layer order, sorted key paths;
        nested trees like BiLSTM's included) — the analog of the
        reference's single contiguous params buffer."""
        parts = [np.asarray(leaf).ravel()
                 for p in self.params for leaf in _flat_leaves(p)]
        return np.concatenate(parts) if parts else np.zeros(0, np.float32)

    def set_params_flat(self, vec: np.ndarray):
        vec = np.asarray(vec)
        to_array = lambda chunk, leaf: jnp.asarray(
            chunk.reshape(leaf.shape), dtype=leaf.dtype)
        pos = 0
        new_params = []
        for p in self.params:
            d, pos = _unflatten_like(p, vec, pos, to_array)
            new_params.append(d)
        self.params = tuple(new_params)

    def clone(self) -> "MultiLayerNetwork":
        m = MultiLayerNetwork(self.conf)
        if self.params is not None:
            # Deep-copy buffers: _train_step donates its inputs, so sharing
            # arrays with the original would leave the clone holding deleted
            # buffers after the original trains (and vice versa).
            copy = lambda a: jnp.array(a, copy=True)
            m.params = jax.tree_util.tree_map(copy, self.params)
            m.state = jax.tree_util.tree_map(copy, self.state)
            m.updater_state = jax.tree_util.tree_map(copy, self.updater_state)
            m._input_types = self._input_types
            m._rng = self._rng
        m.iteration_count = self.iteration_count
        return m


class _NetworkSuperstepAdapter:
    """SuperstepRunner hooks for MultiLayerNetwork (see nn/superstep.py):
    array-shaped batches, masks optional. With ``m>1`` dispatch routes the
    window through the accumulated superstep in [K, M] groups."""

    def __init__(self, net: MultiLayerNetwork, m: int = 1,
                 skip_nonfinite: bool = False):
        self.net = net
        self.m = int(m)
        self.skip_nonfinite = bool(skip_nonfinite)

    @staticmethod
    def _shape(a):
        return None if a is None else tuple(np.shape(a))

    def signature(self, ds):
        x = ds.features
        if not hasattr(x, "ndim"):
            x = np.asarray(x)
        self.net._check_input_width(x)
        return (self._shape(ds.features), self._shape(ds.labels),
                self._shape(ds.features_mask), self._shape(ds.labels_mask))

    def batch_nbytes(self, ds):
        from ..datasets.pipeline import batch_nbytes
        return batch_nbytes((ds.features, ds.labels, ds.features_mask,
                             ds.labels_mask))

    def stage(self, window):
        from ..datasets.pipeline import stage_window
        return stage_window([ds.device_tuple() for ds in window])

    def dispatch(self, staged, n, step0):
        net = self.net
        if self.m == 1:
            xs, ys, fm, lm = staged
            (net.params, net.state, net.updater_state, net._rng,
             scores) = net._superstep_fn(
                net.params, net.state, net.updater_state,
                jnp.asarray(step0, jnp.int32), net._rng, xs, ys, fm, lm)
            return scores
        from .superstep import dispatch_accum_groups
        fn = net._accum_superstep_fn(self.skip_nonfinite)

        def run_group(seg, step):
            xs, ys, fm, lm = seg
            (net.params, net.state, net.updater_state, net._rng, scores,
             mscores) = fn(net.params, net.state, net.updater_state,
                           jnp.asarray(step, jnp.int32), net._rng,
                           xs, ys, fm, lm)
            return scores, mscores

        return dispatch_accum_groups(staged, n, self.m, step0, run_group)

    def on_window_end(self, window):
        net = self.net
        last = window[-1]
        net.last_input = last.device_tuple()[0]
        net.last_batch_size = int(np.shape(last.features)[0])
        net._track_signature_shapes(
            self._shape(last.features), self._shape(last.labels),
            self._shape(last.features_mask), self._shape(last.labels_mask))
