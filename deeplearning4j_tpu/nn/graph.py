"""ComputationGraph — the DAG model.

Capability parity with `nn/graph/ComputationGraph.java:79` (2447 LoC):
multiple inputs/outputs, vertex system, topological execution, fit on
DataSet/MultiDataSet, evaluate, rnn state. TPU-first design mirrors
MultiLayerNetwork: params/state are dicts keyed by vertex name, the whole DAG
(all vertices in topo order) traces into ONE jitted train step, backward via
`jax.grad` of the summed output losses.
"""
from __future__ import annotations

import functools
import logging
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import functools as _functools

from .conf import NeuralNetConfiguration
from .conf.base import LayerConf, cast_floating
from .conf.graph import ComputationGraphConfiguration, GraphVertex
from .gradnorm import apply_gradient_normalization
from .layers.feedforward import BaseOutputLayerConf
from ..datasets.iterators import DataSet, DataSetIterator, MultiDataSet
from ..eval.evaluation import Evaluation
from ..telemetry.compile_watch import watch_compiles
from ..telemetry.runtime import (active as _tel_active,
                                 null_span as _null_span, span as _span)
from ..telemetry.tracing import named_step

log = logging.getLogger("deeplearning4j_tpu")

__all__ = ["ComputationGraph"]


class ComputationGraph:
    # everything a training step mutates — TrainingGuard snapshot scope
    _fault_state_attrs = ("params", "state", "updater_state", "_rng",
                          "iteration_count", "epoch_count", "_score")

    def __init__(self, conf: ComputationGraphConfiguration):
        self.conf = conf
        self.iteration_count = 0
        self.epoch_count = 0
        self.listeners = []
        self.last_batch_size = 0
        self.params: Optional[Dict[str, Dict]] = None
        self.state: Optional[Dict[str, Dict]] = None
        self.updater_state: Optional[Dict[str, Any]] = None
        self._score = float("nan")
        self._rng = None

    # ------------------------------------------------------------------
    @property
    def layer_vertices(self) -> Dict[str, LayerConf]:
        return {k: v for k, v in self.conf.vertices.items()
                if isinstance(v, LayerConf)}

    def get_layer(self, name: str) -> LayerConf:
        return self.conf.vertices[name]

    @property
    def topological_order(self) -> List[str]:
        return self.conf.topological_order

    def init(self, seed: Optional[int] = None) -> "ComputationGraph":
        """Make the parameters from the seed, under the span `dl4j/nn/init`
        (`layers`, `leaves`, `given` 0)."""
        with _span("dl4j/nn/init", layers=len(self.layer_vertices),
                   given=0) as span:
            self._init(seed)
            span.set(leaves=len(jax.tree_util.tree_leaves(self.params)))
        return self

    def _init(self, seed):
        from . import activations as _acts
        for layer in self.layer_vertices.values():
            if layer.activation is not None:  # fail fast on bad names
                _acts.get(layer.activation)
        seed = self.conf.conf.seed if seed is None else seed
        self._rng = jax.random.PRNGKey(seed)
        self._rng, init_rng = jax.random.split(self._rng)
        names = sorted(self.layer_vertices)
        rngs = dict(zip(names, jax.random.split(init_rng, max(1, len(names)))))
        params, state = {}, {}
        for name, layer in self.layer_vertices.items():
            it = self._input_type_for(name)
            params[name] = layer.init_params(rngs[name], it)
            state[name] = layer.init_state(it)
        self.params = params
        self.state = state
        self.updater_state = {
            name: self._layer_updater(self.conf.vertices[name]).init(p)
            for name, p in params.items()}

    def _input_type_for(self, name):
        rec = self.conf.inferred_input_types.get(name)
        if rec is not None:
            it = rec[1]
            if isinstance(it, list):
                it = it[0]
            return it
        from .conf.input_type import InputType
        layer = self.conf.vertices[name]
        n_in = getattr(layer, "n_in", None)
        if layer.has_params and not n_in:
            raise ValueError(
                f"Vertex '{name}' needs n_in or graph input_types")
        return InputType.feed_forward(n_in or 0)

    def _layer_updater(self, layer):
        return (layer.updater if isinstance(layer, LayerConf) and layer.updater
                else self.conf.conf.updater)

    @_functools.cached_property
    def _compute_dtype(self):
        """jnp dtype for mixed-precision compute, or None when disabled."""
        cdt = self.conf.conf.compute_dtype
        if cdt is None or jnp.dtype(cdt) == jnp.dtype(self.conf.conf.dtype):
            return None
        return jnp.dtype(cdt)

    def _precision_remat_context(self):
        """FitCheckpointer context entries (see MultiLayerNetwork) — the
        policies whose mismatch a resume should warn about."""
        c = self.conf.conf
        return {"compute_dtype": c.compute_dtype, "remat": c.remat,
                "remat_policy": c.remat_policy}

    # ------------------------------------------------------------------
    # Functional core
    # ------------------------------------------------------------------
    def _apply_vertex(self, name, rng_i, values, masks, new_state,
                      new_carries, params, state, train, cdt, out_set,
                      carries):
        """Apply one vertex in place (values/masks/new_state/new_carries are
        mutated). Shared by the plain topo loop and the remat-segment path."""
        v = self.conf.vertices[name]
        in_names = self.conf.vertex_inputs[name]
        ins = [values[i_] for i_ in in_names]
        in_masks = [masks.get(i_) for i_ in in_names]
        if isinstance(v, LayerConf):
            x = ins[0]
            m = in_masks[0]
            rec = self.conf.inferred_input_types.get(name)
            if rec is not None and rec[0] is not None:
                x = rec[0].apply(x)
                m = rec[0].apply_mask(m)
            if name in out_set and isinstance(v, BaseOutputLayerConf):
                values[name] = (x, m)  # defer loss/activation to caller
                masks[name] = m
                return
            p_v = params[name]
            # Mixed precision: hidden vertices compute in cdt; output
            # layers keep master-dtype params (see MultiLayerNetwork).
            if cdt is not None and not isinstance(v, BaseOutputLayerConf):
                p_v = cast_floating(p_v, cdt)
            if carries is not None and getattr(v, "is_recurrent", False):
                (y, new_carries[name]), new_state[name] = v.apply(
                    p_v, state[name], x, train=train, rng=rng_i,
                    mask=m, carry=carries.get(name), return_carry=True)
            else:
                y, new_state[name] = v.apply(p_v, state[name], x,
                                             train=train, rng=rng_i,
                                             mask=m)
            values[name] = y
            masks[name] = v.output_mask(m)
        else:
            values[name] = v.apply(ins, in_masks)
            masks[name] = v.output_mask(in_masks)

    def _forward_values(self, params, state, inputs: Dict[str, Any], train,
                        rng, fmasks: Optional[Dict[str, Any]] = None,
                        stop_at_outputs: bool = False, carries=None):
        """Execute vertices in topo order. Returns (values, masks, new_state)
        — or (values, masks, new_state, new_carries) when `carries` (a dict
        keyed by recurrent vertex name) is given, for stateful streaming
        inference (reference ComputationGraph.rnnTimeStep).
        Output-layer vertices contribute their *pre-activation input* (the
        caller applies loss or activation)."""
        cdt = self._compute_dtype
        if cdt is not None:
            inputs = {k: (v.astype(cdt)
                          if jnp.issubdtype(jnp.asarray(v).dtype, jnp.floating)
                          else v) for k, v in inputs.items()}
        values: Dict[str, Any] = dict(inputs)
        new_carries: Dict[str, Any] = {}
        masks: Dict[str, Any] = dict(fmasks or {})
        for k in self.conf.network_inputs:
            masks.setdefault(k, None)
        new_state = dict(state)
        layer_names = [n for n in self.conf.topological_order
                       if n in self.conf.vertices]
        rngs = (jax.random.split(rng, max(1, len(layer_names)))
                if rng is not None else [None] * len(layer_names))
        out_set = set(self.conf.network_outputs) if stop_at_outputs else set()
        remat = self.conf.conf.remat
        if remat in ("layer", "blocks") and train and carries is None:
            if all(m is None for m in masks.values()):
                self._forward_segments(
                    remat, layer_names, rngs, values, masks, new_state,
                    params, state, train, cdt, out_set)
                return values, masks, new_state
            import warnings
            warnings.warn(
                f"remat={remat!r} is inactive for this step: segment "
                "checkpointing does not support mask arrays — training "
                "falls back to the save-everything path (no activation "
                "memory savings)", stacklevel=3)
        for i, name in enumerate(layer_names):
            self._apply_vertex(name, rngs[i], values, masks, new_state,
                               new_carries, params, state, train, cdt,
                               out_set, carries)
        if carries is not None:
            return values, masks, new_state, new_carries
        return values, masks, new_state

    @_functools.cached_property
    def _block_segments(self) -> List[List[str]]:
        """Partition the topo order into remat segments, cutting wherever
        exactly ONE value is live (consumed by later vertices). For residual
        nets the skip connection keeps the block input live across the block,
        so cuts land on block boundaries; linear chains cut at every vertex
        (≡ per-layer checkpointing)."""
        layer_names = [n for n in self.conf.topological_order
                       if n in self.conf.vertices]
        pos = {n: i for i, n in enumerate(layer_names)}
        last_use: Dict[str, int] = {}
        for j, n in enumerate(layer_names):
            for src in self.conf.vertex_inputs[n]:
                last_use[src] = max(last_use.get(src, -1), j)
        outputs = set(self.conf.network_outputs)
        segments: List[List[str]] = []
        cur: List[str] = []
        for i, n in enumerate(layer_names):
            cur.append(n)
            if i == len(layer_names) - 1:
                cut = True
            else:
                live = {v for v, lu in last_use.items()
                        if lu > i and pos.get(v, -1) <= i}
                live |= {o for o in outputs if pos.get(o, len(layer_names)) <= i}
                cut = live == {n}
            if cut:
                segments.append(cur)
                cur = []
        if cur:
            segments.append(cur)
        return segments

    def _forward_segments(self, remat, layer_names, rngs, values, masks,
                          new_state, params, state, train, cdt, out_set):
        """Run the topo order as jax.checkpoint segments: only segment
        boundaries (and the small per-segment state updates) are saved for
        backward; intra-segment activations are rematerialized. Mutates
        values/masks/new_state (masks stay None — guarded by caller)."""
        pos = {n: i for i, n in enumerate(layer_names)}
        segments = ([[n] for n in layer_names] if remat == "layer"
                    else self._block_segments)
        last_use: Dict[str, int] = {}
        for j, n in enumerate(layer_names):
            for src in self.conf.vertex_inputs[n]:
                last_use[src] = max(last_use.get(src, -1), j)
        for seg in segments:
            seg_set = set(seg)
            seg_end = pos[seg[-1]]
            boundary = {}
            for n in seg:
                for src in self.conf.vertex_inputs[n]:
                    if src not in seg_set:
                        boundary[src] = values[src]
            seg_params = {n: params[n] for n in seg if n in params}
            seg_state = {n: state[n] for n in seg if n in state}
            seg_rngs = ([rngs[pos[n]] for n in seg]
                        if rngs[0] is not None else None)
            if seg_rngs is not None:
                seg_rngs = jnp.stack(seg_rngs)
            keep = [n for n in seg
                    if last_use.get(n, -1) > seg_end or n in out_set]

            def seg_fn(boundary, seg_params, seg_state, seg_rngs,
                       _seg=tuple(seg), _keep=tuple(keep)):
                vals = dict(boundary)
                msk = {k: None for k in vals}
                ns: Dict[str, Any] = {}
                for k, name in enumerate(_seg):
                    r = seg_rngs[k] if seg_rngs is not None else None
                    self._apply_vertex(name, r, vals, msk, ns, {},
                                       seg_params, seg_state, train, cdt,
                                       out_set, None)
                return {n: vals[n] for n in _keep}, ns

            from .remat import resolve_policy
            res, ns = jax.checkpoint(
                seg_fn,
                policy=resolve_policy(self.conf.conf.remat_policy))(
                    boundary, seg_params, seg_state, seg_rngs)
            values.update(res)
            masks.update({n: None for n in res})
            new_state.update(ns)

    def _reg_score(self, params):
        """Full-network l1/l2 penalty (MultiLayerNetwork._reg_score
        counterpart — single source for every scoring path)."""
        reg = jnp.float32(0.0)
        for name, p in params.items():
            if p:
                reg = reg + self.conf.vertices[name].reg_score(p)
        return reg

    def _loss_fn(self, params, state, inputs, labels, rng, fmasks=None,
                 lmasks=None, train=True):
        """labels: dict {output_name: labels}; lmasks likewise."""
        values, masks, new_state = self._forward_values(
            params, state, inputs, train, rng, fmasks, stop_at_outputs=True)
        total = jnp.float32(0.0)
        batch = next(iter(inputs.values())).shape[0]
        live = jnp.zeros((batch,), jnp.float32)
        all_masked = True
        for i, name in enumerate(self.conf.network_outputs):
            v = self.conf.vertices[name]
            if not isinstance(v, BaseOutputLayerConf):
                raise ValueError(
                    f"Network output '{name}' must be an output/loss layer "
                    "for training")
            x, m = values[name]
            lm = (lmasks or {}).get(name)
            eff = lm if lm is not None else m
            # output layers may carry input dropout (e.g. GoogLeNet's 0.6
            # head) — give each output head its own key
            out_rng = (jax.random.fold_in(rng, i)
                       if (rng is not None and train) else None)
            total = total + v.loss_score(params[name], state[name], x,
                                         labels[name], train=train,
                                         rng=out_rng, mask=eff)
            if eff is None:
                all_masked = False
            else:
                live = jnp.maximum(live, eff.astype(jnp.float32).reshape(
                    (eff.shape[0], -1)).max(axis=1))
        # Regularization normalizes by REAL rows (live in ANY output's
        # mask), not the padded batch size, so PadToBatchIterator's
        # weight-zero rows are a learning no-op (each output's loss is
        # already a masked mean); an unmasked output counts every row
        if all_masked:
            batch = jnp.maximum(jnp.sum(live), 1.0)
        score = total + self._reg_score(params) / batch
        # layer auxiliary losses (MoE router load balancing) — train only
        if train:
            for name, s in new_state.items():
                v = self.conf.vertices.get(name)
                if v is not None and hasattr(v, "aux_score"):
                    score = score + v.aux_score(s)
        return score, new_state

    def _make_train_step(self):
        base_loss = self._loss_fn
        if self.conf.conf.remat == "full":
            # save only the step inputs; recompute the entire forward in
            # backward (jax.checkpoint over the whole loss)
            from .remat import resolve_policy
            pol = resolve_policy(self.conf.conf.remat_policy)

            def loss_fn(params, state, inputs, labels, rng,
                        fmasks=None, lmasks=None):
                f = lambda p, s, i_, l_, r_: base_loss(
                    p, s, i_, l_, r_, fmasks=fmasks, lmasks=lmasks)
                return jax.checkpoint(f, policy=pol)(params, state, inputs,
                                                     labels, rng)
        else:
            loss_fn = base_loss

        def train_step(params, state, opt_state, step, inputs, labels, rng,
                       fmasks, lmasks):
            (score, new_state), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, state, inputs, labels,
                                       rng, fmasks=fmasks,
                                       lmasks=lmasks)
            if not self.conf.conf.minimize:
                grads = jax.tree_util.tree_map(lambda g: -g, grads)
            new_params, new_opt = self.apply_vertex_updates(
                params, grads, opt_state, step)
            return new_params, new_state, new_opt, score

        return named_step("train_step", train_step)

    def apply_vertex_updates(self, params, grads, opt_state, step):
        """Apply per-vertex updaters to the gradient tree — the update
        half of the train step, shared with the ZeRO sharded-optimizer
        step (parallel/zero.py), which reduces the gradients itself and
        needs only the update applied. Pure/traceable."""
        new_params, new_opt = {}, {}
        for name, p in params.items():
            layer = self.conf.vertices[name]
            g, os = grads[name], opt_state[name]
            if not p or layer.frozen:
                new_params[name] = p
                new_opt[name] = os
                continue
            g = apply_gradient_normalization(
                layer.gradient_normalization,
                layer.gradient_normalization_threshold or 1.0, g)
            upd = self._layer_updater(layer)
            lr = self._layer_lr(layer, step)
            updates, os = upd.update(g, os, step, lr)
            if getattr(layer, "bias_learning_rate", None) is not None:
                # same bias-lr rescale as the multilayer step (updater
                # steps are linear in lr, so rescaling is exact)
                from .multilayer import _rescale_bias_updates
                if lr is None:
                    eff = getattr(upd, "learning_rate", 1.0) or 1.0
                    scale = layer.bias_learning_rate / eff
                else:
                    scale = layer.bias_learning_rate / jnp.maximum(
                        jnp.asarray(lr, jnp.float32), 1e-30)
                updates = _rescale_bias_updates(updates, scale)
            # tree-wise: vertex params may be nested dicts (BiLSTM)
            new_params[name] = jax.tree_util.tree_map(
                lambda a, u: a - u, p, updates)
            new_opt[name] = os
        return new_params, new_opt

    def _layer_lr(self, layer, step):
        sched = self.conf.conf.lr_schedule
        base = layer.learning_rate
        if sched is None:
            return base
        lr = sched(step)
        if base is not None and sched.base_lr:
            lr = lr * (base / sched.base_lr)
        return lr

    @functools.cached_property
    def train_step_fn(self):
        return self._make_train_step()

    @functools.cached_property
    def grad_step_fn(self):
        """Gradient half of the graph train step — ``(params, state,
        inputs, labels, rng, fmasks, lmasks) -> (score, new_state,
        grads)`` with remat="full" and the minimize sign folded in
        (MultiLayerNetwork.grad_step_fn counterpart; composed by the
        accumulation superstep and the ZeRO step)."""
        base_loss = self._loss_fn
        if self.conf.conf.remat == "full":
            from .remat import resolve_policy
            pol = resolve_policy(self.conf.conf.remat_policy)

            def loss_fn(params, state, inputs, labels, rng,
                        fmasks=None, lmasks=None):
                f = lambda p, s, i_, l_, r_: base_loss(
                    p, s, i_, l_, r_, fmasks=fmasks, lmasks=lmasks)
                return jax.checkpoint(f, policy=pol)(params, state, inputs,
                                                     labels, rng)
        else:
            loss_fn = base_loss
        minimize = self.conf.conf.minimize

        def grad_step(params, state, inputs, labels, rng, fmasks, lmasks):
            (score, new_state), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, state, inputs, labels, rng,
                                       fmasks=fmasks, lmasks=lmasks)
            if not minimize:
                grads = jax.tree_util.tree_map(lambda g: -g, grads)
            return score, new_state, grads

        return grad_step

    def apply_updates(self, params, grads, opt_state, step):
        """Update half on a full gradient tree (apply_vertex_updates under
        the shared grad/update split the accumulation and ZeRO steps
        compose). Pure/traceable."""
        return self.apply_vertex_updates(params, grads, opt_state, step)

    def _accum_superstep_fn(self, skip_nonfinite: bool):
        """Jitted accumulated superstep over stacked input/label DICT
        windows [K, M, batch, ...] (None mask leaves pass through as
        static absence) — see nn/superstep.build_accum_superstep. Cached
        per skip flag; K/M are shape-derived."""
        cache = self.__dict__.setdefault("_accum_superstep_cache", {})
        fn = cache.get(bool(skip_nonfinite))
        if fn is None:
            from .superstep import build_accum_superstep
            fn = cache[bool(skip_nonfinite)] = watch_compiles(
                jax.jit(build_accum_superstep(self.grad_step_fn,
                                              self.apply_updates,
                                              bool(skip_nonfinite)),
                        donate_argnums=(0, 1, 2)),
                "graph/accum_superstep")
        return fn

    @functools.cached_property
    def _train_step(self):
        return watch_compiles(
            jax.jit(self.train_step_fn, donate_argnums=(0, 1, 2)),
            "graph/train_step")

    @functools.cached_property
    def predict_fn(self):
        """Raw (unjitted) pure inference step — for callers that jit it
        themselves with custom shardings (distributed evaluation plane)."""
        def predict(params, state, inputs, fmasks):
            values, masks, _ = self._forward_values(
                params, state, inputs, False, None, fmasks,
                stop_at_outputs=True)
            return self._collect_outputs(params, state, values)
        return predict

    @functools.cached_property
    def _predict_fn(self):
        return watch_compiles(jax.jit(self.predict_fn), "graph/predict")

    def _collect_outputs(self, params, state, values):
        """Activate the network outputs from forward values (shared by the
        predict and rnn-step paths)."""
        outs = []
        for name in self.conf.network_outputs:
            v = self.conf.vertices[name]
            if isinstance(v, BaseOutputLayerConf):
                x, m = values[name]
                y, _ = v.apply(params[name], state[name], x, train=False,
                               rng=None, mask=m)
            else:
                y = values[name]
            outs.append(y)
        return tuple(outs)

    @functools.cached_property
    def _score_fn(self):
        def score(params, state, inputs, labels, fmasks, lmasks):
            s, _ = self._loss_fn(params, state, inputs, labels, None,
                                 fmasks=fmasks, lmasks=lmasks, train=False)
            return s
        return watch_compiles(jax.jit(score), "graph/score")

    # ------------------------------------------------------------------
    # Data plumbing
    # ------------------------------------------------------------------
    def _to_inputs(self, ds) -> Tuple[Dict, Dict, Dict, Dict]:
        ins = self.conf.network_inputs
        outs = self.conf.network_outputs
        if isinstance(ds, DataSet):
            if len(ins) != 1 or len(outs) != 1:
                raise ValueError("DataSet fits single-input/single-output "
                                 "graphs; use MultiDataSet")
            x, y, fm, lm = ds.device_tuple()
            return ({ins[0]: x}, {outs[0]: y}, {ins[0]: fm}, {outs[0]: lm})
        if isinstance(ds, MultiDataSet):
            f, l, fm, lm = ds.device_tuple()
            inputs = dict(zip(ins, f))
            labels = dict(zip(outs, l))
            fm = fm or (None,) * len(ins)
            lm = lm or (None,) * len(outs)
            fmasks = dict(zip(ins, fm))
            lmasks = dict(zip(outs, lm))
            return inputs, labels, fmasks, lmasks
        raise TypeError(f"Cannot fit on {type(ds)}")

    # ------------------------------------------------------------------
    # Public API
    # ------------------------------------------------------------------
    def fit(self, data, epochs: int = 1, *, superstep=1,
            grad_accumulation: int = 1, prefetch: bool = False,
            pad_ragged: bool = False, time_buckets=None,
            checkpoint_dir: Optional[str] = None, checkpoint_every: int = 0,
            resume: bool = False, guard=None):
        """fit(DataSet/MultiDataSet) or fit(iterator). `pad_ragged` pads
        ragged final batches to the fixed batch size with weight-zero rows
        (one train-step compile per fit, learning no-op); `prefetch` moves
        `device_tuple()` to a background thread one batch ahead so
        host->device transfer overlaps compute (see datasets/pipeline.py).

        `superstep=K` (iterator inputs) runs windows of K batches as ONE
        jitted `lax.scan` dispatch — bit-identical to the K=1 per-batch
        loop, with listeners/guard/checkpoints firing at superstep edges
        on the per-window loss vector (see nn/superstep.py). "auto" sizes
        K from batch bytes and adapts it to the measured dispatch/compute
        ratio, "epoch" windows the whole epoch. Line-search optimizers
        fall back to per-batch dispatch.

        `grad_accumulation=M` accumulates M consecutive iterator
        microbatches into one optimizer step (fp32 accumulators, update on
        the mean — effective batch M·b at b's activation memory), exactly
        as on `MultiLayerNetwork.fit`; composes with `superstep` (windows
        of K·M microbatches), listener/checkpoint cadence per optimizer
        step.

        Fault-tolerance knobs (`checkpoint_dir`/`checkpoint_every`/
        `resume`/`guard`) behave exactly as on `MultiLayerNetwork.fit`:
        crash-safe interval checkpoints + SIGTERM snapshot, resume that
        replays counters/RNG/shuffle epoch so it matches an uninterrupted
        run, and a TrainingGuard applying its non-finite-loss policy per
        batch (see fault/)."""
        from .superstep import validate_grad_accumulation
        accum_m = validate_grad_accumulation(grad_accumulation)
        if self.params is None:
            self.init()
        if isinstance(data, (DataSet, MultiDataSet)):
            if checkpoint_dir is not None or resume:
                raise ValueError(
                    "checkpoint_dir/resume need an iterator fit (the "
                    "checkpoint records epoch/batch progress)")
            if accum_m != 1:
                raise ValueError(
                    f"grad_accumulation={accum_m} needs an iterator fit "
                    "(M consecutive microbatches form one optimizer step)")
            if superstep != 1:
                log.info("superstep=%r ignored for a single-DataSet fit "
                         "(one batch is one step); pass an iterator to "
                         "window batches", superstep)
            if guard is not None:
                guard.run_step(self, lambda: self._fit_batch(data))
            else:
                self._fit_batch(data)
            return self
        from ..fault.resume import maybe_fit_checkpointer
        ckpt = maybe_fit_checkpointer(self, checkpoint_dir, checkpoint_every,
                                      resume,
                                      context={"grad_accumulation": accum_m})
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
                    data.reset()
                    if runner is not None:
                        runner.run_epoch(data)
                    else:
                        while data.has_next():
                            ds = (guard.next_batch(data) if guard is not None
                                  else data.next())
                            if skip:
                                skip -= 1   # resume: prefix already trained
                                continue
                            if guard is not None:
                                guard.run_step(self,
                                               lambda b=ds: self._fit_batch(b))
                            else:
                                self._fit_batch(ds)
                            if ckpt is not None:
                                ckpt.on_batch()
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
        (superstep=1 with grad_accumulation=1, or a line-search
        optimizer — which rejects M>1 rather than silently changing the
        effective batch)."""
        from .conf import OptimizationAlgorithm as OA
        from .superstep import (SuperstepRunner, accum_skip_nonfinite,
                                validate_superstep)

        k = validate_superstep(superstep)
        if k == 1 and accum_m == 1:
            return None
        if self.conf.conf.optimization_algo != OA.STOCHASTIC_GRADIENT_DESCENT:
            if accum_m != 1:
                raise ValueError(
                    f"grad_accumulation={accum_m} is not supported with "
                    "line-search optimizers (per-batch sequential)")
            log.info("superstep=%r falls back to per-batch dispatch: "
                     "line-search optimizers are per-batch sequential",
                     superstep)
            return None
        adapter = _GraphSuperstepAdapter(
            self, m=accum_m,
            skip_nonfinite=accum_skip_nonfinite(guard, accum_m))
        return SuperstepRunner(self, adapter, k, guard=guard, ckpt=ckpt,
                               grad_accumulation=accum_m)

    @_functools.cached_property
    def _superstep_fn(self):
        """Device-resident superstep: `lax.scan` of the graph train step
        over a [K, batch, ...] window of stacked input/label dicts, RNG
        chain threaded inside — bit-identical to the per-batch loop (see
        nn/superstep.py)."""
        from .superstep import build_superstep
        return watch_compiles(
            jax.jit(build_superstep(self.train_step_fn),
                    donate_argnums=(0, 1, 2)),
            "graph/superstep")

    @_functools.cached_property
    def _line_solver(self):
        from ..optimize.solvers import GraphLineSearchSolver
        return GraphLineSearchSolver(
            self, self.conf.conf.optimization_algo,
            max_line_search_iterations=
            self.conf.conf.max_num_line_search_iterations)

    def _fit_batch(self, ds):
        with _span("dl4j/fit/step") as fit_step:
            self._fit_step(ds)
            fit_step.set(iteration=self.iteration_count)

    def _fit_step(self, ds):
        from .conf import OptimizationAlgorithm as OA

        tel = _tel_active()
        with _span("host/batch_prep"):
            inputs, labels, fmasks, lmasks = self._to_inputs(ds)
        self._rng, step_rng = jax.random.split(self._rng)
        if self.conf.conf.optimization_algo != OA.STOCHASTIC_GRADIENT_DESCENT:
            with _span("device/dispatch", kind="line_search"):
                self.params, self.state, score = self._line_solver.fit_batch(
                    self.params, self.state, inputs, labels, step_rng,
                    fmasks, lmasks)
            self._score = score
            self.last_batch_size = int(
                next(iter(inputs.values())).shape[0])
            self.iteration_count += 1
            self._iteration_done()
            return
        step = jnp.asarray(self.iteration_count, jnp.int32)
        with _span("device/dispatch", kind="train_step"):
            (self.params, self.state, self.updater_state,
             score) = self._train_step(self.params, self.state,
                                       self.updater_state, step, inputs,
                                       labels, step_rng, fmasks, lmasks)
        if tel is not None and tel.sync_per_step:
            with _span("device/sync"):
                jax.block_until_ready(score)
        self._score = score
        self.last_batch_size = int(next(iter(inputs.values())).shape[0])
        self.iteration_count += 1
        self._iteration_done()

    def _iteration_done(self):
        # a listener that reads the score blocks here until the device has
        # finished the step
        with _span("dl4j/fit/listeners"):
            for listener in self.listeners:
                listener.iteration_done(self, self.iteration_count)

    def fit_scan_arrays(self, xs, ys, epochs: int = 1):
        """Device-resident multi-step training: the whole [T]-step pass runs
        as ONE `lax.scan` dispatch (MultiLayerNetwork.fit_scan_arrays
        analog for graphs). `xs`: [T, batch, ...] array (single-input
        graphs) or dict {input_name: [T, batch, ...]}; `ys` likewise for
        outputs. Pass device-resident arrays (jax.device_put once) so the
        window is not re-uploaded on every call.

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
                "line-search optimizers are per-batch sequential — use fit()")
        if not isinstance(xs, dict):
            xs = {self.conf.network_inputs[0]: xs}
        if not isinstance(ys, dict):
            ys = {self.conf.network_outputs[0]: ys}
        with _span("host/batch_prep"):
            xs = {k: jnp.asarray(v) for k, v in xs.items()}
            ys = {k: jnp.asarray(v) for k, v in ys.items()}
        key = (tuple(sorted((k, tuple(v.shape), str(v.dtype))
                            for k, v in xs.items())),
               tuple(sorted((k, tuple(v.shape), str(v.dtype))
                            for k, v in ys.items())))
        cache = self.__dict__.setdefault("_scan_epoch_cache", {})
        epoch_fn = cache.get(key)
        if epoch_fn is None:
            step_fn = self.train_step_fn

            @jax.jit
            def epoch_fn(params, state, opt, step0, xs, ys, rng):
                n = next(iter(xs.values())).shape[0]
                keys = jax.random.split(rng, n)

                def body(carry, inp):
                    params, state, opt, step = carry
                    xt, yt, k = inp
                    params, state, opt, score = step_fn(
                        params, state, opt, step, xt, yt, k, None, None)
                    return (params, state, opt, step + 1), score

                (params, state, opt, _), scores = jax.lax.scan(
                    body, (params, state, opt, step0), (xs, ys, keys))
                return params, state, opt, scores

            epoch_fn = cache[key] = watch_compiles(epoch_fn,
                                                   "graph/scan_epoch")
        n_steps = int(next(iter(xs.values())).shape[0])
        if self.listeners:
            from ..optimize.listeners import warn_scan_replay
            warn_scan_replay(self.listeners)
        for _ in range(epochs):
            self._rng, k = jax.random.split(self._rng)
            with _span("device/dispatch", kind="scan_epoch"):
                (self.params, self.state, self.updater_state,
                 scores) = epoch_fn(
                    self.params, self.state, self.updater_state,
                    jnp.asarray(self.iteration_count, jnp.int32), xs, ys, k)
            self.last_batch_size = int(next(iter(xs.values())).shape[1])
            if self.listeners:
                with _span("device/sync", kind="scan_scores"):
                    host_scores = np.asarray(scores)
                for i in range(n_steps):
                    self._score = host_scores[i]
                    self.iteration_count += 1
                    for listener in self.listeners:
                        listener.iteration_done(self, self.iteration_count)
            else:
                self._score = scores[-1]
                self.iteration_count += n_steps
            self.epoch_count += 1
        return self

    def output(self, *features, features_masks=None):
        if self.params is None:
            self.init()
        ins = self.conf.network_inputs
        inputs = {n: jnp.asarray(f) for n, f in zip(ins, features)}
        fmasks = {n: None for n in ins}
        if features_masks is not None:
            fmasks = {n: (None if m is None else jnp.asarray(m))
                      for n, m in zip(ins, features_masks)}
        return self._predict_fn(self.params, self.state, inputs, fmasks)

    def output_single(self, *features, **kw):
        return self.output(*features, **kw)[0]

    # -- stateful RNN inference (reference ComputationGraph.rnnTimeStep) --
    @functools.cached_property
    def _rnn_step_fn(self):
        def step(params, state, inputs, carries):
            values, masks, _, new_carries = self._forward_values(
                params, state, inputs, False, None, None,
                stop_at_outputs=True, carries=carries)
            return self._collect_outputs(params, state, values), new_carries
        return watch_compiles(jax.jit(step), "graph/rnn_step")

    def rnn_time_step(self, *features):
        """Feed one (or a few) timesteps through the graph, carrying hidden
        state of every recurrent vertex across calls. 2-D inputs are
        treated as single timesteps per input (mixed-rank multi-input
        graphs keep their static inputs 2-D)."""
        if self.params is None:
            self.init()
        xs = [jnp.asarray(f) for f in features]
        squeeze = xs[0].ndim == 2
        xs = [x[:, None, :] if x.ndim == 2 else x for x in xs]
        inputs = dict(zip(self.conf.network_inputs, xs))
        batch = int(xs[0].shape[0])
        carries = getattr(self, "_rnn_carries", None)
        if carries:  # non-empty: a graph with no recurrent vertices caches {}
            cached_batch = jax.tree_util.tree_leaves(carries)[0].shape[0]
            if cached_batch != batch:
                raise ValueError(
                    f"rnn_time_step batch changed from {cached_batch} to "
                    f"{batch}; call rnn_clear_previous_state() first")
        if carries is None:
            rec = {name: v for name, v in self.conf.vertices.items()
                   if getattr(v, "is_recurrent", False)}
            not_stepable = [n for n, v in rec.items()
                            if not hasattr(v, "init_carry")]
            if not_stepable:
                raise ValueError(
                    f"rnn_time_step unsupported for vertices "
                    f"{not_stepable} (bidirectional layers need the full "
                    "sequence — the reference rejects these too)")
            carries = {name: v.init_carry(batch, xs[0].dtype)
                       for name, v in rec.items()}
        outs, self._rnn_carries = self._rnn_step_fn(
            self.params, self.state, inputs, carries)
        if squeeze:
            outs = tuple(o[:, 0] if o.ndim == 3 else o for o in outs)
        return outs if len(outs) > 1 else outs[0]

    def rnn_clear_previous_state(self):
        self._rnn_carries = None

    def score(self, ds=None) -> float:
        if ds is None:
            return float(self._score)
        inputs, labels, fmasks, lmasks = self._to_inputs(ds)
        return float(self._score_fn(self.params, self.state, inputs, labels,
                                    fmasks, lmasks))

    @functools.cached_property
    def score_examples_fn(self):
        """Raw per-example scoring step — jitted by callers (see
        _score_examples_fn and the ParallelTrainer scoring plane)."""
        def per_example(params, state, inputs, labels, fmasks, lmasks,
                        add_reg):
            values, masks, _ = self._forward_values(
                params, state, inputs, False, None, fmasks,
                stop_at_outputs=True)
            per = None
            for name in self.conf.network_outputs:
                v = self.conf.vertices[name]
                x, m = values[name]
                lm = (lmasks or {}).get(name)
                eff = lm if lm is not None else m
                contrib = v.loss_per_example(params[name], state[name], x,
                                             labels[name], mask=eff)
                per = contrib if per is None else per + contrib
            if add_reg:
                per = per + self._reg_score(params)
            return per
        return per_example

    @functools.cached_property
    def _score_examples_fn(self):
        return watch_compiles(
            jax.jit(self.score_examples_fn, static_argnums=(6,)),
            "graph/score_examples")

    def score_examples(self, data, add_regularization_terms: bool = True
                       ) -> np.ndarray:
        """Per-example scores summed over all output layers — reference
        `ComputationGraph.scoreExamples` (ComputationGraph.java; the map
        half of Spark's `ScoreExamplesFunction.java:1`). Accepts DataSet /
        MultiDataSet or an iterator thereof."""
        if self.params is None:
            self.init()
        if not isinstance(data, (DataSet, MultiDataSet)):
            data.reset()
            outs = []
            while data.has_next():
                outs.append(self.score_examples(data.next(),
                                                add_regularization_terms))
            return (np.concatenate(outs) if outs
                    else np.zeros(0, np.float32))
        inputs, labels, fmasks, lmasks = self._to_inputs(data)
        per = self._score_examples_fn(self.params, self.state, inputs,
                                      labels, fmasks, lmasks,
                                      bool(add_regularization_terms))
        return np.asarray(per)

    def evaluate(self, iterator, labels_list=None, top_n: int = 1) -> Evaluation:
        ev = Evaluation(labels=labels_list, top_n=top_n)
        iterator.reset()
        while iterator.has_next():
            ds = iterator.next()
            if isinstance(ds, DataSet):
                out = self.output(ds.features,
                                  features_masks=[ds.features_mask])[0]
                ev.eval(ds.labels, np.asarray(out), mask=ds.labels_mask)
            else:
                outs = self.output(*ds.features,
                                   features_masks=ds.features_masks)
                for o, l, m in zip(outs, ds.labels,
                                   ds.labels_masks or [None] * len(ds.labels)):
                    ev.eval(l, np.asarray(o), mask=m)
        return ev

    def set_listeners(self, *listeners):
        self.listeners = list(listeners)
        return self

    def num_params(self) -> int:
        return sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(self.params))

    def params_flat(self) -> np.ndarray:
        from .multilayer import _flat_leaves
        parts = [np.asarray(leaf).ravel()
                 for name in sorted(self.params)
                 for leaf in _flat_leaves(self.params[name])]
        return np.concatenate(parts) if parts else np.zeros(0, np.float32)

    def set_params_flat(self, vec: np.ndarray):
        from .multilayer import _unflatten_like
        vec = np.asarray(vec)
        to_array = lambda chunk, leaf: jnp.asarray(
            chunk.reshape(leaf.shape), dtype=leaf.dtype)
        pos = 0
        new_params = {}
        for name in sorted(self.params):
            new_params[name], pos = _unflatten_like(
                self.params[name], vec, pos, to_array)
        self.params = new_params

    def clone(self) -> "ComputationGraph":
        g = ComputationGraph(self.conf)
        if self.params is not None:
            copy = lambda a: jnp.array(a, copy=True)
            g.params = jax.tree_util.tree_map(copy, self.params)
            g.state = jax.tree_util.tree_map(copy, self.state)
            g.updater_state = jax.tree_util.tree_map(copy, self.updater_state)
            g._rng = self._rng
        g.iteration_count = self.iteration_count
        return g


class _GraphSuperstepAdapter:
    """SuperstepRunner hooks for ComputationGraph (see nn/superstep.py):
    batches are dicts keyed by input/output name (DataSet or MultiDataSet
    sources), masks are dicts whose values may be None — None leaves pass
    through the scan as the same static absence the per-batch step sees.
    With ``m>1`` dispatch routes the window through the accumulated
    superstep in [K, M] groups."""

    def __init__(self, net: ComputationGraph, m: int = 1,
                 skip_nonfinite: bool = False):
        self.net = net
        self.m = int(m)
        self.skip_nonfinite = bool(skip_nonfinite)

    @staticmethod
    def _shape(a):
        return None if a is None else tuple(np.shape(a))

    def signature(self, ds):
        if isinstance(ds, MultiDataSet):
            seq = lambda xs: (None if xs is None else
                              tuple(self._shape(a) for a in xs))
            return (seq(ds.features), seq(ds.labels),
                    seq(ds.features_masks), seq(ds.labels_masks))
        return (self._shape(ds.features), self._shape(ds.labels),
                self._shape(ds.features_mask), self._shape(ds.labels_mask))

    def batch_nbytes(self, ds):
        from ..datasets.pipeline import batch_nbytes
        if isinstance(ds, MultiDataSet):
            arrays = list(ds.features) + list(ds.labels)
            for ms in (ds.features_masks, ds.labels_masks):
                if ms is not None:
                    arrays.extend(ms)
            return batch_nbytes(arrays)
        return batch_nbytes((ds.features, ds.labels, ds.features_mask,
                             ds.labels_mask))

    def stage(self, window):
        from ..datasets.pipeline import stage_window
        return stage_window([self.net._to_inputs(ds) for ds in window])

    def dispatch(self, staged, n, step0):
        net = self.net
        if self.m == 1:
            xs, ys, fms, lms = staged
            (net.params, net.state, net.updater_state, net._rng,
             scores) = net._superstep_fn(
                net.params, net.state, net.updater_state,
                jnp.asarray(step0, jnp.int32), net._rng, xs, ys, fms, lms)
            return scores
        from .superstep import dispatch_accum_groups
        fn = net._accum_superstep_fn(self.skip_nonfinite)

        def run_group(seg, step):
            xs, ys, fms, lms = seg
            (net.params, net.state, net.updater_state, net._rng, scores,
             mscores) = fn(net.params, net.state, net.updater_state,
                           jnp.asarray(step, jnp.int32), net._rng,
                           xs, ys, fms, lms)
            return scores, mscores

        return dispatch_accum_groups(staged, n, self.m, step0, run_group)

    def on_window_end(self, window):
        last = window[-1]
        feats = (last.features[0] if isinstance(last, MultiDataSet)
                 else last.features)
        self.net.last_batch_size = int(np.shape(feats)[0])
