"""Pipeline parallelism over a mesh axis.

NEW capability relative to the reference (SURVEY.md §2.4: pipeline parallelism
absent). Two generations live here:

**Mesh-native 1F1B (ISSUE 15, the production path).** `PipelinePlan` +
`make_pp_step`/`make_pp_accum_superstep` compile an ENTIRE M-microbatch
optimizer step into ONE SPMD program on a (data, model, pipe) mesh: the
model's homogeneous layer run (e.g. the TransformerBlock depth) is
stage-stacked on a leading axis sharded over "pipe", and a single
`lax.scan` over microbatch slots ticks activations through the stage ring
— the stacked buffer shift lowers to XLA `collective-permute`s that ride
ONLY the pipe axis (the GSPMD pipelining formulation; the IR lint budgets
verify no permute leaks onto `data`/`model`). The scan is differentiable
end-to-end, so `jax.value_and_grad` derives the backward pipeline as the
transposed reverse scan (reverse collective-permutes) inside the SAME
compiled program: warmup / steady interleaved forward-backward / cooldown
with bubble fraction (S-1)/(M+S-1) — the non-interleaved 1F1B number —
at ONE XLA dispatch per optimizer step instead of the host-driven
O(stages·microbatches) storm below. Stage activation residuals are
rematerialized per tick (`jax.checkpoint` on the stage body; the saved
set is policy-selectable via `remat_policy`, accounted by
`pp_stage_saved_bytes`), bounding what the backward holds live. The step
honors `compute_dtype` mixed precision with the same bf16-compute/
fp32-master semantics as every other fit path. Composed into `ParallelTrainer` as
`strategy="pp"` (pure pipe) and `"zero1_tp_pp"` (ZeRO-1 moments over
`data` × Megatron TP over `model` × 1F1B over `pipe`).

**Host-driven GPipe (legacy).** `PipelinedNetworkTrainer`
/ `PipelinedGraphTrainer` run the GPipe two-phase schedule host-side with
per-stage jits — dozens of dispatches per step. Kept for
`ComputationGraph` and for models whose heterogeneous stages the SPMD
formulation cannot stack.

Restriction (standard for SPMD pipelining): pipelined stages must share one
program = identical layer structure and [.., F] -> [.., F] activation shape.
Heterogeneous head/tail layers (embedding, classifier) run replicated outside
the pipe region.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..datasets.iterators import DataSet
from ..telemetry.compile_watch import watch_compiles

__all__ = ["pipeline_forward", "PipelinedDenseStack",
           "PipelinedNetworkTrainer", "PipelinedGraphTrainer",
           "PipelinePlan", "make_pp_step", "make_pp_accum_superstep",
           "pp_stage_saved_bytes"]


# ===========================================================================
# Mesh-native 1F1B pipeline (ISSUE 15)
# ===========================================================================

def _conf_eq(a, b) -> bool:
    """Layer-conf equality for stage homogeneity. Dataclass `==` compares
    every field, but updater objects are plain classes whose default
    equality is identity — two identically-built Adam(1e-3) instances
    must still count as the same stage program."""
    import dataclasses

    if type(a) is not type(b):
        return False
    for f in dataclasses.fields(a):
        va, vb = getattr(a, f.name), getattr(b, f.name)
        if f.name == "updater":
            if va is None and vb is None:
                continue
            if va is None or vb is None or type(va) is not type(vb) \
                    or vars(va) != vars(vb):
                return False
            continue
        if va != vb:
            return False
    return True


def _tree_sig(tree):
    """(structure, shapes, dtypes) signature of a pytree — two layers are
    stackable iff their param/state signatures match exactly."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return (treedef, tuple((np.shape(l), np.dtype(jnp.result_type(l)))
                           for l in leaves))


class PipelinePlan:
    """Static stage partition of a `MultiLayerNetwork` for the mesh-native
    1F1B step.

    Finds the longest contiguous run of IDENTICAL layers (same conf, same
    param/state signature — the `TransformerBlock` depth of an LM, the
    hidden run of a uniform MLP), splits it into `S = mesh.shape[pipe]`
    stages of `v` layers each, and provides the stack/unstack maps between
    the model's per-layer tuples and the pipeline ("pp") form:

        {"head": (per-layer trees before the run),
         "stack": (v slot trees, each leaf [S, ...] — slot r of stage s is
                   model layer lo + s*v + r),
         "tail": (per-layer trees from the run's end, incl. the loss head)}

    Head/tail run replicated over `pipe` (every pipe group member computes
    them redundantly — they are tiny next to the stage run); only the
    stacked region is pipe-sharded, and only its activation handoffs cross
    pipe boundaries.
    """

    def __init__(self, model, mesh: Mesh, pipe_axis: str = "pipe",
                 model_axis: str = "model", data_axis: str = "data",
                 tp: bool = False):
        from ..nn.graph import ComputationGraph
        from ..nn.layers.feedforward import BaseOutputLayerConf

        if isinstance(model, ComputationGraph):
            raise ValueError(
                "the mesh-native pipeline strategies stack a MultiLayer"
                "Network's homogeneous layer run; ComputationGraph models "
                "are not supported — use strategy='pipeline' (host-driven "
                "GPipe) or a chain model")
        if model.params is None:
            model.init()
        layers = model.layers
        n = len(layers)
        if n < 2 or not isinstance(layers[-1], BaseOutputLayerConf):
            raise ValueError("last layer must be an output/loss layer")
        for i, layer in enumerate(layers):
            if getattr(layer, "is_recurrent", False):
                raise ValueError(
                    f"layer {i} ({type(layer).__name__}) is recurrent — "
                    "the 1F1B step supports feed-forward models only")
            if hasattr(layer, "aux_score"):
                raise ValueError(
                    f"layer {i} ({type(layer).__name__}) carries an "
                    "auxiliary loss (aux_score) the pipelined loss does "
                    "not propagate; use a SYNC strategy for MoE models")
        self.model = model
        self.mesh = mesh
        self.pipe_axis = pipe_axis
        self.model_axis = model_axis
        self.data_axis = data_axis
        self.tp = bool(tp)
        S = int(mesh.shape[pipe_axis])
        if S < 2:
            raise ValueError(
                f"pipeline needs a pipe axis of size >= 2, got {S} — "
                "build the mesh with mesh_shape=(d, m, p)")
        self.n_stages = S

        # longest homogeneous run among the non-output layers
        sigs = [(layers[i], _tree_sig(model.params[i]),
                 _tree_sig(model.state[i])) for i in range(n - 1)]
        best = (0, 0)   # (length, lo)
        i = 0
        while i < n - 1:
            j = i + 1
            while j < n - 1 and _conf_eq(sigs[j][0], sigs[i][0]) \
                    and sigs[j][1] == sigs[i][1] and sigs[j][2] == sigs[i][2]:
                j += 1
            if j - i > best[0]:
                best = (j - i, i)
            i = j
        L, lo = best
        if L < S:
            raise ValueError(
                f"model has no homogeneous layer run of >= {S} identical "
                f"layers to stage over the pipe axis (longest run: {L}). "
                "Pipeline the repeated block depth (e.g. TransformerBlock "
                "x depth) or shrink the pipe axis")
        if L % S:
            raise ValueError(
                f"homogeneous run of {L} layers does not divide into "
                f"{S} pipeline stages — use a depth divisible by the "
                f"pipe-axis size (e.g. {(L // S) * S} or {(L // S + 1) * S} "
                "layers)")
        self.lo, self.hi = lo, lo + L
        self.slots = L // S
        for i in range(self.lo + 1, self.hi):
            if i in model.conf.preprocessors:
                raise ValueError(
                    f"preprocessor at layer {i} sits inside the pipelined "
                    "stage run [" f"{self.lo}, {self.hi}) — stages must "
                    "share one program; move it outside the homogeneous "
                    "run or use strategy='pipeline'")

    # -- stack/unstack between per-layer tuples and pp form ---------------
    def stack(self, per_layer):
        """Per-layer sequence (params, state or updater state) -> pp form
        (pure jnp — usable at placement time and inside jit)."""
        lo, hi, S, v = self.lo, self.hi, self.n_stages, self.slots
        head = tuple(per_layer[:lo])
        tail = tuple(per_layer[hi:])
        stack = tuple(
            jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs),
                *[per_layer[lo + s * v + r] for s in range(S)])
            if jax.tree_util.tree_leaves(per_layer[lo + r])
            else per_layer[lo + r]
            for r in range(v))
        return {"head": head, "stack": stack, "tail": tail}

    def unstack(self, pp):
        """pp form -> per-layer tuple congruent with model.layers."""
        lo, hi, S, v = self.lo, self.hi, self.n_stages, self.slots
        mid = [None] * (S * v)
        for r, slot in enumerate(pp["stack"]):
            for s in range(S):
                mid[s * v + r] = jax.tree_util.tree_map(
                    lambda a, _s=s: a[_s], slot) \
                    if jax.tree_util.tree_leaves(slot) else slot
        return tuple(pp["head"]) + tuple(mid) + tuple(pp["tail"])

    def unstack_host(self, pp):
        """Host-side unstack (device_get first): the publish/_sync_back
        path — re-assembling a per-layer view must not run S gather
        programs against the live sharded buffers."""
        host = jax.tree_util.tree_map(lambda a: np.asarray(a), pp)
        per_layer = PipelinePlan.unstack(self, host)
        return tuple(jax.tree_util.tree_map(jnp.asarray, t)
                     for t in per_layer)

    # -- shardings --------------------------------------------------------
    def _tp_entries(self, layer, key, shape):
        from .sharding import _tp_spec_for

        if not self.tp or self.model_axis not in self.mesh.axis_names \
                or int(self.mesh.shape[self.model_axis]) < 2:
            return ()
        return tuple(_tp_spec_for(key, shape, self.model_axis, self.mesh,
                                  layer=layer))

    def param_specs(self):
        """pp-form PartitionSpec tree: stacked leaves P(pipe, *tp...),
        head/tail leaves the plain TP spec (or replicated)."""
        m = self.model
        if self.tp:
            size = int(dict(self.mesh.shape).get(self.model_axis, 1))
            for layer in m.layers:
                validate = getattr(layer, "tp_validate", None)
                if validate is not None:
                    validate(size)

        def leaf_specs(layer, tree, stacked):
            def spec(path, leaf):
                key = str(path[-1].key) if path and hasattr(path[-1], "key") \
                    else ""
                shape = np.shape(leaf)
                if stacked:
                    entries = self._tp_entries(layer, key, shape[1:])
                    return P(self.pipe_axis, *entries)
                return P(*self._tp_entries(layer, key, shape)) \
                    if self.tp else P()
            return jax.tree_util.tree_map_with_path(spec, tree)

        head = tuple(leaf_specs(m.layers[i], m.params[i], False)
                     for i in range(self.lo))
        tail = tuple(leaf_specs(m.layers[i], m.params[i], False)
                     for i in range(self.hi, len(m.layers)))
        params_pp = self.stack(m.params)
        stack = tuple(leaf_specs(m.layers[self.lo + r],
                                 params_pp["stack"][r], True)
                      for r in range(self.slots))
        return {"head": head, "stack": stack, "tail": tail}

    def state_specs(self):
        """pp-form specs for layer state: stacked leaves P(pipe),
        everything else replicated."""
        m = self.model
        rep = lambda t: jax.tree_util.tree_map(lambda a: P(), t)
        state_pp = self.stack(m.state)
        return {"head": tuple(rep(s) for s in state_pp["head"]),
                "stack": tuple(jax.tree_util.tree_map(
                    lambda a: P(self.pipe_axis), s)
                    for s in state_pp["stack"]),
                "tail": tuple(rep(s) for s in state_pp["tail"])}

    def shardings(self, specs):
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P))

    # -- regularization / update halves ----------------------------------
    def reg_score(self, params_pp):
        """Full-network l1/l2 penalty on pp-form params. Per-layer
        penalties are elementwise sums, so a stacked slot's penalty over
        its [S, ...] leaves equals the sum of the S per-layer penalties
        (identical confs by construction)."""
        m = self.model
        total = jnp.float32(0.0)
        for i in range(self.lo):
            p = params_pp["head"][i]
            if p:
                total = total + m.layers[i].reg_score(p)
        for r in range(self.slots):
            p = params_pp["stack"][r]
            if p:
                total = total + m.layers[self.lo + r].reg_score(p)
        for k, i in enumerate(range(self.hi, len(m.layers))):
            p = params_pp["tail"][k]
            if p:
                total = total + m.layers[i].reg_score(p)
        return total

    def apply_updates(self, params_pp, grads_pp, opt_pp, step):
        """The update half on pp-form trees: head/tail through the
        model's own `apply_layer_updates`, stacked slots through the SAME
        helper vmapped over the stage axis as a one-layer slice
        (elementwise updaters + per-tensor gradient normalization are
        exactly per-layer under vmap; stage confs are identical by
        construction — one source of truth for the update math)."""
        m = self.model
        head_p, head_o = m.apply_layer_updates(
            m.layers[:self.lo], list(params_pp["head"]),
            list(grads_pp["head"]), list(opt_pp["head"]), step)
        tail_p, tail_o = m.apply_layer_updates(
            m.layers[self.hi:], list(params_pp["tail"]),
            list(grads_pp["tail"]), list(opt_pp["tail"]), step)
        stack_p, stack_o = [], []
        for r in range(self.slots):
            conf = m.layers[self.lo + r]
            p, g, o = (params_pp["stack"][r], grads_pp["stack"][r],
                       opt_pp["stack"][r])
            if not p or conf.frozen:
                stack_p.append(p)
                stack_o.append(o)
                continue

            def one(p1, g1, o1, _conf=conf):
                np1, no1 = m.apply_layer_updates(
                    [_conf], [p1], [g1], [o1], step)
                return np1[0], no1[0]

            np_, no_ = jax.vmap(one)(p, g, o)
            stack_p.append(np_)
            stack_o.append(no_)
        return ({"head": tuple(head_p), "stack": tuple(stack_p),
                 "tail": tuple(tail_p)},
                {"head": tuple(head_o), "stack": tuple(stack_o),
                 "tail": tuple(tail_o)})


#: with_sharding_constraint sites the 1F1B builder emits into one forward
#: trace (inject buffer, post-inject buf, post-stage y, post-roll buf, out
#: buffer) — the declared schedule half of the IR contract. The traced
#: program carries AT LEAST this many `sharding_constraint` eqns (the AD
#: transpose re-emits the in-loss sites); a count below it means a stage
#: constraint was dropped and GSPMD propagation is free to replicate the
#: pipe-sharded buffers.
PP_CONSTRAINT_SITES = 5


def _stage_body(plan: "PipelinePlan", cdt=None):
    """ONE stage's v-layer forward (vmapped over the stage axis and
    wrapped in the policy-aware jax.checkpoint by the caller). Factored
    out of `_pp_loss_fn` so `pp_stage_saved_bytes` measures EXACTLY the
    body the step checkpoints. `cdt` = mixed-precision compute dtype:
    slot params are cast per tick (stage layers are never output
    layers, so the cast covers every slot)."""
    from ..nn.conf.base import cast_floating

    layers, lo, v = plan.model.layers, plan.lo, plan.slots

    def stage_apply(slot_params, slot_states, x, keys):
        new_states = []
        for r in range(v):
            p_r = (slot_params[r] if cdt is None
                   else cast_floating(slot_params[r], cdt))
            x, s_r = layers[lo + r].apply(
                p_r, slot_states[r], x, train=True,
                rng=keys[r], mask=None)
            new_states.append(s_r)
        return x, tuple(new_states)

    return stage_apply


def pp_stage_saved_bytes(plan: "PipelinePlan", micro_shape,
                         policy: Optional[str] = None) -> int:
    """Static activation-byte accounting for the 1F1B stage checkpoint
    (the `_ZeroPlan.info` counterpart for rematerialization): bytes of
    intermediate residuals ONE ring tick's checkpointed stage body saves
    for backward under the named `nn/remat.py` policy, for a stage-entry
    activation of shape `micro_shape` (microbatch rows first, NO stage
    axis — e.g. ``(mb, T, width)`` for the transformer LM). policy=None
    is the blanket save-nothing boundary (0 by construction);
    policy="everything" is what an UN-checkpointed stage would hold —
    the baseline the selective policies are measured against. Pure
    trace-time accounting: nothing is executed on device."""
    from ..nn.remat import saved_bytes

    m = plan.model
    S, v = plan.n_stages, plan.slots
    cdt = m._compute_dtype
    zeros = lambda t: jax.tree_util.tree_map(
        lambda a: jnp.zeros(a.shape, a.dtype), t)
    params_stack = zeros(plan.stack(m.params)["stack"])
    state_stack = zeros(plan.stack(m.state)["stack"])
    dtype = cdt if cdt is not None else jnp.dtype(m.conf.conf.dtype)
    buf = jnp.zeros((S,) + tuple(micro_shape), dtype)
    keys = jnp.zeros((S, v, 2), jnp.uint32)
    vstage = jax.vmap(_stage_body(plan, cdt))
    return saved_bytes(vstage, params_stack, state_stack, buf, keys,
                       policy=policy)


def _pp_loss_fn(plan: PipelinePlan, mutate: Optional[str] = None):
    """Build the pipelined M-microbatch loss:

        loss_fn(params_pp, state_pp, keys[M, 2], xs[M, mb, ...],
                ys[M, mb, ...], lms or None)
            -> (mean_score, (new_state_pp, micro_scores[M]))

    Per-microbatch math mirrors `MultiLayerNetwork._loss_fn` exactly —
    the same `jax.random.split` chain (micro key -> (forward, out_rng) ->
    per-layer keys), the same preprocessor application points, the same
    masked-mean loss + live-row-normalized regularization — so an M-step
    is equivalent to `fit(grad_accumulation=M)` on the identical
    microbatches at f32-ulp (the pipeline reassociates matmuls into the
    stage-batched form; nothing else differs).

    `mutate` (IR-probe seeding only — never a training path):
      "drop_stage_constraint"  emit NO buffer sharding constraints
      "permute_data_axis"      additionally roll the INJECTION buffer
                               along its data-sharded row axis (a halo
                               exchange before the ring scan) — a
                               collective-permute leaking onto `data`
    """
    from ..nn.conf.base import cast_floating
    from ..nn.remat import resolve_policy

    m = plan.model
    layers = m.layers
    n = len(layers)
    lo, hi, S, v = plan.lo, plan.hi, plan.n_stages, plan.slots
    preproc = m.conf.preprocessors
    mesh = plan.mesh
    # bf16-compute / fp32-master (ISSUE 18): same semantics as
    # MultiLayerNetwork._forward — floating inputs cast once, hidden
    # layers compute on cast params (the cast's cotangent returns in the
    # master dtype), the output layer keeps master params so softmax/
    # loss stay f32. The old compute_dtype rejection is lifted.
    cdt = m._compute_dtype
    # selective remat (ISSUE 18): the stage layers' (inherited) policy
    # decides what each ring tick's checkpoint boundary saves — the
    # stage run is homogeneous, so layers[lo] speaks for every slot
    stage_policy = resolve_policy(getattr(layers[lo], "remat_policy",
                                          None))
    pipe, data = plan.pipe_axis, plan.data_axis
    drop_constraints = mutate == "drop_stage_constraint"
    permute_data = mutate == "permute_data_axis"
    if mutate not in (None, "drop_stage_constraint", "permute_data_axis"):
        raise ValueError(f"unknown mutation {mutate!r}")

    def constrain(x, spec):
        if drop_constraints:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, spec))

    def micro_keys(k):
        # the _loss_fn chain: k -> (forward rng, out_rng); forward rng ->
        # one key per non-output layer (upto = n - 1)
        rng_f, out_rng = jax.random.split(k)
        lk = jax.random.split(rng_f, max(1, n - 1))
        return lk, out_rng

    def head_apply(params_head, state_head, x, lk):
        new_state = list(state_head)
        for i in range(lo):
            if i in preproc:
                x = preproc[i].apply(x)
            p_i = (params_head[i] if cdt is None
                   else cast_floating(params_head[i], cdt))
            x, new_state[i] = layers[i].apply(
                p_i, state_head[i], x, train=True, rng=lk[i],
                mask=None)
        if lo in preproc:
            x = preproc[lo].apply(x)
        return x, tuple(new_state)

    # ONE stage's v layers; vmapped over the stage axis below (confs are
    # identical across stages by PipelinePlan construction)
    stage_apply = _stage_body(plan, cdt)

    def tail_loss(params_tail, state_tail, h, y, lk, out_rng, lm):
        new_state = list(state_tail)
        for k, i in enumerate(range(hi, n - 1)):
            if i in preproc:
                h = preproc[i].apply(h)
            p_k = (params_tail[k] if cdt is None
                   else cast_floating(params_tail[k], cdt))
            h, new_state[k] = layers[i].apply(
                p_k, state_tail[k], h, train=True, rng=lk[i],
                mask=None)
        if (n - 1) in preproc:
            h = preproc[n - 1].apply(h)
        # output layer on MASTER params: its matmul promotes cdt
        # activations back up, softmax/loss stay f32
        loss = layers[-1].loss_score(params_tail[-1], state_tail[-1], h, y,
                                     train=True, rng=out_rng, mask=lm)
        return loss, tuple(new_state)

    def loss_fn(params_pp, state_pp, keys, xs, ys, lms):
        f32 = jnp.float32
        if cdt is not None and jnp.issubdtype(xs.dtype, jnp.floating):
            xs = xs.astype(cdt)
        M = xs.shape[0]
        T = M + S - 1
        lk_all, out_all = jax.vmap(micro_keys)(keys)   # [M, n-1, 2], [M, 2]
        pipe_keys = lk_all[:, lo:hi].reshape(M, S, v, 2)
        reg = plan.reg_score(params_pp)

        # one-hot [M] selectors replace every TRACED-index read/write on
        # the microbatch-slot buffers inside the ring scan: a
        # dynamic-update-slice on a mesh-sharded buffer inside a
        # differentiated while loop trips XLA's partitioned-DUS index
        # typing under x64 (s64 loop index vs s32 partition offset — the
        # same verifier bug the accum supersteps dodge with carried int32
        # buffers), while the one-hot contraction partitions cleanly and
        # its AD transpose is another contraction. Values are
        # bit-identical: one slot carries 1.0, the rest contribute exact
        # zeros.
        slot_iota = jnp.arange(M, dtype=jnp.int32)

        def onehot(i):
            return (slot_iota == i).astype(f32)

        def read_slot(buf_m, i):
            # selector cast to the buffer dtype (1.0/0.0 are exact in
            # bf16 too) so mixed-precision buffers don't promote to f32
            oh = onehot(i).astype(buf_m.dtype).reshape(
                (M,) + (1,) * (buf_m.ndim - 1))
            return jnp.sum(buf_m * oh, axis=0)

        def write_slot(buf_m, val, i):
            oh = onehot(i).astype(buf_m.dtype).reshape(
                (M,) + (1,) * (buf_m.ndim - 1))
            return buf_m + oh * val[None]

        # -- 1) head: microbatches in order (state threads), the M
        #       iterations UNROLLED (M is static and small — the
        #       microbatch count). A lax.scan here would stack the
        #       differentiated body's sharded residuals with the same
        #       mis-typed partitioned DUS the one-hot forms avoid; the
        #       unrolled loop has no residual stacking at all.
        if lo:
            hstate = state_pp["head"]
            hs = []
            for i in range(M):
                h, hstate = head_apply(params_pp["head"], hstate, xs[i],
                                       lk_all[i])
                hs.append(h)
            head_state = hstate
            inj = jnp.stack(hs)
        else:
            head_state, inj = state_pp["head"], xs
        inj = constrain(inj, P(None, data))
        if permute_data:
            # IR-probe mutation: a halo exchange riding the DATA axis —
            # exactly the leak the per-axis byte budgets exist to catch
            # (math is irrelevant; probes only compile)
            inj = jnp.roll(inj, 1, axis=1)
            inj = constrain(inj, P(None, data))

        # -- 2) the pipeline ring: one scan over M+S-1 ticks. buf[s] is
        #       the activation ENTERING stage s this tick; the stacked
        #       stage axis is pipe-sharded, so the end-of-tick shift
        #       lowers to a collective-permute on `pipe` only.
        vstage = jax.checkpoint(jax.vmap(stage_apply),
                                policy=stage_policy)
        buf0 = jnp.zeros((S,) + inj.shape[1:], inj.dtype)
        out0 = jnp.zeros_like(inj)
        stage_ids = jnp.arange(S, dtype=jnp.int32)

        def tick(carry, t):
            buf, sstack, out = carry
            inject = jnp.where(t < M,
                               read_slot(inj, jnp.clip(t, 0, M - 1)),
                               jnp.zeros_like(buf[0]))
            buf = buf.at[0].set(inject)
            buf = constrain(buf, P(pipe, data))
            mi = t - stage_ids
            valid = (mi >= 0) & (mi < M)
            midx = jnp.clip(mi, 0, M - 1)
            keys_t = pipe_keys[midx, stage_ids]        # [S, v, 2]
            y, new_sstack = vstage(params_pp["stack"], sstack, buf, keys_t)
            y = constrain(y, P(pipe, data))
            # warmup/cooldown slots carry garbage — their state updates
            # must not land (their activations never reach the loss, so
            # AD already gives them zero cotangents)
            new_sstack = jax.tree_util.tree_map(
                lambda nw, od: jnp.where(
                    valid.reshape((S,) + (1,) * (nw.ndim - 1)), nw, od),
                new_sstack, sstack)
            oi = t - (S - 1)
            fin = jnp.where(oi >= 0, y[S - 1], jnp.zeros_like(y[S - 1]))
            out = write_slot(out, fin, jnp.clip(oi, 0, M - 1))
            out = constrain(out, P(None, data))
            buf = jnp.roll(y, 1, axis=0)
            buf = constrain(buf, P(pipe, data))
            return (buf, new_sstack, out), None

        (_, stack_state, out), _ = jax.lax.scan(
            tick, (buf0, state_pp["stack"], out0),
            jnp.arange(T, dtype=jnp.int32))

        # -- 3) tail + loss: microbatches in order (state threads),
        #       UNROLLED like the head (static integer indexing into the
        #       finished-output buffer; a differentiated lax.scan would
        #       stack its sharded residuals/cotangents with the
        #       mis-typed partitioned DUS).
        tstate = state_pp["tail"]
        mscore_list = []
        for i in range(M):
            h = out[i]
            lm = None if lms is None else lms[i]
            score, tstate = tail_loss(params_pp["tail"], tstate, h, ys[i],
                                      lk_all[i], out_all[i], lm)
            batch = h.shape[0]
            if lm is not None:
                live = lm.astype(f32).reshape((lm.shape[0], -1)).max(axis=1)
                batch = jnp.maximum(jnp.sum(live), 1.0)
            mscore_list.append((score + reg / batch).astype(f32))
        tail_state = tstate
        mscores = jnp.stack(mscore_list)
        new_state = {"head": head_state, "stack": stack_state,
                     "tail": tail_state}
        return jnp.mean(mscores), (new_state, mscores)

    return loss_fn


def _pp_opt_step(plan: PipelinePlan, zero_plan=None,
                 mutate: Optional[str] = None):
    """One optimizer step on pp-form trees: pipelined forward/backward,
    mean gradient over the M microbatches, update (vmapped over stages),
    ZeRO-1 shard constraints when composed. Shared by the per-batch step
    and the accumulated superstep."""
    loss_fn = _pp_loss_fn(plan, mutate=mutate)
    minimize = plan.model.conf.conf.minimize

    def opt_step(params, state, opt, step, keys, xs, ys, lms):
        (score, (new_state, mscores)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, state, keys, xs, ys, lms)
        if not minimize:
            grads = jax.tree_util.tree_map(lambda g: -g, grads)
        new_params, new_opt = plan.apply_updates(params, grads, opt, step)
        if zero_plan is not None:
            new_params = zero_plan.constrain_params(new_params)
            new_opt = zero_plan.constrain_opt(new_opt)
        return new_params, new_state, new_opt, score, mscores

    return opt_step


def _pp_info(plan: PipelinePlan, zero_plan=None):
    m = plan.model
    info = {"pp_constraints": PP_CONSTRAINT_SITES,
            "n_stages": plan.n_stages, "slots": plan.slots,
            "stage_run": (plan.lo, plan.hi),
            "expected_constraints": PP_CONSTRAINT_SITES,
            # remat/precision accounting (ISSUE 18, the _ZeroPlan.info
            # pattern): the stage checkpoint's effective policy + the
            # compute dtype; per-shape activation bytes via
            # `pp_stage_saved_bytes(plan, micro_shape, policy=...)`
            "remat": {"policy": getattr(m.layers[plan.lo], "remat_policy",
                                        None),
                      "compute_dtype": m.conf.conf.compute_dtype}}
    if zero_plan is not None:
        info["zero"] = dict(zero_plan.info)
        info["expected_constraints"] += zero_plan.expected_constraints()
    return info


def _check_pp_masks(fm):
    if fm is not None and jax.tree_util.tree_leaves(fm):
        raise ValueError(
            "the 1F1B step threads the weight-zero LABEL mask through "
            "the last-stage loss, but features masks (time_buckets "
            "padding) are not supported — drop time_buckets or use a "
            "SYNC strategy")


def make_pp_step(model, plan: PipelinePlan, *, zero_plan=None,
                 mutate: Optional[str] = None):
    """The per-batch 1F1B train step (M = 1): signature-compatible with
    `model.train_step_fn` on pp-form trees — (params, state, opt, step,
    x, y, rng, fmask, lmask) -> (params, state, opt, score) — so
    `ParallelTrainer` jits it with the pipeline shardings and
    `build_superstep` scans it unchanged. `rng` is the microbatch key
    (the caller's per-batch split), exactly as on every other strategy.
    Returns (step_fn, info)."""
    opt_step = _pp_opt_step(plan, zero_plan=zero_plan, mutate=mutate)

    def step(params, state, opt_state, step_i, x, y, rng, fmask, lmask):
        _check_pp_masks(fmask)
        lms = None if lmask is None or not jax.tree_util.tree_leaves(lmask) \
            else lmask[None]
        params, state, opt_state, score, _ = opt_step(
            params, state, opt_state, step_i, rng[None], x[None], y[None],
            lms)
        return params, state, opt_state, score

    return step, _pp_info(plan, zero_plan)


def make_pp_accum_superstep(model, plan: PipelinePlan, *, zero_plan=None,
                            mutate: Optional[str] = None):
    """The ACCUMULATED 1F1B superstep: the pipeline's microbatches ARE
    the accumulation microbatches (ISSUE 15 unifying ISSUE 12's
    machinery) — a [K, M, batch, ...] window runs K optimizer steps, each
    ONE M-microbatch 1F1B schedule, in a single dispatch. Signature
    matches `nn/superstep.build_accum_superstep`: (params, state, opt,
    step0, rng0, xs, ys, fm, lm) -> (params, state, opt, rng, scores[K],
    micro_scores[K, M]); the RNG chain advances per MICROBATCH with the
    identical split sequence, so the step is equivalent to
    `fit(grad_accumulation=M)` at f32-ulp. Returns (fn, info)."""
    opt_step = _pp_opt_step(plan, zero_plan=zero_plan, mutate=mutate)

    def superstep(params, state, opt_state, step0, rng0, xs, ys, fm, lm):
        _check_pp_masks(fm)

        def body(carry, inp):
            params, state, opt, step, rng = carry
            x, y, l = inp
            M = x.shape[0]

            def draw(r, _):
                r, k = jax.random.split(r)
                return r, k

            rng, keys = jax.lax.scan(draw, rng, None, length=M)
            params, state, opt, score, mscores = opt_step(
                params, state, opt, step, keys, x, y, l)
            return (params, state, opt, step + 1, rng), (score, mscores)

        lms = None if lm is None or not jax.tree_util.tree_leaves(lm) \
            else lm
        (params, state, opt, _step, rng), (scores, mscores) = jax.lax.scan(
            body, (params, state, opt_state, step0, rng0), (xs, ys, lms))
        return params, state, opt, rng, scores, mscores

    return superstep, _pp_info(plan, zero_plan)


def pipeline_forward(stage_fn: Callable, stacked_params, x_microbatches,
                     axis_name: str, n_stages: int):
    """Run inside shard_map. Each device holds stacked_params' local block
    (its stage's params, leading axis 1) and the full microbatch stream.

    stage_fn(params, x) -> y, with y.shape == x.shape.
    x_microbatches: [M, mb, F] (replicated). Returns [M, mb, F]: microbatch
    outputs after all stages (valid on the LAST stage; other stages carry
    in-flight values).
    """
    stage = jax.lax.axis_index(axis_name)
    M = x_microbatches.shape[0]
    n_ticks = M + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    mb_shape = x_microbatches.shape[1:]
    buf = jnp.zeros((M,) + mb_shape, x_microbatches.dtype)
    carry_in = jnp.zeros(mb_shape, x_microbatches.dtype)

    def tick(t, state):
        carry_in, buf = state
        # stage 0 injects microbatch t (if any); others take the permuted input
        inject = jax.lax.dynamic_index_in_dim(
            x_microbatches, jnp.clip(t, 0, M - 1), keepdims=False)
        x_in = jnp.where(stage == 0, inject, carry_in)
        y = stage_fn(jax.tree_util.tree_map(lambda a: a[0], stacked_params),
                     x_in)
        # last stage writes its finished microbatch t - (n_stages-1)
        out_idx = t - (n_stages - 1)
        write = jnp.logical_and(stage == n_stages - 1, out_idx >= 0)
        buf = jax.lax.cond(
            write,
            lambda b: jax.lax.dynamic_update_index_in_dim(
                b, y, jnp.clip(out_idx, 0, M - 1), axis=0),
            lambda b: b, buf)
        carry_next = jax.lax.ppermute(y, axis_name, perm)
        return carry_next, buf

    _, buf = jax.lax.fori_loop(0, n_ticks, tick, (carry_in, buf))
    # only the last stage holds finished outputs; psum makes the result
    # genuinely replicated across the pipe axis
    buf = jnp.where(stage == n_stages - 1, buf, jnp.zeros_like(buf))
    return jax.lax.psum(buf, axis_name)


class PipelinedDenseStack:
    """S identical Dense(F->F, activation) stages pipelined over `axis`.
    The minimal concrete pipeline model used for equivalence tests and as the
    template for pipelining homogeneous blocks of a larger net."""

    def __init__(self, features: int, n_stages: int, mesh: Mesh,
                 axis: str = "pipe", activation: str = "tanh", seed: int = 0):
        from ..nn import activations as _act

        self.features = features
        self.n_stages = n_stages
        self.mesh = mesh
        self.axis = axis
        self._act = _act.get(activation)
        k = jax.random.split(jax.random.PRNGKey(seed), n_stages)
        scale = 1.0 / np.sqrt(features)
        self.params = {
            "W": jnp.stack([jax.random.normal(k[i], (features, features))
                            * scale for i in range(n_stages)]),
            "b": jnp.zeros((n_stages, features)),
        }

    def _stage_fn(self, p, x):
        return self._act(x @ p["W"] + p["b"])

    def reference_forward(self, params, x):
        """Sequential single-device execution (oracle)."""
        for s in range(self.n_stages):
            p = jax.tree_util.tree_map(lambda a: a[s], params)
            x = self._stage_fn(p, x)
        return x

    def pipelined_forward(self, params, x, n_microbatches: Optional[int] = None):
        """x: [B, F] -> [B, F] through the pipeline."""
        from jax import shard_map

        M = n_microbatches or self.n_stages
        B = x.shape[0]
        assert B % M == 0, "batch must divide into microbatches"
        xm = x.reshape(M, B // M, self.features)

        fn = shard_map(
            functools.partial(pipeline_forward, self._stage_fn,
                              axis_name=self.axis, n_stages=self.n_stages),
            mesh=self.mesh,
            in_specs=(P(self.axis), P()),
            out_specs=P(),
            check_vma=False)

        def wrapper(params, xm):
            return fn(params, xm)

        stage_sh = NamedSharding(self.mesh, P(self.axis))
        params = jax.device_put(params, stage_sh)
        out = watch_compiles(jax.jit(wrapper),
                             "pipeline/spmd_forward")(params, xm)
        return out.reshape(B, self.features)


def _jit_stage(fn, name: str):
    """Build ONE stage's jitted callable. Per-stage jits are constructed
    once per trainer at cached-property build time and reused for the
    trainer's lifetime — hoisting the `jax.jit` construction here (out of
    the per-stage build loops) keeps that contract visible to graftlint's
    `jit-in-loop` rule without pragmas: each call site builds exactly one
    jit with a persistent cache."""
    return watch_compiles(jax.jit(fn), name)


class PipelinedNetworkTrainer:
    """GPipe-schedule pipeline training for a REAL `MultiLayerNetwork`
    (heterogeneous stages — the capability `PipelinedDenseStack` only
    templated).

    Contiguous layer ranges (balanced by parameter count, or explicit
    `boundaries`) become stages pinned to the devices of the mesh's `pipe`
    axis. A training step runs the GPipe two-phase schedule host-side:
    forward all microbatches stage by stage (boundary activations stay on
    each stage's device; inter-stage transfer is a device-to-device copy),
    then backward per stage via `jax.vjp` with stage-granular recompute
    (activation checkpointing at stage boundaries). Gradients average over
    microbatches — identical to the single-device full-batch gradient for
    mean losses, the equivalence the tests assert (the
    `TestCompareParameterAveragingSparkVsSingleMachine.java:44` pattern).

    Dropout-carrying models train with a per-(step, microbatch, stage)
    PRNG (`fold_in` chain) threaded through the stage functions — the
    backward recompute folds the SAME key so masks reproduce exactly.
    Mixed-precision (`compute_dtype`) models cast per-stage exactly as the
    single-device step does (hidden layers in the compute dtype, output
    head in the master dtype).

    Restrictions: feed-forward layers (no TBPTT carries), no masks.
    """

    def __init__(self, model, mesh: Mesh, axis: str = "pipe",
                 n_microbatches: Optional[int] = None,
                 boundaries: Optional[list] = None):
        from ..nn.layers.feedforward import BaseOutputLayerConf

        if model.params is None:
            model.init()
        self.model = model
        self.mesh = mesh
        self.axis = axis
        self.n_stages = mesh.shape[axis]
        self.n_microbatches = n_microbatches or self.n_stages
        n_layers = len(model.layers)
        if self.n_stages > n_layers:
            raise ValueError(f"{self.n_stages} stages > {n_layers} layers")
        if not isinstance(model.layers[-1], BaseOutputLayerConf):
            raise ValueError("last layer must be an output layer")
        self.boundaries = (list(boundaries) if boundaries is not None
                           else self._balance(n_layers))
        self._setup_devices_and_state()

    def _setup_devices_and_state(self):
        """Pin one device per pipe-axis stage (first index in other axes)
        and initialize the training bookkeeping — shared by the chain and
        graph trainers."""
        mesh, axis = self.mesh, self.axis
        idx = [0] * len(mesh.axis_names)
        ax = mesh.axis_names.index(axis)
        devs = []
        for s in range(self.n_stages):
            idx[ax] = s
            devs.append(mesh.devices[tuple(idx)])
        self.devices = devs
        self._place_params()
        self.iteration_count = 0
        self._score = float("nan")
        self._rng = (self.model._rng
                     if getattr(self.model, "_rng", None) is not None
                     else jax.random.PRNGKey(0))

    # -- stage partitioning ---------------------------------------------
    def _balance(self, n_layers: int) -> list:
        """Contiguous split minimizing per-stage param-count imbalance
        (greedy threshold; boundaries[s] = first layer of stage s+1)."""
        sizes = [sum(int(np.prod(v.shape)) for v in p.values()) or 1
                 for p in self.model.params]
        total = sum(sizes)
        target = total / self.n_stages
        bounds, acc, need = [], 0.0, 1
        for i, sz in enumerate(sizes):
            remaining_layers = len(sizes) - i
            remaining_stages = self.n_stages - need + 1
            if (acc + sz / 2 >= target * need
                    and need < self.n_stages
                    and remaining_layers > remaining_stages - 1):
                bounds.append(i)
                need += 1
            acc += sz
        while len(bounds) < self.n_stages - 1:  # force S stages
            for i in range(n_layers - 1, 0, -1):
                if i not in bounds:
                    bounds.append(i)
                    break
            bounds.sort()
        return bounds[:self.n_stages - 1]

    def _stage_range(self, s: int):
        lo = 0 if s == 0 else self.boundaries[s - 1]
        hi = (len(self.model.layers) if s == self.n_stages - 1
              else self.boundaries[s])
        return lo, hi

    def _place_params(self):
        self.stage_params, self.stage_state, self.stage_opt = [], [], []
        for s in range(self.n_stages):
            lo, hi = self._stage_range(s)
            put = lambda t: jax.device_put(t, self.devices[s])
            self.stage_params.append(put(tuple(self.model.params[lo:hi])))
            self.stage_state.append(put(tuple(self.model.state[lo:hi])))
            self.stage_opt.append(put(tuple(self.model.updater_state[lo:hi])))

    # -- per-stage functions (jitted once per stage) ---------------------
    def _stage_forward(self, s: int):
        """(params, state, x, rng) -> (y, new_state) through layers
        [lo, hi). `rng` is the stage key: split across the stage's layers
        (dropout/sampling); the backward recompute passes the SAME key so
        masks reproduce exactly. Mixed precision: hidden layers compute in
        the compute dtype (params cast per layer, input cast once at stage
        0), the output head stays master-dtype — mirroring
        MultiLayerNetwork._forward."""
        from ..nn.conf.base import cast_floating
        from ..nn.layers.feedforward import BaseOutputLayerConf

        m = self.model
        lo, hi = self._stage_range(s)
        is_last = s == self.n_stages - 1
        cdt = m._compute_dtype

        def fwd(params, state, x, rng):
            if s == 0 and cdt is not None and jnp.issubdtype(
                    x.dtype, jnp.floating):
                x = x.astype(cdt)
            new_state = list(state)
            idxs = range(lo, hi if not is_last else hi - 1)
            rngs = jax.random.split(rng, max(1, len(idxs)))
            for k, i in enumerate(idxs):
                if i in m.conf.preprocessors:
                    x = m.conf.preprocessors[i].apply(x)
                p_i = params[k]
                if cdt is not None and not isinstance(
                        m.layers[i], BaseOutputLayerConf):
                    p_i = cast_floating(p_i, cdt)
                x, new_state[k] = m.layers[i].apply(
                    p_i, state[k], x, train=True, rng=rngs[k], mask=None)
            return x, tuple(new_state)

        return fwd

    @functools.cached_property
    def _stage_fwd_jits(self):
        return [watch_compiles(jax.jit(self._stage_forward(s)),
                               "pipeline/stage_fwd")
                for s in range(self.n_stages)]

    @functools.cached_property
    def _stage_bwd_jits(self):
        """Stage backward with recompute: (params, state, x, cot, rng) ->
        (param_grads, x_cot, new_state). `rng` must equal the forward
        stage key (dropout mask reproduction)."""
        jits = []
        for s in range(self.n_stages):
            fwd = self._stage_forward(s)

            def bwd(params, state, x, cot, rng, _fwd=fwd):
                (y, new_state), vjp = jax.vjp(
                    lambda p, xi: _fwd(p, state, xi, rng), params, x)
                gp, gx = vjp((cot, jax.tree_util.tree_map(jnp.zeros_like,
                                                          new_state)))
                return gp, gx, new_state
            # one jit per stage, built once (via _jit_stage)
            jits.append(_jit_stage(bwd, "pipeline/stage_bwd"))
        return jits

    @functools.cached_property
    def _last_stage_grad(self):
        """Last stage: forward rest + loss; returns (loss, param_grads,
        x_cot, new_state). Regularization is handled separately (it is
        per-step, not per-microbatch)."""
        m = self.model
        s = self.n_stages - 1
        lo, hi = self._stage_range(s)
        fwd = self._stage_forward(s)
        out_layer = m.layers[hi - 1]
        out_k = hi - 1 - lo

        def loss_fn(params, state, x, y, rng, lm):
            rng_f, out_rng = jax.random.split(rng)
            h, new_state = fwd(params, state, x, rng_f)
            i = hi - 1
            if i in m.conf.preprocessors:
                h = m.conf.preprocessors[i].apply(h)
            loss = out_layer.loss_score(params[out_k], state[out_k], h, y,
                                        train=True, rng=out_rng, mask=lm)
            return loss, new_state

        def grad_fn(params, state, x, y, rng, lm=None):
            (loss, new_state), vjp = jax.vjp(
                lambda p, xi: loss_fn(p, state, xi, y, rng, lm), params, x)
            gp, gx = vjp((jnp.float32(1.0),
                          jax.tree_util.tree_map(jnp.zeros_like, new_state)))
            return loss, gp, gx, new_state

        return watch_compiles(jax.jit(grad_fn),
                              "pipeline/last_stage_grad")

    @functools.cached_property
    def _stage_reg_grads(self):
        """Per-stage d(reg)/d(params); added once per step scaled 1/B."""
        jits = []
        for s in range(self.n_stages):
            lo, hi = self._stage_range(s)
            layers = self.model.layers[lo:hi]

            def reg(params, _layers=layers):
                total = jnp.float32(0.0)
                for layer, p in zip(_layers, params):
                    if p:
                        total = total + layer.reg_score(p)
                return total
            jits.append(_jit_stage(jax.value_and_grad(reg),
                                   "pipeline/stage_reg"))
        return jits

    @functools.cached_property
    def _stage_update_jits(self):
        jits = []
        for s in range(self.n_stages):
            lo, hi = self._stage_range(s)
            layers = self.model.layers[lo:hi]

            def upd(params, grads, opt, step, _layers=layers):
                if not self.model.conf.conf.minimize:
                    # maximize: ascend (the model's own train step negates
                    # the same way before apply_layer_updates)
                    grads = jax.tree_util.tree_map(lambda a: -a, grads)
                p, o = self.model.apply_layer_updates(
                    _layers, params, grads, opt, step)
                return tuple(p), tuple(o)
            jits.append(_jit_stage(upd, "pipeline/stage_update"))
        return jits

    # -- training --------------------------------------------------------
    def fit(self, data, epochs: int = 1):
        if isinstance(data, DataSet):
            self._fit_batch(data)
            return self
        for _ in range(epochs):
            data.reset()
            while data.has_next():
                self._fit_batch(data.next())
        return self

    def _fit_batch(self, ds: DataSet):
        if ds.features_mask is not None:
            raise ValueError(
                "pipeline trainer does not support features masks "
                "(time_buckets padding); the weight-zero LABELS mask "
                "(pad_ragged) threads through the last-stage loss")
        x = np.asarray(ds.features)
        y = np.asarray(ds.labels)
        lmask = (None if ds.labels_mask is None
                 else np.asarray(ds.labels_mask))
        B = x.shape[0]
        M = self.n_microbatches
        if B % M != 0:
            raise ValueError(f"batch {B} not divisible by {M} microbatches")
        xs = np.split(x, M)
        ys = np.split(y, M)
        # per-microbatch label-mask slices (ISSUE 15 satellite: pad_ragged
        # composes — padded rows are weight-zero in the last-stage loss);
        # B_live normalizes the regularization term by REAL rows, exactly
        # as the masked single-device _loss_fn does
        lms = [None] * M if lmask is None else np.split(lmask, M)
        if lmask is None:
            B_live = float(B)
        else:
            live = lmask.astype(np.float32).reshape(B, -1).max(axis=1)
            B_live = max(1.0, float(live.sum()))
        S = self.n_stages
        step = jnp.asarray(self.iteration_count, jnp.int32)
        # per-(step, microbatch, stage) PRNG: dropout-carrying models get
        # independent masks per microbatch; the backward recompute folds
        # the SAME key so its masks match the forward exactly
        self._rng, step_rng = jax.random.split(self._rng)
        skey = lambda mi, s: jax.random.fold_in(
            jax.random.fold_in(step_rng, mi), s)

        # forward phase: boundary activations per (microbatch, stage)
        acts = [[None] * S for _ in range(M)]
        for mi in range(M):
            a = jax.device_put(jnp.asarray(xs[mi]), self.devices[0])
            for s in range(S - 1):
                acts[mi][s] = a
                a, _ = self._stage_fwd_jits[s](self.stage_params[s],
                                               self.stage_state[s], a,
                                               skey(mi, s))
                a = jax.device_put(a, self.devices[min(s + 1, S - 1)])
            acts[mi][S - 1] = a

        # backward phase: per-stage grad accumulation over microbatches
        grad_acc = [None] * S
        losses = []
        new_states = list(self.stage_state)
        for mi in range(M):
            yb = jax.device_put(jnp.asarray(ys[mi]), self.devices[S - 1])
            lb = (None if lms[mi] is None else
                  jax.device_put(jnp.asarray(lms[mi]), self.devices[S - 1]))
            loss, gp, cot, st = self._last_stage_grad(
                self.stage_params[S - 1], self.stage_state[S - 1],
                acts[mi][S - 1], yb, skey(mi, S - 1), lb)
            losses.append(loss)
            new_states[S - 1] = st
            grad_acc[S - 1] = gp if grad_acc[S - 1] is None else \
                jax.tree_util.tree_map(jnp.add, grad_acc[S - 1], gp)
            for s in range(S - 2, -1, -1):
                cot = jax.device_put(cot, self.devices[s])
                gp, cot, st = self._stage_bwd_jits[s](
                    self.stage_params[s], self.stage_state[s],
                    acts[mi][s], cot, skey(mi, s))
                new_states[s] = st
                grad_acc[s] = gp if grad_acc[s] is None else \
                    jax.tree_util.tree_map(jnp.add, grad_acc[s], gp)

        # update phase (mean over microbatches + reg/B, then updaters)
        reg_total = 0.0
        for s in range(S):
            g = jax.tree_util.tree_map(lambda a: a / M, grad_acc[s])
            reg_v, reg_g = self._stage_reg_grads[s](self.stage_params[s])
            g = jax.tree_util.tree_map(lambda a, b: a + b / B_live, g,
                                       reg_g)
            reg_total = reg_total + jax.device_get(reg_v)
            self.stage_params[s], self.stage_opt[s] = \
                self._stage_update_jits[s](self.stage_params[s], g,
                                           self.stage_opt[s], step)
        self.stage_state = new_states
        self._score = float(np.mean([jax.device_get(l) for l in losses])
                            + reg_total / B_live)
        self.iteration_count += 1

    def score(self) -> float:
        return float(self._score)

    def sync_back(self):
        """Copy stage params/state/updater-state back into the model."""
        params, state, opt = [], [], []
        for s in range(self.n_stages):
            params.extend(jax.device_get(self.stage_params[s]))
            state.extend(jax.device_get(self.stage_state[s]))
            opt.extend(jax.device_get(self.stage_opt[s]))
        to_dev = lambda t: jax.tree_util.tree_map(jnp.asarray, t)
        self.model.params = tuple(to_dev(p) for p in params)
        self.model.state = tuple(to_dev(s) for s in state)
        self.model.updater_state = tuple(to_dev(o) for o in opt)
        return self.model


class PipelinedGraphTrainer(PipelinedNetworkTrainer):
    """GPipe-schedule pipeline training for a REAL `ComputationGraph`
    (round-3: the last parallel mode that was MultiLayerNetwork-only —
    the reference parallelizes ComputationGraph everywhere,
    `SparkComputationGraph.java` / `ParallelWrapper.java:48`).

    Stage partitioning for a DAG: scan the topological order tracking the
    LIVE value set (values produced before a position and consumed at or
    after it); positions where exactly one value is live are clean cut
    points — a residual block's output, the stem pool, etc. Stages are
    contiguous topo slices between clean cuts, balanced by parameter
    count. Within a stage the full DAG structure (branches, merges,
    residual adds) executes as-is; only the single boundary tensor
    crosses stages, exactly like the chain trainer.

    Dropout and mixed precision (`compute_dtype`) are supported exactly as
    in the chain trainer: a per-(step, microbatch, stage) PRNG threads
    through the stage functions (backward recompute folds the same key),
    and hidden vertices compute in the compute dtype with master-dtype
    output heads.

    Restrictions: single-input/single-output graphs, feed-forward (no
    recurrent carries), no masks, DataSet batches.
    """

    def __init__(self, model, mesh: Mesh, axis: str = "pipe",
                 n_microbatches: Optional[int] = None,
                 boundaries: Optional[list] = None):
        from ..nn.layers.feedforward import BaseOutputLayerConf

        if model.params is None:
            model.init()
        conf = model.conf
        if len(conf.network_inputs) != 1 or len(conf.network_outputs) != 1:
            raise ValueError("graph pipeline needs single-input/"
                             "single-output graphs")
        self.model = model
        self.mesh = mesh
        self.axis = axis
        self.n_stages = mesh.shape[axis]
        self.n_microbatches = n_microbatches or self.n_stages
        self._topo = [n for n in conf.topological_order
                      if n in conf.vertices]
        out_name = conf.network_outputs[0]
        if self._topo[-1] != out_name:
            raise ValueError("output vertex must be last in topo order")
        if not isinstance(conf.vertices[out_name], BaseOutputLayerConf):
            raise ValueError("network output must be an output/loss layer")
        for n in self._topo:
            if hasattr(conf.vertices[n], "aux_score"):
                raise ValueError(
                    f"vertex '{n}' carries an auxiliary loss (aux_score) "
                    "which the per-stage pipeline loss does not propagate; "
                    "use SYNC/TENSOR_PARALLEL for MoE graphs")
        cuts = self._clean_cuts()
        if len(cuts) < self.n_stages - 1:
            raise ValueError(
                f"graph has {len(cuts)} clean cut points, need "
                f"{self.n_stages - 1} for {self.n_stages} stages")
        if boundaries is not None:
            bad = [b for b in boundaries if b not in cuts]
            if bad or sorted(boundaries) != list(boundaries) \
                    or len(boundaries) != self.n_stages - 1:
                raise ValueError(
                    f"boundaries {boundaries} invalid: must be "
                    f"{self.n_stages - 1} sorted clean-cut positions "
                    f"(legal cuts: {cuts})")
            self.boundaries = list(boundaries)
        else:
            self.boundaries = self._balance_cuts(cuts)
        self._setup_devices_and_state()

    # -- DAG partitioning ------------------------------------------------
    def _clean_cuts(self):
        """Positions i where the cut before topo[i] carries exactly ONE
        live value: the output of topo[i-1]."""
        conf = self.model.conf
        pos = {n: i for i, n in enumerate(self._topo)}
        pos[conf.network_inputs[0]] = -1
        last_use = {}
        for n in self._topo:
            for src in conf.vertex_inputs[n]:
                last_use[src] = pos[n]
        cuts = []
        for i in range(1, len(self._topo)):
            live = [v for v, p in pos.items()
                    if p < i and last_use.get(v, -2) >= i]
            if live == [self._topo[i - 1]]:
                cuts.append(i)
        return cuts

    def _balance_cuts(self, cuts):
        """Pick n_stages-1 boundaries from the legal cuts, balancing
        per-stage parameter counts (greedy threshold over topo order)."""
        params = self.model.params
        sizes = [sum(int(np.prod(np.shape(v)))
                     for v in (params.get(n) or {}).values())
                 for n in self._topo]
        total = sum(sizes) or 1
        target = total / self.n_stages
        bounds, acc, need = [], 0.0, 1
        cutset = sorted(cuts)
        for i, sz in enumerate(sizes):
            if (i in cutset and need < self.n_stages
                    and acc + sz / 2 >= target * need
                    and len(cutset) - cutset.index(i) >
                    self.n_stages - 1 - len(bounds) - 1):
                bounds.append(i)
                need += 1
            acc += sz
        while len(bounds) < self.n_stages - 1:
            for c in reversed(cutset):
                if c not in bounds:
                    bounds.append(c)
                    break
            else:
                raise ValueError("not enough clean cuts")
            bounds.sort()
        return sorted(bounds)[:self.n_stages - 1]

    def _stage_names(self, s: int):
        lo = 0 if s == 0 else self.boundaries[s - 1]
        hi = (len(self._topo) if s == self.n_stages - 1
              else self.boundaries[s])
        return self._topo[lo:hi], (self.model.conf.network_inputs[0]
                                   if s == 0 else self._topo[lo - 1])

    def _place_params(self):
        from ..nn.conf.base import LayerConf

        conf = self.model.conf
        self.stage_params, self.stage_state, self.stage_opt = [], [], []
        for s in range(self.n_stages):
            names, _ = self._stage_names(s)
            lnames = [n for n in names
                      if isinstance(conf.vertices[n], LayerConf)]
            put = lambda t: jax.device_put(t, self.devices[s])
            self.stage_params.append(put(
                {n: self.model.params[n] for n in lnames}))
            self.stage_state.append(put(
                {n: self.model.state[n] for n in lnames}))
            self.stage_opt.append(put(
                {n: self.model.updater_state[n] for n in lnames}))

    # -- per-stage functions ---------------------------------------------
    def _stage_forward(self, s: int):
        from ..nn.conf.base import LayerConf, cast_floating
        from ..nn.layers.feedforward import BaseOutputLayerConf

        m = self.model
        conf = m.conf
        names, boundary = self._stage_names(s)
        is_last = s == self.n_stages - 1
        run = names[:-1] if is_last else names  # loss head handled apart
        cdt = m._compute_dtype

        def fwd(params, state, x, rng):
            if s == 0 and cdt is not None and jnp.issubdtype(
                    x.dtype, jnp.floating):
                x = x.astype(cdt)
            values = {boundary: x}
            new_state = dict(state)
            rngs = jax.random.split(rng, max(1, len(run)))
            for k, name in enumerate(run):
                v = conf.vertices[name]
                ins = [values[i_] for i_ in conf.vertex_inputs[name]]
                if isinstance(v, LayerConf):
                    h = ins[0]
                    rec = conf.inferred_input_types.get(name)
                    if rec is not None and rec[0] is not None:
                        h = rec[0].apply(h)
                    p_v = params[name]
                    if cdt is not None and not isinstance(
                            v, BaseOutputLayerConf):
                        p_v = cast_floating(p_v, cdt)
                    y, new_state[name] = v.apply(
                        p_v, state[name], h, train=True, rng=rngs[k],
                        mask=None)
                    values[name] = y
                else:
                    values[name] = v.apply(ins, [None] * len(ins))
            return values[run[-1] if run else boundary], new_state

        return fwd

    @functools.cached_property
    def _last_stage_grad(self):
        m = self.model
        conf = m.conf
        s = self.n_stages - 1
        names, _ = self._stage_names(s)
        out_name = names[-1]
        out_layer = conf.vertices[out_name]
        fwd = self._stage_forward(s)

        def loss_fn(params, state, x, y, rng, lm):
            rng_f, out_rng = jax.random.split(rng)
            h, new_state = fwd(params, state, x, rng_f)
            rec = conf.inferred_input_types.get(out_name)
            if rec is not None and rec[0] is not None:
                h = rec[0].apply(h)
            loss = out_layer.loss_score(params[out_name], state[out_name],
                                        h, y, train=True, rng=out_rng,
                                        mask=lm)
            return loss, new_state

        def grad_fn(params, state, x, y, rng, lm=None):
            (loss, new_state), vjp = jax.vjp(
                lambda p, xi: loss_fn(p, state, xi, y, rng, lm), params, x)
            gp, gx = vjp((jnp.float32(1.0),
                          jax.tree_util.tree_map(jnp.zeros_like, new_state)))
            return loss, gp, gx, new_state

        return watch_compiles(jax.jit(grad_fn),
                              "pipeline/graph_last_stage_grad")

    @functools.cached_property
    def _stage_reg_grads(self):
        conf = self.model.conf
        jits = []
        for s in range(self.n_stages):
            names, _ = self._stage_names(s)

            def reg(params, _names=tuple(names)):
                total = jnp.float32(0.0)
                for n in _names:
                    p = params.get(n)
                    if p:
                        total = total + conf.vertices[n].reg_score(p)
                return total
            jits.append(_jit_stage(jax.value_and_grad(reg),
                                   "pipeline/graph_stage_reg"))
        return jits

    @functools.cached_property
    def _stage_update_jits(self):
        """Per-stage parameter update mirroring the graph train step's
        per-vertex updater semantics (graph.py _make_train_step)."""
        from ..nn.gradnorm import apply_gradient_normalization

        m = self.model
        conf = m.conf
        jits = []
        for s in range(self.n_stages):
            names, _ = self._stage_names(s)

            def upd(params, grads, opt, step, _names=tuple(names)):
                if not m.conf.conf.minimize:
                    # maximize: ascend (graph._make_train_step negates the
                    # same way)
                    grads = jax.tree_util.tree_map(lambda a: -a, grads)
                new_p, new_o = dict(params), dict(opt)
                for n in _names:
                    p = params.get(n)
                    if p is None:
                        continue
                    layer = conf.vertices[n]
                    if not p or layer.frozen:
                        continue
                    g = apply_gradient_normalization(
                        layer.gradient_normalization,
                        layer.gradient_normalization_threshold or 1.0,
                        grads[n])
                    u = m._layer_updater(layer)
                    lr = m._layer_lr(layer, step)
                    updates, new_o[n] = u.update(g, opt[n], step, lr)
                    if getattr(layer, "bias_learning_rate", None) is not None:
                        from ..nn.multilayer import _rescale_bias_updates
                        if lr is None:
                            eff = getattr(u, "learning_rate", 1.0) or 1.0
                            scale = layer.bias_learning_rate / eff
                        else:
                            scale = layer.bias_learning_rate / jnp.maximum(
                                jnp.asarray(lr, jnp.float32), 1e-30)
                        updates = _rescale_bias_updates(updates, scale)
                    # tree-wise: vertex params may be nested (BiLSTM)
                    new_p[n] = jax.tree_util.tree_map(
                        lambda a, u_: a - u_, p, updates)
                return new_p, new_o
            jits.append(_jit_stage(upd, "pipeline/graph_stage_update"))
        return jits

    def sync_back(self):
        params = dict(self.model.params)
        state = dict(self.model.state)
        opt = dict(self.model.updater_state)
        for s in range(self.n_stages):
            params.update(jax.device_get(self.stage_params[s]))
            state.update(jax.device_get(self.stage_state[s]))
            opt.update(jax.device_get(self.stage_opt[s]))
        to_dev = lambda t: jax.tree_util.tree_map(jnp.asarray, t)
        self.model.params = {k: to_dev(v) for k, v in params.items()}
        self.model.state = {k: to_dev(v) for k, v in state.items()}
        self.model.updater_state = {k: to_dev(v) for k, v in opt.items()}
        self.model.iteration_count = self.iteration_count
        return self.model
