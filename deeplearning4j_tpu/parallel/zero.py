"""ZeRO-style sharded data parallelism (stages 1 and 2).

The plain SYNC data-parallel step pays a "replicated updater" tax: every
device holds the FULL optimizer state and redundantly applies the FULL
parameter update after the gradient allreduce (an earlier
installation's capture, BASELINE.md, attributed most of the 8-device
Adam step on the virtual CPU mesh to this). ZeRO (Rajbhandari et al.,
2020) removes it by partitioning optimizer state — and, at stage 2, the
reduced gradients — across the data-parallel axis:

  reduce(-scatter) grads  ->  each device updates only ITS shard of the
  moments and params      ->  allgather of the updated params

Expressed GSPMD-natively here: optimizer moments are device_put with
FSDP-style PartitionSpecs over the ``data`` axis (`zero_opt_shardings`),
the step constrains the updated params (and, for ZERO2, the gradients) to
those same specs with `with_sharding_constraint`, and the jit's replicated
out-sharding for params becomes the trailing allgather. XLA then partitions
the elementwise updater math 1/N per device and fuses the collectives —
the reduce-scatter of a late-layer gradient bucket is issued as soon as
backward produces it, overlapping with the remaining backward compute
(PyTorch DDP's bucketing design, Li et al., 2020, made explicit for the
XLA scheduler by the per-bucket flush chain below).

Stage semantics:
  * ZERO1 — optimizer state sharded. Gradients are fully reduced (the
    familiar allreduce; every device still sees full grads, so per-tensor
    gradient-normalization modes read whole tensors locally), the update
    runs sharded, params are allgathered.
  * ZERO2 — + gradient partitioning: gradients are packed into
    size-bounded buckets (reverse layer order ≈ backward production
    order) and each bucket is reduce-scattered; no device ever
    materializes the full replicated gradient tree. `reduce_dtype`
    ("bfloat16") optionally narrows the wire format of that reduction
    while the master update stays in the gradient/param dtype (fp32).

Both stages keep params replicated between steps, so evaluation, scoring,
early stopping and checkpointing see an ordinary replicated model; only
`updater_state` is mesh-sharded (orbax writes it shard-wise through
`parallel/checkpoint.py`).

Gradient accumulation (ISSUE 12, `make_zero_accum_superstep`): this is
where ZERO2's memory story pays off — each microbatch's gradients are
reduce-scattered as backward produces them and SUMMED INTO THE SHARDED
LAYOUT, so the fp32 accumulator costs ~1/N per device instead of a full
replicated tree, and the barrier token threads through the microbatch
scan so bucket flushes stay ordered across microbatches (microbatch i's
collective traffic overlaps microbatch i+1's backward on hardware with
async collectives — `collective_overlap_fraction` reports the structural
number). One param allgather per OPTIMIZER step, not per microbatch.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import MeshAxes
from .sharding import _fsdp_spec_for, _opt_sharding_like

__all__ = ["ZeroConfig", "assign_buckets", "collective_overlap_fraction",
           "make_zero_accum_superstep", "make_zero_step",
           "zero_grad_specs", "zero_opt_shardings"]

DEFAULT_BUCKET_MB = 4.0


@dataclass(frozen=True)
class ZeroConfig:
    """Knobs for the ZeRO step.

    stage         1 (shard optimizer state) or 2 (+ shard reduced grads).
    bucket_mb     gradient-bucket size bound in MiB (stage 2). Smaller
                  buckets overlap earlier but issue more collectives;
                  DDP's classic default is 25 MB, small CPU-mesh models
                  want less.
    reduce_dtype  optional wire dtype for the stage-2 gradient reduction
                  (e.g. "bfloat16"). The updater math — the fp32 master
                  update — always runs in the original gradient dtype.
    ordered_flush chain bucket reduce-scatters in production order with
                  optimization_barrier so XLA cannot collapse them into
                  one monolithic end-of-backward collective.
    """

    stage: int = 1
    bucket_mb: float = DEFAULT_BUCKET_MB
    reduce_dtype: Optional[str] = None
    ordered_flush: bool = True


def _is_p(x) -> bool:
    return isinstance(x, P)


def _nontrivial(spec: P) -> bool:
    return any(ax is not None for ax in tuple(spec))


def _spec_shards(spec: P, mesh: Mesh) -> int:
    """Number of shards a spec splits a tensor into (product of the named
    mesh axis sizes; tuple entries multiply)."""
    n = 1
    for entry in tuple(spec):
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        for ax in axes:
            n *= int(mesh.shape[ax])
    return n


def _add_data_axis(spec: P, shape, data_axis: str, mesh: Mesh) -> P:
    """Extend a (possibly model-sharded) base spec with the ZeRO ``data``
    axis: the largest FREE dimension divisible by the data-axis size takes
    it; if every free dim resists, the data axis STACKS onto an
    already-sharded dim whose per-shard extent still divides (a
    column-parallel bias [F] sharded over ``model`` becomes
    P(("model", "data")) — 1/(m·d) per device). Leaves with no divisible
    home stay at the base spec (their update cost is noise)."""
    d = int(mesh.shape[data_axis])
    if d <= 1:
        # a degenerate data axis shards nothing; adding it would only
        # perturb the specs away from the base layout (GSPMD then pays
        # rematerializations to "reshard" onto the size-1 axis)
        return spec
    entries = list(tuple(spec)) + [None] * (len(shape) - len(tuple(spec)))
    free = [i for i, e in enumerate(entries) if e is None]
    for ax in sorted(free, key=lambda i: -shape[i]):
        if shape[ax] % d == 0 and shape[ax] >= d:
            entries[ax] = data_axis
            return P(*entries)
    for ax, e in enumerate(entries):
        if e is None:
            continue
        axes = e if isinstance(e, tuple) else (e,)
        per_shard = shape[ax] // int(np.prod([mesh.shape[a] for a in axes]))
        if per_shard % d == 0 and per_shard >= d:
            entries[ax] = tuple(axes) + (data_axis,)
            return P(*entries)
    return spec


def zero_grad_specs(params, mesh: Mesh, data_axis: str = MeshAxes.DATA,
                    base=None):
    """Per-leaf PartitionSpec pytree sharding each gradient/moment tensor
    over the ``data`` axis on its largest divisible dimension (biases and
    other tensors with no divisible axis stay replicated — their update
    cost is noise). `base` (a congruent P pytree, e.g. the Megatron TP
    specs) composes: the data axis lands on a dimension the base spec
    left free (or stacks onto a sharded one), so ZERO1×TP moments shard
    over BOTH mesh axes."""
    if base is None:
        return jax.tree_util.tree_map(
            lambda a: _fsdp_spec_for(np.shape(a), data_axis, mesh), params)
    leaves, treedef = jax.tree_util.tree_flatten(params)
    base_leaves = jax.tree_util.tree_leaves(base, is_leaf=_is_p)
    out = [_add_data_axis(s, np.shape(a), data_axis, mesh)
           for a, s in zip(leaves, base_leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)


def zero_opt_shardings(opt_state, params, mesh: Mesh,
                       data_axis: str = MeshAxes.DATA, base=None):
    """NamedSharding pytree for the optimizer state: each moment tensor
    gets its param's ZeRO shard spec (matched by shape), scalars and
    unmatched leaves replicated. `base` as in `zero_grad_specs`."""
    specs = zero_grad_specs(params, mesh, data_axis, base=base)
    p_sh = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs, is_leaf=_is_p)
    return _opt_sharding_like(opt_state, params, p_sh)


def assign_buckets(sizes: Sequence[int], bucket_bytes: int
                   ) -> List[List[int]]:
    """Greedy, order-preserving pack of leaf indices into size-bounded
    buckets. `sizes` must already be in gradient PRODUCTION order (the
    caller reverses the forward layer order). A leaf larger than the bound
    gets a bucket of its own; every index lands in exactly one bucket."""
    cap = max(1, int(bucket_bytes))
    buckets: List[List[int]] = []
    cur: List[int] = []
    cur_b = 0
    for i, b in enumerate(sizes):
        b = int(b)
        if cur and cur_b + b > cap:
            buckets.append(cur)
            cur, cur_b = [], 0
        cur.append(i)
        cur_b += b
    if cur:
        buckets.append(cur)
    return buckets


def _check_updaters(model):
    """ZeRO partitions the update elementwise over the data axis; an
    updater whose state transform is NOT elementwise (a future LAMB trust
    ratio, Shampoo preconditioner...) would silently re-gather inside the
    step — refuse it up front instead."""
    from ..nn.graph import ComputationGraph

    if isinstance(model, ComputationGraph):
        pairs = [(model.conf.vertices[name], p)
                 for name, p in model.params.items()]
    else:
        pairs = list(zip(model.layers, model.params))
    for layer, p in pairs:
        if not p or getattr(layer, "frozen", False):
            continue
        upd = model._layer_updater(layer)
        if not getattr(upd, "elementwise_state", True):
            raise ValueError(
                f"updater {type(upd).__name__} declares "
                "elementwise_state=False — its update cannot be sharded "
                "over the data axis; use ShardingStrategy.REPLICATED for "
                "this model")


class _ZeroPlan:
    """The static ZeRO layout + traced building blocks, shared by the
    per-batch step (`make_zero_step`) and the accumulated superstep
    (`make_zero_accum_superstep`): per-leaf shard specs, gradient buckets
    in backward-production order, the bucketed reduce-scatter with its
    optimization_barrier ordering token, shard constraints for params /
    optimizer moments / fp32 accumulators, and the static per-step
    accounting (`info`) telemetry consumes."""

    def __init__(self, model, mesh: Mesh, data_axis: str,
                 config: ZeroConfig, base_specs=None,
                 model_axis: Optional[str] = None,
                 params=None, opt_state=None):
        # `params`/`opt_state` override the model's own trees when the
        # caller trains a RESTRUCTURED view of the model — the pipeline
        # strategies (parallel/pipeline.py) hand the stage-stacked
        # pp-form trees here, so the ZeRO layout/accounting applies to
        # the buffers the step actually carries. The updater-contract
        # check still runs against the model (same updaters either way).
        if params is None:
            params = model.params
        if opt_state is None:
            opt_state = model.updater_state
        if config.stage not in (1, 2):
            raise ValueError(
                f"ZeRO stage must be 1 or 2, got {config.stage}")
        if config.stage == 1 and config.reduce_dtype is not None:
            # silently ignoring the knob would let a user believe they
            # halved the wire payload; only stage 2 owns the reduction
            raise ValueError(
                "reduce_dtype (zero_reduce_dtype=) only applies to ZERO2 "
                "— stage 1 reduces gradients in their own dtype; use "
                "ShardingStrategy.ZERO2 or drop the knob")
        if base_specs is not None and config.stage >= 2:
            # the bucketed reduce-scatter packs FULL-size leaves; on a
            # model-sharded gradient tree it would reshard over the wrong
            # axis — stage 2 on a 2-D mesh is future work (ROADMAP item 2)
            raise ValueError(
                "ZeRO stage 2 does not compose with tensor-parallel base "
                "specs yet — use stage 1 (ShardingStrategy.ZERO1_TP)")
        _check_updaters(model)
        self.config = config

        # ---- static layout: one spec/sharding per param leaf ------------
        leaves, self.treedef = jax.tree_util.tree_flatten(params)
        base_leaves = (jax.tree_util.tree_leaves(base_specs, is_leaf=_is_p)
                       if base_specs is not None else [P()] * len(leaves))
        specs = jax.tree_util.tree_leaves(
            zero_grad_specs(params, mesh, data_axis,
                            base=base_specs), is_leaf=_is_p)
        self.shardings = [NamedSharding(mesh, s) for s in specs]
        shapes = [np.shape(l) for l in leaves]
        counts = [int(np.prod(s, dtype=np.int64)) if s else 1
                  for s in shapes]
        itemsize = [np.dtype(jnp.result_type(l)).itemsize for l in leaves]
        red_itemsize = (np.dtype(config.reduce_dtype).itemsize
                        if config.reduce_dtype is not None else None)
        # per-leaf model-axis shard factor: data-axis collectives on a
        # model-sharded leaf carry 1/m of the tensor (the 2-D memory/comm
        # story — payload rides the small axis)
        m_fac = [_spec_shards(s, mesh) for s in base_leaves]

        # buckets pack the REVERSED leaf order: backward produces the last
        # layer's gradients first, so reverse-forward order approximates
        # the order buckets fill in PyTorch DDP
        order = list(range(len(leaves)))[::-1]
        wire = lambda i: counts[i] * (red_itemsize or itemsize[i]) \
            // m_fac[i]
        self.buckets = [[order[j] for j in b] for b in assign_buckets(
            [wire(i) for i in order], int(config.bucket_mb * (1 << 20)))]

        # "sharded" = the DATA axis was added beyond the base layout;
        # leaves the data axis could not land on keep the base spec and
        # are left to in/out-sharding propagation
        sharded_idx = [i for i, (s, b) in enumerate(zip(specs, base_leaves))
                       if tuple(s) != tuple(b)]
        self.sharded_set = set(sharded_idx)
        rs_bytes = sum(wire(i) for i in sharded_idx)
        full_bytes = sum(wire(i) for i in range(len(leaves)))
        ag_bytes = sum(counts[i] * itemsize[i] // m_fac[i]
                       for i in sharded_idx)
        n_dev = int(mesh.shape[data_axis])
        m_dev = int(mesh.shape[model_axis]) if model_axis else 1
        # fp32 gradient-accumulator footprint per device: sharded leaves
        # land 1/N per device under ZERO2's post-reduce-scatter layout,
        # vs the full tree when accumulating replicated (the memory story
        # tests/test_accumulation.py asserts)
        acc_sharded = sum(
            (-(-(counts[i] // m_fac[i]) // n_dev) if i in self.sharded_set
             else counts[i] // m_fac[i])
            * 4 for i in range(len(leaves)))
        acc_repl = sum(counts[i] * 4 for i in range(len(leaves)))
        # per-device param + optimizer-moment footprint (moments ~1/(d·m)
        # of the replicated tree; tests/test_mesh2d.py holds it)
        param_local = sum(counts[i] * itemsize[i] // m_fac[i]
                          for i in range(len(leaves)))
        moment_local = sum(
            (counts[i] // m_fac[i]) // (n_dev if i in self.sharded_set
                                        else 1) * itemsize[i]
            for i in range(len(leaves)))
        self.info = {
            "stage": config.stage,
            "n_buckets": len(self.buckets) if config.stage >= 2 else 0,
            "sharded_leaves": len(sharded_idx),
            "replicated_leaves": len(leaves) - len(sharded_idx),
            "devices": n_dev,
            # mesh decomposition of this plan; the declared "bytes" below
            # all ride the DATA axis (model-axis activation psums belong
            # to the model's forward/backward, not the optimizer plan)
            "mesh_axes": {"data": n_dev, "model": m_dev},
            "collective_axis": data_axis,
            "accum_bytes": {"sharded": acc_sharded,
                            "replicated": acc_repl},
            "per_device_bytes": {"params": param_local,
                                 "moments_per_state": moment_local},
            # logical payload per step (what the wire carries, not
            # ×(N-1)/N), on the DATA axis; model-sharded leaves count
            # their 1/m local shard
            "bytes": ({"reduce_scatter": rs_bytes,
                       "all_reduce": full_bytes - rs_bytes,
                       "all_gather": ag_bytes}
                      if config.stage >= 2 else
                      {"reduce_scatter": 0,
                       "all_reduce": sum(counts[i] * itemsize[i]
                                         // m_fac[i]
                                         for i in range(len(leaves))),
                       "all_gather": ag_bytes}),
        }

        # optimizer-state constraints (same specs, matched by shape)
        opt_sh_tree = zero_opt_shardings(opt_state, params,
                                         mesh, data_axis, base=base_specs)
        self.opt_sh_leaves = jax.tree_util.tree_leaves(opt_sh_tree)
        self.opt_treedef = jax.tree_util.tree_structure(opt_state)
        self.opt_shardings_tree = opt_sh_tree

    def expected_constraints(self, accum: bool = False) -> int:
        """The number of `with_sharding_constraint` applications the plan
        emits into ONE trace of its step — the static layout CONTRACT the
        IR lint tier (analysis/ir.py) checks the traced jaxpr against. A
        count below this means a shard constraint was dropped somewhere
        in zero.py: XLA's sharding propagation is then unconstrained and
        free to materialize a replicated copy of a ZeRO shard. Keep this
        formula in sync when adding/removing constraint sites (the IR
        self-host gate in tests/test_analysis.py enforces agreement).

        Sites (scan bodies trace once):
          * reduce_scatter: one constraint per SHARDED leaf (stage 2)
          * constrain_params / constrain_acc: sharded leaves each
          * constrain_opt: every optimizer-state leaf
          * accum superstep adds: acc0 init + per-microbatch accumulator
            + gradient-mean (stage 2), each over the sharded leaves
        """
        n_sharded = len(self.sharded_set)
        n_opt = len(self.opt_sh_leaves)
        stage2 = self.config.stage >= 2
        count = n_sharded + n_opt            # constrain_params + opt
        if stage2:
            count += n_sharded               # reduce_scatter
        if accum and stage2:
            # acc0, per-micro accumulator, gmean (constrain_acc x3)
            count += 3 * n_sharded
        return count

    # ---- the gradient reduction (stage 2): bucketed reduce-scatter ------
    def reduce_scatter(self, grads, token=None):
        """Bucketed reduce-scatter of a gradient tree. `token` chains the
        optimization_barrier ordering ACROSS calls: inside one backward it
        keeps XLA from collapsing the per-bucket flushes into one
        end-of-backward monolith, and threaded through the accumulation
        scan's carry it extends the same ordering across the MICROBATCH
        boundary — microbatch i's buckets flush before microbatch i+1's,
        so their traffic can overlap i+1's backward compute. Returns
        (grads, token) with token a float32 scalar."""
        config = self.config
        flat = jax.tree_util.tree_leaves(grads)
        dtypes = [g.dtype for g in flat]
        out = list(flat)
        if config.reduce_dtype is not None:
            rd = jnp.dtype(config.reduce_dtype)
            out = [g.astype(rd) for g in out]
        for bucket in self.buckets:
            vals = [out[i] for i in bucket]
            if token is not None and config.ordered_flush:
                # chain: this bucket's reduction may not be hoisted before
                # (or merged with) the previous bucket's flush
                *vals, _ = jax.lax.optimization_barrier(
                    tuple(vals) + (token,))
            vals = [jax.lax.with_sharding_constraint(v, self.shardings[i])
                    if i in self.sharded_set else v
                    for v, i in zip(vals, bucket)]
            for v, i in zip(vals, bucket):
                out[i] = v
            t = vals[0]
            t = t if t.ndim == 0 else t[(0,) * t.ndim]
            token = t.astype(jnp.float32)
        if config.reduce_dtype is not None:
            # fp32 master update: widen back after the narrow reduction
            out = [g.astype(dt) for g, dt in zip(out, dtypes)]
        return jax.tree_util.tree_unflatten(self.treedef, out), token

    def constrain_params(self, tree):
        flat = jax.tree_util.tree_leaves(tree)
        flat = [jax.lax.with_sharding_constraint(v, self.shardings[i])
                if i in self.sharded_set else v
                for i, v in enumerate(flat)]
        return jax.tree_util.tree_unflatten(self.treedef, flat)

    def constrain_opt(self, tree):
        flat = jax.tree_util.tree_leaves(tree)
        flat = [jax.lax.with_sharding_constraint(v, s)
                for v, s in zip(flat, self.opt_sh_leaves)]
        return jax.tree_util.tree_unflatten(self.opt_treedef, flat)

    def constrain_acc(self, tree):
        """Pin a param-shaped fp32 ACCUMULATOR tree to the shard layout —
        under ZERO2 each device holds only its 1/N of every accumulated
        (sharded) leaf, the post-reduce-scatter layout the per-microbatch
        sums land in."""
        return self.constrain_params(tree)


def make_zero_step(model, mesh: Mesh, *, data_axis: str = MeshAxes.DATA,
                   config: ZeroConfig = ZeroConfig(), base_specs=None,
                   model_axis: Optional[str] = None
                   ) -> Tuple[Any, Dict[str, Any]]:
    """Build the ZeRO train step for `model` (MultiLayerNetwork or
    ComputationGraph).

    Returns (step_fn, info): `step_fn` has the exact signature of the
    model's `train_step_fn` — (params, state, opt_state, step, x, y, rng,
    fmask, lmask) -> (params, state, opt_state, score) — for the trainer
    to jit with replicated params in/out (the out-sharding IS the ZeRO
    allgather), sharded opt state (`zero_opt_shardings`) and donated
    buffers. `info` carries the static per-step accounting the trainer
    feeds telemetry: logical collective payload bytes by op and the
    gradient bucket count.

    2-D composition (ISSUE 14, strategy ``zero1_tp``): `base_specs` is
    the Megatron TP PartitionSpec tree params live in BETWEEN steps
    (sharded over `model_axis`). The plan then adds the ``data`` axis on
    top — moments and the in-step updated params shard over BOTH axes —
    and the jit's TP param out-sharding makes the trailing allgather ride
    the DATA axis only (each model group gathers its own 1/m shard).
    """
    plan = _ZeroPlan(model, mesh, data_axis, config, base_specs=base_specs,
                     model_axis=model_axis)
    plan.info["expected_constraints"] = plan.expected_constraints()
    # the model's grad half (loss selection incl. remat + minimize sign)
    grad_fn = model.grad_step_fn

    def step(params, state, opt_state, step_i, x, y, rng, fmask, lmask):
        score, new_state, grads = grad_fn(params, state, x, y, rng,
                                          fmask, lmask)
        if config.stage >= 2:
            grads, _ = plan.reduce_scatter(grads)
        new_params, new_opt = model.apply_updates(params, grads, opt_state,
                                                  step_i)
        # each device computes only ITS shard of the new params and
        # moments; the jit's replicated param out-sharding is then the
        # trailing ZeRO allgather
        new_params = plan.constrain_params(new_params)
        new_opt = plan.constrain_opt(new_opt)
        return new_params, new_state, new_opt, score

    return step, plan.info


def make_zero_accum_superstep(model, mesh: Mesh, *,
                              data_axis: str = MeshAxes.DATA,
                              config: ZeroConfig = ZeroConfig(),
                              skip_nonfinite: bool = False,
                              base_specs=None,
                              model_axis: Optional[str] = None
                              ) -> Tuple[Any, Dict[str, Any]]:
    """The ZeRO ACCUMULATED superstep (ISSUE 12): a nested scan over
    [K, M, batch, ...] windows — outer over K optimizer steps, inner over
    each step's M microbatches — where ZERO2 accumulates into the
    *post-reduce-scatter sharded* layout:

      * every microbatch's gradients are bucket-reduce-scattered as its
        backward produces them, and the fp32 accumulator is CONSTRAINED to
        the shard specs, so per-device accumulator memory is ~1/N of the
        replicated tree (`info["accum_bytes"]`);
      * the optimization_barrier token threads through the scan carry, so
        microbatch i's bucket flushes stay ordered before microbatch
        i+1's — on hardware with async collectives, i's reduce-scatter
        traffic overlaps i+1's backward compute (the structural overlap
        `collective_overlap_fraction` reports);
      * the update then runs once per outer step on the sharded mean, and
        the jit's replicated param out-sharding is the trailing
        allgather — ONE allgather per optimizer step, not per microbatch.

    ZERO1 accumulates the unreduced gradient tree (full-size accumulator,
    the classic stage-1 memory story) and lets XLA place the single
    deferred reduction at the update's shard constraints.

    Signature matches ``nn/superstep.build_accum_superstep``: returns
    (params, state, opt, rng, scores[K], micro_scores[K, M]); the trainer
    jits it with the training shardings and donation. `skip_nonfinite`
    mirrors the generic builder (zero the bad microbatch's gradient,
    renormalize over the finite ones).
    """
    plan = _ZeroPlan(model, mesh, data_axis, config, base_specs=base_specs,
                     model_axis=model_axis)
    plan.info["expected_constraints"] = plan.expected_constraints(accum=True)
    grad_fn = model.grad_step_fn
    stage2 = config.stage >= 2

    def superstep(params, state, opt_state, step0, rng0, xs, ys, fm, lm):
        f32 = jnp.float32

        def opt_body(carry, inp):
            params, state, opt, step, rng, token = carry
            n_micro = jax.tree_util.tree_leaves(inp)[0].shape[0]

            def micro_body(mcarry, minp):
                state, rng, acc, n_ok, ssum, token, mbuf, mi = mcarry
                x, y, f, l = minp
                rng, k = jax.random.split(rng)
                score, new_state, grads = grad_fn(params, state, x, y, k,
                                                  f, l)
                if stage2:
                    grads, token = plan.reduce_scatter(grads, token)
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
                if stage2:
                    # keep the running sum pinned to the shard layout —
                    # the accumulator never materializes replicated
                    acc = plan.constrain_acc(acc)
                # carried, int32-indexed score buffer (NOT a scan
                # output): on a 2-D mesh GSPMD shards the scan-output
                # stacking buffer over an axis dividing M and this XLA
                # version mis-types the partitioned update (see
                # nn/superstep.build_accum_superstep)
                mbuf = jax.lax.dynamic_update_index_in_dim(
                    mbuf, score.astype(f32), mi, 0)
                return (state, rng, acc, n_ok, ssum, token, mbuf,
                        mi + jnp.int32(1)), None

            acc0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(jnp.shape(p), f32), params)
            if stage2:
                acc0 = plan.constrain_acc(acc0)
            (state, rng, acc, n_ok, ssum, token, mscores,
             _mi), _ = jax.lax.scan(
                micro_body, (state, rng, acc0, f32(0.0), f32(0.0), token,
                             jnp.zeros((n_micro,), f32), jnp.int32(0)),
                inp)
            denom = jnp.maximum(n_ok, 1.0)
            gmean = jax.tree_util.tree_map(
                lambda a, p: (a / denom).astype(jnp.result_type(p)),
                acc, params)
            if stage2:
                gmean = plan.constrain_acc(gmean)
            new_params, new_opt = model.apply_updates(params, gmean, opt,
                                                      step)
            new_params = plan.constrain_params(new_params)
            new_opt = plan.constrain_opt(new_opt)
            score = jnp.where(n_ok > 0, ssum / denom, jnp.nan)
            return ((new_params, state, new_opt, step + 1, rng, token),
                    (score, mscores))

        token0 = jnp.zeros((), jnp.float32)
        ((params, state, opt, _step, rng, _token),
         (scores, mscores)) = jax.lax.scan(
            opt_body, (params, state, opt_state, step0, rng0, token0),
            (xs, ys, fm, lm))
        return params, state, opt, rng, scores, mscores

    return superstep, plan.info


def collective_overlap_fraction(info: Dict[str, Any], m: int) -> float:
    """Structural collective/compute overlap for the telemetry gauge
    ``dl4j_collective_overlap_fraction``: the fraction of the per-step
    reduce-scatter payload issued while independent backward compute
    remains in flight to hide it. With M accumulation microbatches and B
    buckets per backward, M·B flushes are issued per optimizer step and
    every one except the LAST still has backward work behind it (the next
    bucket's producers, or the next microbatch entirely) — so the
    fraction is 1 - 1/(M·B). Stage 1 defers its reduction to the step end
    (nothing scheduled to overlap): 0.0. This is schedule accounting, not
    a wall-clock measurement — the single-process CPU mesh serializes
    collectives, so the wall-clock number needs a real pod (same caveat
    as the ZeRO efficiency gate)."""
    if int(info.get("stage", 1)) < 2 or not info.get("n_buckets"):
        return 0.0
    flushes = max(1, int(m)) * int(info["n_buckets"])
    return round(1.0 - 1.0 / flushes, 4)
