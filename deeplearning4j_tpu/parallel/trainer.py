"""Parallel training — the TPU-native replacement for the reference's entire
scale-out stack.

Subsumes (SURVEY.md §2.4):
  * `ParallelWrapper` (`deeplearning4j-scaleout-parallelwrapper/.../ParallelWrapper.java:48`)
    — single-node multi-device data parallelism with parameter averaging every
    N iterations (`averageModelsParams` :218, `averageUpdatersState` :239).
  * Spark `ParameterAveragingTrainingMaster` — cluster-synchronous averaging
    over TCP broadcast/aggregate.
  * Aeron parameter server (`ParameterServerParallelWrapper.java:39`) — async
    push/pull.

TPU-native design: one jitted train step over a named mesh. In SYNC mode the
batch is sharded over "data" and XLA inserts ONE gradient psum over ICI per
step — the idiomatic successor of both the averaging wrapper and the parameter
server (commodity-Ethernet workarounds). AVERAGING mode (local SGD /
parameter averaging every N steps) is retained as an option for
DCN-connected slices, exactly the capability the reference's
`averagingFrequency` provided: each device holds its own replica (stacked
leading axis, sharded over "data"), trains locally, and every N iterations
the replicas are averaged with a mean over the device axis (an ICI/DCN
allreduce under jit) — updater state optionally averaged too
(`averageUpdatersState` parity).

Tensor-parallel / FSDP param shardings compose with SYNC mode via
`strategy=` (see `sharding.py`). `ShardingStrategy.ZERO1`/`ZERO2` keep
params replicated but shard optimizer state (and stage-2 reduced
gradients) over the data axis — reduce-scatter -> sharded update ->
allgather instead of allreduce -> replicated update (see `zero.py`),
removing the replicated-updater work an earlier installation's capture
(BASELINE.md) pointed at.
"""
from __future__ import annotations

import functools
import logging
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import MeshAxes, make_mesh
from .sharding import ShardingStrategy, param_specs
from ..datasets.iterators import DataSet, DataSetIterator, MultiDataSet
from ..telemetry.compile_watch import watch_compiles
from ..telemetry.runtime import (active as _tel_active,
                                 null_span as _null_span, span as _span)

__all__ = ["ParallelTrainer", "ParallelWrapper", "TrainingMode",
           "configure_flash_attention"]

log = logging.getLogger("deeplearning4j_tpu")


class TrainingMode:
    SYNC = "sync"              # per-step gradient allreduce (idiomatic)
    AVERAGING = "averaging"    # local SGD, average params every N iterations


def _to_host(tree):
    """Host-local copy of a (fully-replicated) device pytree."""
    return jax.tree_util.tree_map(
        lambda a: jnp.asarray(np.asarray(a)), tree)


#: supported mode × strategy combinations, validated up front in __init__
#: (AVERAGING keeps an independent full replica per device, so every
#: sharded strategy is out; SYNC composes with all of them)
_MODE_STRATEGIES = {
    TrainingMode.SYNC: (
        ShardingStrategy.REPLICATED, ShardingStrategy.TENSOR_PARALLEL,
        ShardingStrategy.FSDP, ShardingStrategy.ZERO1,
        ShardingStrategy.ZERO2, ShardingStrategy.ZERO1_TP,
        ShardingStrategy.PIPELINE, ShardingStrategy.PP,
        ShardingStrategy.ZERO1_TP_PP),
    TrainingMode.AVERAGING: (ShardingStrategy.REPLICATED,),
}

#: the mesh-native 1F1B strategies (ISSUE 15): one jitted SPMD program
#: per optimizer step on a (data, model, pipe) mesh
_PP_STRATEGIES = (ShardingStrategy.PP, ShardingStrategy.ZERO1_TP_PP)

#: strategies that compose with a 2-D (data, model) mesh (model axis
#: size > 1): replicated ignores the model axis (baseline arm of the
#: mesh2d ablations), tensor_parallel is DP×TP, zero1_tp is ZeRO-1×TP
_MESH2D_STRATEGIES = (ShardingStrategy.REPLICATED,
                      ShardingStrategy.TENSOR_PARALLEL,
                      ShardingStrategy.ZERO1_TP,
                      ShardingStrategy.ZERO1_TP_PP)

#: why each remaining strategy is NOT a 2-D citizen (the actionable half
#: of the rejection message)
_MESH2D_HINTS = {
    ShardingStrategy.ZERO1: (
        "zero1 shards moments over 'data' only and would leave the model "
        "axis training redundant replicas — use strategy='zero1_tp' to "
        "shard params over 'model' AND moments over 'data'"),
    ShardingStrategy.ZERO2: (
        "zero2's bucketed reduce-scatter packs full-size gradient leaves "
        "and is not generalized to model-sharded gradients yet — use "
        "strategy='zero1_tp' (ZeRO-1 × tensor parallel)"),
    ShardingStrategy.FSDP: (
        "fsdp shards params over 'data'; composing it with a model axis "
        "is not supported — use strategy='zero1_tp'"),
    ShardingStrategy.PIPELINE: (
        "the pipeline trainer stages over its own 'pipe' axis — build "
        "the mesh with {'pipe': n} instead of a model axis"),
}


def _validate_mode_strategy(mode: str, strategy: str, mesh=None,
                            model_axis: str = MeshAxes.MODEL,
                            data_axis: str = MeshAxes.DATA,
                            pipe_axis: str = MeshAxes.PIPE) -> None:
    """One actionable error for every unsupported (mode, strategy,
    mesh-shape) combination — raised before any mesh/model work instead
    of failing deep in _prepare (or as a KeyError inside param_specs)."""
    pairs = "; ".join(
        f"{m}: {', '.join(s)}" for m, s in sorted(_MODE_STRATEGIES.items()))
    if mode not in _MODE_STRATEGIES:
        raise ValueError(
            f"unknown training mode '{mode}'. Supported mode -> "
            f"strategies: {pairs}")
    if strategy not in _MODE_STRATEGIES[TrainingMode.SYNC]:
        raise ValueError(
            f"unknown sharding strategy '{strategy}'. Supported mode -> "
            f"strategies: {pairs}")
    if strategy not in _MODE_STRATEGIES[mode]:
        hint = ""
        if mode == TrainingMode.AVERAGING:
            hint = (" — parameter averaging needs every device to hold an "
                    "independent FULL replica; use TrainingMode.SYNC for "
                    "sharded strategies (tensor_parallel/fsdp/zero1/zero2/"
                    "zero1_tp/pipeline)")
        raise ValueError(
            f"mode={mode} does not support strategy='{strategy}'{hint}. "
            f"Supported mode -> strategies: {pairs}")
    if mesh is None:
        return
    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    model_size = int(axes.get(model_axis, 1))
    pipe_size = int(axes.get(pipe_axis, 1))
    if strategy in (ShardingStrategy.TENSOR_PARALLEL,
                    ShardingStrategy.ZERO1_TP) \
            and model_axis not in mesh.axis_names:
        raise ValueError(
            f"strategy='{strategy}' shards params over a '{model_axis}' "
            f"mesh axis, but the mesh only carries {mesh.axis_names}. "
            "Build a 2-D mesh: ParallelTrainer(model, mesh_shape=(d, m)) "
            "or mesh=make_mesh({'data': d, 'model': m})")
    if strategy in _PP_STRATEGIES:
        if pipe_axis not in mesh.axis_names or pipe_size < 2:
            raise ValueError(
                f"strategy='{strategy}' stages the model over a "
                f"'{pipe_axis}' mesh axis of size >= 2, but the mesh "
                f"carries {dict(axes)}. Build a 3-D mesh: "
                "ParallelTrainer(model, mesh_shape=(d, m, p))")
        if strategy == ShardingStrategy.PP \
                and (int(axes.get(data_axis, 1)) > 1 or model_size > 1):
            raise ValueError(
                f"strategy='pp' is the pure pipeline (data=model=1); the "
                f"mesh carries {dict(axes)} — use strategy='zero1_tp_pp' "
                "to compose data/model axes with the pipeline")
    elif pipe_size > 1 and strategy != ShardingStrategy.PIPELINE:
        raise ValueError(
            f"the mesh carries a '{pipe_axis}' axis of size {pipe_size}, "
            f"but strategy='{strategy}' does not stage over it — use "
            "strategy='pp' or 'zero1_tp_pp' (mesh-native 1F1B), "
            "strategy='pipeline' (host-driven GPipe), or drop the pipe "
            "axis")
    if model_size > 1:
        if mode == TrainingMode.AVERAGING:
            raise ValueError(
                f"mode={mode} does not support a 2-D mesh (model axis "
                f"size {model_size}) — parameter averaging keeps one "
                "independent replica per DATA device; use "
                "TrainingMode.SYNC with strategy='tensor_parallel' or "
                "'zero1_tp' on 2-D meshes")
        if strategy not in _MESH2D_STRATEGIES:
            raise ValueError(
                f"strategy='{strategy}' does not support a 2-D mesh "
                f"(model axis size {model_size}): "
                f"{_MESH2D_HINTS[strategy]}. Supported 2-D strategies: "
                f"{', '.join(_MESH2D_STRATEGIES)}")


#: strategies whose sharded step can host the Pallas flash kernel via
#: shard_map (ISSUE 18): the Megatron roles model-shard the head axis,
#: so each shard's local [B/d, T, H/m, Dh] block is a standalone
#: attention problem — zero collectives inside the kernel region. The
#: 1F1B strategies stay on einsum: the stage body nests shard_map under
#: vmap under scan under jax.checkpoint, and the (data, model, pipe)
#: specs don't cover the pipe axis.
_FLASH_SPMD_STRATEGIES = (ShardingStrategy.TENSOR_PARALLEL,
                          ShardingStrategy.ZERO1_TP)


def configure_flash_attention(model, mesh, strategy,
                              model_axis: str = MeshAxes.MODEL,
                              data_axis: str = MeshAxes.DATA,
                              force=None):
    """Capability-gated attention-implementation selection for every
    trainer-managed layer with a `flash` switch (TransformerBlock).

    GSPMD cannot partition a Pallas custom call, so the plain flash
    kernel inside a sharded jit would force replication — the silent
    reshard the IR lint exists to catch. Instead of the old blanket
    `flash=False` pin, pick per capability:

      * "spmd" — `kernels.attention.flash_attention_spmd`: the kernel
        under `shard_map` over (data, model). Requires a strategy whose
        activations are laid out [B@data, T, H@model, Dh] locally
        (`_FLASH_SPMD_STRATEGIES`) and a live Pallas backend
        (`kernels.pallas_supported()` — TPU, not disabled).
      * False — einsum `attention_reference` fallback (GSPMD shards
        plain einsums cleanly). CPU/virtual meshes land here: the
        interpret-mode kernel is a correctness tool, not a fast path.

    `force` overrides the probe ("spmd"/False) — tests and IR probes
    use force="spmd" to exercise the shard_map lowering on the virtual
    mesh, where interpret mode makes it correct but slow.

    Mutates instance attrs only (`conf_l.flash`, `conf_l.flash_spmd`);
    class-level "auto" stays for standalone/single-device use. Returns
    `(mode, reason)` and logs one line; (None, reason) when the model
    has no flash-switched layers.
    """
    from ..nn.graph import ComputationGraph

    layer_confs = [conf_l for conf_l in
                   (model.conf.vertices.values()
                    if isinstance(model, ComputationGraph)
                    else getattr(model, "layers", ()) or ())
                   if hasattr(conf_l, "flash")]
    if not layer_confs:
        return None, "no attention layers"
    if force is not None:
        mode, reason = force, f"forced ({force!r})"
    elif strategy not in _FLASH_SPMD_STRATEGIES:
        mode, reason = False, (
            f"strategy '{strategy}' has no shard_map flash path "
            f"(supported: {', '.join(_FLASH_SPMD_STRATEGIES)}) — einsum "
            "attention_reference (GSPMD-partitionable) selected")
    else:
        from ..kernels import pallas_supported

        if pallas_supported():
            mode, reason = "spmd", (
                "Pallas flash attention under shard_map over "
                f"('{data_axis}', '{model_axis}') — per-shard kernel, "
                "zero collectives in the kernel region")
        else:
            mode, reason = False, (
                f"backend '{jax.default_backend()}' has no compiled "
                "Pallas path (CPU/virtual mesh, or "
                "DL4J_TPU_DISABLE_PALLAS) — einsum attention_reference "
                "selected; rerun on a TPU backend for the kernel")
    for conf_l in layer_confs:
        conf_l.flash = mode
        conf_l.flash_spmd = ((mesh, data_axis, model_axis)
                             if mode == "spmd" else None)
    log.info("flash attention [%d layer(s), strategy=%s]: %s",
             len(layer_confs), strategy, reason)
    return mode, reason


class ParallelTrainer:
    """fit(iterator) over a device mesh.

    Builder-style kwargs mirror ParallelWrapper's:
      workers ~ mesh size (derived), averaging_frequency, average_updaters,
      prefetch_buffer (host-side async iterator wrapping).
    """

    # TrainingGuard snapshot scope: the mesh-resident trees + counters the
    # sharded step mutates (fault/guard.py)
    _fault_state_attrs = ("_params", "_state", "_opt", "_rng",
                          "iteration_count", "_score")

    def _fault_restored(self):
        """TrainingGuard rollback hook: the restore rewinds
        iteration_count, so the per-step eval-view caches keyed on it
        could serve pre-rollback params at a reused key — drop them."""
        self._host_cache = None
        self._eval_cache = None
        self._pp_pub_iter = None
        self._pp_pub_iter = None

    def __init__(self, model, mesh: Optional[Mesh] = None,
                 mode: str = TrainingMode.SYNC,
                 strategy: str = ShardingStrategy.REPLICATED,
                 averaging_frequency: int = 5,
                 average_updaters: bool = True,
                 data_axis: str = MeshAxes.DATA,
                 model_axis: str = MeshAxes.MODEL,
                 collect_stats: bool = False,
                 zero_bucket_mb: Optional[float] = None,
                 zero_reduce_dtype: Optional[str] = None,
                 mesh_shape: Optional[tuple] = None,
                 flash=None):
        if mesh_shape is not None:
            # mesh shorthand: (d, m) builds the 2-D (data, model) mesh
            # (ISSUE 14); (d, m, p) the 3-D (data, model, pipe) mesh for
            # the 1F1B pipeline strategies (ISSUE 15) — d-way ZeRO/data
            # parallelism × m-way Megatron tensor parallelism × p-way
            # pipeline stages on d·m·p devices
            if mesh is not None:
                raise ValueError(
                    "pass mesh= OR mesh_shape=(d, m[, p]), not both")
            if len(mesh_shape) == 2:
                axes = {data_axis: int(mesh_shape[0]),
                        model_axis: int(mesh_shape[1])}
            elif len(mesh_shape) == 3:
                axes = {data_axis: int(mesh_shape[0]),
                        model_axis: int(mesh_shape[1]),
                        MeshAxes.PIPE: int(mesh_shape[2])}
            else:
                raise ValueError(
                    "mesh_shape must be (data, model) or (data, model, "
                    f"pipe), got {mesh_shape!r}")
            # a product smaller than the device count uses the FIRST
            # d·m[·p] devices (e.g. mesh_shape=(1, 1, 4) on the 8-dev
            # CPU mesh); make_mesh still rejects a product larger than
            # the machine
            total = int(np.prod(list(axes.values())))
            devs = jax.devices()
            mesh = make_mesh(axes, devices=devs[:total]
                             if 0 < total < len(devs) else None)
        mesh = mesh if mesh is not None else make_mesh()
        _validate_mode_strategy(mode, strategy, mesh, model_axis, data_axis)
        if (strategy not in (ShardingStrategy.ZERO1, ShardingStrategy.ZERO2)
                and (zero_bucket_mb is not None
                     or zero_reduce_dtype is not None)):
            # silently ignoring the knobs would let a user believe they
            # enabled bucketing / the bf16 wire on a step that has neither
            # (ZERO1_TP is stage 1: no buckets, no narrow wire)
            raise ValueError(
                "zero_bucket_mb/zero_reduce_dtype only apply to the ZeRO "
                f"strategies (zero1/zero2); strategy='{strategy}' ignores "
                "them — drop the knobs or switch strategy")
        if model.params is None:
            model.init()
        # attention implementation per capability (ISSUE 18): shard_map'd
        # Pallas kernel where the strategy/backend supports it, einsum
        # fallback (with one log line) elsewhere — replaces the old
        # blanket flash=False pin
        self.flash_mode, _ = configure_flash_attention(
            model, mesh, strategy, model_axis, data_axis, force=flash)
        self.model = model
        self.mesh = mesh
        self.mode = mode
        self.strategy = strategy
        self.averaging_frequency = max(1, int(averaging_frequency))
        self.average_updaters = average_updaters
        # per-phase timing (SparkTrainingStats analog); adds one host sync
        # per step, so it's opt-in like the reference's collectTrainingStats
        self.stats = None
        if collect_stats:
            from .stats import TrainingStats

            self.stats = TrainingStats()
        self.data_axis = data_axis
        self.model_axis = model_axis
        # ZeRO knobs (strategy zero1/zero2): gradient bucket size bound
        # (None = zero.DEFAULT_BUCKET_MB) and the optional narrow wire
        # dtype for the stage-2 reduction
        self.zero_bucket_mb = (None if zero_bucket_mb is None
                               else float(zero_bucket_mb))
        self.zero_reduce_dtype = zero_reduce_dtype
        self._zero_info = None
        self._host_cache = None
        self._eval_cache = None
        self._pp_pub_iter = None
        if strategy == ShardingStrategy.PIPELINE:
            # stage-partitioned training of a real MultiLayerNetwork: the
            # mesh must carry a "pipe" axis; delegate to the GPipe trainer
            from .pipeline import (PipelinedGraphTrainer,
                                   PipelinedNetworkTrainer)
            from ..nn.graph import ComputationGraph

            axis = (MeshAxes.PIPE if MeshAxes.PIPE in self.mesh.axis_names
                    else data_axis)
            cls = (PipelinedGraphTrainer
                   if isinstance(model, ComputationGraph)
                   else PipelinedNetworkTrainer)
            self._pipe = cls(model, self.mesh, axis=axis)
            self.n_data = 1
            self.iteration_count = 0
            self._pp_plan = None
            self._rng = self._pipe._rng
            return
        self._pipe = None
        self._pp_plan = None
        self._pp_zero_plan = None
        self.n_data = self.mesh.shape[data_axis]
        if mode == TrainingMode.AVERAGING and jax.process_count() > 1:
            # the multi-host dataset plane (global_batch_array assembly)
            # only exists for SYNC; AVERAGING would hand host-local arrays
            # to shard_map over a partially-addressable mesh and fail with
            # an opaque XLA error deep in dispatch
            raise ValueError(
                "AVERAGING mode is single-process only; use "
                "TrainingMode.SYNC for multi-process meshes (per-step "
                "gradient allreduce), optionally with a local-SGD cadence "
                "via averaging_frequency on a single host")
        self._prepare()

    # ------------------------------------------------------------------
    def _prepare(self):
        if self._pipe is not None:
            # legacy host-GPipe: re-place the model's (restored) trees on
            # the stage devices — the checkpoint-restore path
            # (_ShardedTrainerStore.restore) re-prepares through here
            p = self._pipe
            p._place_params()
            p.iteration_count = int(self.model.iteration_count)
            p._score = float("nan")
            self.iteration_count = p.iteration_count
            rng = getattr(self.model, "_rng", None)
            p._rng = rng if rng is not None else jax.random.PRNGKey(0)
            self._rng = p._rng
            self._host_cache = None
            self._eval_cache = None
            return
        m = self.model
        mesh = self.mesh
        repl = NamedSharding(mesh, P())
        batch_sh = NamedSharding(mesh, P(self.data_axis))
        # kept for the evaluation/scoring plane (jit of predict/score fns
        # with the same shardings as the train step)
        self._repl = repl
        self._batch_sh = batch_sh
        self._p_sh = repl
        self._s_sh = repl
        if self.mode == TrainingMode.SYNC \
                and self.strategy in _PP_STRATEGIES:
            # mesh-native 1F1B (ISSUE 15): the model's homogeneous layer
            # run is stage-stacked and pipe-sharded; the trainer-resident
            # trees live in pp form ({"head", "stack", "tail"}) — the
            # step is ONE jitted SPMD program per optimizer step.
            # ZERO1_TP_PP additionally TP-shards params over `model` and
            # ZeRO-1-shards the optimizer moments over `data` (the
            # trailing param allgather rides ONLY the data axis).
            from .pipeline import PipelinePlan, make_pp_step
            from .sharding import _opt_sharding_like

            two_d = self.strategy == ShardingStrategy.ZERO1_TP_PP
            plan = PipelinePlan(m, mesh, pipe_axis=MeshAxes.PIPE,
                                model_axis=self.model_axis,
                                data_axis=self.data_axis, tp=two_d)
            self._pp_plan = plan
            p_specs = plan.param_specs()
            p_sh = plan.shardings(p_specs)
            s_sh = plan.shardings(plan.state_specs())
            params_pp = plan.stack(m.params)
            state_pp = plan.stack(m.state)
            opt_pp = plan.stack(m.updater_state)
            zero_plan = None
            if two_d:
                from .zero import ZeroConfig, _ZeroPlan
                zero_plan = _ZeroPlan(m, mesh, self.data_axis,
                                      ZeroConfig(stage=1),
                                      base_specs=p_specs,
                                      model_axis=self.model_axis,
                                      params=params_pp, opt_state=opt_pp)
                o_sh = zero_plan.opt_shardings_tree
                self._zero_info = dict(zero_plan.info)
                self._zero_info["expected_constraints"] = \
                    zero_plan.expected_constraints()
            else:
                o_sh = _opt_sharding_like(opt_pp, params_pp, p_sh)
            self._pp_zero_plan = zero_plan
            step_fn, self._pp_info = make_pp_step(m, plan,
                                                  zero_plan=zero_plan)
            self._p_sh = p_sh
            self._s_sh = s_sh
            self._o_sh = o_sh
            self._params = jax.device_put(params_pp, p_sh)
            self._state = jax.device_put(state_pp, s_sh)
            self._opt = jax.device_put(opt_pp, o_sh)
            self._raw_step_fn = step_fn
            self._step_fn = watch_compiles(jax.jit(
                step_fn,
                in_shardings=(p_sh, s_sh, o_sh, repl, batch_sh, batch_sh,
                              repl, batch_sh, batch_sh),
                out_shardings=(p_sh, s_sh, o_sh, repl),
                donate_argnums=(0, 1, 2)),
                "parallel/zero1_tp_pp_step" if two_d
                else "parallel/pp_step")
        elif self.mode == TrainingMode.SYNC and self.strategy in (
                ShardingStrategy.ZERO1, ShardingStrategy.ZERO2,
                ShardingStrategy.ZERO1_TP):
            # ZeRO: params replicated between steps, optimizer moments
            # sharded over the data axis; the step reduce-scatters grads
            # (stage 2), updates only the local shard and allgathers the
            # new params via the replicated out-sharding. Buffers donate
            # end-to-end exactly like the replicated step.
            #
            # ZERO1_TP (ISSUE 14): params live MODEL-sharded between
            # steps (Megatron specs from sharding.py), moments shard over
            # (model, data), and the TP param out-sharding pins the
            # trailing allgather to the DATA axis only — no device holds
            # more than 1/m of the params or ~1/(d·m) of the moments.
            from .sharding import model_layer_hints
            from .zero import (DEFAULT_BUCKET_MB, ZeroConfig, make_zero_step,
                               zero_opt_shardings)
            two_d = self.strategy == ShardingStrategy.ZERO1_TP
            cfg = ZeroConfig(
                stage=2 if self.strategy == ShardingStrategy.ZERO2 else 1,
                bucket_mb=(DEFAULT_BUCKET_MB if self.zero_bucket_mb is None
                           else self.zero_bucket_mb),
                reduce_dtype=self.zero_reduce_dtype)
            base_specs = None
            p_sh = repl
            if two_d:
                base_specs = param_specs(
                    m.params, self.strategy, mesh, self.model_axis,
                    self.data_axis, layers=model_layer_hints(m))
                p_sh = jax.tree_util.tree_map(
                    lambda s: NamedSharding(mesh, s), base_specs,
                    is_leaf=lambda x: isinstance(x, P))
            step_fn, self._zero_info = make_zero_step(
                m, mesh, data_axis=self.data_axis, config=cfg,
                base_specs=base_specs,
                model_axis=self.model_axis if two_d else None)
            o_sh = zero_opt_shardings(m.updater_state, m.params, mesh,
                                      self.data_axis, base=base_specs)
            self._p_sh = p_sh
            self._state = jax.device_put(m.state, repl)
            if jax.process_count() > 1:
                # device_put of a host tree onto a NON-fully-addressable
                # sharded layout needs a cross-process equality check the
                # CPU backend lacks; place replicated, then let an SPMD
                # identity slice each process's shards out
                opt = jax.device_put(m.updater_state, repl)
                self._opt = watch_compiles(
                    jax.jit(lambda t: t, out_shardings=o_sh),
                    "parallel/opt_placement")(opt)
                if two_d:
                    par = jax.device_put(m.params, repl)
                    self._params = watch_compiles(
                        jax.jit(lambda t: t, out_shardings=p_sh),
                        "parallel/param_placement")(par)
                else:
                    self._params = jax.device_put(m.params, repl)
            else:
                self._opt = jax.device_put(m.updater_state, o_sh)
                self._params = jax.device_put(m.params, p_sh)
            self._raw_step_fn = step_fn
            self._o_sh = o_sh
            self._step_fn = watch_compiles(jax.jit(
                step_fn,
                in_shardings=(p_sh, repl, o_sh, repl, batch_sh, batch_sh,
                              repl, batch_sh, batch_sh),
                out_shardings=(p_sh, repl, o_sh, repl),
                donate_argnums=(0, 1, 2)),
                "parallel/zero_tp_step" if two_d else "parallel/zero_step")
        elif self.mode == TrainingMode.SYNC:
            from .sharding import model_layer_hints
            specs = param_specs(m.params, self.strategy, mesh,
                                self.model_axis, self.data_axis,
                                layers=model_layer_hints(m))
            p_sh = jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), specs,
                is_leaf=lambda x: isinstance(x, P))
            from .sharding import _opt_sharding_like
            o_sh = _opt_sharding_like(m.updater_state, m.params, p_sh)
            self._p_sh = p_sh
            self._params = jax.device_put(m.params, p_sh)
            self._state = jax.device_put(m.state, repl)
            self._opt = jax.device_put(m.updater_state, o_sh)
            self._raw_step_fn = m.train_step_fn
            self._o_sh = o_sh
            self._step_fn = watch_compiles(jax.jit(
                m.train_step_fn,
                in_shardings=(p_sh, repl, o_sh, repl, batch_sh, batch_sh,
                              repl, batch_sh, batch_sh),
                out_shardings=(p_sh, repl, o_sh, repl),
                donate_argnums=(0, 1, 2)), "parallel/train_step")
        else:
            # AVERAGING: no superstep (per-replica local SGD averages on a
            # host-driven cadence) — per-batch dispatch only
            self._raw_step_fn = None
            self._o_sh = None
            # AVERAGING: per-device replicas — stack params on a leading
            # device axis sharded over data
            n = self.n_data
            stack_sh = NamedSharding(mesh, P(self.data_axis))

            def stack(a):
                return jnp.broadcast_to(a[None], (n,) + a.shape)

            self._params = jax.device_put(
                jax.tree_util.tree_map(stack, m.params), stack_sh)
            self._state = jax.device_put(
                jax.tree_util.tree_map(stack, m.state), stack_sh)
            self._opt = jax.device_put(
                jax.tree_util.tree_map(stack, m.updater_state), stack_sh)

            from jax import shard_map
            axis = self.data_axis

            def local_step(params, state, opt, step, x, y, fm, lm, rng):
                # leading axis is the local replica block (size 1); x/y are
                # arrays (MultiLayerNetwork) or dicts (ComputationGraph
                # MultiDataSet batches) — tree ops cover both; fm/lm are
                # optional masks (None = empty pytree, passes through)
                sq = lambda t: jax.tree_util.tree_map(lambda a: a[0], t)
                uq = lambda t: jax.tree_util.tree_map(lambda a: a[None], t)
                dev = jax.lax.axis_index(axis)
                rng = jax.random.fold_in(rng, dev)
                p, s, o, score = self.model.train_step_fn(
                    sq(params), sq(state), sq(opt), step, sq(x), sq(y), rng,
                    sq(fm), sq(lm))
                return uq(p), uq(s), uq(o), score[None]

            spec = P(axis)
            self._local_step = watch_compiles(jax.jit(shard_map(
                local_step, mesh=mesh,
                in_specs=(spec, spec, spec, P(), spec, spec, spec, spec,
                          P()),
                out_specs=(spec, spec, spec, spec),
                check_vma=False), donate_argnums=(0, 1, 2)),
                "parallel/local_step")

            def average(params, opt):
                pa = jax.tree_util.tree_map(
                    lambda a: jnp.broadcast_to(a.mean(0, keepdims=True),
                                               a.shape), params)
                if self.average_updaters:
                    oa = jax.tree_util.tree_map(
                        lambda a: jnp.broadcast_to(a.mean(0, keepdims=True),
                                                   a.shape), opt)
                else:
                    oa = opt
                return pa, oa

            self._average = watch_compiles(jax.jit(
                average,
                in_shardings=(stack_sh, stack_sh),
                out_shardings=(stack_sh, stack_sh),
                donate_argnums=(0, 1)), "parallel/average")

        self.iteration_count = 0
        self._score = float("nan")
        # evaluation-view caches (per trained step; see _host_view). Reset
        # here because a checkpoint restore re-prepares with NEW params at
        # a possibly-identical iteration count
        self._host_cache = None
        self._eval_cache = None
        self._pp_pub_iter = None
        # a restore re-prepares with a fresh raw step closure; drop the
        # cached superstep jits so they can't capture the stale one
        self.__dict__.pop("_superstep_jit", None)
        self.__dict__.pop("_accum_superstep_cache", None)
        self._rng = m._rng if getattr(m, "_rng", None) is not None else \
            jax.random.PRNGKey(0)

    # ------------------------------------------------------------------
    def fit(self, data, epochs: int = 1, *, superstep=1,
            grad_accumulation: int = 1, prefetch: bool = False,
            pad_ragged: bool = False, time_buckets=None,
            checkpoint_dir: Optional[str] = None, checkpoint_every: int = 0,
            resume: bool = False, guard=None):
        """`pad_ragged` pads ragged final batches up to the fixed batch
        size with weight-zero mask rows (the same `_pad_to` zero-fill, made
        a learning no-op by mask-normalized loss/regularization) — every
        example trains instead of the remainder being dropped, and the
        sharded step keeps ONE signature. `prefetch` stages
        `device_tuple()` one batch ahead on a background thread (see
        datasets/pipeline.py).

        `superstep=K` composes the device-resident superstep (one jitted
        `lax.scan` dispatch per K-batch window — nn/superstep.py) with the
        SYNC sharded step: REPLICATED, TENSOR_PARALLEL, FSDP and the ZeRO
        strategies all scan their own step with the training shardings
        carried through the window. REPLICATED windows are BIT-IDENTICAL
        to per-batch; the ZeRO strategies are allclose-tight (~float32
        ulp) — XLA may reassociate the step's collectives inside the scan
        body. Falls back to per-batch dispatch (with a log line) for
        AVERAGING/PIPELINE, multi-process meshes, and `collect_stats`
        (whose phase timers are per-batch by contract).

        `grad_accumulation=M` accumulates M consecutive iterator
        microbatches into one optimizer step for every SYNC strategy
        (effective global batch M·b at b's activation memory; one
        iteration/listener event and one lr-schedule step per OPTIMIZER
        step). Under ZERO2 each microbatch's gradient buckets are
        reduce-scattered as backward produces them and summed into the
        SHARDED fp32 accumulator (~1/N accumulator memory per device),
        with the bucket-ordering barrier token threaded across microbatch
        boundaries so collective traffic can overlap the next
        microbatch's backward; params allgather once per optimizer step.
        The structural overlap lands in the
        `dl4j_collective_overlap_fraction` gauge. Configurations that
        train per batch (AVERAGING/PIPELINE, multi-process meshes,
        collect_stats) REJECT M>1 — silently training a different
        effective batch would be worse than an error.

        Fault-tolerance knobs mirror `MultiLayerNetwork.fit`, backed by
        the **sharded** store (`parallel/checkpoint.py`): step dirs with
        COMMIT markers, resume restores params/updater/counters/trainer
        RNG and re-places them on the mesh. AVERAGING-mode saves record
        the averaged replica view, so a resume restores that average to
        every replica (per-replica local-SGD divergence inside the current
        averaging window is not persisted). `guard` applies its
        non-finite-loss policy to the mesh-wide step score."""
        from ..nn.superstep import validate_grad_accumulation
        accum_m = validate_grad_accumulation(grad_accumulation)
        if self._pipe is not None:
            return self._fit_pipe(data, epochs, accum_m, prefetch,
                                  pad_ragged, time_buckets, checkpoint_dir,
                                  checkpoint_every, resume, guard)
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
                import logging
                logging.getLogger("deeplearning4j_tpu").info(
                    "superstep=%r ignored for a single-DataSet fit (one "
                    "batch is one step); pass an iterator to window "
                    "batches", superstep)
            if guard is not None:
                guard.run_step(self, lambda: self._fit_batch(data))
            else:
                self._fit_batch(data)
            self._sync_back()
            return self
        from ..fault.resume import sharded_fit_checkpointer
        ckpt = sharded_fit_checkpointer(
            self, checkpoint_dir, checkpoint_every, resume,
            context={"grad_accumulation": accum_m,
                     **self.model._precision_remat_context()})
        skip, done_epochs = (0, 0) if ckpt is None else ckpt.resume_into(data)
        from ..datasets.pipeline import build_pipeline
        data, close = build_pipeline(data, pad_ragged=pad_ragged,
                                     prefetch=prefetch,
                                     time_buckets=time_buckets)
        runner = self._make_superstep_runner(superstep, guard, ckpt, accum_m)
        self._set_overlap_gauge(accum_m)
        if runner is not None:
            runner.skip(skip)
            skip = 0
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
                    if ckpt is not None:
                        ckpt.on_epoch()
                if ckpt is not None:
                    ckpt.on_fit_end()
        finally:
            close()
        self._sync_back()
        return self

    def _fit_pipe(self, data, epochs, accum_m, prefetch, pad_ragged,
                  time_buckets, checkpoint_dir, checkpoint_every, resume,
                  guard):
        """fit() for the legacy host-GPipe PIPELINE strategy. The
        fault knobs route through the standard sharded store (ISSUE 15
        satellite — PR 5's blanket rejection lifted): the GPipe step has
        clean optimizer-step boundaries, saves publish the synced-back
        model, restores re-place the stage params (`_prepare`) and skip
        the trained prefix — kill-mid-write resume is bit-exact like
        every other strategy. `pad_ragged` pads ragged final batches
        with weight-zero label-mask rows the last-stage loss consumes."""
        if guard is not None:
            raise ValueError(
                "guard is not supported for the host-driven PIPELINE "
                "strategy (per-stage dispatch has no whole-step snapshot "
                "boundary); use strategy='pp'/'zero1_tp_pp' (mesh-native "
                "1F1B) for guarded pipeline training")
        if accum_m != 1:
            raise ValueError(
                f"grad_accumulation={accum_m} is not supported for "
                "the PIPELINE strategy (its GPipe schedule already "
                "microbatches; use n_microbatches on the pipe "
                "trainer)")
        if isinstance(data, (DataSet, MultiDataSet)):
            if checkpoint_dir is not None or resume:
                raise ValueError(
                    "checkpoint_dir/resume need an iterator fit (the "
                    "checkpoint records epoch/batch progress)")
            self._pipe.fit(data, epochs=epochs)
            self.iteration_count = self._pipe.iteration_count
            self._rng = self._pipe._rng
            self._pipe.sync_back()
            return self
        from ..datasets.pipeline import build_pipeline
        from ..fault.resume import sharded_fit_checkpointer

        ckpt = sharded_fit_checkpointer(self, checkpoint_dir,
                                        checkpoint_every, resume)
        skip, done_epochs = (0, 0) if ckpt is None else \
            ckpt.resume_into(data)
        # a restore reinstated self._rng/iteration_count — push them into
        # the pipe trainer so the resumed PRNG/step chain continues
        self._pipe._rng = self._rng
        self._pipe.iteration_count = self.iteration_count
        data, close = build_pipeline(data, pad_ragged=pad_ragged,
                                     prefetch=prefetch,
                                     time_buckets=time_buckets)
        sigterm = (ckpt.sigterm_snapshot() if ckpt is not None
                   else _null_span())
        try:
            with sigterm:
                for _ in range(max(0, epochs - done_epochs)):
                    data.reset()
                    while data.has_next():
                        ds = data.next()
                        if skip:
                            skip -= 1   # resume: prefix already trained
                            continue
                        self._pipe._fit_batch(ds)
                        self.iteration_count = self._pipe.iteration_count
                        self._rng = self._pipe._rng
                        if ckpt is not None:
                            ckpt.on_batch()
                    if ckpt is not None:
                        ckpt.on_epoch()
                if ckpt is not None:
                    ckpt.on_fit_end()
        finally:
            close()
        self._pipe.sync_back()
        self.model.iteration_count = self.iteration_count
        return self

    def _make_superstep_runner(self, superstep, guard, ckpt, accum_m=1):
        """SuperstepRunner composing the window scan with the sharded SYNC
        step, or None for per-batch dispatch (superstep=1 with
        grad_accumulation=1, AVERAGING, PIPELINE, multi-process,
        collect_stats — the latter configurations REJECT accumulation
        instead of silently changing the effective batch)."""
        from ..nn.superstep import (SuperstepRunner, accum_skip_nonfinite,
                                    validate_superstep)

        k = validate_superstep(superstep)
        if k == 1 and accum_m == 1:
            return None
        reason = None
        if getattr(self, "_raw_step_fn", None) is None:
            reason = (f"mode={self.mode}/strategy={self.strategy} trains "
                      "per batch (host-driven averaging/pipeline schedule)")
        elif jax.process_count() > 1:
            reason = ("multi-process meshes assemble the global batch per "
                      "step on host")
        elif self.stats is not None:
            reason = "collect_stats times phases per batch by contract"
        if reason is not None:
            if accum_m != 1:
                raise ValueError(
                    f"grad_accumulation={accum_m} is not supported here: "
                    f"{reason}")
            import logging
            logging.getLogger("deeplearning4j_tpu").info(
                "superstep=%r falls back to per-batch dispatch: %s",
                superstep, reason)
            return None
        adapter = _TrainerSuperstepAdapter(
            self, m=accum_m,
            skip_nonfinite=accum_skip_nonfinite(guard, accum_m))
        return SuperstepRunner(self, adapter, k, guard=guard, ckpt=ckpt,
                               grad_accumulation=accum_m)

    @functools.cached_property
    def _superstep_jit(self):
        """Jitted superstep for the SYNC strategies: `lax.scan` of the raw
        (ZeRO or plain) train step over a [K, batch, ...] window, with the
        training shardings carried through — the window's batch axis 1 is
        sharded over `data`, params/opt keep their strategy shardings, and
        buffers donate end-to-end like the per-batch step."""
        from ..nn.superstep import build_superstep

        win = NamedSharding(self.mesh, P(None, self.data_axis))
        repl = self._repl
        return watch_compiles(jax.jit(
            build_superstep(self._raw_step_fn),
            in_shardings=(self._p_sh, self._s_sh, self._o_sh, repl, repl,
                          win, win, win, win),
            out_shardings=(self._p_sh, self._s_sh, self._o_sh, repl, repl),
            donate_argnums=(0, 1, 2)), "parallel/superstep")

    def _accum_superstep_jit(self, skip_nonfinite: bool):
        """Jitted ACCUMULATED superstep for the SYNC strategies: nested
        scan over [K, M, batch, ...] windows with the training shardings
        carried through (window batch axis 2 sharded over `data`). The
        ZeRO strategies route through `make_zero_accum_superstep` — the
        sharded-accumulator, token-chained reduce-scatter variant — while
        REPLICATED/TP/FSDP compose the generic builder with the model's
        grad/update split. Cached per skip flag; K and M are
        shape-derived (one XLA compile per distinct grouping)."""
        cache = self.__dict__.setdefault("_accum_superstep_cache", {})
        fn = cache.get(bool(skip_nonfinite))
        if fn is not None:
            return fn
        if self.strategy in _PP_STRATEGIES:
            # the pipeline's microbatches ARE the accumulation
            # microbatches: a [K, M, b, ...] window runs K optimizer
            # steps, each one M-microbatch 1F1B schedule, in ONE dispatch
            from .pipeline import make_pp_accum_superstep
            if skip_nonfinite:
                raise ValueError(
                    "guard policy 'skip_batch' cannot neutralize single "
                    "microbatches inside the 1F1B schedule (the pipeline "
                    "interleaves them); use warn/rollback/halt with the "
                    "pipeline strategies")
            raw, _info = make_pp_accum_superstep(
                self.model, self._pp_plan, zero_plan=self._pp_zero_plan)
            name = ("parallel/zero1_tp_pp_accum_superstep"
                    if self.strategy == ShardingStrategy.ZERO1_TP_PP
                    else "parallel/pp_accum_superstep")
        elif self.strategy in (ShardingStrategy.ZERO1,
                               ShardingStrategy.ZERO2,
                               ShardingStrategy.ZERO1_TP):
            from .sharding import model_layer_hints
            from .zero import (DEFAULT_BUCKET_MB, ZeroConfig,
                               make_zero_accum_superstep)
            two_d = self.strategy == ShardingStrategy.ZERO1_TP
            cfg = ZeroConfig(
                stage=2 if self.strategy == ShardingStrategy.ZERO2 else 1,
                bucket_mb=(DEFAULT_BUCKET_MB if self.zero_bucket_mb is None
                           else self.zero_bucket_mb),
                reduce_dtype=self.zero_reduce_dtype)
            base_specs = None
            if two_d:
                base_specs = param_specs(
                    self.model.params, self.strategy, self.mesh,
                    self.model_axis, self.data_axis,
                    layers=model_layer_hints(self.model))
            raw, _info = make_zero_accum_superstep(
                self.model, self.mesh, data_axis=self.data_axis,
                config=cfg, skip_nonfinite=bool(skip_nonfinite),
                base_specs=base_specs,
                model_axis=self.model_axis if two_d else None)
            name = "parallel/zero_accum_superstep"
        else:
            from ..nn.superstep import build_accum_superstep
            raw = build_accum_superstep(self.model.grad_step_fn,
                                        self.model.apply_updates,
                                        bool(skip_nonfinite))
            name = "parallel/accum_superstep"
        win = NamedSharding(self.mesh, P(None, None, self.data_axis))
        repl = self._repl
        fn = watch_compiles(jax.jit(
            raw,
            in_shardings=(self._p_sh, self._s_sh, self._o_sh, repl, repl,
                          win, win, win, win),
            out_shardings=(self._p_sh, self._s_sh, self._o_sh, repl, repl,
                           repl),
            donate_argnums=(0, 1, 2)), name)
        cache[bool(skip_nonfinite)] = fn
        return fn

    def _set_overlap_gauge(self, accum_m: int):
        """Publish the structural collective/compute overlap of this
        fit's schedule (zero.collective_overlap_fraction) to the
        `dl4j_collective_overlap_fraction` gauge — 1 - 1/(M·buckets) for
        ZERO2's token-ordered bucket flushes, 0.0 for stage 1's deferred
        reduction; no-op for non-ZeRO strategies or a disabled session."""
        tel = _tel_active()
        if tel is None or self._zero_info is None:
            return
        from .zero import collective_overlap_fraction
        tel.registry.gauge(
            "dl4j_collective_overlap_fraction",
            "fraction of per-step reduce-scatter payload issued with "
            "independent backward compute still in flight (structural, "
            "from the schedule)").set(
            collective_overlap_fraction(self._zero_info, accum_m))

    def _to_batch(self, ds):
        """(inputs, labels, fmasks, lmasks) pytrees: arrays for
        MultiLayerNetwork, dicts for ComputationGraph (which takes DataSet
        or MultiDataSet — the SparkComputationGraph / ParallelWrapper 'any
        Model' parity). Masks thread through to the train step exactly as
        in single-device fit (dp==single parity holds for masked data)."""
        from ..nn.graph import ComputationGraph

        def none_free(d):
            # drop None-valued entries: None leaves are empty pytrees, and
            # an all-None dict just becomes {} (same as no masks)
            if not isinstance(d, dict):
                return d
            out = {k: v for k, v in d.items() if v is not None}
            return out or None

        if isinstance(self.model, ComputationGraph):
            inputs, labels, fmasks, lmasks = self.model._to_inputs(ds)
            return inputs, labels, none_free(fmasks), none_free(lmasks)
        # device_tuple() (not raw jnp.asarray) so a DevicePrefetchIterator's
        # staged transfer is a cache HIT here instead of a second H2D copy
        return ds.device_tuple()

    def _fit_batch(self, ds: DataSet):
        import contextlib

        tmap = jax.tree_util.tree_map
        tel = _tel_active()
        phase = (self.stats.time if self.stats is not None
                 else (lambda key: contextlib.nullcontext()))
        with phase("data"), _span("host/batch_prep"):
            local_shard = bool(getattr(ds, "is_local_shard", False))
            xd, yd, fm, lm = self._to_batch(ds)
            n = self.n_data
            # a local shard spans only this process's devices
            n_div = (max(1, n // jax.process_count()) if local_shard else n)
            bs = jax.tree_util.tree_leaves(xd)[0].shape[0]
            if bs % n_div:
                # the remainder is dropped (the reference round-robins
                # leftovers); fit(pad_ragged=True) instead pads up to the
                # fixed batch size with weight-zero mask rows upstream, so
                # every example trains and the step keeps one signature
                keep = (bs // n_div) * n_div
                if keep == 0:
                    return
                trim = lambda t: tmap(lambda a: a[:keep], t)
                xd, yd, fm, lm = trim(xd), trim(yd), trim(fm), trim(lm)
            if jax.process_count() > 1 and self.mode == TrainingMode.SYNC:
                # multi-host dataset plane: assemble the sharded global
                # array (SPMD over DCN+ICI). Two sources: a replicated
                # global batch (each process contributes its slice) or a
                # LocalShardDataSet from the export/path plane (this
                # process already holds ONLY its shard —
                # datasets/export.py, the reference's
                # RDDTrainingApproach.Export analog)
                from .distributed import global_batch_array, local_batch_slice
                bs2 = jax.tree_util.tree_leaves(xd)[0].shape[0]
                sl = (slice(None) if local_shard
                      else local_batch_slice(bs2))
                mk = lambda t: tmap(lambda a: global_batch_array(
                    self.mesh, np.asarray(a)[sl], self.data_axis), t)
                xd, yd, fm, lm = mk(xd), mk(yd), mk(fm), mk(lm)
        self._rng, rng = jax.random.split(self._rng)
        step = jnp.asarray(self.iteration_count, jnp.int32)
        if self.mode == TrainingMode.SYNC:
            with phase("step"):
                with _span("device/dispatch", kind="sync_step"):
                    (self._params, self._state, self._opt,
                     score) = self._step_fn(
                        self._params, self._state, self._opt, step,
                        xd, yd, rng, fm, lm)
                self._score = score
                if tel is not None and self._zero_info is not None:
                    self._record_zero_metrics(tel)
                else:
                    # no telemetry session: the sanitizer's collective
                    # hasher (if installed) still observes the schedule
                    self._feed_collective_hasher()
                if self.stats is not None or (tel is not None
                                              and tel.sync_per_step):
                    with _span("device/sync"):
                        float(jnp.asarray(score))  # sync for honest timing
        else:
            with phase("step"):
                resh = lambda t: tmap(
                    lambda a: a.reshape(n, -1, *a.shape[1:]), t)
                xs, ys, fms, lms = resh(xd), resh(yd), resh(fm), resh(lm)
                with _span("device/dispatch", kind="local_step"):
                    (self._params, self._state, self._opt,
                     scores) = self._local_step(
                        self._params, self._state, self._opt, step, xs, ys,
                        fms, lms, rng)
                self._score = scores.mean()
                if self.stats is not None or (tel is not None
                                              and tel.sync_per_step):
                    with _span("device/sync"):
                        float(jnp.asarray(self._score))
            if (self.iteration_count + 1) % self.averaging_frequency == 0:
                with phase("average"), _span("device/average"):
                    self._params, self._opt = self._average(self._params,
                                                            self._opt)
                    if self.stats is not None:
                        jax.block_until_ready(
                            jax.tree_util.tree_leaves(self._params)[0])
        self.iteration_count += 1
        if tel is not None and self.iteration_count % tel.report_window == 0:
            # per-device watermarks over THIS trainer's mesh
            tel.watermarks.sample(devices=list(self.mesh.devices.flat))

    def _record_zero_metrics(self, tel, n_micro: int = 1, n_steps: int = 1,
                             micro_m: Optional[int] = None):
        """ZeRO collective-traffic counters (static accounting from
        make_zero_step / make_zero_accum_superstep):
          dl4j_collective_bytes_total{op}   logical payload bytes by
                                            collective op
          dl4j_dp_bucket_flushes_total      gradient bucket reduce-scatter
                                            flushes (stage 2)
        Under accumulation the reduce-scatter (and its bucket flushes)
        runs once per MICROBATCH while the all-reduce/param-allgather run
        once per OPTIMIZER step — hence the two multipliers. Counters are
        get-or-create against the active session's registry, cached until
        the session changes."""
        cached = getattr(self, "_zero_metrics", None)
        if cached is None or cached[0] is not tel:
            reg = tel.registry
            cached = (tel,
                      reg.counter("dl4j_collective_bytes_total",
                                  "logical payload bytes moved by "
                                  "data-parallel collectives",
                                  labels=("op",)),
                      reg.counter("dl4j_dp_bucket_flushes_total",
                                  "gradient bucket reduce-scatter flushes"))
            self._zero_metrics = cached
        _, c_bytes, c_flush = cached
        info = self._zero_info
        for op, b in info["bytes"].items():
            if b:
                mult = n_micro if op == "reduce_scatter" else n_steps
                c_bytes.inc(b * mult, op=op)
        if info["n_buckets"] and n_micro:
            c_flush.inc(info["n_buckets"] * n_micro)
        self._feed_collective_hasher(n_micro, n_steps, micro_m=micro_m)

    def collective_accounting(self):
        """The step's declared static collective accounting (a copy of
        `parallel/zero.py`'s plan info: logical payload bytes by op,
        bucket count, the `with_sharding_constraint` schedule) — what
        telemetry counters AND the graftlint IR tier diff the compiled
        program against (analysis/ir.py `ir-implicit-reshard`). None for
        strategies that publish no accounting (replicated/averaging)."""
        return dict(self._zero_info) if self._zero_info else None

    def _feed_collective_hasher(self, n_micro: int = 1, n_steps: int = 1,
                                micro_m: Optional[int] = None):
        """Per-step collective-sequence hash (the runtime half of the IR
        tier's order check): when a sanitizer hasher is installed, record
        the issue schedule of each of the `n_steps` OPTIMIZER steps that
        just ran (a superstep window dispatches several at once) — per
        microbatch the bucketed reduce-scatter flushes, then the
        step-level reductions and the param allgather — closing one
        digest per optimizer step, so a K-step window and K per-batch
        steps produce the identical digest stream. Item 4's kill/rejoin
        drills compare the per-process streams; a worker whose plan or
        bucket layout diverged after an elastic resize hashes differently
        BEFORE it deadlocks the mesh inside a mismatched collective."""
        from ..analysis.sanitizer import current_collective_hasher
        from ..telemetry.recorder import flight_recorder

        h = current_collective_hasher()
        rec = flight_recorder()
        if self._zero_info is None or (h is None and not rec.enabled):
            return
        info = self._zero_info
        rs, nb = info["bytes"].get("reduce_scatter", 0), info["n_buckets"]
        n_micro = max(1, int(n_micro))
        if micro_m is not None:
            # the window's ACTUAL per-step grouping: full groups of m,
            # then the ragged tail — dispatch_accum_groups' segmentation
            # ([m]*q + [r]), which a ceil-split reconstruction would
            # misreport for ragged windows (e.g. 9 micro at m=4 dispatch
            # as [4,4,1], not [3,3,3])
            m = max(1, int(micro_m))
            counts = [m] * (n_micro // m)
            if n_micro % m:
                counts.append(n_micro % m)
        else:
            n_steps = max(1, int(n_steps))
            m = -(-n_micro // n_steps)
            counts = [m] * (n_steps - 1) + [n_micro - m * (n_steps - 1)]
        if h is not None:
            for count in counts:
                for _ in range(count if rs else 0):
                    h.record("reduce_scatter", rs, n=max(1, nb))
                for op in ("all_reduce", "all_gather"):
                    b = info["bytes"].get(op, 0)
                    if b:
                        h.record(op, b)
                h.end_step()
        if rec.enabled:
            # one flight-recorder event per optimizer step carrying the
            # collective-sequence digest. With a sanitizer hasher the
            # digest is the live per-step stream it just closed; without
            # one, a static plan digest (hash of the declared bytes-by-op
            # + bucket layout) still lets dump comparisons across workers
            # catch a diverged plan. Pure host-side hashing — no syncs.
            if h is not None and h.step_digests:
                digests = h.step_digests[-len(counts):]
            else:
                plan = getattr(self, "_collective_plan_digest", None)
                if plan is None:
                    import hashlib
                    basis = repr((sorted(info["bytes"].items()),
                                  info["n_buckets"]))
                    plan = hashlib.sha256(basis.encode()).hexdigest()[:16]
                    self._collective_plan_digest = plan
                digests = [plan] * len(counts)
            for count, d in zip(counts, digests):
                rec.record("train/collectives", digest=d, micro=count,
                           n_buckets=nb)

    @property
    def params_replicated(self) -> bool:
        """True when every device holds the FULL params between steps —
        REPLICATED and the ZeRO strategies (which shard optimizer state,
        not params). Host-local evaluation paths are only sound then."""
        return self.strategy in ShardingStrategy.PARAMS_REPLICATED

    def score(self, ds=None) -> float:
        """No-arg: last minibatch training score (reference ParallelWrapper
        behavior). With a DataSet/MultiDataSet: the scalar model score of
        that batch computed over the mesh — the scoring half the reference
        ran through `impl/common/score/` Spark functions; used by
        EarlyStoppingParallelTrainer's score calculators. Multi-process:
        the example-count-weighted mean over every process's row share
        (for masked time-series data this weights by examples, not mask
        entries — `DataSetLossCalculator`'s own convention)."""
        if ds is None:
            if self._pipe is not None:
                return self._pipe.score()
            return float(jnp.asarray(self._score).mean())
        if self._pipe is not None:
            self._pipe.sync_back()
            return self.model.score(ds)
        if self._pp_plan is not None:
            # stage-stacked params: publish a per-layer view and score on
            # the reassembled model (host memory caveat documented in the
            # README pipeline section)
            self.publish_view()
            return self.model.score(ds)
        if jax.process_count() > 1:
            # each process scores its row share; the weighted mean is
            # allreduced so EVERY process returns the identical global
            # value — divergent per-process scores would let an
            # early-stopping condition fire on one host only and hang the
            # others in the next collective
            from jax.experimental import multihost_utils as mhu
            sub = self._local_rows(ds)
            params, state = self._local_params_state()
            if sub is None:
                part = np.zeros(2)
            else:
                xs, ys, fm, lm = self._to_batch(sub)
                n = sub.num_examples()
                s = float(self._score_raw(params, state, xs, ys, fm, lm))
                # _score_raw folds reg/n_local into each share's scalar;
                # strip it before re-weighting or the allreduce counts the
                # (process-identical) reg term once PER process instead of
                # once globally (review r5)
                reg = self._reg_value(params)
                part = np.asarray([(s - reg / n) * n, float(n)])
            tot = np.asarray(mhu.process_allgather(part)).sum(axis=0)
            n_global = max(tot[1], 1.0)
            reg = self._reg_value(self._local_params_state()[0])
            return float((tot[0] + reg) / n_global)
        x, y, fm, lm = self._to_batch(ds)
        bs = jax.tree_util.tree_leaves(x)[0].shape[0]
        if bs % self.n_data == 0:
            params, state = self._eval_params_state()
            return float(self._eval_score(params, state, x, y, fm, lm))
        # ragged batch: the scalar is a mean over REAL rows only, so the
        # pad-and-slice trick doesn't apply — score host-local instead.
        # Only sound with replicated params (they fit one device by
        # definition; ZeRO qualifies — only its OPT state is sharded);
        # materializing a SHARDED model on one device could OOM the very
        # model the sharding exists for (review r5)
        if not self.params_replicated:
            raise ValueError(
                f"score(ds) with strategy={self.strategy} needs a batch "
                f"divisible by the data axis ({self.n_data}); got {bs}. "
                "Pad or re-batch the validation set")
        params, state = self._host_view()
        return float(self._score_raw(params, state, x, y, fm, lm))

    def _reg_value(self, params) -> float:
        """Full-network l1/l2 penalty (identical on every process — params
        are replicated on this path). Both model families expose
        `_reg_score`, the same function their `_loss_fn`s fold in."""
        return float(self.model._reg_score(params))

    @functools.cached_property
    def _score_fn_raw(self):
        from ..nn.graph import ComputationGraph

        if isinstance(self.model, ComputationGraph):
            def f(p, s, xs, ys, fm, lm):
                return self.model._loss_fn(p, s, xs, ys, None, fmasks=fm,
                                           lmasks=lm, train=False)[0]
        else:
            def f(p, s, x, y, fm, lm):
                return self.model._loss_fn(p, s, x, y, None, fmask=fm,
                                           lmask=lm, train=False)[0]
        return f

    @functools.cached_property
    def _score_raw(self):
        return watch_compiles(jax.jit(self._score_fn_raw), "parallel/score")

    @functools.cached_property
    def _eval_score(self):
        b = self._batch_sh
        return watch_compiles(
            jax.jit(self._score_fn_raw,
                    in_shardings=(self._p_sh, self._repl, b, b, b, b),
                    out_shardings=self._repl), "parallel/eval_score")

    # ------------------------------------------------------------------
    # Distributed evaluation / scoring plane.
    #
    # The reference evaluates and scores over the cluster:
    # `SparkDl4jMultiLayer.evaluate(RDD)` backed by
    # `dl4j-spark/.../impl/multilayer/evaluation/IEvaluateFlatMapFunction.java:1`
    # (map: evaluate a partition) + `IEvaluationReduceFunction.java` (reduce:
    # merge Evaluations), per-example scoring via
    # `impl/common/score/ScoreExamplesFunction.java` and VAE reconstruction
    # scoring via
    # `impl/common/score/BaseVaeReconstructionProbWithKeyFunctionAdapter.java`.
    #
    # TPU-native shape: ONE jitted forward over the mesh with the batch
    # sharded on the data axis (XLA's collectives are the shuffle); the
    # map/reduce structure survives as per-device-shard Evaluations merged
    # via `Evaluation.merge` (count-exact, so multi-device == single-device
    # is an equality, not a tolerance). Across processes each host computes
    # its local shard and the evaluation state is allreduced
    # (`distributed.allreduce_evaluation`).
    # ------------------------------------------------------------------
    def _eval_params_state(self):
        if self.mode == TrainingMode.SYNC:
            # live refs — no gather, no copy (the eval jits carry the
            # training shardings, so sharded strategies evaluate SPMD
            # without ever materializing the full tree)
            return self._params, self._state
        # AVERAGING: same view _sync_back publishes — params averaged over
        # replicas, state from replica 0. The mean is DERIVED work, so it
        # is cached per trained step: a multi-batch validation pass (early
        # stopping, evaluate over an iterator) computes it once, not once
        # per batch; the next fit step invalidates via iteration_count
        cached = self._eval_cache
        if cached is not None and cached[0] == self.iteration_count:
            return cached[1], cached[2]
        tmap = jax.tree_util.tree_map
        params = tmap(lambda a: a.mean(0), self._params)
        state = tmap(lambda a: a[0], self._state)
        self._eval_cache = (self.iteration_count, params, state)
        return params, state

    def _host_view(self):
        """Host-local gathered copy of (params, state) for the host-side
        scoring/eval paths, cached per trained step — repeated score()/
        evaluate() calls between fit steps pull the model device-to-host
        ONCE instead of re-gathering per call (the next fit step advances
        iteration_count, invalidating the cache; _prepare clears it on
        checkpoint restore)."""
        cached = self._host_cache
        if cached is not None and cached[0] == self.iteration_count:
            return cached[1], cached[2]
        params, state = self._eval_params_state()
        params, state = _to_host(params), _to_host(state)
        self._host_cache = (self.iteration_count, params, state)
        return params, state

    @functools.cached_property
    def _eval_predict(self):
        return watch_compiles(
            jax.jit(self.model.predict_fn,
                    in_shardings=(self._p_sh, self._repl, self._batch_sh,
                                  self._batch_sh),
                    out_shardings=self._repl), "parallel/eval_predict")

    @functools.cached_property
    def _eval_score_examples(self):
        b = self._batch_sh
        return watch_compiles(
            jax.jit(self.model.score_examples_fn,
                    in_shardings=(self._p_sh, self._repl, b, b, b, b),
                    out_shardings=self._repl, static_argnums=(6,)),
            "parallel/eval_score_examples")

    def _pad_to(self, tree, n_div):
        """Zero-pad the batch axis to a multiple of the data axis so SPMD
        shards evenly; callers slice padding off the (replicated) result.
        Eval-mode forward is per-example (BN running stats, no dropout), so
        padding cannot perturb real rows."""
        tmap = jax.tree_util.tree_map
        bs = jax.tree_util.tree_leaves(tree)[0].shape[0]
        pad = (-bs) % n_div
        if pad:
            tree = tmap(lambda a: jnp.concatenate(
                [a, jnp.zeros((pad,) + a.shape[1:], a.dtype)]), tree)
        return tree, bs

    def _eval_batches(self, data):
        """Yield DataSet/MultiDataSet batches from a dataset or iterator."""
        if isinstance(data, (DataSet, MultiDataSet)):
            yield data
            return
        data.reset()
        while data.has_next():
            yield data.next()

    def _lockstep_batches(self, data):
        """Multi-process batch loop for paths with per-batch collectives:
        every round, processes agree (one tiny allgather) whether ANY of
        them still has a batch; exhausted processes keep participating
        with `None` until all are done. Unequal per-process batch counts
        therefore contribute empty shares instead of desynchronizing the
        collectives into a distributed hang (review r5)."""
        from jax.experimental import multihost_utils as mhu

        it = self._eval_batches(data)
        while True:
            ds = next(it, None)
            have = np.asarray([0 if ds is None else 1], np.int32)
            if int(np.asarray(mhu.process_allgather(have)).sum()) == 0:
                return
            yield ds

    def _label_pairs(self, ds, outs):
        """[(labels, out, labels_mask), ...] per network output, host-side."""
        from ..nn.graph import ComputationGraph

        if not isinstance(self.model, ComputationGraph):
            return [(np.asarray(ds.labels), outs, ds.labels_mask)]
        if isinstance(ds, DataSet):
            return [(np.asarray(ds.labels), outs[0], ds.labels_mask)]
        lmasks = ds.labels_masks or [None] * len(ds.labels)
        return [(np.asarray(l), o, m)
                for l, o, m in zip(ds.labels, outs, lmasks)]

    def _local_rows(self, ds):
        """This process's row share of an evaluation batch, matching fit()'s
        interpretation of the same inputs: a LocalShardDataSet (export
        plane) is already this process's data; a REPLICATED batch — the
        form fit() slices with `local_batch_slice` — is split into
        contiguous even slices so the union over processes covers every
        row exactly once, in process order. Returns None for an empty
        share (more processes than rows)."""
        if getattr(ds, "is_local_shard", False):
            return ds
        n = ds.num_examples()
        p, i = jax.process_count(), jax.process_index()
        lo, hi = (i * n) // p, ((i + 1) * n) // p
        if lo == hi:
            return None
        cut = lambda a: None if a is None else a[lo:hi]
        if isinstance(ds, MultiDataSet):
            cl = lambda xs: None if xs is None else [cut(a) for a in xs]
            return MultiDataSet(features=cl(ds.features),
                                labels=cl(ds.labels),
                                features_masks=cl(ds.features_masks),
                                labels_masks=cl(ds.labels_masks))
        return DataSet(cut(ds.features), cut(ds.labels),
                       cut(ds.features_mask), cut(ds.labels_mask))

    def evaluate(self, data, labels_list=None, top_n: int = 1):
        """Distributed evaluation: `SparkDl4jMultiLayer.evaluate(RDD)` /
        `SparkComputationGraph.evaluate` analog. Accepts a DataSet or any
        DataSetIterator — replicated data is split across processes,
        per-process shard iterators (export plane) are used as-is — and
        returns the merged Evaluation, identical on every process."""
        from ..eval import Evaluation

        if self._pipe is not None or self._pp_plan is not None:
            # stage-partitioned/stacked params: publish and evaluate on
            # the reassembled model
            from ..datasets.iterators import ListDataSetIterator

            self.publish_view()
            if isinstance(data, DataSet):
                data = ListDataSetIterator([data])
            return self.model.evaluate(data, labels_list=labels_list,
                                       top_n=top_n)
        ev = Evaluation(labels=labels_list, top_n=top_n)
        multi = jax.process_count() > 1
        if multi:
            params, state = self._local_params_state()
        else:
            params, state = self._eval_params_state()
        for ds in self._eval_batches(data):
            if multi:
                # map side: this process evaluates only its row share,
                # host-locally (replicated params were pulled local); the
                # reduce is the cross-process allreduce below
                ds = self._local_rows(ds)
                if ds is None:
                    continue
                out = self._local_predict(params, state, ds)
            else:
                # single process: one sharded forward over the mesh; the
                # count accumulation into `ev` is the (associative) reduce
                x, _, fm, _ = self._to_batch(ds)
                (x, fm), bs = self._pad_to((x, fm), self.n_data)
                out = self._eval_predict(params, state, x, fm)
            for labels, o, lmask in self._label_pairs(ds, out):
                o = np.asarray(o)[:labels.shape[0]]
                ev.eval(labels, o,
                        mask=None if lmask is None else np.asarray(lmask))
        if multi:
            from .distributed import allreduce_evaluation
            ev = allreduce_evaluation(ev)
            ev.label_names = list(labels_list) if labels_list else None
        return ev

    def score_examples(self, data, add_regularization_terms: bool = True
                       ) -> np.ndarray:
        """Per-example scores over the mesh — Spark
        `ScoreExamplesFunction.java` analog of
        `MultiLayerNetwork.score_examples`. Multi-process: each host scores
        its row share (shard files as-is, replicated batches split — see
        `_local_rows`) and the rows are allgathered in process order, so
        every process returns the identical global array with one row per
        example."""
        if self._pipe is not None or self._pp_plan is not None:
            self.publish_view()
            return self.model.score_examples(data, add_regularization_terms)
        multi = jax.process_count() > 1
        outs = []
        if multi:
            # gather per BATCH (every process participates, empty share
            # included) so rows come back in true example order: each
            # batch's share slices are contiguous in process order.
            # _lockstep_batches keeps the collectives aligned even when
            # per-process shard iterators yield unequal batch counts
            from .distributed import allgather_rows
            params, state = self._local_params_state()
            for ds in self._lockstep_batches(data):
                sub = None if ds is None else self._local_rows(ds)
                local = (np.zeros(0, np.float32) if sub is None else
                         self._local_score_examples(
                             params, state, sub, add_regularization_terms))
                outs.append(allgather_rows(local))
        else:
            params, state = self._eval_params_state()
            for ds in self._eval_batches(data):
                x, y, fm, lm = self._to_batch(ds)
                bs = jax.tree_util.tree_leaves(x)[0].shape[0]
                (x, y, fm, lm), _ = self._pad_to((x, y, fm, lm), self.n_data)
                per = self._eval_score_examples(
                    params, state, x, y, fm, lm,
                    bool(add_regularization_terms))
                outs.append(np.asarray(per)[:bs])
        return (np.concatenate(outs) if outs else np.zeros(0, np.float32))

    def reconstruction_log_probability(self, data, num_samples: int = 5,
                                       seed: int = 0) -> np.ndarray:
        """VAE reconstruction log-probability through the same plane —
        `BaseVaeReconstructionProbWithKeyFunctionAdapter.java:1` analog
        (anomaly scoring over the cluster)."""
        from ..nn.layers.generative import VariationalAutoencoder

        layer0 = self.model.layers[0]
        if not isinstance(layer0, VariationalAutoencoder):
            raise ValueError("reconstruction_log_probability requires the "
                             "first layer to be a VariationalAutoencoder")
        multi = jax.process_count() > 1
        outs = []
        if multi:
            from .distributed import allgather_rows
            params, _ = self._local_params_state()
            for ds in self._lockstep_batches(data):
                sub = None if ds is None else self._local_rows(ds)
                if sub is None:
                    local = np.zeros(0, np.float32)
                else:
                    local = np.asarray(self.model._recon_logp_fn(
                        params[0], jnp.asarray(sub.features),
                        jax.random.PRNGKey(seed), num_samples))
                outs.append(allgather_rows(local))
        else:
            params, _ = self._eval_params_state()
            fn = self._eval_recon_logp
            for ds in self._eval_batches(data):
                x = jnp.asarray(ds.features)
                (x,), bs = self._pad_to((x,), self.n_data)
                outs.append(np.asarray(fn(
                    params[0], x, jax.random.PRNGKey(seed),
                    num_samples))[:bs])
        return (np.concatenate(outs) if outs else np.zeros(0, np.float32))

    @functools.cached_property
    def _eval_recon_logp(self):
        layer0 = self.model.layers[0]
        p_sh0 = (self._p_sh[0] if isinstance(self._p_sh, (tuple, list))
                 else self._p_sh)
        return watch_compiles(jax.jit(
            lambda p, x, rng, n: layer0.reconstruction_probability(
                p, x, rng, num_samples=n),
            in_shardings=(p_sh0, self._batch_sh, self._repl),
            out_shardings=self._repl, static_argnums=(3,)),
            "parallel/eval_recon_logp")

    # -- multi-process map side: host-local compute on the local shard -----
    def _local_params_state(self):
        """Host-local copy of the trained params for per-process map-side
        evaluation (requires replicated params — every host holds the full
        value, like every Spark executor held the broadcast params; the
        ZeRO strategies qualify, their params are replicated between
        steps). Cached per training step via _host_view: a multi-batch
        validation pass pulls the model device-to-host once, not once per
        batch (review r5)."""
        if not self.params_replicated:
            raise ValueError(
                "multi-process evaluate/score needs replicated params; "
                f"strategy={self.strategy} shards them across hosts")
        return self._host_view()

    def _local_predict(self, params, state, ds):
        x, _, fm, _ = self._to_batch(ds)
        return self.model._predict_fn(params, state, x, fm)

    def _local_score_examples(self, params, state, ds, add_reg):
        x, y, fm, lm = self._to_batch(ds)
        return np.asarray(self.model._score_examples_fn(
            params, state, x, y, fm, lm, bool(add_reg)))

    def publish_view(self):
        """Bind the current mesh params into the wrapped model WITHOUT
        perturbing training state (unlike `_sync_back`, which in AVERAGING
        mode collapses the live replicas to their mean, destroying the
        local-SGD window). Used by checkpointing and best-model saving;
        returns the wrapped model."""
        if self._pipe is not None:
            self._pipe.sync_back()
            self.model.iteration_count = self._pipe.iteration_count
            return self.model
        if self._pp_plan is not None:
            # pp-form trees -> the model's per-layer tuples (host-side
            # unstack; the live pipe-sharded buffers stay untouched).
            # Cached per trained step — score/evaluate between fits must
            # not re-pay the whole-model host round-trip (the pp analog
            # of _host_view; invalidated by _prepare and the guard's
            # _fault_restored rollback hook)
            if self._pp_pub_iter != self.iteration_count:
                plan = self._pp_plan
                self.model.params = plan.unstack_host(self._params)
                self.model.state = plan.unstack_host(self._state)
                self.model.updater_state = plan.unstack_host(self._opt)
                self._pp_pub_iter = self.iteration_count
            self.model.iteration_count = self.iteration_count
            return self.model
        if self.mode == TrainingMode.SYNC:
            self.model.params = self._params
            self.model.state = self._state
            self.model.updater_state = self._opt
        else:
            tmap = jax.tree_util.tree_map
            params, state = self._eval_params_state()
            self.model.params = params
            self.model.state = state
            self.model.updater_state = tmap(lambda a: a.mean(0), self._opt)
        self.model.iteration_count = self.iteration_count
        return self.model

    def elastic_state(self):
        """The logical, mesh-shape-INDEPENDENT training state (ISSUE 19):
        the model-level {params, state, updater_state} trees (per-layer
        tuples — pp strategies unstack their stage form) plus the scalar
        metadata a restore needs to continue bit-exactly: iteration
        count and the per-batch RNG chain key. The RNG chain advances
        once per optimizer step (`jax.random.split` in `_fit_batch`)
        regardless of mesh factorization, so restoring (trees, meta)
        onto ANY (d, m, p) reshape continues the identical sequence.
        Leaves may still be device arrays (possibly non-addressable in a
        multi-process world); the coordinated store host-fetches them."""
        model = self.publish_view()
        tree = {"params": model.params, "state": model.state,
                "updater_state": model.updater_state}
        meta = {"iteration_count": int(self.iteration_count),
                "epoch_count": int(getattr(model, "epoch_count", 0)),
                "strategy": self.strategy,
                "mesh_axes": {k: int(v)
                              for k, v in dict(self.mesh.shape).items()},
                "trainer_rng": np.asarray(self._rng).tolist()}
        return tree, meta

    def load_elastic_state(self, tree, meta):
        """Re-land a logical state captured by `elastic_state` (possibly
        on a different mesh shape/strategy) onto THIS trainer's mesh:
        install the model-level trees, then `_prepare()` re-places them
        per this trainer's strategy — the same re-placement path the
        sharded restore uses — and reinstate the iteration count and
        RNG chain the re-prepare reset."""
        m = self.model
        m.params = tree["params"]
        m.state = tree["state"]
        m.updater_state = tree["updater_state"]
        m.iteration_count = int(meta.get("iteration_count", 0))
        m.epoch_count = int(meta.get("epoch_count", 0))
        self._prepare()
        self.iteration_count = m.iteration_count
        rng = meta.get("trainer_rng")
        if rng is not None:
            self._rng = jnp.asarray(np.asarray(rng, dtype=np.uint32))
        return self

    def _sync_back(self):
        """Write averaged/replicated params back into the wrapped model."""
        if self._pp_plan is not None:
            self.publish_view()
            return
        if self.mode == TrainingMode.SYNC:
            self.model.params = self._params
            self.model.state = self._state
            self.model.updater_state = self._opt
        else:
            self._params, self._opt = self._average(self._params, self._opt)
            take = lambda t: jax.tree_util.tree_map(lambda a: jnp.array(a[0]), t)
            self.model.params = take(self._params)
            self.model.state = take(self._state)
            self.model.updater_state = take(self._opt)
        self.model.iteration_count = self.iteration_count


class _TrainerSuperstepAdapter:
    """SuperstepRunner hooks for ParallelTrainer (see nn/superstep.py):
    batches route through `_to_batch` (arrays for MultiLayerNetwork, dicts
    for ComputationGraph) and are trimmed to the data-axis multiple
    exactly as the per-batch step trims them; a batch that trims to zero
    rows is consumed untrained (signature None), matching per-batch. With
    ``m>1`` dispatch routes the window through the accumulated superstep
    (sharded accumulators under the ZeRO strategies) in [K, M] groups."""

    def __init__(self, trainer: ParallelTrainer, m: int = 1,
                 skip_nonfinite: bool = False):
        self.trainer = trainer
        self.m = int(m)
        self.skip_nonfinite = bool(skip_nonfinite)
        self._memo = {}   # id(ds) -> trimmed batch (signature -> stage)

    def _trimmed(self, ds):
        key = id(ds)
        if key in self._memo:
            return self._memo[key]
        tr = self.trainer
        tmap = jax.tree_util.tree_map
        xd, yd, fm, lm = tr._to_batch(ds)
        bs = jax.tree_util.tree_leaves(xd)[0].shape[0]
        keep = (bs // tr.n_data) * tr.n_data
        if keep == 0:
            return None
        if keep != bs:
            trim = lambda t: tmap(lambda a: a[:keep], t)
            xd, yd, fm, lm = trim(xd), trim(yd), trim(fm), trim(lm)
        self._memo[key] = (xd, yd, fm, lm)
        return self._memo[key]

    def _take(self, ds):
        return self._memo.pop(id(ds), None) or self._trimmed(ds)

    def signature(self, ds):
        batch = self._trimmed(ds)
        if batch is None:
            return None
        shape = lambda t: tuple(
            (tuple(p), tuple(a.shape))
            for p, a in jax.tree_util.tree_flatten_with_path(t)[0])
        return tuple(shape(t) for t in batch)

    def batch_nbytes(self, ds):
        from ..datasets.pipeline import batch_nbytes
        batch = self._trimmed(ds)
        if batch is None:
            return 0
        return batch_nbytes(jax.tree_util.tree_leaves(batch))

    def stage(self, window):
        from ..datasets.pipeline import stage_window
        return stage_window([self._take(ds) for ds in window])

    def dispatch(self, staged, n, step0):
        tr = self.trainer
        if self.m == 1:
            xs, ys, fms, lms = staged
            (tr._params, tr._state, tr._opt, tr._rng,
             scores) = tr._superstep_jit(
                tr._params, tr._state, tr._opt,
                jnp.asarray(step0, jnp.int32), tr._rng, xs, ys, fms, lms)
            return scores
        from ..nn.superstep import dispatch_accum_groups
        fn = tr._accum_superstep_jit(self.skip_nonfinite)

        def run_group(seg, step):
            xs, ys, fms, lms = seg
            (tr._params, tr._state, tr._opt, tr._rng, scores,
             mscores) = fn(tr._params, tr._state, tr._opt,
                           jnp.asarray(step, jnp.int32), tr._rng,
                           xs, ys, fms, lms)
            return scores, mscores

        return dispatch_accum_groups(staged, n, self.m, step0, run_group)

    def on_window_end(self, window):
        from ..nn.superstep import steps_in

        tr = self.trainer
        n = len(window)
        n_steps = steps_in(n, self.m)
        tel = _tel_active()
        if tel is None:
            # the sanitizer's collective hasher (if installed) observes
            # the window's schedule even without a telemetry session
            tr._feed_collective_hasher(n_micro=n, n_steps=n_steps,
                                       micro_m=self.m)
            return
        if tr._zero_info is not None:
            # static accounting scales over the window: reduce-scatter per
            # microbatch, all-reduce/allgather per optimizer step
            tr._record_zero_metrics(tel, n_micro=n, n_steps=n_steps,
                                    micro_m=self.m)
        w = tel.report_window
        if (tr.iteration_count + n_steps) // w > tr.iteration_count // w:
            tel.watermarks.sample(devices=list(tr.mesh.devices.flat))


# DL4J-familiar alias
ParallelWrapper = ParallelTrainer
