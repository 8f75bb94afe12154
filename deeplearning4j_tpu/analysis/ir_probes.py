"""IR-tier probes: build real jit entry points for analysis/ir.py.

The AST tier's `unwatched-jit-entry` rule drove the telemetry
`watch_compiles` roster to 100% coverage of the package's jit entry
points; these probes construct representatives of every entry-point
FAMILY on the virtual 8-device mesh — tiny models (d=8 MLP, one-edge
graph) so each trace+lower+compile is tens of milliseconds — and hand
them to the IR rules with the metadata the rules diff against:

  * the ZeRO step/superstep entries carry `parallel/zero.py`'s static
    accounting (declared collective payload bytes, the declared
    `with_sharding_constraint` schedule) and the bit-exactness the
    equivalence suite asserts;
  * the serving entries are the registry's AOT-compiled executables,
    audited as compiled text (no re-lowering — what serves is what is
    checked);
  * everything else (single-device nn entries) is audited for donation
    aliasing and schedule determinism.

Tests reuse the builders here to seed mutations (drop a shard
constraint, unorder the bucket flushes, donate an unaliasable buffer)
and prove each rule fires.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

from .ir import IrEntry

__all__ = ["build_entries", "tiny_mlp", "nn_entries", "graph_entries",
           "parallel_entries", "zero_accum_entry", "mesh2d_entries",
           "mesh2d_zero1_tp_entry", "flash_spmd_entry", "flash_entries",
           "pp_entry", "pp_entries", "serving_entries", "decode_entry",
           "decode_entries", "elastic_restore_entry", "elastic_entries",
           "virtual_mesh"]


def virtual_mesh():
    """The lint mesh: every local device on one `data` axis (8 under the
    CI/CLI `--xla_force_host_platform_device_count=8` setup)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from ..parallel.mesh import MeshAxes

    devs = np.array(jax.devices())
    return Mesh(devs.reshape(devs.size), (MeshAxes.DATA,))


def tiny_mlp(seed: int = 0):
    """8->16->4 MLP with Adam — four param leaves, one of each shape
    class (two matrices, two biases), enough for the ZeRO plan to have
    sharded AND replicated leaves and >=2 gradient buckets at a small
    bucket bound."""
    from .. import (Adam, DenseLayer, InputType, MultiLayerNetwork,
                    NeuralNetConfiguration, OutputLayer)

    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Adam(1e-3))
            .list()
            .layer(DenseLayer(n_out=16, activation="relu"))
            .layer(OutputLayer(n_out=4, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(8)).build())
    return MultiLayerNetwork(conf).init()


def _batch(b: int = 16):
    import jax.numpy as jnp
    import numpy as np

    r = np.random.default_rng(0)
    x = jnp.asarray(r.normal(size=(b, 8)).astype(np.float32))
    y = jnp.asarray(np.eye(4, dtype=np.float32)[np.arange(b) % 4])
    return x, y


def nn_entries() -> List[IrEntry]:
    """MultiLayerNetwork family: the per-batch train step (donates
    params/state/opt), score, predict, and the accumulated superstep
    (nested scan) — the single-device half of the roster."""
    import jax
    import jax.numpy as jnp

    model = tiny_mlp()
    x, y = _batch()
    step = jnp.asarray(0, jnp.int32)
    rng = jax.random.PRNGKey(0)
    p, s, o = model.params, model.state, model.updater_state
    entries = [
        IrEntry("nn/train_step", "nn/multilayer.py",
                fn=model._train_step.__wrapped__,
                args=(p, s, o, step, x, y, rng, None, None)),
        IrEntry("nn/score", "nn/multilayer.py",
                fn=model._score_fn.__wrapped__,
                args=(p, s, x, y, None, None)),
        IrEntry("nn/predict", "nn/multilayer.py",
                fn=model._predict_fn.__wrapped__,
                args=(p, s, x, None)),
    ]
    K, M, B = 2, 2, 8
    xs = jnp.zeros((K, M, B, 8), jnp.float32)
    ys = jnp.asarray(jnp.broadcast_to(
        jnp.eye(4, dtype=jnp.float32)[jnp.arange(B) % 4], (K, M, B, 4)))
    ones = jnp.ones((K, M, B), jnp.float32)
    entries.append(IrEntry(
        "nn/accum_superstep", "nn/superstep.py",
        fn=model._accum_superstep_fn(False).__wrapped__,
        args=(p, s, o, step, rng, xs, ys, ones, ones)))
    entries.append(IrEntry(
        "nn/superstep", "nn/superstep.py",
        fn=model._superstep_fn.__wrapped__,
        args=(p, s, o, step, rng, xs[:, 0], ys[:, 0], ones[:, 0],
              ones[:, 0])))
    return entries


def graph_entries() -> List[IrEntry]:
    """ComputationGraph family representative (the graph train step has
    its own step builder and donation wiring)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from .. import (Adam, DenseLayer, InputType, NeuralNetConfiguration,
                    OutputLayer)
    from ..nn.graph import ComputationGraph

    conf = (NeuralNetConfiguration.builder().seed(0).updater(Adam(1e-3))
            .graph_builder()
            .add_inputs("in")
            .add_layer("dense", DenseLayer(n_out=16, activation="relu"),
                       "in")
            .add_layer("out", OutputLayer(n_out=4, activation="softmax",
                                          loss="mcxent"), "dense")
            .set_outputs("out")
            .set_input_types(InputType.feed_forward(8))
            .build())
    model = ComputationGraph(conf).init()
    r = np.random.default_rng(0)
    x = {"in": jnp.asarray(r.normal(size=(16, 8)).astype(np.float32))}
    y = {"out": jnp.asarray(np.eye(4, dtype=np.float32)[np.arange(16) % 4])}
    return [IrEntry(
        "graph/train_step", "nn/graph.py",
        fn=model._train_step.__wrapped__,
        args=(model.params, model.state, model.updater_state,
              jnp.asarray(0, jnp.int32), x, y, jax.random.PRNGKey(0),
              None, None))]


def _trainer_entry(strategy, name: str, bucket_mb: Optional[float] = None
                   ) -> IrEntry:
    import jax
    import jax.numpy as jnp

    from ..parallel.trainer import ParallelTrainer

    model = tiny_mlp()
    kw = {} if bucket_mb is None else {"zero_bucket_mb": bucket_mb}
    tr = ParallelTrainer(model, strategy=strategy, **kw)
    x, y = _batch()
    info = tr.collective_accounting()
    entry = IrEntry(
        name, "parallel/zero.py" if info else "parallel/trainer.py",
        fn=tr._step_fn.__wrapped__,
        args=(tr._params, tr._state, tr._opt, jnp.asarray(0, jnp.int32),
              x, y, jax.random.PRNGKey(0), None, None),
        mesh_axes=tuple(tr.mesh.axis_names),
        asserts_bitexact=True)   # tests/test_zero.py asserts replicated==zero
    if info:
        entry.declared_bytes = sum(info["bytes"].values())
        entry.check_bytes = True           # scan-free: text == per-step
        entry.expected_constraints = info.get("expected_constraints")
    return entry


def parallel_entries() -> List[IrEntry]:
    """ParallelTrainer family on the virtual mesh: the SYNC replicated,
    ZeRO-1 and ZeRO-2 per-batch steps (each carrying its declared static
    accounting where the strategy publishes one) plus the AVERAGING
    shard_map local step."""
    import jax
    import jax.numpy as jnp

    from ..parallel.trainer import (ParallelTrainer, ShardingStrategy,
                                    TrainingMode)

    entries = [
        _trainer_entry(ShardingStrategy.REPLICATED, "parallel/train_step"),
        _trainer_entry(ShardingStrategy.ZERO1, "parallel/zero1_step"),
        _trainer_entry(ShardingStrategy.ZERO2, "parallel/zero2_step",
                       bucket_mb=0.0005),
    ]
    tr = ParallelTrainer(tiny_mlp(), mode=TrainingMode.AVERAGING)
    n = tr.n_data
    x, y = _batch(16)
    resh = lambda a: jnp.reshape(a, (n, -1) + a.shape[1:])
    entries.append(IrEntry(
        "parallel/local_step", "parallel/trainer.py",
        fn=tr._local_step.__wrapped__,
        args=(tr._params, tr._state, tr._opt, jnp.asarray(0, jnp.int32),
              resh(x), resh(y), None, None, jax.random.PRNGKey(0)),
        mesh_axes=tuple(tr.mesh.axis_names)))
    return entries


def zero_accum_entry(stage: int = 2, bucket_mb: float = 0.0005,
                     ordered_flush: bool = True, model=None,
                     K: int = 2, M: int = 2, B: int = 16) -> IrEntry:
    """The ZeRO accumulated superstep (nested scan, barrier-token-ordered
    bucket flushes, sharded fp32 accumulators) jitted exactly as
    ParallelTrainer jits it. Public so tests can seed mutations through
    the same builder (ordered_flush=False, monkeypatched constraints)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..parallel.mesh import MeshAxes
    from ..parallel.zero import (ZeroConfig, make_zero_accum_superstep,
                                 zero_opt_shardings)

    from ..telemetry.compile_watch import watch_compiles

    model = model if model is not None else tiny_mlp()
    mesh = virtual_mesh()
    cfg = ZeroConfig(stage=stage, bucket_mb=bucket_mb,
                     ordered_flush=ordered_flush)
    fn, info = make_zero_accum_superstep(model, mesh, config=cfg)
    repl = NamedSharding(mesh, P())
    win = NamedSharding(mesh, P(None, None, MeshAxes.DATA))
    o_sh = zero_opt_shardings(model.updater_state, model.params, mesh,
                              MeshAxes.DATA)
    jitted = watch_compiles(jax.jit(
        fn,
        in_shardings=(repl, repl, o_sh, repl, repl, win, win, win, win),
        out_shardings=(repl, repl, o_sh, repl, repl, repl),
        donate_argnums=(0, 1, 2)),
        f"analysis/ir_probe:zero{stage}_accum_superstep").__wrapped__
    xs = jnp.zeros((K, M, B, 8), jnp.float32)
    ys = jnp.asarray(jnp.broadcast_to(
        jnp.eye(4, dtype=jnp.float32)[jnp.arange(B) % 4], (K, M, B, 4)))
    ones = jnp.ones((K, M, B), jnp.float32)
    return IrEntry(
        f"parallel/zero{stage}_accum_superstep", "parallel/zero.py",
        fn=jitted,
        args=(model.params, model.state, model.updater_state,
              jnp.asarray(0, jnp.int32), jax.random.PRNGKey(0),
              xs, ys, ones, ones),
        mesh_axes=tuple(mesh.axis_names),
        expected_constraints=info.get("expected_constraints"),
        requires_ordered_reductions=(stage >= 2
                                     and info.get("n_buckets", 0) >= 2),
        asserts_bitexact=True)


def _mesh2d_tp_entry(shape: Tuple[int, int]
                     ) -> Tuple[IrEntry, int, int]:
    """The DP×TP train step on a (data, model) mesh, plus its measured
    MODEL-axis collective bytes (the Megatron activation-psum traffic)
    and its "other"-bucket bytes (collectives spanning neither single
    axis: whole-mesh groups, permutes). Both measurements become byte
    BUDGETS for the matching ZERO1×TP entry: ZeRO-1 only adds data-axis
    optimizer collectives, so extra model-axis traffic means a model
    shard is being silently resharded — and the "other" budget closes
    the remaining hole, a rematerialization compiled as ONE gather over
    BOTH axes (replica group size d·m) that axis-bucketed budgets alone
    would never see."""
    import jax
    import jax.numpy as jnp

    from ..analysis.ir import measured_collective_bytes_by_axis
    from ..parallel.trainer import ParallelTrainer, ShardingStrategy

    d, m = shape
    tr = ParallelTrainer(tiny_mlp(), mesh_shape=shape,
                         strategy=ShardingStrategy.TENSOR_PARALLEL)
    x, y = _batch()
    args = (tr._params, tr._state, tr._opt, jnp.asarray(0, jnp.int32),
            x, y, jax.random.PRNGKey(0), None, None)
    fn = tr._step_fn.__wrapped__
    text = fn.trace(*args).lower().compile().as_text()
    by_axis = measured_collective_bytes_by_axis(
        text, {"data": d, "model": m})
    model_bytes = sum(by_axis.get("model", {}).values())
    other_bytes = sum(by_axis.get("other", {}).values())
    entry = IrEntry(
        f"parallel/tp_step_{d}x{m}", "parallel/trainer.py",
        fn=fn, args=args, mesh_axes=tuple(tr.mesh.axis_names))
    return entry, model_bytes, other_bytes


def mesh2d_zero1_tp_entry(shape: Tuple[int, int] = (2, 4),
                          model_budget: Optional[int] = None,
                          other_budget: int = 0,
                          mutate: Optional[str] = None) -> IrEntry:
    """The ZERO1×TP train step on a (data, model) mesh, carrying the
    extended 2-D contract: per-AXIS byte budgets (data = the plan's
    declared optimizer payload, model = the paired TP step's measured
    activation traffic) and the plan's `with_sharding_constraint`
    schedule. Public so tests can seed mutations through the same
    builder:

      mutate="drop_constraints"  the step skips constrain_params/opt
                                 entirely — the traced constraint count
                                 falls below the declared schedule
      mutate="drop_model_axis"   constraints keep their COUNT but lose
                                 the model axis (data-only specs): the
                                 update materializes params replicated
                                 over `model` and the model-axis bytes
                                 blow the TP-derived budget
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..parallel.mesh import MeshAxes, make_mesh
    from ..parallel.sharding import (ShardingStrategy, model_layer_hints,
                                     param_specs)
    from ..parallel.zero import (ZeroConfig, _ZeroPlan, make_zero_step,
                                 zero_opt_shardings)
    from ..telemetry.compile_watch import watch_compiles

    d, m = shape
    model = tiny_mlp()
    mesh = make_mesh({MeshAxes.DATA: d, MeshAxes.MODEL: m})
    base = param_specs(model.params, ShardingStrategy.ZERO1_TP, mesh,
                       layers=model_layer_hints(model))
    cfg = ZeroConfig(stage=1)
    if mutate is None:
        step, info = make_zero_step(model, mesh, config=cfg,
                                    base_specs=base,
                                    model_axis=MeshAxes.MODEL)
    else:
        # seeded mutations re-assemble the step body so the contract
        # (expected constraints / per-axis budgets) stays the TRUE plan's
        true_plan = _ZeroPlan(model, mesh, MeshAxes.DATA, cfg,
                              base_specs=base, model_axis=MeshAxes.MODEL)
        info = dict(true_plan.info)
        info["expected_constraints"] = true_plan.expected_constraints()
        if mutate == "drop_model_axis":
            plan = _ZeroPlan(model, mesh, MeshAxes.DATA, cfg)  # data-only
        elif mutate == "drop_constraints":
            plan = None
        else:
            raise ValueError(f"unknown mutation {mutate!r}")
        grad_fn = model.grad_step_fn

        def step(params, state, opt_state, step_i, x, y, rng, fm, lm):
            score, new_state, grads = grad_fn(params, state, x, y, rng,
                                              fm, lm)
            new_params, new_opt = model.apply_updates(params, grads,
                                                      opt_state, step_i)
            if plan is not None:
                new_params = plan.constrain_params(new_params)
                new_opt = plan.constrain_opt(new_opt)
            return new_params, new_state, new_opt, score

    repl = NamedSharding(mesh, P())
    batch = NamedSharding(mesh, P(MeshAxes.DATA))
    p_sh = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), base,
        is_leaf=lambda s: isinstance(s, P))
    o_sh = zero_opt_shardings(model.updater_state, model.params, mesh,
                              base=base)
    jitted = watch_compiles(jax.jit(
        step,
        in_shardings=(p_sh, repl, o_sh, repl, batch, batch, repl, batch,
                      batch),
        out_shardings=(p_sh, repl, o_sh, repl),
        donate_argnums=(0, 1, 2)),
        f"analysis/ir_probe:zero1_tp_step_{d}x{m}").__wrapped__
    x, y = _batch()
    params = jax.device_put(model.params, p_sh)
    opt = jax.device_put(model.updater_state, o_sh)
    entry = IrEntry(
        f"parallel/zero1_tp_step_{d}x{m}", "parallel/zero.py",
        fn=jitted,
        args=(params, model.state, opt, jnp.asarray(0, jnp.int32),
              x, y, jax.random.PRNGKey(0), None, None),
        mesh_axes=tuple(mesh.axis_names),
        expected_constraints=info.get("expected_constraints"))
    if model_budget is not None:
        entry.axis_sizes = {"data": d, "model": m}
        # "other" is budgeted too (TP-measured + slack floor): a sharded
        # tensor rematerialized via ONE whole-mesh gather (group size
        # d·m) lands in that bucket, not under either axis
        entry.declared_bytes_by_axis = {
            "data": sum(info["bytes"].values()),
            "model": model_budget,
            "other": int(other_budget)}
    return entry


def mesh2d_entries() -> List[IrEntry]:
    """The 2-D train-step family (ISSUE 14) on BOTH reshapes of the
    8-device mesh — (2, 4) and (4, 2), distinct axis sizes so the
    per-axis byte classification is unambiguous. Each reshape registers
    the DP×TP step and the ZERO1×TP step; the TP step's measured
    model-axis traffic becomes the ZeRO entry's model-axis budget."""
    entries: List[IrEntry] = []
    for shape in ((2, 4), (4, 2)):
        tp_entry, model_bytes, other_bytes = _mesh2d_tp_entry(shape)
        entries.append(tp_entry)
        entries.append(mesh2d_zero1_tp_entry(shape,
                                             model_budget=model_bytes,
                                             other_budget=other_bytes))
    return entries


def _tiny_lm(vocab: int, width: int, heads: int, seq: int, seed: int):
    """One-block GPT-style LM (embedding -> TransformerBlock -> softmax
    head): what the flash and the decode probes trace."""
    from .. import (Adam, EmbeddingSequenceLayer, InputType,
                    MultiLayerNetwork, NeuralNetConfiguration,
                    RnnOutputLayer, TransformerBlock)

    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Adam(1e-3))
            .list()
            .layer(EmbeddingSequenceLayer(n_in=vocab, n_out=width))
            .layer(TransformerBlock(n_heads=heads))
            .layer(RnnOutputLayer(n_out=vocab, activation="softmax",
                                  loss="mcxent"))
            .set_input_type(InputType.recurrent(1, seq)).build())
    return MultiLayerNetwork(conf).init()


def _flash_arm(shape: Tuple[int, int], flash):
    """Build the ZERO1×TP transformer-LM trainer with the attention mode
    FORCED (``flash="spmd"`` -> shard_map'd Pallas kernel, interpret mode
    on the CPU mesh; ``flash=False`` -> the einsum reference) and return
    the jitted step fn plus its args. Both arms share the model, mesh and
    batch so their compiled texts differ only in the attention body."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..parallel.trainer import ParallelTrainer, ShardingStrategy

    vocab, seq, b = 32, 8, 8
    tr = ParallelTrainer(_tiny_lm(vocab, 16, 4, seq, seed=7),
                         mesh_shape=shape,
                         strategy=ShardingStrategy.ZERO1_TP, flash=flash)
    r = np.random.default_rng(0)
    x = r.integers(0, vocab, (b, seq, 1)).astype(np.float32)
    y = np.eye(vocab, dtype=np.float32)[r.integers(0, vocab, (b, seq))]
    args = (tr._params, tr._state, tr._opt, jnp.asarray(0, jnp.int32),
            x, y, jax.random.PRNGKey(0), None, None)
    return tr._step_fn.__wrapped__, args, tuple(tr.mesh.axis_names)


def flash_spmd_entry(shape: Tuple[int, int] = (2, 4),
                     budgets: Optional[dict] = None,
                     mutate: Optional[str] = None) -> IrEntry:
    """The flash-attention ZERO1×TP train step: the shard_map'd Pallas
    kernel must SURVIVE into the traced program (`expects_custom_call` —
    a silent einsum fallback is a perf regression, not an error) and its
    per-axis collective bytes must stay inside the paired einsum arm's
    measured budgets (the kernel is per-shard local, so it may remove
    attention collectives but never add reshard traffic). Public so tests
    can seed the mutation through the same builder:

      mutate="drop_flash"  the step body is the einsum fallback while the
                           entry still declares the kernel contract — the
                           jaxpr carries no pallas_call and
                           `ir-missing-custom-call` fires
    """
    if mutate not in (None, "drop_flash"):
        raise ValueError(f"unknown mutation {mutate!r}")
    d, m = shape
    fn, args, axes = _flash_arm(
        shape, False if mutate == "drop_flash" else "spmd")
    entry = IrEntry(
        f"parallel/flash_spmd_step_{d}x{m}", "kernels/attention.py",
        fn=fn, args=args, mesh_axes=axes, expects_custom_call=True)
    if budgets is not None:
        entry.axis_sizes = {"data": d, "model": m}
        entry.declared_bytes_by_axis = dict(budgets)
    return entry


def flash_entries() -> List[IrEntry]:
    """The flash-under-SPMD pair (ISSUE 18): compile the EINSUM arm of
    the same ZERO1×TP transformer-LM step first and measure its per-axis
    collective payloads; those measurements become the flash entry's
    budgets on every bucket (data, model, other), so any reshard byte the
    shard_map'd kernel adds over the fallback is a finding."""
    from ..analysis.ir import measured_collective_bytes_by_axis

    shape = (2, 4)
    fn, args, _ = _flash_arm(shape, False)
    text = fn.trace(*args).lower().compile().as_text()
    by_axis = measured_collective_bytes_by_axis(
        text, {"data": shape[0], "model": shape[1]})
    budgets = {ax: sum(by_axis.get(ax, {}).values())
               for ax in ("data", "model", "other")}
    return [flash_spmd_entry(shape, budgets=budgets)]


def _pp_stack_model(depth: int, hidden: int = 8, seed: int = 0):
    """Uniform Dense(hidden->hidden) stack + softmax head: the minimal
    homogeneous-run model the PipelinePlan stages (input width == hidden
    so every Dense layer is stackable)."""
    from .. import (Adam, DenseLayer, InputType, MultiLayerNetwork,
                    NeuralNetConfiguration, OutputLayer)

    b = (NeuralNetConfiguration.builder().seed(seed).updater(Adam(1e-3))
         .list())
    for _ in range(depth):
        b = b.layer(DenseLayer(n_out=hidden, activation="tanh"))
    conf = (b.layer(OutputLayer(n_out=4, activation="softmax",
                                loss="mcxent"))
            .set_input_type(InputType.feed_forward(hidden)).build())
    return MultiLayerNetwork(conf).init()


def _pp_build(shape: Tuple[int, int, int], zero: bool, M: int, B: int,
              mutate: Optional[str] = None, hidden: int = 8,
              tp: Optional[bool] = None):
    """Assemble the 1F1B accumulated-superstep jit + args on a 3-D
    (data, model, pipe) mesh, exactly as ParallelTrainer jits it.
    Returns (jitted_unwrapped, args, info, mesh)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..parallel.mesh import MeshAxes, make_mesh
    from ..parallel.pipeline import PipelinePlan, make_pp_accum_superstep
    from ..parallel.sharding import _opt_sharding_like
    from ..telemetry.compile_watch import watch_compiles

    d, m, p = shape
    tp = zero if tp is None else tp
    mesh = make_mesh({MeshAxes.DATA: d, MeshAxes.MODEL: m,
                      MeshAxes.PIPE: p})
    model = _pp_stack_model(depth=p, hidden=hidden)
    plan = PipelinePlan(model, mesh, tp=tp)
    params_pp = plan.stack(model.params)
    state_pp = plan.stack(model.state)
    opt_pp = plan.stack(model.updater_state)
    p_specs = plan.param_specs()
    p_sh = plan.shardings(p_specs)
    s_sh = plan.shardings(plan.state_specs())
    zero_plan = None
    if zero:
        from ..parallel.zero import ZeroConfig, _ZeroPlan
        zero_plan = _ZeroPlan(model, mesh, MeshAxes.DATA,
                              ZeroConfig(stage=1), base_specs=p_specs,
                              model_axis=MeshAxes.MODEL,
                              params=params_pp, opt_state=opt_pp)
        o_sh = zero_plan.opt_shardings_tree
    else:
        o_sh = _opt_sharding_like(opt_pp, params_pp, p_sh)
    fn, info = make_pp_accum_superstep(model, plan, zero_plan=zero_plan,
                                       mutate=mutate)
    repl = NamedSharding(mesh, P())
    win = NamedSharding(mesh, P(None, None, MeshAxes.DATA))
    name = ("zero1_tp_pp" if zero else "pp") + f"_step_{d}x{m}x{p}"
    jitted = watch_compiles(jax.jit(
        fn,
        in_shardings=(p_sh, s_sh, o_sh, repl, repl, win, win, win, win),
        out_shardings=(p_sh, s_sh, o_sh, repl, repl, repl),
        donate_argnums=(0, 1, 2)),
        f"analysis/ir_probe:{name}").__wrapped__
    xs = jnp.zeros((1, M, B, hidden), jnp.float32)
    ys = jnp.asarray(jnp.broadcast_to(
        jnp.eye(4, dtype=jnp.float32)[jnp.arange(B) % 4], (1, M, B, 4)))
    args = (jax.device_put(params_pp, p_sh),
            jax.device_put(state_pp, s_sh),
            jax.device_put(opt_pp, o_sh),
            jnp.asarray(0, jnp.int32), jax.random.PRNGKey(0),
            xs, ys, None, None)
    return jitted, args, info, mesh


def pp_entry(shape: Tuple[int, int, int] = (1, 1, 8), *, zero: bool = False,
             M: int = 8, B: int = 32, mutate: Optional[str] = None,
             budgets: Optional[dict] = None,
             budget_from_plan: bool = False) -> IrEntry:
    """The 1F1B step family on a (data, model, pipe) mesh, carrying the
    pipeline contract: the declared `with_sharding_constraint` schedule
    (the 1F1B builder's buffer constraints + the ZeRO plan's shard
    constraints) and optional per-AXIS byte budgets — the `data` budget
    is the ZeRO plan's declared optimizer payload, `model`/`other` come
    from the PAIRED no-ZeRO build (`pp_entries`), and the `pipe` axis is
    deliberately unbudgeted (stage handoffs ride it by design). Public
    so tests can seed mutations through the same builder:

      mutate="drop_stage_constraint"  the step emits NO buffer sharding
                                      constraints — the traced count
                                      falls below the declared schedule
      mutate="permute_data_axis"      the injection buffer is
                                      additionally rolled along its
                                      data-sharded row axis before the
                                      ring scan (a halo exchange) — a
                                      collective-permute leaking onto
                                      `data` that blows that axis's
                                      byte budget
    """
    d, m, p = shape
    jitted, args, info, mesh = _pp_build(shape, zero, M, B, mutate=mutate)
    kind = "zero1_tp_pp" if zero else "pp"
    entry = IrEntry(
        f"parallel/{kind}_step_{d}x{m}x{p}", "parallel/pipeline.py",
        fn=jitted, args=args, mesh_axes=tuple(mesh.axis_names),
        expected_constraints=info["expected_constraints"])
    if budget_from_plan and zero:
        budgets = dict(budgets or {})
        budgets["data"] = sum(info["zero"]["bytes"].values())
    if budgets is not None:
        entry.axis_sizes = {"data": d, "model": m, "pipe": p}
        entry.declared_bytes_by_axis = dict(budgets)
        # the data bucket carries GSPMD's activation-buffer staging
        # gathers on top of the plan's declared optimizer payload — a
        # wider slack than the scan-free 2-D steps, still far below the
        # ~Nx a replicated stage-param materialization would cost
        entry.byte_slack = 2.0
    return entry


def pp_entries() -> List[IrEntry]:
    """The 1F1B roster (ISSUE 15): the pure pipeline on (1, 1, 8) with
    hard zero budgets on `data`/`model` (no traffic may ride them at
    all — d = m = 1), and the ZERO1×TP×PP composition on both
    distinct-size reshapes (2, 1, 4) and (1, 2, 4) — the data budget
    from the ZeRO plan's declared accounting, the model/other budgets
    from the PAIRED no-ZeRO build of the identical step (ZeRO-1 adds
    only data-axis optimizer traffic, so anything extra on `model` is a
    resharded stage/TP param). The `pipe` axis stays unbudgeted: stage
    handoffs ride it by design."""
    from .ir import measured_collective_bytes_by_axis

    entries: List[IrEntry] = []
    entries.append(pp_entry((1, 1, 8),
                            budgets={"data": 0, "model": 0}))
    for shape in ((2, 1, 4), (1, 2, 4)):
        d, m, p = shape
        # the paired arm: the IDENTICAL TP×PP step without the ZeRO
        # plan — its model/other traffic is the legitimate Megatron
        # boundary payload the ZeRO entry may not exceed
        jitted, args, _info, _mesh = _pp_build(shape, False, 8, 32,
                                               tp=True)
        text = jitted.trace(*args).lower().compile().as_text()
        by_axis = measured_collective_bytes_by_axis(
            text, {"data": d, "model": m, "pipe": p})
        paired = {ax: sum(ops.values()) for ax, ops in by_axis.items()}
        entries.append(pp_entry(
            shape, zero=True, budget_from_plan=True,
            budgets={"model": paired.get("model", 0),
                     "other": paired.get("other", 0)}))
    return entries


def serving_entries() -> List[IrEntry]:
    """The serving plane's AOT executables: register a tiny model, then
    audit exactly the compiled runners request threads will invoke."""
    from ..serving.registry import ModelRegistry

    reg = ModelRegistry()
    reg.register("ir-probe", tiny_mlp(), buckets=(8,))
    return [IrEntry(f"serving/aot:{name}:b{bucket}", "serving/registry.py",
                    compiled=co)
            for name, bucket, co in reg.aot_executables()]


def _decode_build(seed: int = 0):
    """Tiny generate-capable LM (vocab=16, width=8, 1 block) registered
    into a fresh registry, plus the paged decode engine over it — small
    enough that tracing both decode-plane steps is milliseconds."""
    from ..serving.decode.engine import DecodeEngine
    from ..serving.registry import ModelRegistry

    reg = ModelRegistry()
    reg.register("ir-gen", _tiny_lm(16, 8, 2, 16, seed), buckets=(1,))
    eng = DecodeEngine(reg, "ir-gen", block_len=4, decode_buckets=(1, 2))
    return eng, reg.get("ir-gen")


def decode_entry(phase: str = "tick",
                 mutate: Optional[str] = None) -> IrEntry:
    """One decode-plane jit entry (`phase` in prefill|tick), donation and
    byte budget declared: the cache pytree (arg 1) is donated and must
    alias the output arena bit-for-bit; a single-device step declares 0
    collective payload bytes.

    Mutations (each must trip exactly one IR rule):

      mutate="donate_tokens"  the int32 token ids are donated TOO — they
        can alias nothing in the (f32/int8 cache, int32 ids of the
        largest bucket's length, f32 logits) outputs, so the
        lowering/XLA must drop that donation
        -> ir-ineffective-donation.
    """
    import jax
    import jax.numpy as jnp

    from ..serving.decode.cache import make_cache
    from ..serving.decode.engine import build_prefill_fn, build_tick_fn

    eng, v = _decode_build()
    spec = eng.spec
    if mutate is None:
        donate = (1,)
    elif mutate == "donate_tokens":
        donate = (1, 3)
    else:
        raise ValueError(f"unknown mutation {mutate!r}")
    w = spec.table_width
    if phase == "prefill":
        fn = build_prefill_fn(v.model, v.snapshot, spec)
        args = (v.snapshot.data, make_cache(spec),
                jnp.zeros((1, 8), jnp.int32), jnp.ones((1,), jnp.int32),
                jnp.zeros((1, w), jnp.int32))
    elif phase == "tick":
        # the tick as it is served: the last tick's ids before the tokens
        # (of a largest bucket of 4, so that the 2 tokens alias no output)
        fn = build_tick_fn(v.model, v.snapshot, spec, 4)
        args = (v.snapshot.data, make_cache(spec),
                jnp.zeros((4,), jnp.int32), jnp.zeros((2,), jnp.int32),
                jnp.zeros((2,), jnp.int32), jnp.zeros((2, w), jnp.int32))
    else:
        raise ValueError(f"unknown decode phase {phase!r}")
    from ..telemetry.compile_watch import watch_compiles
    jitted = watch_compiles(
        jax.jit(fn, donate_argnums=donate),
        f"analysis/ir_probe:decode_{phase}").__wrapped__
    return IrEntry(f"serving/decode_{phase}", "serving/decode/engine.py",
                   fn=jitted, args=args,
                   declared_bytes=0, check_bytes=True)


def decode_entries() -> List[IrEntry]:
    """The generation plane's two compiled signatures (ISSUE 16): the
    batch-1 prompt prefill and the batched decode tick, audited for
    donation aliasing (the arena must update in place, never copy) and
    the zero-collective byte budget of a single-device step."""
    return [decode_entry("prefill"), decode_entry("tick")]


def elastic_restore_entry(shape: Tuple[int, int] = (2, 4),
                          hidden: int = 64,
                          mutate: Optional[str] = None) -> IrEntry:
    """The elastic-restore re-placement step (ISSUE 19): after a mesh
    reshape, `load_elastic_state` -> `_prepare` re-lands the restored
    host trees through identity jits with sharded out_shardings (the
    `parallel/{param,opt}_placement` entries). Landing replicated host
    bytes onto shards is pure slicing — the compiled program must move
    ZERO collective bytes on EVERY axis (floor-budgeted at the linter's
    1KiB slack floor). A hidden width of 64 makes each dense kernel
    (8x64, 2KiB f32) bigger than that floor, so a single wrong-direction
    gather is an unambiguous finding. Public so tests can seed the
    mutation through the same builder:

      mutate="gather_replicated"  the inputs arrive SHARDED and the
                                  out_shardings are replicated — the
                                  restore path compiles to all-gathers
                                  (a resize that re-materializes every
                                  shard on every device) and the
                                  per-axis byte budgets blow
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from .. import (Adam, DenseLayer, InputType, MultiLayerNetwork,
                    NeuralNetConfiguration, OutputLayer)
    from ..parallel.mesh import MeshAxes, make_mesh
    from ..parallel.sharding import (ShardingStrategy, model_layer_hints,
                                     param_specs)
    from ..parallel.zero import zero_opt_shardings
    from ..telemetry.compile_watch import watch_compiles

    if mutate not in (None, "gather_replicated"):
        raise ValueError(f"unknown mutation {mutate!r}")
    d, m = shape
    conf = (NeuralNetConfiguration.builder().seed(0).updater(Adam(1e-3))
            .list()
            .layer(DenseLayer(n_out=hidden, activation="relu"))
            .layer(OutputLayer(n_out=4, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(8)).build())
    model = MultiLayerNetwork(conf).init()
    mesh = make_mesh({MeshAxes.DATA: d, MeshAxes.MODEL: m})
    base = param_specs(model.params, ShardingStrategy.ZERO1_TP, mesh,
                      layers=model_layer_hints(model))
    p_sh = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), base,
        is_leaf=lambda s: isinstance(s, P))
    o_sh = zero_opt_shardings(model.updater_state, model.params, mesh,
                              base=base)
    repl_p = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, P()), p_sh,
        is_leaf=lambda s: isinstance(s, NamedSharding))
    repl_o = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, P()), o_sh,
        is_leaf=lambda s: isinstance(s, NamedSharding))
    if mutate == "gather_replicated":
        params = jax.device_put(model.params, p_sh)
        opt = jax.device_put(model.updater_state, o_sh)
        out_sh = (repl_p, repl_o)
    else:
        # restore lands host (replicated) trees onto the shards
        params = jax.device_put(model.params, repl_p)
        opt = jax.device_put(model.updater_state, repl_o)
        out_sh = (p_sh, o_sh)
    jitted = watch_compiles(
        jax.jit(lambda p, o: (p, o), out_shardings=out_sh),
        f"analysis/ir_probe:elastic_restore_{d}x{m}").__wrapped__
    entry = IrEntry(
        f"parallel/elastic_restore_{d}x{m}", "parallel/elastic.py",
        fn=jitted, args=(params, opt),
        mesh_axes=tuple(mesh.axis_names))
    entry.axis_sizes = {"data": d, "model": m}
    entry.declared_bytes_by_axis = {"data": 0, "model": 0, "other": 0}
    return entry


def elastic_entries() -> List[IrEntry]:
    """The elastic-training plane's compiled surface (ISSUE 19): the
    restore re-placement identity step on the (2, 4) mesh, hard-floored
    at zero collective bytes on every axis — a restore that compiles to
    gathers would silently turn every resize into a full-state
    re-broadcast."""
    return [elastic_restore_entry((2, 4))]


def build_entries() -> List[IrEntry]:
    """The full IR roster, in deterministic order. Every entry family the
    package registers through watch_compiles/record_aot is represented;
    the self-host gate (tests/test_analysis.py) runs these against the
    `ir_findings` baseline section."""
    entries: List[IrEntry] = []
    entries += nn_entries()
    entries += graph_entries()
    entries += parallel_entries()
    entries.append(zero_accum_entry())
    entries += pp_entries()
    entries += mesh2d_entries()
    entries += flash_entries()
    entries += serving_entries()
    entries += decode_entries()
    entries += elastic_entries()
    return entries
