"""Data-parallel scaling-efficiency harness (BASELINE config #5).

The capability analog of the reference's ParallelWrapper / Spark scaling
story, measured the way its stats pipeline measures phases
(`dl4j-spark/.../impl/paramavg/stats/ParameterAveragingTrainingMasterStats.java`):
per-step wall time at fixed GLOBAL batch, 1 device vs N devices (strong
scaling), with per-phase attribution from `TrainingStats` (data/step) and an
updater ablation (Adam vs plain SGD) that MEASURES how much of the loss is
replicated-updater work — on the virtual CPU mesh every "device" shares the
same host cores, so optimizer math that is replicated per-device costs N
times the flops, an artifact real pods don't have.

On a real pod over ICI the ideal is t_n = t_1/N. On the virtual CPU mesh
(`--xla_force_host_platform_device_count`) total compute per step is constant
and the ideal is t_n = t_1; efficiency = t_1/t_n then isolates framework +
collective overhead (the thing the virtual mesh *can* measure — ICI
bandwidth needs real chips).

Two ablations isolate the updater cost:
  * Adam vs SGD (``--no-ablation`` to skip): how much of the scaling loss
    is updater work at all.
  * replicated vs ZeRO (``--no-zero`` to skip; ``--zero-stage``): the
    same Adam step with the optimizer state SHARDED over the data axis
    (parallel/zero.py) — measured in ALTERNATING windows against a
    replicated trainer on the same devices so load drift cancels out of
    the delta. ``zero_ablation.efficiency_zero`` is the headline the
    ROADMAP-item-2 ``multichip`` gate checks against ≥0.85.

Run standalone:
    python -m deeplearning4j_tpu.parallel.scaling_bench --devices 8 \
        --model vgg16 --global-batch 64 --steps 4
Prints one JSON line with t1/tn, phases, efficiency, and the updater +
ZeRO ablations.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _provision(n_devices: int) -> None:
    """Make this process see `n_devices` devices. With JAX_PLATFORMS=cpu it
    provisions the virtual CPU mesh; otherwise it takes the real
    accelerators. A chip belongs to one process, so only the CPU-forced
    form may be started from a parent that has touched JAX (bench.py's
    children use `child_env_with_virtual_devices` for that reason); the
    accelerator form must be the only JAX process on the machine."""
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        # caller asked for the virtual CPU mesh (bench.py does)
        from ..util.platform import provision_virtual_devices

        ok = provision_virtual_devices(n_devices)
    else:
        import jax  # real accelerators: leave the platform alone

        ok = len(jax.devices()) >= n_devices
    if not ok:
        import jax

        raise SystemExit(
            f"need {n_devices} devices, have {len(jax.devices())}; set "
            "JAX_PLATFORMS=cpu + XLA_FLAGS=--xla_force_host_platform_"
            "device_count before jax imports or run in a fresh process")


def _build_model(model: str, updater: str, image: int, hidden: int):
    from ..nn.conf import InputType, NeuralNetConfiguration
    from ..nn.layers import DenseLayer, OutputLayer
    from ..nn.multilayer import MultiLayerNetwork
    from ..nn.updaters import Adam, Sgd

    upd = Adam(1e-3) if updater == "adam" else Sgd(1e-2)
    if model == "vgg16":
        from ..models.zoo import vgg16

        return vgg16(n_classes=10, image=image, updater=upd).init()
    conf = (NeuralNetConfiguration.builder()
            .seed(7).updater(upd)
            .list()
            .layer(DenseLayer(n_out=hidden, activation="relu"))
            .layer(DenseLayer(n_out=hidden, activation="relu"))
            .layer(OutputLayer(n_out=10, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(784))
            .build())
    return MultiLayerNetwork(conf).init()


def _bench_data(model: str, global_batch: int, image: int):
    import numpy as np

    from ..datasets.iterators import DataSet

    r = np.random.default_rng(0)
    if model == "vgg16":
        x = r.normal(size=(global_batch, image, image, 3)).astype(np.float32)
    else:
        x = r.normal(size=(global_batch, 784)).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[r.integers(0, 10, global_batch)]
    return DataSet(x, y)


def _make_trainer(n_devices: int, model: str, updater: str, image: int,
                  hidden: int, strategy: str = "replicated",
                  collect_stats: bool = True):
    import jax

    from .mesh import make_mesh
    from .trainer import ParallelTrainer, TrainingMode

    net = _build_model(model, updater, image, hidden)
    mesh = make_mesh({"data": n_devices},
                     devices=jax.devices()[:n_devices])
    return ParallelTrainer(net, mesh=mesh, mode=TrainingMode.SYNC,
                           strategy=strategy, collect_stats=collect_stats)


def _window(trainer, ds, steps: int):
    """One measured window of `steps` fit calls; returns (ms/step,
    per-phase ms/step) with an honest trailing sync."""
    trainer.stats.reset()
    t0 = time.perf_counter()
    for _ in range(steps):
        trainer.fit(ds)
    float(trainer.score())
    dt = (time.perf_counter() - t0) / steps
    return dt * 1000.0, {k: round(v * 1000.0 / steps, 2)
                         for k, v in trainer.stats.totals().items()}


def measure(n_devices: int, global_batch: int = 64, steps: int = 4,
            warmup: int = 2, hidden: int = 512, model: str = "vgg16",
            updater: str = "adam", image: int = 32, reps: int = 1,
            strategy: str = "replicated"):
    """Per-step timing for SYNC data-parallel training at fixed
    `global_batch` sharded over an n-device mesh, as `reps` independent
    measured windows of `steps` steps (median reported, per-rep times
    recorded so a load-contaminated capture is diagnosable from the
    artifact alone — round-5 reporting contract). Phases measured by the
    trainer's TrainingStats (honest per-phase sync, SparkTrainingStats
    style); the reported phases belong to the median rep. `strategy`
    selects the sharding strategy (replicated | zero1 | zero2 | ...)."""
    trainer = _make_trainer(n_devices, model, updater, image, hidden,
                            strategy)
    ds = _bench_data(model, global_batch, image)
    for _ in range(warmup):
        trainer.fit(ds)
    float(trainer.score())  # host materialization: real sync barrier
    rep_ms, rep_phases = [], []
    for _ in range(max(1, int(reps))):
        ms, phases = _window(trainer, ds, steps)
        rep_ms.append(ms)
        rep_phases.append(phases)
    mid = _median_idx(rep_ms)
    return {"median_ms": rep_ms[mid],
            "rep_ms": [round(v, 2) for v in rep_ms],
            "phases_ms": rep_phases[mid]}


def measure_paired_zero(n_devices: int, global_batch: int = 64,
                        steps: int = 4, warmup: int = 2, hidden: int = 512,
                        model: str = "vgg16", updater: str = "adam",
                        image: int = 32, reps: int = 3,
                        strategy: str = "zero1"):
    """Replicated-vs-ZeRO ablation with ALTERNATING measured windows on
    the same devices: rep i measures the replicated trainer then the ZeRO
    trainer back-to-back, so slow host-load drift on a shared box
    contaminates both variants equally and the DELTA — the replicated-
    updater tax the ZeRO step removes — stays honest. Returns per-variant
    medians, rep series and the median rep's per-phase decomposition."""
    repl = _make_trainer(n_devices, model, updater, image, hidden,
                         "replicated")
    zero = _make_trainer(n_devices, model, updater, image, hidden,
                         strategy)
    ds = _bench_data(model, global_batch, image)
    for tr in (repl, zero):
        for _ in range(warmup):
            tr.fit(ds)
        float(tr.score())
    out = {"replicated": {"rep_ms": [], "phases": []},
           strategy: {"rep_ms": [], "phases": []}}
    for _ in range(max(1, int(reps))):
        for name, tr in (("replicated", repl), (strategy, zero)):
            ms, phases = _window(tr, ds, steps)
            out[name]["rep_ms"].append(round(ms, 2))
            out[name]["phases"].append(phases)
    for name in out:
        mid = _median_idx(out[name]["rep_ms"])
        out[name]["median_ms"] = out[name]["rep_ms"][mid]
        out[name]["phases_ms"] = out[name]["phases"][mid]
        del out[name]["phases"]
    return out


def measure_paired_accum(n_devices: int, micro_batch: int = 32, m: int = 8,
                         steps: int = 2, warmup: int = 1, hidden: int = 1024,
                         model: str = "mlp", image: int = 32, reps: int = 3,
                         strategy: str = "zero2"):
    """Gradient-accumulation ablation (ISSUE 12): effective batch M·b via
    M microbatch accumulation vs the NATIVE M·b batch, in ALTERNATING
    measured windows on the same devices (load drift hits both arms
    equally). Every optimizer step consumes the same M·b samples, so the
    per-step wall-time ratio native/accum IS the effective-batch
    throughput ratio — the acceptance number ISSUE 12 gates at >= 0.9
    ("within 10% of native") on the 8-dev virtual mesh. Also reports the
    static fp32 accumulator footprint (ZERO2 sharded vs replicated —
    the ~1/N memory story) and the structural collective/compute overlap
    fraction of the accumulated schedule.

    Virtual-mesh caveat (same class as the ZeRO efficiency gate): the
    single-process CPU mesh SERIALIZES collectives, so the per-microbatch
    reduce-scatter traffic that overlaps backward on real ICI is paid
    inline here — the measured ratio is a LOWER bound for hardware. The
    default hidden=1024 keeps each b32 microbatch compute-dense enough to
    be representative; at toy widths (hidden<=512 on a 2-core host) the
    per-microbatch dispatch floor dominates and the ratio collapses —
    that regime is exactly what composing superstep>1 with accumulation
    exists for."""
    import numpy as np

    from .zero import collective_overlap_fraction
    from ..datasets.iterators import DataSet, ListDataSetIterator

    accum = _make_trainer(n_devices, model, "adam", image, hidden,
                          strategy, collect_stats=False)
    native = _make_trainer(n_devices, model, "adam", image, hidden,
                           strategy, collect_stats=False)
    big = _bench_data(model, micro_batch * m, image)
    xs, ys = np.asarray(big.features), np.asarray(big.labels)
    micros = [DataSet(xs[i * micro_batch:(i + 1) * micro_batch],
                      ys[i * micro_batch:(i + 1) * micro_batch])
              for i in range(m)]

    def accum_window(n_steps):
        it = ListDataSetIterator(list(micros) * n_steps)
        t0 = time.perf_counter()
        accum.fit(it, grad_accumulation=m)
        float(accum.score())
        return (time.perf_counter() - t0) / n_steps

    def native_window(n_steps):
        t0 = time.perf_counter()
        for _ in range(n_steps):
            native.fit(big)
        float(native.score())
        return (time.perf_counter() - t0) / n_steps

    accum_window(warmup)    # pays the accum-superstep compile
    native_window(warmup)   # pays the per-batch step compile
    rep = {"accum": [], "native": []}
    for _ in range(max(1, int(reps))):
        rep["accum"].append(round(accum_window(steps) * 1e3, 2))
        rep["native"].append(round(native_window(steps) * 1e3, 2))
    t_acc = _median(rep["accum"])
    t_nat = _median(rep["native"])
    # paired per-round ratios (drift cancels within a round)
    ratios = sorted(n_ / a_ for a_, n_ in zip(rep["accum"], rep["native"]))
    info = accum._zero_info or {}
    acc_bytes = info.get("accum_bytes", {})
    out = {"mode": "accum", "strategy": strategy, "devices": n_devices,
           "micro_batch": micro_batch, "m": m,
           "effective_batch": micro_batch * m,
           "t_accum_step_ms": round(t_acc, 2),
           "t_native_step_ms": round(t_nat, 2),
           "rep_ms": rep,
           "throughput_ratio": round(t_nat / t_acc, 3),
           "throughput_ratio_paired": round(ratios[len(ratios) // 2], 3),
           "throughput_ratio_spread": [round(ratios[0], 3),
                                       round(ratios[-1], 3)],
           "overlap_fraction": collective_overlap_fraction(info, m),
           "accumulator_bytes": {
               "sharded_per_device": acc_bytes.get("sharded"),
               "replicated_per_device": acc_bytes.get("replicated"),
               "ratio": (round(acc_bytes["sharded"]
                               / acc_bytes["replicated"], 4)
                         if acc_bytes.get("replicated") else None)},
           "gate": {"metric": f"accum-effective-b{micro_batch * m}-"
                              f"{n_devices}dev",
                    "value": round(ratios[len(ratios) // 2], 3),
                    "target": 0.9,
                    "ok": ratios[len(ratios) // 2] >= 0.9}}
    return out


def _build_transformer_lm(vocab: int, width: int, heads: int, depth: int,
                          seq: int, compute_dtype=None, remat_policy=None):
    """GPT-style LM for the mesh2d tokens/s config (ISSUE 14 / ROADMAP
    item 5): vocab-shardable embedding -> `depth` transformer blocks
    (Megatron-role params, kernels/attention.py core) -> time-distributed
    softmax head. Widths are chosen divisible by every mesh axis the
    8-device reshapes use (vocab/width/ffn % 8 == 0, heads % 4 == 0).
    `compute_dtype`/`remat_policy` feed the flash-mode precision/remat
    arms (ISSUE 18)."""
    from ..nn.conf import InputType, NeuralNetConfiguration
    from ..nn.layers import (EmbeddingSequenceLayer, RnnOutputLayer,
                             TransformerBlock)
    from ..nn.multilayer import MultiLayerNetwork
    from ..nn.updaters import Adam

    b = NeuralNetConfiguration.builder().seed(7).updater(Adam(1e-3))
    if compute_dtype is not None:
        b = b.compute_dtype(compute_dtype)
    if remat_policy is not None:
        b = b.remat_policy(remat_policy)
    b = b.list().layer(EmbeddingSequenceLayer(n_in=vocab, n_out=width))
    for _ in range(depth):
        b = b.layer(TransformerBlock(n_heads=heads))
    conf = (b.layer(RnnOutputLayer(n_out=vocab, activation="softmax",
                                   loss="mcxent"))
            .set_input_type(InputType.recurrent(1, seq))
            .build())
    return MultiLayerNetwork(conf).init()


def _lm_data(vocab: int, seq: int, global_batch: int):
    import numpy as np

    from ..datasets.iterators import DataSet

    r = np.random.default_rng(0)
    x = r.integers(0, vocab, (global_batch, seq, 1)).astype(np.float32)
    y = np.eye(vocab, dtype=np.float32)[
        r.integers(0, vocab, (global_batch, seq))]
    return DataSet(x, y)


def _tree_local_bytes(tree):
    """Bytes actually resident on device 0 (one shard per leaf) — the
    measured per-device footprint, not the static accounting."""
    import jax

    return sum(l.addressable_shards[0].data.nbytes
               for l in jax.tree_util.tree_leaves(tree))


def measure_mesh2d(n_devices: int = 8, vocab: int = 256, width: int = 128,
                   heads: int = 8, depth: int = 2, seq: int = 128,
                   global_batch: int = 16, steps: int = 2, warmup: int = 1,
                   reps: int = 3, measure_collectives: bool = True):
    """2-D mesh parallelism ablation (ISSUE 14): the transformer-block LM
    trained TP-only (1×8) vs DP×TP (2×4) vs ZERO1×TP on BOTH reshapes
    (2×4 and 4×2) of the same 8 virtual devices, in ALTERNATING measured
    windows (rep i times every arm back-to-back, so host-load drift
    contaminates all arms equally). Reports:

      * tokens/s per arm (global_batch · seq / step wall) with the paired
        per-round ratios zero1_tp/dp_tp;
      * measured per-device param + optimizer-moment bytes per arm (from
        the actual device buffers) and the moment ratio vs the replicated
        tree — the ~1/(d·m) memory headline the gate checks;
      * (measure_collectives) per-AXIS collective payload bytes of the
        ZERO1×TP (2,4) step, parsed from its compiled HLO by
        replica-group size (analysis/ir.py) and diffed against the plan's
        declared data-axis accounting — the optimizer traffic must ride
        the small `data` axis, the model axis only Megatron's activation
        psums.

    Virtual-mesh caveat (same class as the ZeRO/accum gates): the
    single-process CPU mesh SERIALIZES the 8 devices onto the host cores,
    so absolute tokens/s is not hardware-representative and the
    wall-clock ratios only bound the framework overhead — the MEMORY
    ratios and per-axis payloads are exact, which is why the gate rides
    on moments ~1/(d·m), not on throughput."""
    import time as _time

    import jax
    import numpy as np

    from .trainer import ParallelTrainer, ShardingStrategy

    if n_devices != 8:
        # the arms ARE the three reshapes of 8 devices; deriving shapes
        # for other counts would silently change what the ablation
        # compares
        raise SystemExit(
            f"mesh2d mode benches the (1,8)/(2,4)/(4,2) reshapes of an "
            f"8-device mesh; got --devices {n_devices}")
    model_builder = lambda: _build_transformer_lm(vocab, width, heads,
                                                  depth, seq)
    ds = _lm_data(vocab, seq, global_batch)
    arms = [
        ("tp_only_1x8", (1, 8), ShardingStrategy.TENSOR_PARALLEL),
        ("dp_tp_2x4", (2, 4), ShardingStrategy.TENSOR_PARALLEL),
        ("zero1_tp_2x4", (2, 4), ShardingStrategy.ZERO1_TP),
        ("zero1_tp_4x2", (4, 2), ShardingStrategy.ZERO1_TP),
    ]
    trainers = {}
    for name, shape, strat in arms:
        trainers[name] = ParallelTrainer(model_builder(), mesh_shape=shape,
                                         strategy=strat,
                                         collect_stats=False)
    repl = ParallelTrainer(model_builder(), collect_stats=False)
    trainers["replicated_8"] = repl
    for tr in trainers.values():
        for _ in range(max(1, warmup)):
            tr.fit(ds)
        float(tr.score())

    tokens = global_batch * seq * steps
    rep_tps = {name: [] for name in trainers}
    for _ in range(max(1, int(reps))):
        for name, tr in trainers.items():
            t0 = _time.perf_counter()
            for _ in range(steps):
                tr.fit(ds)
            float(tr.score())
            rep_tps[name].append(tokens / (_time.perf_counter() - t0))

    moments_full = _tree_local_bytes(repl._opt)
    params_full = _tree_local_bytes(repl._params)
    out = {"mode": "mesh2d", "devices": n_devices,
           "model": {"vocab": vocab, "width": width, "heads": heads,
                     "depth": depth, "seq": seq,
                     "global_batch": global_batch},
           "arms": {}}
    for name, tr in trainers.items():
        tps = sorted(rep_tps[name])
        pb, ob = _tree_local_bytes(tr._params), _tree_local_bytes(tr._opt)
        arm = {"tokens_per_s": round(_median(tps), 1),
               "tokens_per_s_rep": [round(v, 1) for v in tps],
               "per_device_bytes": {
                   "params": pb, "moments": ob,
                   "param_ratio_vs_replicated": round(pb / params_full, 4),
                   "moment_ratio_vs_replicated": round(ob / moments_full,
                                                       4)}}
        info = tr.collective_accounting()
        if info:
            arm["declared_data_axis_bytes"] = dict(info["bytes"])
            arm["mesh_axes"] = dict(info["mesh_axes"])
        out["arms"][name] = arm
    # paired per-round ratios: zero1_tp vs dp_tp on the same (2,4) mesh
    # (the cost of adding the ZeRO-1 optimizer sharding to DP×TP)
    ratios = sorted(z / d for z, d in zip(rep_tps["zero1_tp_2x4"],
                                          rep_tps["dp_tp_2x4"]))
    out["zero1_tp_vs_dp_tp_paired"] = round(ratios[len(ratios) // 2], 3)
    out["zero1_tp_vs_dp_tp_spread"] = [round(ratios[0], 3),
                                       round(ratios[-1], 3)]

    if measure_collectives:
        # compiled-HLO per-axis payload of the ZERO1×TP (2,4) step (one
        # extra lowering of the already-built step; the classification is
        # unambiguous because 2 != 4)
        import jax.numpy as jnp

        from ..analysis.ir import measured_collective_bytes_by_axis
        tr = trainers["zero1_tp_2x4"]
        x, y, fm, lm = tr._to_batch(ds)
        args = (tr._params, tr._state, tr._opt, jnp.asarray(0, jnp.int32),
                x, y, jax.random.PRNGKey(0), fm, lm)
        text = tr._step_fn.__wrapped__.trace(*args).lower().compile() \
            .as_text()
        by_axis = measured_collective_bytes_by_axis(
            text, {"data": 2, "model": 4})
        declared = sum(tr.collective_accounting()["bytes"].values())
        measured_data = sum(by_axis.get("data", {}).values())
        out["collective_bytes_by_axis"] = {
            ax: dict(ops) for ax, ops in by_axis.items()}
        out["data_axis_declared_vs_measured"] = {
            "declared": declared, "measured": measured_data}

    zmom = out["arms"]["zero1_tp_2x4"]["per_device_bytes"][
        "moment_ratio_vs_replicated"]
    out["gate"] = {
        "metric": "mesh2d-zero1-tp-moment-bytes-ratio",
        "value": zmom,
        # 1/(d·m) = 1/8 plus slack for the few leaves the data axis
        # cannot divide; measured from real device buffers so the gate is
        # load-independent (wall-clock gates don't survive the virtual
        # mesh — see docstring)
        "target": 0.15,
        "ok": zmom <= 0.15}
    return out


def measure_flash(n_devices: int = 8, vocab: int = 64, width: int = 32,
                  heads: int = 4, depth: int = 2, seq: int = 16,
                  global_batch: int = 8, steps: int = 2, reps: int = 3):
    """Flash-under-SPMD ablation (ISSUE 18): the transformer LM trained
    ZERO1×TP on the (2,4) mesh with the attention body swapped per arm,
    in ALTERNATING measured windows (rep i times every arm back-to-back
    so host-load drift contaminates them equally):

      * `flash_spmd`  — the shard_map'd Pallas kernel, FORCED on
        (`flash="spmd"`); on the CPU mesh the kernel runs in Pallas
        INTERPRET mode, so its wall-clock is emulation overhead, not a
        hardware prediction;
      * `einsum_fp32` — the einsum fallback, fp32 throughout (the
        capability probe's choice on this backend);
      * `einsum_bf16` — the einsum fallback under bf16-compute /
        fp32-master (`compute_dtype="bfloat16"`).

    Reports tokens/s per arm with paired per-round ratios + spreads for
    flash-vs-einsum and bf16-vs-fp32, and the REMAT-POLICY activation-
    bytes column: `pp_stage_saved_bytes` of the same LM's 1F1B stage on
    the (2,2,2) mesh under every registered policy — the static
    accounting the selective-remat tentpole publishes.

    Virtual-mesh caveat: interpret-mode Pallas is ORDERS slower than the
    compiled einsum on CPU, so there is NO wall-clock gate on the flash
    ratio (the TPU claim is carried by the IR lint: pallas_call present,
    zero reshard-byte regression). The gate rides on the activation-byte
    column instead — `dots` must save strictly less than `everything`
    (the un-checkpointed stage residual set), which is exact arithmetic
    on aval shapes and load-independent."""
    import time as _time

    from .pipeline import pp_stage_saved_bytes
    from .trainer import ParallelTrainer, ShardingStrategy

    if n_devices != 8:
        raise SystemExit(
            f"flash mode benches the (2,4) reshape of an 8-device mesh; "
            f"got --devices {n_devices}")
    arms = [
        ("flash_spmd", "spmd", None),
        ("einsum_fp32", False, None),
        ("einsum_bf16", False, "bfloat16"),
    ]
    ds = _lm_data(vocab, seq, global_batch)
    trainers = {}
    for name, flash, cdt in arms:
        model = _build_transformer_lm(vocab, width, heads, depth, seq,
                                      compute_dtype=cdt)
        trainers[name] = ParallelTrainer(
            model, mesh_shape=(2, 4), strategy=ShardingStrategy.ZERO1_TP,
            collect_stats=False, flash=flash)
    for tr in trainers.values():
        tr.fit(ds)
        float(tr.score())

    tokens = global_batch * seq * steps
    rep_tps = {name: [] for name in trainers}
    for _ in range(max(2, int(reps))):
        for name, tr in trainers.items():
            t0 = _time.perf_counter()
            for _ in range(steps):
                tr.fit(ds)
            float(tr.score())
            rep_tps[name].append(tokens / (_time.perf_counter() - t0))

    out = {"mode": "flash", "devices": n_devices,
           "model": {"vocab": vocab, "width": width, "heads": heads,
                     "depth": depth, "seq": seq,
                     "global_batch": global_batch},
           "arms": {}}
    for name, tr in trainers.items():
        tps = sorted(rep_tps[name])
        out["arms"][name] = {
            "flash_mode": tr.flash_mode,
            "tokens_per_s": round(_median(tps), 1),
            "tokens_per_s_rep": [round(v, 1) for v in tps]}

    def _paired(a, b):
        rs = sorted(x / y for x, y in zip(rep_tps[a], rep_tps[b]))
        return (round(rs[len(rs) // 2], 3),
                [round(rs[0], 3), round(rs[-1], 3)])

    out["flash_vs_einsum_paired"], out["flash_vs_einsum_spread"] = \
        _paired("flash_spmd", "einsum_fp32")
    out["bf16_vs_fp32_paired"], out["bf16_vs_fp32_spread"] = \
        _paired("einsum_bf16", "einsum_fp32")
    out["wall_clock_caveat"] = (
        "flash arm runs the Pallas kernel in interpret mode on the "
        "virtual CPU mesh; its tokens/s is emulation overhead, not a "
        "TPU prediction — the kernel claim is IR-lint-carried")

    # remat-policy activation-bytes column: static 1F1B stage accounting
    # of the SAME LM on the (data=2, model=2, pipe=2) mesh
    pp_tr = ParallelTrainer(
        _build_transformer_lm(vocab, width, heads, depth, seq),
        mesh_shape=(2, 2, 2), strategy=ShardingStrategy.ZERO1_TP_PP,
        collect_stats=False)
    micro = (max(1, global_batch // 4), seq, width)
    col = {str(p): pp_stage_saved_bytes(pp_tr._pp_plan, micro, policy=p)
           for p in (None, "nothing", "dots", "dots_no_batch",
                     "everything")}
    out["remat_policy_saved_bytes"] = col
    out["remat_micro_shape"] = list(micro)

    reduction = (col["everything"] - col["dots"]) / col["everything"] \
        if col["everything"] else 0.0
    out["gate"] = {
        "metric": "flash-remat-dots-vs-everything-saved-bytes",
        "value": round(reduction, 4),
        # `dots` must cut the stage's saved-residual bytes vs the
        # blanket un-checkpointed residual set; exact static arithmetic,
        # so any nonzero target is load-independent
        "target": 0.25,
        "ok": reduction >= 0.25}
    return out


def _median(xs):
    return sorted(xs)[len(xs) // 2]


def _median_idx(xs):
    """Index of the median element (upper median for even counts — same
    convention as _median), so callers can pull the matching per-phase
    record alongside the median time."""
    return sorted(range(len(xs)), key=lambda i: xs[i])[len(xs) // 2]


def measure_pipeline(s_stages: int = 4, microbatches=(1, 2, 4, 8),
                     global_batch: int = 32, steps: int = 3, reps: int = 3,
                     hidden: int = 256, features: int = 1024,
                     mb_rows: int = 256):
    """Pipeline efficiency vs GPipe theory (round-5 VERDICT item 5).

    GPipe (arXiv:1811.06965) schedules M microbatches over S stages in
    M+S-1 ticks: bubble fraction (S-1)/(M+S-1), efficiency M/(M+S-1).

    Two measurements, both on the virtual mesh where RATIOS are
    load-robust even though absolute wall time isn't:

    * `spmd_tick`: the tick-synchronous shard_map schedule
      (`pipeline_forward`, collective-permute ring). Every tick costs the
      same on the virtual mesh (idle stages burn identical flops on the
      carry), so T(M) ∝ (M+S-1) and measured per-sample throughput must
      track M/(M+S-1). Reported: per-tick time (theory: constant over M)
      and measured efficiency normalized at the largest M against its
      own theory point.
    * `f1b` (ISSUE 15, `measure_pipeline_1f1b`): the transformer LM
      trained mesh-native 1F1B vs host-GPipe vs ZERO1×TP in alternating
      paired windows — tokens/s, dispatch-span share and compile counts
      per arm, plus the 1F1B step's per-axis compiled-HLO collective
      payloads. The mode's `gate` is the paired 1F1B-vs-host-GPipe
      throughput ratio (> 1): on the virtual mesh both arms pay the
      same serialized flops, so the delta IS the per-dispatch overhead
      the single compiled schedule removes.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from .mesh import make_mesh
    from .pipeline import PipelinedDenseStack

    mesh = make_mesh({"pipe": s_stages}, devices=jax.devices()[:s_stages])
    r = np.random.default_rng(0)
    out = {"mode": "pipeline", "S": s_stages,
           "microbatches": list(microbatches),
           "bubble_theory": [round((s_stages - 1) / (m + s_stages - 1), 4)
                             for m in microbatches],
           "efficiency_theory": [round(m / (m + s_stages - 1), 4)
                                 for m in microbatches]}

    # -- tick-synchronous SPMD schedule ---------------------------------
    # hoist the jitted shard_map call + sharded params OUT of the timed
    # loop: PipelinedDenseStack.pipelined_forward re-device_puts per call,
    # a fixed cost that would masquerade as bubble at small M
    import functools as _ft

    from jax import shard_map as _shard_map
    from jax.sharding import NamedSharding as _NS, PartitionSpec as _P

    from .pipeline import pipeline_forward as _pf

    stack = PipelinedDenseStack(features, s_stages, mesh)
    from ..telemetry.compile_watch import watch_compiles

    fn = watch_compiles(jax.jit(_shard_map(
        _ft.partial(_pf, stack._stage_fn, axis_name="pipe",
                    n_stages=s_stages),
        mesh=mesh, in_specs=(_P("pipe"), _P()), out_specs=_P(),
        check_vma=False)), "bench/pipeline_tick")
    params_sh = jax.device_put(stack.params, _NS(mesh, _P("pipe")))
    med_t = {}
    for m in microbatches:
        xm = jnp.asarray(r.normal(size=(m, mb_rows, features))
                         .astype(np.float32))
        float(jnp.asarray(fn(params_sh, xm)).sum())
        rep = []
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(steps):
                y = fn(params_sh, xm)
            float(jnp.asarray(y).sum())
            rep.append((time.perf_counter() - t0) / steps)
        med_t[m] = _median(rep)
    m_last = microbatches[-1]
    # normalize measured throughput so the largest M sits on its theory
    # point; the SHAPE of the curve is then the measurement
    norm = (m_last / (m_last + s_stages - 1)) / (m_last * mb_rows
                                                 / med_t[m_last])
    out["spmd_tick"] = {
        "per_tick_ms": {str(m): round(med_t[m] * 1e3 / (m + s_stages - 1), 3)
                        for m in microbatches},
        "efficiency_measured": [
            round((m * mb_rows / med_t[m]) * norm, 4) for m in microbatches],
        "bubble_measured": [
            round(1.0 - (m * mb_rows / med_t[m]) * norm, 4)
            for m in microbatches],
    }

    # -- 1F1B vs host-GPipe vs ZERO1×TP (ISSUE 15) ----------------------
    out["f1b"] = measure_pipeline_1f1b(
        s_stages=s_stages, steps=steps, reps=reps)
    out["gate"] = out["f1b"]["gate"]
    return out


def measure_pipeline_1f1b(s_stages: int = 4, vocab: int = 64,
                          width: int = 64, heads: int = 4, seq: int = 32,
                          micro_batch: int = 8, m: int = 8, steps: int = 2,
                          warmup: int = 1, reps: int = 3):
    """Mesh-native 1F1B vs host-GPipe vs ZERO1×TP, paired (ISSUE 15).

    The transformer LM (depth = `s_stages` blocks, so every arm stages
    the identical model) trains the same effective batch
    (micro_batch · m rows · seq tokens) per optimizer step on each arm,
    in ALTERNATING measured windows so host-load drift contaminates all
    arms equally:

      * `pp_1f1b`      — strategy="pp" on a (1, 1, S) mesh:
                         ONE jitted SPMD dispatch per optimizer step
                         (`fit(grad_accumulation=m)`)
      * `host_gpipe`   — the legacy PipelinedNetworkTrainer on the same
                         S devices: O(S·m) per-stage dispatches per step
      * `zero1_tp_pp`  — strategy="zero1_tp_pp" on (2, 1, S): the 3-D
                         composition on all 8 devices
      * `zero1_tp`     — strategy="zero1_tp" on (2, 4): the 2-D
                         reference without a pipe axis

    Reports tokens/s per arm with the PAIRED per-round
    1F1B-vs-host-GPipe ratio (the acceptance gate: > 1 — the single
    compiled schedule must beat the host-driven dispatch storm even on
    the virtual mesh, where both pay the same serialized flops and the
    delta IS the dispatch overhead), per-arm dispatch-span share and
    compile counts from telemetry (the O(S·M) -> O(1) evidence), the
    structural per-step dispatch counts, and the 1F1B step's per-axis
    compiled-HLO collective payloads (permutes must ride `pipe` only)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..datasets.iterators import DataSet, ListDataSetIterator
    from ..telemetry import runtime as telemetry_runtime
    from .mesh import make_mesh
    from .pipeline import PipelinedNetworkTrainer
    from .trainer import ParallelTrainer

    S = s_stages
    lm = lambda: _build_transformer_lm(vocab, width, heads, S, seq)
    r = np.random.default_rng(0)

    def micros(n):
        return [DataSet(
            r.integers(0, vocab, (micro_batch, seq, 1)).astype(np.float32),
            np.eye(vocab, dtype=np.float32)[
                r.integers(0, vocab, (micro_batch, seq))])
            for _ in range(n)]

    batch_micros = micros(m)
    big = DataSet(
        np.concatenate([np.asarray(d.features) for d in batch_micros]),
        np.concatenate([np.asarray(d.labels) for d in batch_micros]))
    devs = jax.devices()
    pipe_mesh = make_mesh({"pipe": S}, devices=devs[:S])

    arms = {}
    arms["pp_1f1b"] = ParallelTrainer(
        lm(), mesh=make_mesh({"data": 1, "model": 1, "pipe": S},
                             devices=devs[:S]), strategy="pp")
    arms["host_gpipe"] = PipelinedNetworkTrainer(lm(), pipe_mesh,
                                                 n_microbatches=m)
    if len(devs) >= 2 * S:
        arms["zero1_tp_pp"] = ParallelTrainer(
            lm(), mesh=make_mesh({"data": 2, "model": 1, "pipe": S},
                                 devices=devs[:2 * S]),
            strategy="zero1_tp_pp")
        arms["zero1_tp"] = ParallelTrainer(
            lm(), mesh=make_mesh({"data": 2, "model": S},
                                 devices=devs[:2 * S]),
            strategy="zero1_tp")

    def run_step_window(name, tr, n_steps):
        """n_steps optimizer steps over the same effective batch."""
        if name == "host_gpipe":
            for _ in range(n_steps):
                tr._fit_batch(big)
            float(tr.score())
        elif name == "zero1_tp":
            for _ in range(n_steps):
                tr.fit(big)
            float(tr.score())
        else:
            it = ListDataSetIterator(list(batch_micros) * n_steps)
            tr.fit(it, grad_accumulation=m)
            float(tr.score())

    sess = telemetry_runtime.active()
    for name, tr in arms.items():
        run_step_window(name, tr, warmup)

    tokens = micro_batch * m * seq
    rep_tps = {name: [] for name in arms}
    spans = {name: {"dispatch_s": 0.0, "wall_s": 0.0} for name in arms}
    for _ in range(max(1, int(reps))):
        for name, tr in arms.items():
            d0 = (sess.span_totals().get("device/dispatch", 0.0)
                  if sess else 0.0)
            t0 = time.perf_counter()
            run_step_window(name, tr, steps)
            wall = time.perf_counter() - t0
            rep_tps[name].append(tokens * steps / wall)
            if sess:
                spans[name]["dispatch_s"] += (
                    sess.span_totals().get("device/dispatch", 0.0) - d0)
            spans[name]["wall_s"] += wall

    out = {"model": {"vocab": vocab, "width": width, "heads": heads,
                     "depth": S, "seq": seq, "micro_batch": micro_batch,
                     "m": m},
           "arms": {}}
    for name in arms:
        tps = sorted(rep_tps[name])
        arm = {"tokens_per_s": round(_median(tps), 1),
               "tokens_per_s_rep": [round(v, 1) for v in tps]}
        if spans[name]["wall_s"]:
            arm["dispatch_span_share"] = round(
                spans[name]["dispatch_s"] / spans[name]["wall_s"], 3)
        out["arms"][name] = arm
    # structural dispatches per optimizer step: the host schedule pays a
    # fwd + bwd jit per (stage, microbatch) plus per-stage reg/update
    # jits; the 1F1B step is ONE dispatch
    out["dispatches_per_step"] = {
        "host_gpipe": 2 * S * m + 2 * S, "pp_1f1b": 1}
    if sess:
        out["compiles"] = {k: v["count"]
                           for k, v in sess.compiles.report().items()
                           if v["count"] and ("pipeline/" in k
                                              or "pp" in k)}
    ratios = sorted(p / h for p, h in zip(rep_tps["pp_1f1b"],
                                          rep_tps["host_gpipe"]))
    out["f1b_vs_host_gpipe_paired"] = round(ratios[len(ratios) // 2], 3)
    out["f1b_vs_host_gpipe_spread"] = [round(ratios[0], 3),
                                       round(ratios[-1], 3)]

    # per-axis compiled-HLO payload of the 3-D step (permutes must ride
    # `pipe` only; `data` carries the ZeRO/gradient traffic)
    if "zero1_tp_pp" in arms:
        from ..analysis.ir import measured_collective_bytes_by_axis
        tr = arms["zero1_tp_pp"]
        fn = tr._accum_superstep_jit(False).__wrapped__
        xs = jnp.stack([jnp.asarray(np.asarray(d.features))
                        for d in batch_micros])[None]
        ys = jnp.stack([jnp.asarray(np.asarray(d.labels))
                        for d in batch_micros])[None]
        args = (tr._params, tr._state, tr._opt, jnp.asarray(0, jnp.int32),
                jax.random.PRNGKey(0), xs, ys, None, None)
        text = fn.trace(*args).lower().compile().as_text()
        by_axis = measured_collective_bytes_by_axis(
            text, {"data": 2, "model": 1, "pipe": S})
        out["collective_bytes_by_axis"] = {
            ax: dict(ops) for ax, ops in by_axis.items()}
        out["permute_leak_bytes_off_pipe"] = (
            by_axis.get("data", {}).get("collective-permute", 0)
            + by_axis.get("model", {}).get("collective-permute", 0))

    out["gate"] = {"metric": f"pipeline-1f1b-vs-host-gpipe-S{S}",
                   "value": out["f1b_vs_host_gpipe_paired"],
                   "target": 1.0,
                   "ok": out["f1b_vs_host_gpipe_paired"] > 1.0}
    return out


def _telemetry_fields(sess):
    """Compile-count + host/device time attribution for the multichip JSON
    (one line artifact: a regressed efficiency number is diagnosable as
    compile churn vs collective overhead without re-running)."""
    spans = sess.span_totals()
    out = {"xla_compilations": sess.compiles.total(),
           "compiles": {k: v["count"]
                        for k, v in sess.compiles.report().items()},
           "dispatch_seconds": round(spans.get("device/dispatch", 0.0), 4),
           "sync_seconds": round(spans.get("device/sync", 0.0), 4),
           "peak_rss_mb": round(sess.watermarks.peak_rss_mb(), 1)}
    pipe = sess.pipeline_summary()
    if pipe:
        out["pipeline"] = pipe
    dp = sess.dp_summary()
    if dp:
        out["dp"] = dp
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--global-batch", type=int, default=None,
                    help="default per mode: dp/pipeline 64, mesh2d 16")
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--reps", type=int, default=1)
    ap.add_argument("--model", choices=("vgg16", "mlp"), default=None)
    # dp mode benches the declared VGG16 config; accum mode defaults to
    # the compute-dense MLP — VGG16 convs inside the accumulation scan
    # take minutes of XLA:CPU compile + the documented conv-in-scan
    # slowdown, which would measure the artifact, not the schedule
    ap.add_argument("--image", type=int, default=32)
    ap.add_argument("--no-ablation", action="store_true")
    ap.add_argument("--no-zero", action="store_true",
                    help="skip the paired replicated-vs-ZeRO ablation")
    ap.add_argument("--zero-stage", type=int, choices=(1, 2),
                default=None)  # dp mode: 1; accum mode: 2
    ap.add_argument("--mode",
                    choices=("dp", "pipeline", "accum", "mesh2d", "flash"),
                    default="dp")
    ap.add_argument("--micro-batch", type=int, default=32)
    ap.add_argument("--accum-m", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128,
                    help="mesh2d mode: LM sequence length")
    ap.add_argument("--width", type=int, default=128,
                    help="mesh2d mode: transformer width (divisible by 8)")
    ap.add_argument("--depth", type=int, default=2,
                    help="mesh2d mode: transformer blocks")
    ap.add_argument("--no-collective-measure", action="store_true",
                    help="mesh2d mode: skip the per-axis compiled-HLO "
                         "payload measurement (saves one lowering)")
    ap.add_argument("--hidden", type=int, default=None,
                    help="mlp hidden width override (accum mode; default "
                         "1024 — compute-dense enough to be representative)")
    a = ap.parse_args(argv)
    if a.global_batch is None and a.mode not in ("mesh2d", "flash"):
        a.global_batch = 64   # the declared dp/pipeline config
    _provision(a.devices)
    from ..telemetry import runtime as telemetry_runtime
    sess = telemetry_runtime.enable()
    if a.mode == "accum":
        # accumulation defaults to ZERO2 — the stage whose sharded
        # accumulators the ablation exists to measure
        stage = a.zero_stage if a.zero_stage is not None else 2
        kw = {} if a.hidden is None else {"hidden": a.hidden}
        out = measure_paired_accum(
            a.devices, micro_batch=a.micro_batch, m=a.accum_m,
            steps=a.steps, reps=max(2, a.reps), model=a.model or "mlp",
            image=a.image,
            strategy="replicated" if a.no_zero else f"zero{stage}", **kw)
        sess.watermarks.sample()
        out["telemetry"] = _telemetry_fields(sess)
        print(json.dumps(out))
        return
    if a.mode == "flash":
        out = measure_flash(
            a.devices, seq=min(a.seq, 16), steps=a.steps,
            global_batch=a.global_batch or 8, reps=max(2, a.reps))
        sess.watermarks.sample()
        out["telemetry"] = _telemetry_fields(sess)
        print(json.dumps(out))
        return
    if a.mode == "mesh2d":
        out = measure_mesh2d(
            a.devices, width=a.width, depth=a.depth, seq=a.seq,
            global_batch=a.global_batch or 16,
            steps=a.steps, reps=max(2, a.reps),
            measure_collectives=not a.no_collective_measure)
        sess.watermarks.sample()
        out["telemetry"] = _telemetry_fields(sess)
        print(json.dumps(out))
        return
    if a.mode == "pipeline":
        out = measure_pipeline(
            s_stages=min(4, a.devices), global_batch=a.global_batch,
            steps=a.steps, reps=max(3, a.reps))
        sess.watermarks.sample()
        out["telemetry"] = _telemetry_fields(sess)
        print(json.dumps(out))
        return
    model = a.model or "vgg16"
    m1 = measure(1, a.global_batch, a.steps, model=model,
                 image=a.image, reps=a.reps)
    mn = measure(a.devices, a.global_batch, a.steps, model=model,
                 image=a.image, reps=a.reps)
    t1, tn = m1["median_ms"], mn["median_ms"]
    # conservative efficiency bounds from the rep spreads
    eff_lo = min(m1["rep_ms"]) / max(mn["rep_ms"])
    eff_hi = max(m1["rep_ms"]) / min(mn["rep_ms"])
    out = {"model": model, "t1_ms": round(t1, 2), "tn_ms": round(tn, 2),
           "t1_rep_ms": m1["rep_ms"], "tn_rep_ms": mn["rep_ms"],
           "devices": a.devices, "efficiency": round(t1 / tn, 3),
           "efficiency_spread": [round(eff_lo, 3), round(eff_hi, 3)],
           "phases_1dev_ms": m1["phases_ms"],
           "phases_ndev_ms": mn["phases_ms"]}
    if not a.no_ablation:
        # replicated-updater artifact: on the virtual mesh the optimizer
        # update runs once per device on shared cores. Adam-vs-SGD step
        # delta at n devices minus the same delta at 1 device == measured
        # cost of the replication.
        m1s = measure(1, a.global_batch, a.steps, model=model,
                      image=a.image, updater="sgd", reps=a.reps)
        mns = measure(a.devices, a.global_batch, a.steps, model=model,
                      image=a.image, updater="sgd", reps=a.reps)
        t1s, tns = m1s["median_ms"], mns["median_ms"]
        out["updater_ablation"] = {
            "t1_sgd_ms": round(t1s, 2), "tn_sgd_ms": round(tns, 2),
            "t1_sgd_rep_ms": m1s["rep_ms"], "tn_sgd_rep_ms": mns["rep_ms"],
            "efficiency_sgd": round(t1s / tns, 3),
            "efficiency_sgd_spread": [
                round(min(m1s["rep_ms"]) / max(mns["rep_ms"]), 3),
                round(max(m1s["rep_ms"]) / min(mns["rep_ms"]), 3)],
            "phases_1dev_sgd_ms": m1s["phases_ms"],
            "phases_ndev_sgd_ms": mns["phases_ms"],
            "replicated_updater_cost_ms": round((tn - tns) - (t1 - t1s), 2)}
    if not a.no_zero:
        # ZeRO ablation (ROADMAP item 2): replicated vs sharded-optimizer
        # step in alternating windows on the same devices. On the virtual
        # CPU mesh the replicated updater costs N× the flops on shared
        # cores — exactly the artifact the sharded update removes — so
        # efficiency_zero = t1/tn_zero is the headline the ≥0.85 target
        # gates on
        strategy = f"zero{a.zero_stage or 1}"
        pz = measure_paired_zero(a.devices, a.global_batch, a.steps,
                                 model=model, image=a.image,
                                 reps=max(2, a.reps), strategy=strategy)
        tz = pz[strategy]["median_ms"]
        tr_ = pz["replicated"]["median_ms"]
        za = {"strategy": strategy,
              "tn_zero_ms": round(tz, 2),
              "tn_repl_paired_ms": round(tr_, 2),
              "rep_ms": {"replicated": pz["replicated"]["rep_ms"],
                         strategy: pz[strategy]["rep_ms"]},
              "phases_ndev_zero_ms": pz[strategy]["phases_ms"],
              "phases_ndev_repl_paired_ms": pz["replicated"]["phases_ms"],
              "efficiency_zero": round(t1 / tz, 3),
              "efficiency_zero_spread": [
                  round(min(m1["rep_ms"]) / max(pz[strategy]["rep_ms"]), 3),
                  round(max(m1["rep_ms"]) / min(pz[strategy]["rep_ms"]), 3)],
              # drift-cancelled form: t1/tn was measured minutes before the
              # paired windows, so host-load drift between the two captures
              # would leak straight into t1/tz; rescaling tz by the PAIRED
              # replicated window (measured seconds apart, same load) maps
              # it back onto the t1/tn timeline —
              # t1/(tz·tn/tn_repl_paired) = (t1/tn)·(tn_repl_paired/tz)
              "efficiency_zero_paired": round((t1 / tn) * (tr_ / tz), 3),
              # the step-time the sharded update recovers vs the paired
              # replicated windows (positive = ZeRO faster)
              "updater_saving_vs_replicated_ms": round(tr_ - tz, 2)}
        if not a.no_ablation:
            # same decomposition as replicated_updater_cost_ms with the
            # ZeRO step in place of the replicated Adam step: what the
            # updater phase still costs AFTER sharding
            za["zero_updater_cost_ms"] = round((tz - tns) - (t1 - t1s), 2)
        out["zero_ablation"] = za
        # the MULTICHIP gate for ROADMAP item 2 (≥0.85 strong scaling
        # with the replicated-updater tax removed) — gated on the
        # drift-cancelled paired form so a load ramp between the t1
        # capture and the ZeRO windows can't decide the verdict
        out["multichip"] = {"metric": f"{strategy}-strong-scaling-"
                                      f"{a.devices}dev",
                            "value": za["efficiency_zero_paired"],
                            "raw_value": za["efficiency_zero"],
                            "target": 0.85,
                            "ok": za["efficiency_zero_paired"] >= 0.85}
    sess.watermarks.sample()
    out["telemetry"] = _telemetry_fields(sess)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
