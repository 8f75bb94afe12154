#!/usr/bin/env bash
# CI loop (reference repo-root `runtests.sh`): run the suite on the
# 8-device virtual CPU mesh, optionally in a loop to shake out flakes.
#   ./runtests.sh            one pass
#   ./runtests.sh 5          five consecutive passes (stop on first failure)
#   ./runtests.sh telemetry  telemetry smoke only (registry/tracing/compile
#                            watcher; tmp_path-only file writes, no network)
#   ./runtests.sh pipeline   input-pipeline smoke only (PadToBatch /
#                            DevicePrefetch, ragged-batch compile counts,
#                            async iterator lifecycle)
#   ./runtests.sh fault      fault-tolerance smoke only (crash-safe
#                            checkpoints, kill-mid-save recovery, resume
#                            equivalence, TrainingGuard policies)
#   ./runtests.sh serving    serving smoke: unit/HTTP tests plus a live
#                            end-to-end pass (ephemeral port, predict,
#                            hot-swap, /metrics scrape, clean shutdown)
#   ./runtests.sh decode     autoregressive decode smoke: the KV-cache
#                            generation suite (prefill+ticks vs full-
#                            forward greedy equivalence, paged-block
#                            reuse bit-exactness, join/leave isolation,
#                            continuous batching, /generate HTTP, IR
#                            probes)
#   ./runtests.sh zero       ZeRO sharded-optimizer smoke: the replicated-
#                            vs-zero1/zero2 equivalence suite on the
#                            8-device virtual mesh
#   ./runtests.sh superstep  superstep smoke: the fit(superstep=K)-vs-
#                            per-batch bit-exact equivalence suite
#                            (both model families + ParallelTrainer,
#                            guard rollback, non-aligned resume)
#   ./runtests.sh accum      gradient-accumulation smoke: the
#                            fit(grad_accumulation=M) equivalence suite
#                            (M×b vs M·b both families, ZERO2 sharded
#                            accumulators, guard micro-skip, mid-
#                            accumulation kill+resume)
#   ./runtests.sh pipe       mesh-native 1F1B pipeline smoke: the
#                            pp/zero1_tp_pp equivalence suite (1F1B vs
#                            single-process accumulation on both 3-D
#                            reshapes, grouping invariance, masks,
#                            kill-mid-write resume, IR seeded
#                            mutations)
#   ./runtests.sh mesh2d     2-D mesh-parallelism smoke: the ZERO1×TP
#                            equivalence suite (vs replicated and 1-D
#                            ZERO1, superstep/accumulation grouping
#                            invariance, kill-mid-write resume with 2-D
#                            layouts, up-front combo validation)
#   ./runtests.sh flash      flash-under-SPMD + precision/remat smoke:
#                            the shard_map'd Pallas attention suite
#                            (spmd-vs-einsum equivalence under zero1_tp,
#                            capability gating + log line, IR custom-
#                            call probe + drop_flash mutation) and the
#                            mixed-precision/selective-remat suite
#                            (policy numerics no-ops, bf16 across fit
#                            paths, 1F1B compute_dtype + resume)
#   ./runtests.sh obs        observability smoke: the ISSUE 17 suite
#                            (connected /generate trace, flight-recorder
#                            ring + guard-trip dumps, SLO surface,
#                            /debug/flightrecord) and the span log's
#                            (ring, nesting, scheduler and fit spans,
#                            the same names in a profiler trace)
#   ./runtests.sh elastic    elastic-training smoke (ISSUE 19): the
#                            coordinated two-phase-commit suite (every
#                            commit boundary crash-injected, torn
#                            COMMIT invisibility), the mesh-reshape
#                            restore contract (zero1_tp_pp (2,2,2) ->
#                            (1,2,4)/(1,1,8)/(4,2,1) bit-exact incl.
#                            sharded optimizer moments), ElasticTrainer
#                            loss/rejoin/drain loops, then the REAL
#                            2-process kill/rejoin drills (slow marker;
#                            capability-gated — they skip where the jax
#                            CPU backend lacks multiprocess collectives)
#   ./runtests.sh continual  online-learning smoke (ISSUE 20): the
#                            continual train-to-serve suite (journal
#                            crash consistency + the every-boundary
#                            crash drill, eval gate, deterministic
#                            canary routing, SLO auto-rollback with
#                            zero failed stable requests, torn-topic-
#                            record recovery, /canary HTTP endpoints)
#                            plus one end-to-end loop rep: bootstrap ->
#                            improvement window auto-promotes -> NaN
#                            window auto-rolls-back, stable untouched
#   ./runtests.sh lint       graftlint, both tiers: the AST pass
#                            (jit/tracer hygiene, recompile hazards,
#                            donation safety, concurrency lint) AND the
#                            IR pass (trace/lower/compile every probe-
#                            built jit entry point on the virtual
#                            8-device mesh; sharding, collective-order,
#                            donation-aliasing and reduction-determinism
#                            verification; the whole pass inside 60 s)
#                            against the checked-in baseline — any
#                            NON-baselined finding fails —
#                            plus the analysis self-tests and runtime-
#                            sanitizer smoke. The same gates run inside
#                            the full suite via tests/test_analysis.py.
set -euo pipefail
cd "$(dirname "$0")"
if [[ "${1:-}" == "lint" ]]; then
    echo "=== graftlint AST pass (baseline: graftlint_baseline.json) ==="
    python -m tools.graftlint deeplearning4j_tpu/
    echo "=== graftlint IR pass (virtual 8-device mesh, ir_findings; 60 s limit) ==="
    env JAX_PLATFORMS=cpu timeout 60 \
        python -m tools.graftlint deeplearning4j_tpu/ --ir
    echo "=== analysis self-tests + runtime sanitizer smoke ==="
    exec python -m pytest tests/test_analysis.py -q
fi
if [[ "${1:-}" == "serving" ]]; then
    echo "=== serving smoke ==="
    python -m pytest tests/test_serving.py -q
    exec python -m deeplearning4j_tpu.serving.server --smoke
fi
if [[ "${1:-}" == "decode" ]]; then
    echo "=== autoregressive decode smoke ==="
    exec python -m pytest tests/test_decode.py -q
fi
if [[ "${1:-}" == "zero" ]]; then
    echo "=== ZeRO sharded-optimizer smoke ==="
    exec python -m pytest tests/test_zero.py -q
fi
if [[ "${1:-}" == "superstep" ]]; then
    echo "=== superstep equivalence smoke ==="
    exec python -m pytest tests/test_superstep.py -q
fi
if [[ "${1:-}" == "accum" ]]; then
    echo "=== gradient-accumulation equivalence smoke ==="
    exec python -m pytest tests/test_accumulation.py -q
fi
if [[ "${1:-}" == "mesh2d" ]]; then
    echo "=== 2-D mesh parallelism equivalence smoke ==="
    exec python -m pytest tests/test_mesh2d.py -q
fi
if [[ "${1:-}" == "flash" ]]; then
    echo "=== flash-under-SPMD + precision/remat smoke ==="
    exec python -m pytest tests/test_flash_spmd.py tests/test_precision_remat.py -q
fi
if [[ "${1:-}" == "elastic" ]]; then
    echo "=== elastic training smoke (2PC, reshape restore, supervision) ==="
    python -m pytest tests/test_elastic.py -q
    echo "=== real 2-process kill/rejoin drills (capability-gated) ==="
    exec python -m pytest tests/test_multiprocess_distributed.py -q \
        -k elastic
fi
if [[ "${1:-}" == "continual" ]]; then
    echo "=== continual train-to-serve smoke ==="
    python -m pytest tests/test_continual.py -q
    echo "=== end-to-end loop rep (promote then rollback) ==="
    exec env JAX_PLATFORMS=cpu \
        python -m deeplearning4j_tpu.continual.trainer
fi
if [[ "${1:-}" == "fault" ]]; then
    echo "=== fault-tolerance smoke ==="
    exec python -m pytest tests/test_fault.py -q
fi
if [[ "${1:-}" == "obs" ]]; then
    echo "=== observability smoke ==="
    exec python -m pytest tests/test_observability.py tests/test_span_log.py -q
fi
if [[ "${1:-}" == "telemetry" ]]; then
    echo "=== telemetry smoke ==="
    exec python -m pytest tests/test_telemetry.py -q
fi
if [[ "${1:-}" == "pipeline" ]]; then
    echo "=== input-pipeline smoke ==="
    exec python -m pytest tests/test_input_pipeline.py -q
fi
if [[ "${1:-}" == "pipe" ]]; then
    echo "=== mesh-native 1F1B pipeline equivalence smoke ==="
    exec python -m pytest tests/test_pipeline_1f1b.py -q
fi
runs="${1:-1}"
for i in $(seq 1 "$runs"); do
    echo "=== test pass $i/$runs ==="
    python -m pytest tests/ -q
done
