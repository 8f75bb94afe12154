"""Production inference plane (ROADMAP open item 1 — the "millions of
users" leg).

Four pieces, layered:
  * `ModelRegistry` (registry.py) — named, versioned servable models with
    **atomic hot-swap** from fault/-verified checkpoint sources (sha256
    manifest zips, committed checkpoint directories, Keras HDF5, live
    model objects). Every (model, shape-bucket, precision) forward is
    jit-lowered AND compiled at registration — the request path only ever
    invokes finished XLA executables, never a cold compile.
  * int8 weight-only quantization + bf16 casting (quantize.py) — the
    reduced-precision serving paths.
  * `DynamicBatcher` (batcher.py) — coalesces concurrent requests into
    padded fixed-shape batches (the PadToBatch row shaping from
    datasets/pipeline.py, applied to traffic instead of datasets) with
    max-wait-µs / max-batch knobs; per-row scatter back to waiters.
  * `InferenceServer` (server.py) — the HTTP front end (`/v1/models`,
    `/v1/models/<name>/predict`, `/v1/models/<name>/swap`, `/healthz`,
    Prometheus `/metrics` via the telemetry registry).
"""
from .batcher import BatcherClosedError, DynamicBatcher
from .quantize import QuantizedTree, cast_tree, quantize_tree
from .registry import (AotCompileError, CanaryState, DEFAULT_BUCKETS,
                       ModelRegistry, PRECISIONS, ServableVersion,
                       ServingError, UnknownModelError, load_source)
from .server import ClientError, InferenceServer

__all__ = [
    "ModelRegistry", "ServableVersion", "ServingError", "UnknownModelError",
    "AotCompileError", "CanaryState",
    "DEFAULT_BUCKETS", "PRECISIONS", "load_source",
    "DynamicBatcher", "BatcherClosedError",
    "InferenceServer", "ClientError",
    "QuantizedTree", "quantize_tree", "cast_tree",
]
