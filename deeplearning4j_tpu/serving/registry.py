"""Multi-model registry with versioned atomic hot-swap and AOT-compiled
inference runners.

The serving plane's core invariants:

  * **No cold compile on the request path.** Every (model, shape-bucket,
    precision) forward is jit-lowered AND compiled at registration/swap
    time (`jax.jit(...).lower(...).compile()`); request threads only ever
    invoke finished XLA executables. A compiled executable *cannot*
    retrace — a shape drifting past the bucket contract raises instead of
    silently recompiling, which is exactly the failure mode the
    CompileWatcher exists to catch in training.
  * **Atomic hot-swap.** A `ServableVersion` is an immutable snapshot
    (parameters, layer state, compiled runners). `swap()` builds and
    compiles the new version completely OFF the request path, then flips
    one pointer under the registry lock. In-flight requests keep the
    version object they already grabbed (old executables + old params
    stay alive via refcount) and finish on it; requests admitted after
    the flip see the new version. Nothing is ever dropped, and no request
    can observe half-old/half-new parameters.
  * **Verified sources.** Checkpoint sources go through the fault/
    machinery: zip checkpoints verify their sha256 manifest on restore
    (`CorruptCheckpointError` on bit rot / torn copy), checkpoint
    directories only trust `ckpt_*.zip` files (whose atomic-rename
    existence is the commit marker) and fall back past corrupt ones,
    newest first.

Executable reuse across swaps: compiled runners are cached per model
entry keyed by the *abstract* signature (param/state shapes+dtypes,
bucket, precision). Swapping in a same-architecture checkpoint reuses the
existing executables with the new parameter snapshot — zero new XLA
compiles: exactly one compile per (model, bucket) across a run with
swaps (tests/test_serving.py holds the count).
"""
from __future__ import annotations

import os
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..datasets.pipeline import pad_rows
from ..telemetry.runtime import span
from .quantize import QuantizedTree, cast_tree, quantize_tree

__all__ = ["ModelRegistry", "ServableVersion", "UnknownModelError",
           "ServingError", "AotCompileError", "CanaryState",
           "DEFAULT_BUCKETS", "PRECISIONS", "load_source"]

DEFAULT_BUCKETS = (1, 8, 32)
PRECISIONS = ("fp32", "bf16", "int8")


class ServingError(RuntimeError):
    """Client-facing serving failure (bad shape, unknown precision, ...)."""


class AotCompileError(ServingError):
    """A candidate version failed its AOT lower+compile during
    `swap()`/`start_canary()`. Structured: carries the model name, the
    batch bucket that failed, and the underlying compiler exception. The
    registry guarantees the failed build is fully discarded — the live
    version keeps serving and the shared executable cache holds no entry
    from the rejected candidate."""

    def __init__(self, model: str, bucket, cause: BaseException):
        self.model = model
        self.bucket = bucket
        self.cause = cause
        super().__init__(
            f"{model}: AOT compile failed for bucket {bucket}: "
            f"{type(cause).__name__}: {cause}")


class UnknownModelError(KeyError):
    """Request for a model name the registry doesn't hold."""


# ---------------------------------------------------------------------------
# Source loading (fault/-verified checkpoint paths, keras h5, live models)
# ---------------------------------------------------------------------------
def load_source(source):
    """Resolve a servable source to a live model.

    Accepts: a model object (anything with `predict_fn`/`params`/`state`),
    a ModelSerializer zip path (sha256-manifest-verified on restore), a
    Keras HDF5 path, or a `fault.resume.CheckpointManager` directory
    (newest committed `ckpt_*.zip` wins; corrupt ones are skipped)."""
    if hasattr(source, "predict_fn"):
        return source, "object"
    if not isinstance(source, (str, os.PathLike)):
        raise ServingError(
            f"unsupported model source {type(source).__name__}: expected a "
            "model object, a checkpoint zip/h5 path, or a checkpoint "
            "directory")
    path = os.fspath(source)
    if os.path.isdir(path):
        import zipfile

        from ..fault.atomic import CorruptCheckpointError
        from ..fault.resume import CheckpointManager
        from ..util.serializer import ModelSerializer

        mgr = CheckpointManager(path)
        last_err = None
        for _, ckpt in reversed(mgr.entries()):
            try:
                return ModelSerializer.restore(ckpt), ckpt
            except (CorruptCheckpointError, OSError, KeyError,
                    ValueError, zipfile.BadZipFile) as e:
                last_err = e
        raise ServingError(
            f"no loadable committed checkpoint in {path!r}"
            + (f" (last error: {type(last_err).__name__}: {last_err})"
               if last_err else ""))
    if not os.path.exists(path):
        raise ServingError(f"model source {path!r} does not exist")
    from ..util.serializer import ModelGuesser
    return ModelGuesser.load(path), path


def _example_shape(model, override: Optional[Sequence[int]]) -> Tuple[int, ...]:
    """Per-example feature shape the compiled buckets are fixed to."""
    if override is not None:
        return tuple(int(d) for d in override)
    conf = getattr(model, "conf", None)
    it = getattr(conf, "input_type", None)
    if it is None:
        its = getattr(conf, "input_types", None)   # ComputationGraph conf
        if its:
            it = its[0]
    if it is not None:
        kind = getattr(it, "kind", None)
        if kind in ("ff", "cnn_flat"):
            return (int(it.flat_size()),)
        if kind == "cnn":
            return (int(it.height), int(it.width), int(it.channels))
        if kind in ("rnn", "cnn1d") and it.timesteps:
            return (int(it.timesteps), int(it.size))
    raise ServingError(
        "cannot derive a fixed per-example input shape from the model "
        "configuration — pass input_shape=(...) at register()/swap() time "
        "(serving compiles fixed-shape buckets, so the shape must be known "
        "up front)")


# ---------------------------------------------------------------------------
# Servable versions
# ---------------------------------------------------------------------------
class ServableVersion:
    """Immutable snapshot of one model version: transformed parameters,
    layer state, and one compiled XLA executable per shape bucket.
    Request threads hold a reference across their whole forward, so a
    concurrent swap can never tear outputs or free buffers under them."""

    __slots__ = ("name", "version", "precision", "buckets", "example_shape",
                 "snapshot", "state", "sig", "runners", "model_kind",
                 "source", "created_at", "param_bytes", "model")

    def __init__(self, name, precision, buckets, example_shape, snapshot,
                 state, runners, model_kind, source, model=None, sig=None):
        self.name = name
        self.version = 0            # assigned at the atomic flip
        self.precision = precision
        self.buckets = buckets
        self.example_shape = example_shape
        self.snapshot = snapshot
        self.state = state
        # `_abstract_sig` of the three above, as the registry computed it
        # to key this version's executables: stated here so that nobody
        # walks the leaves again for it (the decode plane keys a lookup
        # with it on every tick); None on a version built without one
        self.sig = sig
        self.runners = runners      # {bucket: compiled XLA executable}
        self.model_kind = model_kind
        self.source = source
        self.created_at = time.time()
        self.param_bytes = snapshot.nbytes()
        # the live model object (layer configs + predict_fn): the decode
        # plane walks its layer stack to build the KV-cache step; the
        # stateless runners already close over it via predict_fn, so
        # keeping the reference here costs nothing extra
        self.model = model

    def bucket_for(self, rows: int) -> int:
        for b in self.buckets:
            if rows <= b:
                return b
        raise ServingError(
            f"{self.name}: request of {rows} rows exceeds the largest "
            f"compiled batch bucket {self.buckets[-1]}")

    def run_padded(self, x_padded: np.ndarray, bucket: int) -> np.ndarray:
        """One compiled forward over a bucket-shaped batch. Never compiles."""
        out = self.runners[bucket](self.snapshot.data, self.state, x_padded)
        return np.asarray(out)

    def info(self) -> Dict:
        return {
            "name": self.name, "version": self.version,
            "precision": self.precision, "buckets": list(self.buckets),
            "input_shape": list(self.example_shape),
            "model_kind": self.model_kind,
            "source": self.source if isinstance(self.source, str) else
            type(self.source).__name__,
            "param_mb": round(self.param_bytes / 1e6, 3),
            "created_at": self.created_at,
        }


class CanaryState:
    """Live canary for one model: the candidate version, its routing
    fraction, and per-arm observations (requests, errors, latency, SLO
    breaches) that the continual plane's promotion policy reads.

    Routing is DETERMINISTIC: a per-model admission counter sends request
    `i` to the candidate iff ``i % 100 < round(fraction * 100)`` — the
    same request sequence always splits the same way, so canary drills
    are replayable. The internal lock is a leaf lock (nothing else is
    ever acquired under it), touched only for a counter bump or a stats
    write — nanoseconds on the request path."""

    __slots__ = ("version", "fraction", "started_at", "_slice",
                 "_counter", "_lock", "_arms")

    def __init__(self, version: ServableVersion, fraction: float):
        if not 0.0 < fraction < 1.0:
            raise ServingError(
                f"canary fraction must be in (0, 1), got {fraction}")
        self.version = version
        self.fraction = float(fraction)
        self.started_at = time.time()
        self._slice = max(1, round(self.fraction * 100))
        self._counter = 0
        self._lock = threading.Lock()
        self._arms = {arm: {"requests": 0, "errors": 0, "breaches": 0,
                            "latency_sum": 0.0, "latency_max": 0.0}
                      for arm in ("stable", "canary")}

    def route_arm(self) -> str:
        with self._lock:
            i = self._counter
            self._counter += 1
        return "canary" if i % 100 < self._slice else "stable"

    def observe(self, arm: str, latency_s: Optional[float] = None,
                error: bool = False, breach: bool = False):
        s = self._arms[arm]
        with self._lock:
            s["requests"] += 1
            if error:
                s["errors"] += 1
            if breach:
                s["breaches"] += 1
            if latency_s is not None:
                s["latency_sum"] += latency_s
                if latency_s > s["latency_max"]:
                    s["latency_max"] = latency_s

    def stats(self) -> Dict:
        with self._lock:
            arms = {a: dict(s) for a, s in self._arms.items()}
        for s in arms.values():
            n = max(1, s["requests"] - s["errors"])
            s["latency_mean"] = s["latency_sum"] / n
        return {"version": self.version.version, "fraction": self.fraction,
                "started_at": self.started_at, "arms": arms}


class _Entry:
    """Per-model-name mutable registry slot: the current version pointer,
    the executable cache (abstract-signature keyed, survives swaps), an
    optional live canary, and a swap lock serializing rebuilds of this
    one model."""

    __slots__ = ("current", "version_counter", "compiled", "swap_lock",
                 "sig_history", "canary")

    def __init__(self):
        self.current: Optional[ServableVersion] = None
        self.version_counter = 0
        self.compiled: Dict[tuple, object] = {}
        self.sig_history: list = []   # newest-first abstract sigs, max 2
        self.swap_lock = threading.Lock()
        self.canary: Optional[CanaryState] = None


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------
class ModelRegistry:
    """Named, versioned, hot-swappable servable models.

    `metrics` defaults to the active telemetry session's registry (so the
    serving counters land next to training telemetry) or a fresh
    `MetricsRegistry`; `InferenceServer` exposes it at `/metrics`.
    """

    def __init__(self, buckets: Sequence[int] = DEFAULT_BUCKETS,
                 precision: str = "fp32", metrics=None):
        self.default_buckets = tuple(sorted(int(b) for b in buckets))
        if precision not in PRECISIONS:
            raise ServingError(
                f"unknown precision {precision!r}; expected one of "
                f"{PRECISIONS}")
        self.default_precision = precision
        if metrics is None:
            from ..telemetry import runtime
            tel = runtime.active()
            if tel is not None:
                metrics = tel.registry
            else:
                from ..telemetry.registry import MetricsRegistry
                metrics = MetricsRegistry()
        self.metrics = metrics
        self._lock = threading.RLock()
        self._entries: Dict[str, _Entry] = {}
        self._swaps = metrics.counter(
            "dl4j_serving_swaps_total", "model version swaps committed",
            labels=("model",))
        self._version_g = metrics.gauge(
            "dl4j_serving_model_version", "currently served model version",
            labels=("model",))
        self._compiles = metrics.counter(
            "dl4j_serving_compiles_total",
            "XLA inference compiles per (model, bucket) — flat after "
            "startup/swap means the request path never cold-compiles",
            labels=("model", "bucket"))
        self._canary_req = metrics.counter(
            "dl4j_continual_canary_requests_total",
            "requests observed per arm while a canary is active",
            labels=("model", "arm"))

    # -- registration / swap --------------------------------------------
    def register(self, name: str, source, *, precision: Optional[str] = None,
                 buckets: Optional[Sequence[int]] = None,
                 input_shape: Optional[Sequence[int]] = None
                 ) -> ServableVersion:
        """Load, transform, and AOT-compile `source`, then atomically
        install it as the current version of `name` (creating the model on
        first call — `register` and `swap` are the same operation; two
        names for intent)."""
        with self._lock:
            entry = self._entries.setdefault(name, _Entry())
        with entry.swap_lock:
            if entry.canary is not None:
                raise ServingError(
                    f"{name}: a canary (candidate v"
                    f"{entry.canary.version.version}) is active — promote "
                    "or roll it back before swapping a new version in")
            return self._register_locked(entry, name, source,
                                         precision=precision,
                                         buckets=buckets,
                                         input_shape=input_shape)

    swap = register

    def _register_locked(self, entry: _Entry, name: str, source,
                         **kw) -> ServableVersion:
        version = self._build_version(entry, name, source, **kw)
        # the atomic flip: everything above ran off the request path
        with self._lock:
            entry.version_counter += 1
            version.version = entry.version_counter
            entry.current = version
        self._swaps.inc(model=name)
        self._version_g.set(version.version, model=name)
        return version

    def ensure(self, name: str, source, **kw) -> ServableVersion:
        """register() only if `name` isn't already served (the legacy
        /output route: first request loads+compiles, the rest hit cache).
        Concurrent ensure() calls on a new name serialize on the entry's
        swap lock — exactly one builds, the rest return its version."""
        v = self._current(name)
        if v is not None:
            return v
        with self._lock:
            entry = self._entries.setdefault(name, _Entry())
        with entry.swap_lock:
            if entry.current is not None:
                return entry.current
            return self._register_locked(entry, name, source, **kw)

    def unregister(self, name: str):
        with self._lock:
            self._entries.pop(name, None)

    def aot_executables(self):
        """Snapshot of every live AOT-compiled executable as
        (model name, batch bucket, compiled) tuples — the graftlint IR
        tier (analysis/ir.py) audits exactly these: what serves is what
        is checked (collective schedule, buffer aliasing), not a
        re-lowered approximation."""
        with self._lock:
            entries = list(self._entries.items())
        out = []
        for name, entry in entries:
            with entry.swap_lock:
                version = entry.current
                if version is None:
                    continue
                seen = set()
                for bucket in version.buckets:
                    out.append((name, bucket, version.runners[bucket]))
                    seen.add(id(version.runners[bucket]))
                # a live canary serves traffic too — audit its
                # executables as well (a same-architecture candidate
                # shares the stable executables, so dedupe by identity)
                if entry.canary is not None:
                    cand = entry.canary.version
                    for bucket in cand.buckets:
                        r = cand.runners[bucket]
                        if id(r) not in seen:
                            out.append((name, bucket, r))
        return out

    # -- lookup ---------------------------------------------------------
    def _current(self, name: str) -> Optional[ServableVersion]:
        with self._lock:
            entry = self._entries.get(name)
            return entry.current if entry is not None else None

    def get(self, name: str) -> ServableVersion:
        v = self._current(name)
        if v is None:
            raise UnknownModelError(name)
        return v

    def names(self) -> List[str]:
        with self._lock:
            return sorted(n for n, e in self._entries.items()
                          if e.current is not None)

    def models(self) -> List[Dict]:
        return [self.get(n).info() for n in self.names()]

    def __contains__(self, name: str) -> bool:
        return self._current(name) is not None

    # -- inference (direct, unbatched path) -----------------------------
    def predict(self, name: str, features, arm: str = "stable"
                ) -> Tuple[np.ndarray, int]:
        """Direct single-request forward: chunk by the largest bucket, pad
        each chunk up to its bucket with zero rows (the PadToBatch shape
        discipline), run the compiled executable, strip padding. Returns
        `(outputs, version)`. The whole request runs on ONE version —
        the canary candidate's when `arm="canary"` and a canary is active
        (stable otherwise)."""
        v = self.get(name) if arm == "stable" else self.arm_version(name, arm)
        x = _validate_features(v, features)
        top = v.buckets[-1]
        outs = []
        for lo in range(0, x.shape[0], top):
            chunk = x[lo:lo + top]
            bucket = v.bucket_for(chunk.shape[0])
            out = v.run_padded(pad_rows(chunk, bucket - chunk.shape[0]),
                               bucket)
            outs.append(out[:chunk.shape[0]])
        return (outs[0] if len(outs) == 1 else np.concatenate(outs)), \
            v.version

    # -- canary routing (continual train-to-serve plane) ----------------
    def start_canary(self, name: str, source, *, fraction: float = 0.1,
                     precision: Optional[str] = None,
                     buckets: Optional[Sequence[int]] = None,
                     input_shape: Optional[Sequence[int]] = None
                     ) -> ServableVersion:
        """Build and AOT-compile a CANDIDATE version of `name` and expose
        it to a deterministic `fraction` slice of traffic WITHOUT touching
        the current (stable) version. The candidate gets the next
        monotonic version number immediately — version numbers are never
        reused, even if this canary later rolls back. A same-architecture
        candidate reuses the stable version's executables through the
        shared cache: zero new XLA compiles. Raises `AotCompileError`
        (live version + cache untouched) if the candidate fails to
        compile, and `ServingError` if a canary is already active."""
        if not 0.0 < float(fraction) < 1.0:
            raise ServingError(
                f"canary fraction must be in (0, 1), got {fraction}")
        with self._lock:
            entry = self._entries.get(name)
        if entry is None:
            raise UnknownModelError(name)
        with entry.swap_lock:
            if entry.current is None:
                raise UnknownModelError(name)
            if entry.canary is not None:
                raise ServingError(
                    f"{name}: a canary (candidate v"
                    f"{entry.canary.version.version}) is already active")
            version = self._build_version(entry, name, source,
                                          precision=precision,
                                          buckets=buckets,
                                          input_shape=input_shape)
            with self._lock:
                entry.version_counter += 1
                version.version = entry.version_counter
                entry.canary = CanaryState(version, float(fraction))
        return version

    def canary_state(self, name: str) -> Optional[CanaryState]:
        with self._lock:
            entry = self._entries.get(name)
            return entry.canary if entry is not None else None

    def route_arm(self, name: str) -> str:
        """Which arm serves the next request: "canary" for the
        deterministic fraction slice while a canary is active, else
        "stable"."""
        cs = self.canary_state(name)
        return cs.route_arm() if cs is not None else "stable"

    def arm_version(self, name: str, arm: str = "stable") -> ServableVersion:
        """The version serving `arm`. Falls back to the stable version
        when no canary is active — a request routed to "canary" just
        before a rollback still gets a servable version, never an
        error."""
        with self._lock:
            entry = self._entries.get(name)
            if entry is None or entry.current is None:
                raise UnknownModelError(name)
            if arm == "canary" and entry.canary is not None:
                return entry.canary.version
            return entry.current

    def observe_canary(self, name: str, arm: str,
                       latency_s: Optional[float] = None,
                       error: bool = False, breach: bool = False):
        """Feed one request observation into the live canary's per-arm
        stats (and the `dl4j_continual_canary_requests_total` counter).
        No-op when no canary is active."""
        cs = self.canary_state(name)
        if cs is None:
            return
        cs.observe(arm, latency_s=latency_s, error=error, breach=breach)
        self._canary_req.inc(model=name, arm=arm)

    def promote_canary(self, name: str) -> ServableVersion:
        """Atomically make the canary candidate the stable version (the
        same single-pointer flip as `swap()`; in-flight requests finish on
        whichever version they already hold)."""
        with self._lock:
            entry = self._entries.get(name)
        if entry is None:
            raise UnknownModelError(name)
        with entry.swap_lock:
            cs = entry.canary
            if cs is None:
                raise ServingError(f"{name}: no canary is active")
            with self._lock:
                entry.current = cs.version
                entry.canary = None
        self._swaps.inc(model=name)
        self._version_g.set(cs.version.version, model=name)
        return cs.version

    def rollback_canary(self, name: str) -> ServableVersion:
        """Drop the canary candidate; the stable version (bit-identical,
        never touched by the canary) keeps serving all traffic. Returns
        the stable version."""
        with self._lock:
            entry = self._entries.get(name)
        if entry is None:
            raise UnknownModelError(name)
        with entry.swap_lock:
            if entry.canary is None:
                raise ServingError(f"{name}: no canary is active")
            with self._lock:
                entry.canary = None
            return entry.current

    # -- version building -----------------------------------------------
    def _build_version(self, entry: _Entry, name: str, source, *,
                       precision=None, buckets=None,
                       input_shape=None) -> ServableVersion:
        precision = precision or self.default_precision
        if precision not in PRECISIONS:
            raise ServingError(
                f"unknown precision {precision!r}; expected one of "
                f"{PRECISIONS}")
        buckets = tuple(sorted(int(b) for b in (buckets or
                                                self.default_buckets)))
        if not buckets or buckets[0] < 1:
            raise ServingError(f"invalid batch buckets {buckets}")
        model, src = load_source(source)
        if getattr(model, "params", None) is None:
            model.init()
        shape = _example_shape(model, input_shape)
        snapshot = _snapshot_params(model, precision)
        state = jax.tree_util.tree_map(jnp.asarray, model.state)
        fn = jax.jit(_make_forward(model, snapshot))
        sig = _abstract_sig(snapshot, state, precision)
        runners = {}
        # stage fresh compiles locally and merge only after EVERY bucket
        # compiled: a candidate whose compile fails mid-build must leave
        # the shared executable cache (and the live version still serving
        # from it) bit-for-bit untouched
        staged: Dict[tuple, Tuple[object, float]] = {}
        for b in buckets:
            # namespaced key: the stateless plane and the decode plane
            # (serving/decode, keys ("decode", sig, phase, ...)) share one
            # executable cache per model entry, so the plane tag keeps a
            # generate-capable servable and its stateless twin from ever
            # colliding on (or evicting) each other's executables
            key = ("fwd", sig, b)
            compiled = entry.compiled.get(key)
            if compiled is None:
                x_spec = jax.ShapeDtypeStruct((b,) + shape, jnp.float32)
                with span("dl4j/registry/compile", model=name, plane="fwd",
                          label=str(b)) as built:
                    try:
                        compiled = fn.lower(snapshot.data, state,
                                            x_spec).compile()
                    except ServingError:
                        raise
                    except Exception as e:
                        raise AotCompileError(name, b, e) from e
                staged[key] = (compiled, built.seconds)
            runners[b] = compiled
        for key, (compiled, wall) in staged.items():
            entry.compiled[key] = compiled
            self._record_compile(name, key[2], wall)
        # bound the executable cache: keep the current and the previous
        # architecture's executables (A/B rollback stays compile-free),
        # drop older — a long-lived server cycling checkpoints must not
        # grow its compiled set without limit. Pruning filters on the SIG
        # element (key[1]) so decode-plane executables for a kept sig
        # survive a stateless swap and vice versa
        if sig in entry.sig_history:
            entry.sig_history.remove(sig)
        entry.sig_history.insert(0, sig)
        if len(entry.sig_history) > 2:
            keep = set(entry.sig_history[:2])
            del entry.sig_history[2:]
            for key in [k for k in entry.compiled if k[1] not in keep]:
                del entry.compiled[key]
        return ServableVersion(name, precision, buckets, shape, snapshot,
                               state, runners, type(model).__name__, src,
                               model=model, sig=sig)

    def compile_cached(self, name: str, key: tuple, build, label: str):
        """AOT-compile through `name`'s shared executable cache: return the
        cached executable under namespaced `key` (("decode", sig, phase,
        bucket) for the generation plane) or run `build()` (a lower+compile
        closure) once under the entry's swap lock and cache it. `label` is
        the compile-accounting bucket tag (e.g. "decode4", "prefill1x32")
        — one `record_aot` per cache miss, so the server-lifetime compile
        invariant ("one XLA compile per signature") is auditable from the
        CompileWatcher report exactly like the stateless buckets. Each
        build is the span `dl4j/registry/compile` (`model`, `plane`
        "decode", `label`) in the span log."""
        with self._lock:
            entry = self._entries.setdefault(name, _Entry())
        with entry.swap_lock:
            compiled = entry.compiled.get(key)
            if compiled is None:
                with span("dl4j/registry/compile", model=name,
                          plane=key[0], label=label) as built:
                    compiled = build()
                self._record_compile(name, label, built.seconds)
                entry.compiled[key] = compiled
        return compiled

    def _record_compile(self, name: str, bucket, wall_s: float):
        self._compiles.inc(model=name, bucket=str(bucket))
        from ..telemetry import runtime
        tel = runtime.active()
        if tel is not None:
            tel.compiles.record_aot(f"serving/{name}:b{bucket}", wall_s)


# ---------------------------------------------------------------------------
# Forward builders
# ---------------------------------------------------------------------------
def _snapshot_params(model, precision: str) -> QuantizedTree:
    """Freeze the model's parameters into the serving representation for
    `precision`. Always a QuantizedTree (fp32/bf16 just have no quantized
    leaves) so every runner shares one flat-data calling convention."""
    params = model.params
    if precision == "int8":
        return quantize_tree(params)
    if precision == "bf16":
        params = cast_tree(params, jnp.bfloat16)
    leaves, treedef = jax.tree_util.tree_flatten(
        jax.tree_util.tree_map(jnp.asarray, params))
    return QuantizedTree(tuple(leaves), (None,) * len(leaves), treedef,
                         compute_dtype=jnp.float32)


def _make_forward(model, snapshot: QuantizedTree):
    """The traced serving forward: rebuild params from the flat snapshot
    (dequantizing int8 leaves), cast the padded batch to the snapshot's
    compute dtype, run the model's pure predict fn, emit float32."""
    predict = model.predict_fn
    graph_inputs = getattr(getattr(model, "conf", None),
                           "network_inputs", None)
    if graph_inputs is not None and len(graph_inputs) != 1:
        raise ServingError(
            "serving supports single-input models; this ComputationGraph "
            f"declares inputs {list(graph_inputs)}")
    param_dtypes = {jnp.asarray(d).dtype for d, s in
                    zip(snapshot.data, snapshot.scales) if s is None}
    x_dtype = (jnp.bfloat16 if jnp.bfloat16 in param_dtypes
               else jnp.float32)

    def forward(data, state, x):
        params = snapshot.rebuild(data)
        x = x.astype(x_dtype)
        if graph_inputs is not None:
            name = graph_inputs[0]
            out = predict(params, state, {name: x}, {name: None})
            out = out[0]
        else:
            out = predict(params, state, x, None)
        return out.astype(jnp.float32)

    return forward


def _abstract_sig(snapshot: QuantizedTree, state, precision: str) -> tuple:
    """Hashable (shapes+dtypes) signature of a version's compiled-input
    avals — two versions with equal signatures share XLA executables.
    Quantization SCALES are runtime arguments, deliberately absent: a
    re-quantized same-architecture checkpoint signs identically and
    reuses the executables."""
    def leaf_sig(a):
        a = jnp.asarray(a)
        return (tuple(a.shape), str(a.dtype))

    data_sig = tuple(
        leaf_sig(d) if s is None else (leaf_sig(d[0]), leaf_sig(d[1]))
        for d, s in zip(snapshot.data, snapshot.scales))
    flat_state, state_def = jax.tree_util.tree_flatten(state)
    return (precision, data_sig,
            tuple(s is not None for s in snapshot.scales),
            tuple(leaf_sig(s) for s in flat_state), str(state_def))


def _validate_features(v: ServableVersion, features) -> np.ndarray:
    try:
        x = np.asarray(features, np.float32)
    except (TypeError, ValueError) as e:
        raise ServingError(f"features are not a numeric array: {e}") from None
    if x.ndim == len(v.example_shape):      # single example convenience
        x = x[None]
    if x.ndim != len(v.example_shape) + 1 \
            or tuple(x.shape[1:]) != v.example_shape:
        raise ServingError(
            f"{v.name}: features shape {tuple(x.shape)} does not match "
            f"[rows]{list(v.example_shape)}")
    if x.shape[0] == 0:
        raise ServingError(f"{v.name}: empty features batch")
    return x
