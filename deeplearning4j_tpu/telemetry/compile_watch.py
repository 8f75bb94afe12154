"""XLA compile watcher: count compilations per jitted entry point, warn on
recompilation storms.

Every distinct argument signature (shapes/dtypes/static args) costs a full
XLA trace+compile of the function — on TPU often seconds. Shape churn
(ragged final batches, per-call scan lengths) silently multiplies that:
throughput collapses with no error anywhere. The watcher detects a compile
by the growth of the jitted function's executable cache (`_cache_size()`)
across a call; `report()` keeps the first-call wall time (trace + compile
+ first run — the latency the user actually experiences). Where those
seconds went is in the span log: `xla/trace`, `xla/lower`, `xla/compile`
and `xla/cache_load` under the step that compiled (`telemetry/tracing.py`).

`watch_compiles(fn, name)` wraps a jitted callable; with no active
telemetry session the wrapper is a single global read + passthrough call.
"""
from __future__ import annotations

import threading
import time
import warnings
import weakref
from typing import Callable, Dict, List, Optional, Tuple

__all__ = ["CompileWatcher", "watch_compiles", "RecompilationStormWarning",
           "roster", "roster_names"]

# Wrap-time roster of every watch_compiles-registered jit entry point:
# name -> weakref to the underlying jitted callable. This is the coverage
# ledger the `unwatched-jit-entry` lint rule drove to 100% — the IR lint
# tier (analysis/ir.py) uses it to enumerate the entry points it
# abstract-evals, and --metrics reports its size as the coverage
# denominator. Weak references: a wrapped step dies with its model, the
# roster must not keep retraced closures (and their captured params)
# alive.
_ROSTER: Dict[str, "weakref.ref"] = {}
_ROSTER_LOCK = threading.Lock()


def roster() -> List[Tuple[str, Callable]]:
    """Live (name, jitted fn) pairs currently registered, sorted by name.
    Entries whose function was garbage-collected are pruned."""
    out = []
    with _ROSTER_LOCK:
        dead = []
        for name, ref in _ROSTER.items():
            fn = ref()
            if fn is None:
                dead.append(name)
            else:
                out.append((name, fn))
        for name in dead:
            del _ROSTER[name]
    return sorted(out, key=lambda p: p[0])


def roster_names() -> List[str]:
    return [name for name, _ in roster()]


class RecompilationStormWarning(RuntimeWarning):
    """More XLA recompilations of one function than shape-stable training
    can explain — look for batch-shape churn."""


def _cache_size(fn) -> int:
    get = getattr(fn, "_cache_size", None)
    if get is None:
        return -1  # not introspectable: caller falls back to signatures
    try:
        return int(get())
    except Exception:
        return -1


def _signature(args, kwargs):
    """Fallback compile detector for callables without `_cache_size`:
    abstract every array leaf to (shape, dtype), keep scalars as-is."""
    import jax

    def leaf(a):
        shape = getattr(a, "shape", None)
        if shape is not None:
            return (tuple(shape), str(getattr(a, "dtype", "")))
        return a if isinstance(a, (int, float, bool, str, bytes,
                                   type(None))) else type(a).__name__

    flat, treedef = jax.tree_util.tree_flatten((args, kwargs))
    return (tuple(leaf(a) for a in flat), str(treedef))


class CompileWatcher:
    def __init__(self, registry=None, storm_threshold: int = 3):
        self._lock = threading.Lock()
        self._counts: Dict[str, int] = {}
        self._time: Dict[str, float] = {}
        self._warned = set()
        self._sigs: Dict[str, set] = {}
        self.storm_threshold = max(1, int(storm_threshold))
        self._compilations = None
        if registry is not None:
            self._compilations = registry.counter(
                "dl4j_xla_compilations_total",
                "XLA compilations per jitted entry point",
                labels=("function",))

    def call(self, name: str, fn: Callable, args, kwargs):
        """Invoke `fn`, detecting whether this call compiled."""
        before = _cache_size(fn)
        if before < 0:
            with self._lock:
                sigs = self._sigs.setdefault(name, set())
                sig = _signature(args, kwargs)
                fresh = sig not in sigs
                sigs.add(sig)
            if not fresh:
                return fn(*args, **kwargs)
            t0 = time.perf_counter()
            out = fn(*args, **kwargs)
            self._record(name, 1, time.perf_counter() - t0)
            return out
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        grew = _cache_size(fn) - before
        if grew > 0:
            self._record(name, grew, time.perf_counter() - t0)
        return out

    def _record(self, name: str, n: int, wall_s: float):
        with self._lock:
            total = self._counts.get(name, 0) + n
            self._counts[name] = total
            self._time[name] = self._time.get(name, 0.0) + wall_s
            storm = (total > self.storm_threshold
                     and name not in self._warned)
            if storm:
                self._warned.add(name)
        if self._compilations is not None:
            self._compilations.inc(n, function=name)
        if storm:
            warnings.warn(
                f"XLA recompilation storm: '{name}' has compiled {total} "
                f"times (> {self.storm_threshold}). Every distinct batch "
                "signature recompiles the whole step — pad batches to a "
                "fixed size (fit(..., pad_ragged=True) / "
                "datasets.pipeline.PadToBatchIterator) or drop the ragged "
                "tail (ArrayDataSetIterator(drop_last=True))",
                RecompilationStormWarning, stacklevel=3)

    def record_aot(self, name: str, wall_s: float, n: int = 1):
        """Record an ahead-of-time lower+compile (serving registration,
        precompiled executables) under `name`. AOT compiles never show up
        as jit-cache growth — the executable is built before any call —
        so the builder reports them explicitly; counts and storm warnings
        then cover jit and AOT entry points uniformly."""
        self._record(name, n, wall_s)

    def count(self, name: str) -> int:
        with self._lock:
            return self._counts.get(name, 0)

    def total(self) -> int:
        with self._lock:
            return sum(self._counts.values())

    def report(self) -> Dict[str, Dict]:
        """{function: {count, wall_s}} — the compile-attribution artifact."""
        with self._lock:
            return {k: {"count": self._counts[k],
                        "wall_s": round(self._time.get(k, 0.0), 4)}
                    for k in sorted(self._counts)}


def watch_compiles(fn: Callable, name: str) -> Callable:
    """Wrap a jitted callable so the ACTIVE telemetry session (if any)
    observes its compilations. Disabled cost: one global read per call.
    Wrapping also registers `name` in the module roster (latest wrap
    wins — a model rebuilding its step re-registers the same name)."""
    from . import runtime

    try:
        ref = weakref.ref(fn)
    except TypeError:       # non-weakrefable callable: skip the roster
        ref = None
    if ref is not None:
        with _ROSTER_LOCK:
            _ROSTER[name] = ref

    def watched(*args, **kwargs):
        tel = runtime.active()
        if tel is None:
            return fn(*args, **kwargs)
        return tel.compiles.call(name, fn, args, kwargs)

    watched.__name__ = getattr(fn, "__name__", name)
    watched.__wrapped__ = fn
    return watched
