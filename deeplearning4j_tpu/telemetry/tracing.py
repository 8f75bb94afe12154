"""The span log: one always-on, process-wide ring of spans and events.

Every span the program records lands here: `telemetry.span(...)` on the
fit and scheduler threads, `TraceContext.emit` for per-request spans with
explicit timestamps, the compile path as jax reports it (`xla/trace`,
`xla/lower`, `xla/compile`, `xla/cache_load`), instants and counter
samples. The ring holds `capacity` events and overwrites the OLDEST, so
a server that has run for an hour still shows its last minutes;
`dropped_events` counts what was overwritten. The default, 262,144, is
what a traced benchmark run of a serving cell needs with room to spare:
a scheduler at 100 loops a second writes some 1,000 events a second,
83,000 from the first request to the readers' turn (chip run, PR 35: a
ring of 65,536 had lost the window's start by then).

A record is (seq, ph, name, t0, t1, tid, thread, id, parent, trace_id,
attrs): `t0`/`t1` in `time.perf_counter_ns()`, `id` the span's own id,
`parent` the id of the span that caused it — the innermost span open on
the same thread (a `threading.local` stack), or one given explicitly, as
a request's spans name their root. `snapshot()` returns the records as
dicts, oldest first; `events()` / `export_chrome_trace()` render them as
Chrome trace-event JSON (Perfetto / chrome://tracing).

`span()` also enters `jax.profiler.TraceAnnotation(name)`: inert without
a profiler session, and under one the span is in the xplane on the
device's clock, on its thread's line. Spans with explicit timestamps go
to the log only.

Write-path concurrency is `FlightRecorder.record`'s: `next(count)` is a
GIL-atomic slot reservation and the slot write is one list-item
assignment of a complete tuple — no lock, no torn event. Written-so-far
is derived from the largest sequence number present, so the drop count
stays exact without synchronization.
"""
from __future__ import annotations

import itertools
import json
import os
import threading
import time
from typing import Dict, List, Optional

import jax
from jax.profiler import TraceAnnotation

__all__ = ["Tracer", "tracer", "install", "named_step"]

# counter tracks get synthetic tids from this base so each counter name
# renders as its own named row instead of interleaving on a thread's row.
# Real thread idents are pthread pointers (Linux) or small handles
# (Windows); a dedicated 2^31-aligned range collides with neither in
# practice.
_COUNTER_TID_BASE = 0x80000000

_CURRENT = object()      # `parent` default: the span open on this thread

_FIELDS = ("seq", "ph", "name", "t0", "t1", "tid", "thread", "id", "parent",
           "trace_id", "attrs")


class _Span:
    """One open span. After the block: `t0`, `t1` (perf_counter_ns) and
    `seconds`; `set()` adds attributes known only at the end. A span that
    was `hold()`-ed is timed by its block and written by `write()`, later,
    as a child of the span open THEN: work that was started in one place
    and is accounted for where it is finished (a decode tick's dispatch,
    under the span of the loop that retires the tick)."""

    __slots__ = ("_tracer", "name", "attrs", "id", "parent", "t0", "t1",
                 "_stack", "_ann", "_held")

    def __init__(self, tracer, name, attrs):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs
        self.id = self.parent = self._stack = None
        self._held = False

    def set(self, **attrs):
        self.attrs.update(attrs)

    def hold(self):
        self._held = True
        return self

    def write(self):
        """Write a held span, once its block has closed, under the span
        open on the calling thread now."""
        if self.id is not None:
            tr = self._tracer
            tr._write("X", self.name, self.t0, self.t1, self.id,
                      tr.current_span(), None, self.attrs)

    @property
    def seconds(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def __enter__(self):
        tr = self._tracer
        if tr.enabled:
            stack = self._stack = tr._thread().stack
            if stack:
                self.parent = stack[-1]
            self.id = next(tr._span_ids)
            stack.append(self.id)
            self._ann = TraceAnnotation(self.name)
            self._ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self.t1 = time.perf_counter_ns()
        if self._stack is not None:
            self._ann.__exit__(*exc)
            self._stack.pop()
            if not self._held:
                self._tracer._write("X", self.name, self.t0, self.t1,
                                    self.id, self.parent, None, self.attrs)
        return False


class Tracer:
    def __init__(self, capacity: int = 262_144, enabled: bool = True,
                 process_name: str = "deeplearning4j_tpu"):
        self.capacity = max(1, int(capacity))
        self.enabled = bool(enabled)
        self._buf: List[Optional[tuple]] = [None] * self.capacity
        self._seq = itertools.count()
        self._span_ids = itertools.count(1)
        self._tls = threading.local()
        self._origin = time.perf_counter_ns()
        self._pid = os.getpid()
        self._process_name = process_name

    # -- write path (no locks) -------------------------------------------
    def _thread(self):
        """This thread's record: its open-span stack, ident and name."""
        t = self._tls
        if getattr(t, "stack", None) is None:
            t.stack = []
            t.tid = threading.get_ident()
            t.name = threading.current_thread().name
        return t

    def _write(self, ph, name, t0, t1, span_id, parent, trace_id, attrs):
        t = self._thread()
        i = next(self._seq)                  # GIL-atomic slot reservation
        self._buf[i % self.capacity] = (i, ph, name, t0, t1, t.tid, t.name,
                                        span_id, parent, trace_id, attrs)

    def current_span(self):
        """Id of the innermost span open on the calling thread, or None."""
        stack = self._thread().stack
        return stack[-1] if stack else None

    def span(self, name: str, **attrs) -> _Span:
        """Context manager recording a span around the block, a child of
        the span open on this thread."""
        return _Span(self, name, attrs)

    def emit(self, name: str, t0_ns: int, t1_ns: int, *, span_id=None,
             parent=_CURRENT, trace_id=None, **attrs):
        """A complete span with explicit perf_counter_ns timestamps (taken
        on another thread, or before the span's name was known). `parent`
        defaults to the span open on the calling thread; None makes a
        root. Returns its id."""
        if not self.enabled:
            return span_id
        if span_id is None:
            span_id = next(self._span_ids)
        if parent is _CURRENT:
            parent = self.current_span()
        self._write("X", name, int(t0_ns), int(t1_ns), span_id, parent,
                    trace_id, attrs)
        return span_id

    def instant(self, name: str, **attrs):
        """A point event, tied to the span open on the calling thread."""
        if not self.enabled:
            return
        now = time.perf_counter_ns()
        self._write("i", name, now, now, None, self.current_span(), None,
                    attrs)

    def counter(self, name: str, **series):
        """A counter sample (rendered as a stacked area chart on a row of
        its own, named `counter:<name>`)."""
        if not self.enabled:
            return
        now = time.perf_counter_ns()
        self._write("C", name, now, now, None, None, None, series)

    # -- read side ---------------------------------------------------------
    def _live(self) -> List[tuple]:
        # list() copies the slot references in one pass; each slot is a
        # complete tuple or None, never partial
        live = [e for e in list(self._buf) if e is not None]
        live.sort(key=lambda e: e[0])
        return live

    def snapshot(self) -> List[Dict]:
        """The records in the ring, oldest first, as dicts of `seq`, `ph`
        ("X" span, "i" instant, "C" counter), `name`, `t0`, `t1`
        (perf_counter_ns), `tid`, `thread`, `id`, `parent`, `trace_id`,
        `attrs`."""
        return [dict(zip(_FIELDS, e)) for e in self._live()]

    def total_written(self) -> int:
        live = [e[0] for e in list(self._buf) if e is not None]
        return max(live) + 1 if live else 0

    @property
    def dropped_events(self) -> int:
        """Events overwritten by newer ones."""
        return max(0, self.total_written() - self.capacity)

    def __len__(self):
        return sum(e is not None for e in list(self._buf))

    def _us(self, t_ns: int) -> float:
        return round((t_ns - self._origin) / 1e3, 3)

    def events(self) -> List[Dict]:
        """Chrome trace events, oldest first: one metadata event naming the
        process, then the ring's records. A span's `args` hold its
        attributes and `span_id`, `parent_id`, and `trace_id` where it
        belongs to a request."""
        out = [{"ph": "M", "name": "process_name", "pid": self._pid,
                "tid": 0, "args": {"name": self._process_name}}]
        counter_tids: Dict[str, int] = {}
        for (_, ph, name, t0, t1, tid, _thread, sid, parent, trace_id,
             attrs) in self._live():
            ev = {"ph": ph, "name": name, "cat": "runtime",
                  "ts": self._us(t0), "pid": self._pid, "tid": tid}
            args = dict(attrs)
            if ph == "C":
                if name not in counter_tids:
                    counter_tids[name] = _COUNTER_TID_BASE + len(counter_tids)
                    out.append({"ph": "M", "name": "thread_name",
                                "pid": self._pid, "tid": counter_tids[name],
                                "args": {"name": f"counter:{name}"}})
                ev["tid"] = counter_tids[name]
            elif ph == "X":
                ev["dur"] = round((t1 - t0) / 1e3, 3)
                args["span_id"], args["parent_id"] = sid, parent
            else:
                ev["s"] = "t"
                if parent is not None:
                    args["parent_id"] = parent
            if trace_id is not None:
                args["trace_id"] = trace_id
            if args:
                ev["args"] = args
            out.append(ev)
        return out

    def chrome_trace(self) -> Dict:
        return {"traceEvents": self.events(), "displayTimeUnit": "ms",
                "otherData": {"dropped_events": self.dropped_events}}

    def export_chrome_trace(self, path) -> str:
        """Write the trace JSON; open the file in Perfetto
        (https://ui.perfetto.dev) or chrome://tracing."""
        with open(path, "w", encoding="utf-8", newline="\n") as f:
            json.dump(self.chrome_trace(), f, default=str)
        return str(path)


_tracer = Tracer()


def tracer() -> Tracer:
    """The process-wide span log every instrumented path writes to."""
    return _tracer


def install(new: Tracer) -> Tracer:
    """Swap the process-wide log (tests isolate through this); returns
    the previous one. Module-global rebinding is GIL-atomic."""
    global _tracer
    prev = _tracer
    _tracer = new
    return prev


def named_step(name: str, fn):
    """`fn` under `jax.named_scope("dl4j/<name>")` and called
    `dl4j_<name>`: what a device trace shows of a jitted step is its
    module, `jit_dl4j_<name>`, and its operations' scope."""
    def step(*args, **kwargs):
        with jax.named_scope(f"dl4j/{name}"):
            return fn(*args, **kwargs)

    step.__name__ = step.__qualname__ = f"dl4j_{name}"
    return step


# -- the compile path, as jax reports it -------------------------------------
# jax records each of these when the step it times ENDS, on the thread that
# did the work, inside the call that needed the executable: so the span open
# there is the program step that asked, and end = now, start = now - the
# duration. The persistent cache's events fire inside a backend compile, on
# the same thread, before the compile's own duration: a hit (and its load),
# or a request that used the cache and found nothing (a miss); "off" where
# no cache directory is set. A served window meets none of this: its
# executables are built before it opens (tests/test_decode.py holds it).
_DURATION_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "xla/trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "xla/lower",
    "/jax/core/compile/backend_compile_duration": "xla/compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "xla/cache_load",
}
_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "miss",
    "/jax/compilation_cache/cache_misses": "miss",
    "/jax/compilation_cache/cache_hits": "hit",
}


def _cache_state(t):
    """The thread's record of the backend compile in progress: when its
    first cache event came, what the cache said, and the id its span will
    have (reserved by a load written before it)."""
    state = getattr(t, "compiling", None)
    if state is None:
        state = t.compiling = {"t": time.perf_counter_ns(), "cache": "off",
                               "id": None}
    return state


def _on_event(event, **_):
    cache = _CACHE_EVENTS.get(event)
    if cache is not None and _tracer.enabled:
        state = _cache_state(_tracer._thread())
        # jax asks the cache even where no directory is set: nothing to find
        if state["cache"] != "hit" and jax.config.jax_compilation_cache_dir:
            state["cache"] = cache


def _on_duration(event, duration, **kwargs):
    name = _DURATION_SPANS.get(event)
    tr = _tracer
    if name is None or not tr.enabled:
        return
    t1 = time.perf_counter_ns()
    t0 = t1 - int(duration * 1e9)
    if name == "xla/cache_load":
        state = _cache_state(tr._thread())
        state["id"] = state["id"] or next(tr._span_ids)
        tr.emit(name, t0, t1, parent=state["id"], seconds=float(duration))
        return
    attrs = {"fun": kwargs.get("fun_name"), "seconds": float(duration)}
    span_id = None
    if name == "xla/compile":
        t = tr._thread()
        state, t.compiling = getattr(t, "compiling", None), None
        # events older than this compile belong to one that raised
        fresh = state is not None and state["t"] >= t0
        attrs["cache"] = state["cache"] if fresh else "off"
        span_id = state["id"] if fresh else None
    tr.emit(name, t0, t1, span_id=span_id, **attrs)


jax.monitoring.register_event_listener(_on_event)
jax.monitoring.register_event_duration_secs_listener(_on_duration)
