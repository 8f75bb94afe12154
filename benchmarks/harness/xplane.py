"""Reduction from a profiler trace to numbers.

A `Trace` is plain data: for each device the operations that ran on it, and
the host spans the drivers wrote (jax.profiler.TraceAnnotation), all on one
clock in nanoseconds. `read()` fills it from an `.xplane.pb`; the tests fill
it by hand. Every device metric of the benchmark is computed from it here.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "bench/window"        # the drivers wrap the traced window in it
SPAN_PREFIX = "bench/"


@dataclass
class Trace:
    devices: dict = field(default_factory=dict)   # plane -> [(name, start, dur)]
    host: list = field(default_factory=list)      # [(name, start, dur)]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    trace = Trace()
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name.startswith(OPS_LINE):
                    trace.devices.setdefault(plane.name, []).extend(
                        (op_name(e.name), int(e.start_ns), int(e.duration_ns))
                        for e in line.events if e.duration_ns > 0)
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                trace.host.extend(
                    (e.name, int(e.start_ns), int(e.duration_ns))
                    for e in line.events if e.name.startswith(SPAN_PREFIX))
    return trace


def op_name(event_name: str) -> str:
    """The TPU's operation events carry the whole HLO instruction,
    `%fusion.3 = (f32[...]) fusion(...)`: keep the instruction's name. A
    Pallas kernel's name is a part of it (`%transpose_jvp_flash_bwd_dq__.7`)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _kernel_of(name: str, order) -> str:
    for n in order:
        if n in name:
            return n
    return ""


def window(trace: Trace) -> tuple:
    """(start, end) of the traced window: the drivers' window span, or where
    there is none the first start and the last end of the device's work."""
    spans = [(s, s + d) for n, s, d in trace.host if n == WINDOW_SPAN]
    if spans:
        return min(s for s, _ in spans), max(e for _, e in spans)
    ev = [e for evs in trace.devices.values() for e in evs]
    if not ev:
        raise ValueError("the trace holds no device operation")
    return min(s for _, s, _ in ev), max(s + d for _, s, d in ev)


def _busy_intervals(events, lo, hi) -> list:
    """Union of the operations' intervals, clipped to [lo, hi]."""
    out = []
    for s, e in sorted((max(s, lo), min(s + d, hi)) for _, s, d in events):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_seconds(trace: Trace) -> tuple:
    """(busy_s, window_s): seconds in which an operation ran on the device,
    averaged over the devices, and the length of the traced window."""
    lo, hi = window(trace)
    if not trace.devices:
        raise ValueError("the trace holds no device plane")
    busy = [sum(e - s for s, e in _busy_intervals(evs, lo, hi))
            for evs in trace.devices.values()]
    return sum(busy) / len(busy) / 1e9, (hi - lo) / 1e9


def _window_ops(trace: Trace):
    """(name, seconds) of every operation that lies inside the window, each
    device's seconds divided by the number of devices."""
    lo, hi = window(trace)
    k = len(trace.devices)
    for evs in trace.devices.values():
        for name, s, d in evs:
            if s >= lo and s + d <= hi:
                yield name, d / 1e9 / k


def kernel_stats(trace: Trace, names) -> dict:
    """{kernel: (device seconds, calls)} of the operations whose name holds
    one of `names`, over the window, averaged over the devices: {} where none
    ran. A longer name wins (`flash_bwd_dq` before `flash_bwd`)."""
    order = sorted(names, key=len, reverse=True)
    k = len(trace.devices)
    out = {}
    for name, sec in _window_ops(trace):
        n = _kernel_of(name, order)
        if n:
            total, calls = out.get(n, (0.0, 0.0))
            out[n] = (total + sec, calls + 1.0 / k)
    return out


def _family(name: str) -> str:
    """`flash_bwd_dkv__.22` and `.23` are one kernel in twelve layers: sum
    them under the name without its number. A plain `fusion.N` says nothing
    without its number and keeps it."""
    stem, dot, num = name.rpartition(".")
    return stem if dot and num.isdigit() and stem != "fusion" else name


def top_ops(trace: Trace, n: int = 10) -> list:
    """[[name, seconds]]: the device operations that took most time, the
    numbered instances of one operation summed."""
    total = {}
    for name, sec in _window_ops(trace):
        name = _family(name)
        total[name] = total.get(name, 0.0) + sec
    return [[name, v] for name, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Trace, n: int = 10) -> list:
    """[[what the host was doing, seconds]]: the first device's idle time,
    each gap given to the driver span that covers most of it, summed by span
    name; "unattributed" where no span of the drivers covers the gap."""
    lo, hi = window(trace)
    evs = next(iter(trace.devices.values()))
    busy = _busy_intervals(evs, lo, hi)
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    spans = sorted((s, s + d, nm) for nm, s, d in trace.host
                   if nm != WINDOW_SPAN)
    total, live, nxt = {}, [], 0
    for g0, g1 in gaps:                     # in time order, as the spans are
        while nxt < len(spans) and spans[nxt][0] < g1:
            live.append(spans[nxt])
            nxt += 1
        live = [sp for sp in live if sp[1] > g0]
        # the span that covers most of the gap; of two that cover as much,
        # the shorter, which is the inner one of a nest
        best, name = (0, 0), "unattributed"
        for s, e, nm in live:
            o = min(e, g1) - max(s, g0)
            if o > 0 and (o, s - e) > best:
                best, name = (o, s - e), nm
        total[name] = total.get(name, 0.0) + (g1 - g0) / 1e9
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]
