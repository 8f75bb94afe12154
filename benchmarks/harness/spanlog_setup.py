"""Set-up, from the program's span log: what a run did before its window.

The set-up is every span of the log whose `t1` lies before the window's
first span: the first `dl4j/sched/loop` of `spanlog.serve_window` in a
serving cell, the first of `spanlog.fit_steps` in a training cell. In it the
program writes `dl4j/nn/init` (a model's parameters made, or taken where
`given` is 1), `dl4j/registry/compile` (an executable the serving registry
lowered and compiled: `plane` "fwd" for the stateless forward, "decode" for a
prefill or a tick, `label` the bucket), and the compile path as jax reports
it, each span under the program step that asked for it: `xla/trace`,
`xla/lower`, `xla/compile` (`cache` hit, miss or off) and `xla/cache_load`
inside a hit. An inner jit traces inside an outer one, so the `xla/*` sums
are unions of intervals on each thread.

Where the ring no longer holds record 0 (it has overwritten the start of the
set-up), where there is no window, and where the program writes none of the
spans a number is made of (a commit before PR 38), a function returns None.
"""
from __future__ import annotations

from harness import spanlog

INIT, REGISTRY_COMPILE = "dl4j/nn/init", "dl4j/registry/compile"
XLA_TRACE, XLA_LOWER, XLA_COMPILE, XLA_LOAD = (
    "xla/trace", "xla/lower", "xla/compile", "xla/cache_load")


def setup_spans(log, facts):
    """The spans that ended before the window's first span, or None."""
    if not log or log[0]["seq"] != 0:
        return None
    w = spanlog.serve_window(log, facts)
    if w:
        first = min(s["t0"] for s in w.loops)
    else:
        steps = spanlog.fit_steps(log, facts)
        if not steps:
            return None
        first = steps[0]["t0"]
    return [r for r in log if r["ph"] == "X" and r["t1"] < first]


def _union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _overlap(a, b) -> int:
    """Length of the intersection of two unions of intervals."""
    total = i = j = 0
    while i < len(a) and j < len(b):
        total += max(0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def span_seconds(log, facts, name, **attrs):
    """Summed duration of the set-up's spans called `name` whose attributes
    hold `attrs`; None where the set-up holds no span of that name."""
    spans = setup_spans(log, facts)
    if spans is None:
        return None
    named = [s for s in spans if s["name"] == name]
    if not named:
        return None
    return sum(s["t1"] - s["t0"] for s in named
               if all(s["attrs"].get(k) == v for k, v in attrs.items())) / 1e9


def union_seconds(log, facts, names, less=()):
    """On each thread the union of the set-up's spans called one of `names`,
    less the part of it that spans called one of `less` cover; summed over
    the threads. None where the set-up holds no `xla/compile` span (a
    program that writes no compile path)."""
    spans = setup_spans(log, facts)
    if spans is None or not any(s["name"] == XLA_COMPILE for s in spans):
        return None
    total = 0
    for tid in {s.get("tid") for s in spans}:
        mine = [s for s in spans if s.get("tid") == tid]
        have = _union((s["t0"], s["t1"]) for s in mine if s["name"] in names)
        cut = _union((s["t0"], s["t1"]) for s in mine if s["name"] in less)
        total += sum(e - s for s, e in have) - _overlap(have, cut)
    return total / 1e9


# -- the metrics, one function a reader -------------------------------------

def init_s(log, facts):
    return span_seconds(log, facts, INIT)


def aot_s(log, facts, plane):
    return span_seconds(log, facts, REGISTRY_COMPILE, plane=plane)


def xla_trace_s(log, facts):
    return union_seconds(log, facts, {XLA_TRACE, XLA_LOWER})


def xla_compile_s(log, facts):
    """Compiling proper, and hashing the cache's key: the backend compiles
    less the cache loads inside them."""
    return union_seconds(log, facts, {XLA_COMPILE}, less={XLA_LOAD})


def xla_load_s(log, facts):
    return union_seconds(log, facts, {XLA_LOAD})
