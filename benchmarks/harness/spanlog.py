"""Reduction from the program's span log to numbers.

The program keeps its spans in memory, always on
(`deeplearning4j_tpu.telemetry.tracer()`); the per-layer readers run in
its process and take them from there. The window is found without a
clock, by the ordinals the drivers already record. Serving: the
`dl4j/sched/tick` spans whose `tick` lies in (`counters_before.decode_count`,
`counters_after.decode_count`], their `dl4j/sched/loop` parents and all
that those caused; the `dl4j/sched/admit` spans by their `prefill` against
`prefill_count`. Training: of the `dl4j/fit/step` spans in the log, the
last `trace_steps` left off and the `steps` before them (nothing calls
`fit` after the trace).

A record is a dict of `name`, `t0`, `t1` (nanoseconds on one host clock),
`id`, `parent`, `attrs`, and `ph` ("X" for a span). The tests fill a log
by hand. Where the program keeps no log (a commit before it had one), or
the ring no longer holds the window's start, every function returns None
and the metric is left out.
"""
from __future__ import annotations

import statistics
from dataclasses import dataclass

TICK, LOOP, ADMIT, SAMPLE = ("dl4j/sched/tick", "dl4j/sched/loop",
                             "dl4j/sched/admit", "dl4j/sched/sample")
FIT_STEP, FIT_LISTENERS = "dl4j/fit/step", "dl4j/fit/listeners"
ENGINE_TICK = "dl4j/engine/tick."


def records():
    """The program's span log, oldest first, or None where it keeps none."""
    from deeplearning4j_tpu import telemetry
    tracer = getattr(telemetry, "tracer", None)
    return None if tracer is None else tracer().snapshot()


def duration_ms(span) -> float:
    return (span["t1"] - span["t0"]) / 1e6


def less_ms(span, others) -> float:
    """The span's duration less the part of its interval that `others`
    cover. With its children for `others` this is its self time."""
    lo, hi = span["t0"], span["t1"]
    covered, end = 0, lo
    for s, e in sorted((max(o["t0"], lo), min(o["t1"], hi)) for o in others):
        if e > max(s, end):
            covered += e - max(s, end)
            end = e
    return (hi - lo - covered) / 1e6


def p95(values):
    values = list(values)
    if not values:
        return None
    if len(values) == 1:
        return values[0]
    return statistics.quantiles(values, n=20, method="inclusive")[18]


def mean(values):
    values = list(values)
    return sum(values) / len(values) if values else None


class Index:
    """The log's spans, and for each span id the spans it caused."""

    def __init__(self, log):
        self.spans = [r for r in log if r["ph"] == "X"]
        self.children = {}
        for s in self.spans:
            self.children.setdefault(s["parent"], []).append(s)

    def kids(self, span, name=None) -> list:
        return [c for c in self.children.get(span["id"], ())
                if name is None or c["name"] == name]

    def descendants(self, span) -> list:
        out, todo = [], [span]
        while todo:
            for c in self.children.get(todo.pop()["id"], ()):
                out.append(c)
                todo.append(c)
        return out


@dataclass
class ServeWindow:
    idx: Index
    ticks: list         # the window's tick spans, by ordinal
    loops: list         # their loop parents, each once
    admits: list        # the window's admit spans, by ordinal


def serve_window(log, facts):
    """The serving window of `log`, or None where the log or the counters
    are missing, the window holds no tick, or the ring has lost a part of
    it."""
    before, after = facts.get("counters_before"), facts.get("counters_after")
    if log is None or not before or not after:
        return None
    idx = Index(log)

    def by_ordinal(name, attr, lo, hi):
        found = {s["attrs"][attr]: s for s in idx.spans
                 if s["name"] == name and lo < s["attrs"].get(attr, lo) <= hi}
        whole = sorted(found) == list(range(lo + 1, hi + 1))
        return [found[k] for k in sorted(found)] if whole else None

    ticks = by_ordinal(TICK, "tick", int(before["decode_count"]),
                       int(after["decode_count"]))
    admits = by_ordinal(ADMIT, "prefill", int(before["prefill_count"]),
                        int(after["prefill_count"]))
    if not ticks or admits is None:
        return None
    by_id = {s["id"]: s for s in idx.spans if s["name"] == LOOP}
    loops = {t["parent"]: by_id.get(t["parent"]) for t in ticks}
    if None in loops.values():
        return None
    return ServeWindow(idx, ticks, list(loops.values()), admits)


def fit_steps(log, facts):
    """The training window's `dl4j/fit/step` spans, or None."""
    steps = int(facts.get("steps") or 0)
    if log is None or steps <= 0:
        return None
    found = [s for s in log if s["ph"] == "X" and s["name"] == FIT_STEP]
    traced = int(facts.get("trace_steps") or 0)
    if len(found) < steps + traced:
        return None
    return found[len(found) - traced - steps:len(found) - traced]


# -- the metrics, one function a reader -------------------------------------

def sched_loop_ms(log, facts):
    w = serve_window(log, facts)
    return w and mean(duration_ms(s) for s in w.loops)


def sched_host_ms(log, facts):
    """Mean per loop of its duration less its `*.fetch` descendants: the
    scheduler thread not waiting for the device."""
    w = serve_window(log, facts)
    return w and mean(
        less_ms(s, [d for d in w.idx.descendants(s)
                    if d["name"].endswith(".fetch")]) for s in w.loops)


def tick_child_ms(log, facts, name):
    """Mean per tick of its children called `name`."""
    w = serve_window(log, facts)
    return w and mean(sum(duration_ms(c) for c in w.idx.kids(t, name))
                      for t in w.ticks)


def decode_rows_per_tick(log, facts):
    w = serve_window(log, facts)
    return w and mean(t["attrs"]["rows"] for t in w.ticks)


def admit_p95_ms(log, facts, attr):
    """95th percentile, in ms, of the window's admissions' `attr` (seconds);
    a re-admission after an eviction carries no `first_token_s`."""
    w = serve_window(log, facts)
    return w and p95(1e3 * a["attrs"][attr] for a in w.admits
                     if attr in a["attrs"])


def fit_host_ms(log, facts):
    """Mean per step of `dl4j/fit/step` less `dl4j/fit/listeners`: the
    host's own work a step (the score read blocks under the listeners)."""
    steps = fit_steps(log, facts)
    if not steps:
        return None
    idx = Index(log)
    return mean(less_ms(s, idx.kids(s, FIT_LISTENERS)) for s in steps)
