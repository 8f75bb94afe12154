"""The expert layers' counts of a serving window, from the program's span log.

The engine puts them on its `dl4j/engine/tick.fetch` and
`dl4j/engine/prefill.fetch` spans, summed over the stack's expert layers:
`moe_layers`, `moe_picks` (router picks of live tokens), `moe_identity`,
`moe_held` (picks on experts held here: the pairs they compute),
`moe_held_hit` (held experts with a pick) and `moe_held_load_max` (the largest
load of a held expert, added over the layers). The window is the one
`spanlog.serve_window` finds, by ordinals. A program without these counts (a
commit before it had them, a stack with no expert layer) gives None."""
from __future__ import annotations

from harness import spanlog

TICK_FETCH, PREFILL_FETCH = ("dl4j/engine/tick.fetch",
                             "dl4j/engine/prefill.fetch")


def window_counts(log, facts):
    """{"tick": [attrs of each window tick's fetch], "prefill": [...]} or
    None where there is no window or no count in it."""
    w = spanlog.serve_window(log, facts)
    if not w:
        return None
    counted = lambda spans: [s["attrs"] for s in spans
                             if "moe_picks" in s["attrs"]]
    ticks = counted(c for t in w.ticks for c in w.idx.kids(t, TICK_FETCH))
    prefills = counted(d for a in w.admits for d in w.idx.descendants(a)
                       if d["name"] == PREFILL_FETCH)
    if not ticks:
        return None
    return {"tick": ticks, "prefill": prefills}


def identity_pick_share(log, facts):
    """Percent of the window's picks, ticks and prefills together, that fell
    on identity experts."""
    c = window_counts(log, facts)
    if c is None:
        return None
    every = c["tick"] + c["prefill"]
    picks = sum(a["moe_picks"] for a in every)
    return 100.0 * sum(a["moe_identity"] for a in every) / picks if picks else None


def held_experts_hit(log, facts):
    """Held experts with at least one pick, mean over the window's ticks and
    their expert layers."""
    c = window_counts(log, facts)
    if c is None:
        return None
    return spanlog.mean(a["moe_held_hit"] / a["moe_layers"] for a in c["tick"])


def held_load_max_over_mean(log, facts, held):
    """Over the window's prefills with a pick on a held expert: the largest
    load of a held expert (mean over the layers) over the mean load of the
    `held` experts."""
    c = window_counts(log, facts)
    if c is None:
        return None
    return spanlog.mean(int(held) * a["moe_held_load_max"] / a["moe_held"]
                        for a in c["prefill"] if a["moe_held"])
