"""Layer: whole step. The bytes a decode tick has to move
(harness/flops_phi4flash.py tick_bytes: every weight, the shared cache's live
pages once for each of its 8 readers, the window layers' live ring slots, the
live rows' Mamba state read and written; the counts `pages_live`,
`window_live` and `state_slots_live` from the tick's own `tick.prepare`
span), mean over the window's ticks, over the chip's HBM bandwidth, over the
mean of `tick.dispatch` + `tick.fetch`, as `tick_hbm_share.g4h` reads its
cell. A program whose spans lack the counts (a commit before it had a window
layer) gives nothing."""
from harness import flops_phi4flash as flops
from harness import spanlog

PREPARE, DISPATCH, FETCH = (spanlog.ENGINE_TICK + part
                            for part in ("prepare", "dispatch", "fetch"))


def compute(env):
    w = spanlog.serve_window(spanlog.records(), env.facts)
    if env.peak is None or not w:
        return None
    moved, spent = [], []
    for tick in w.ticks:
        attrs = {}
        for span in w.idx.kids(tick, PREPARE):
            attrs.update(span["attrs"])
        if not {"state_slots_live", "pages_live", "window_live"} <= set(attrs):
            return None
        moved.append(flops.tick_bytes(
            env.config, attrs["state_slots_live"], attrs["pages_live"],
            attrs["window_live"]))
        spent.append(sum(spanlog.duration_ms(s) for name in (DISPATCH, FETCH)
                         for s in w.idx.kids(tick, name)))
    least_ms = 1e3 * spanlog.mean(moved) / env.peak["hbm_bytes_per_s"]
    return 100.0 * least_ms / spanlog.mean(spent)
