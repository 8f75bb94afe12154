"""Layer: whole step. The bytes a decode tick of Nemotron 3 Super's share
has to move (harness/flops_nemotron_h.py tick_bytes: the weights outside
the routed experts, the held experts some row picked, two matrices each in
the latent, the live rows' Mamba-2 state read and written, the live pages;
the three counts from the tick's own spans: `moe_held_hit` on `tick.fetch`,
`state_slots_live` and `pages_live` on `tick.prepare`), mean over the
window's ticks, over the chip's HBM bandwidth, over the mean of
`tick.dispatch` + `tick.fetch` (as `tick_hbm_share.g4h`: with a tick in
flight that is a little under the device's tick, so the share reads a little
over the device's own). A program whose spans lack the counts gives
nothing."""
from harness import flops_nemotron_h as flops
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
        for name in (PREPARE, FETCH):
            for span in w.idx.kids(tick, name):
                attrs.update(span["attrs"])
        if not {"state_slots_live", "pages_live", "moe_held_hit"} <= set(attrs):
            return None
        moved.append(flops.tick_bytes(
            env.config, attrs["state_slots_live"], attrs["moe_held_hit"],
            attrs["pages_live"]))
        spent.append(sum(spanlog.duration_ms(s) for name in (DISPATCH, FETCH)
                         for s in w.idx.kids(tick, name)))
    least_ms = 1e3 * spanlog.mean(moved) / env.peak["hbm_bytes_per_s"]
    return 100.0 * least_ms / spanlog.mean(spent)
