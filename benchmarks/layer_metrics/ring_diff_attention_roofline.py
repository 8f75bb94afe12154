"""Layer: kernels. The least time the chip could take for the traced
`ring_diff_attention` calls (each reads the live slots of one window layer's
rings once: the mean `window_live` of the window's ticks, from their
`tick.prepare` spans, x a slot's keys and values, 2 x kv_width lanes of the
weights' dtype; the kernel's operations are far under its bytes' time) over
the HBM bandwidth, over the calls' device time in the trace by the kernel's
device-op name, in percent. The live slots are what a call must read and at
most what it reads, so the share stays under 100%. Nothing where the trace
holds no such call (a program whose window layers gather their rings, a CPU
run) or the spans carry no ring slots."""
from harness import flops_phi4flash as flops
from harness import spanlog

KERNEL = "ring_diff_attention"
SLOT_BYTES = {"bfloat16": 2, "float32": 4}


def compute(env):
    if env.trace is None or env.peak is None:
        return None
    stats = env.xplane.kernel_stats(env.trace, [KERNEL])
    w = spanlog.serve_window(spanlog.records(), env.facts)
    if KERNEL not in stats or not w:
        return None
    live = [s["attrs"]["window_live"] for t in w.ticks
            for s in w.idx.kids(t, spanlog.ENGINE_TICK + "prepare")
            if "window_live" in s["attrs"]]
    if not live:
        return None
    seconds, calls = stats[KERNEL]
    slot = 2 * flops.dims(env.config)["kv_width"] \
        * SLOT_BYTES[env.config["precision"]["weights"]]
    least = calls * spanlog.mean(live) * slot / env.peak["hbm_bytes_per_s"]
    return 100.0 * least / seconds
