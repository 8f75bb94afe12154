"""Layer: kernels. The least time the chip could take for the traced
`paged_diff_attention` calls (each reads the live pages of the shared keys and
values once: harness/flops_phi4flash.py kernel_bytes of the mean `pages_live`
of the window's ticks, from their `tick.prepare` spans; the kernel's
operations are far under its bytes' time) over the HBM bandwidth, over the
calls' device time in the trace by the kernel's device-op name, in percent.
Nothing where the trace holds no such call (a program without the kernel, a
CPU run)."""
from harness import flops_phi4flash as flops
from harness import spanlog

KERNEL = "paged_diff_attention"


def compute(env):
    if env.trace is None or env.peak is None:
        return None
    stats = env.xplane.kernel_stats(env.trace, [KERNEL])
    w = spanlog.serve_window(spanlog.records(), env.facts)
    if KERNEL not in stats or not w:
        return None
    pages = [s["attrs"]["pages_live"] for t in w.ticks
             for s in w.idx.kids(t, spanlog.ENGINE_TICK + "prepare")
             if "pages_live" in s["attrs"]]
    if not pages:
        return None
    seconds, calls = stats[KERNEL]
    least = calls * flops.kernel_bytes(env.config, spanlog.mean(pages)) \
        / env.peak["hbm_bytes_per_s"]
    return 100.0 * least / seconds
