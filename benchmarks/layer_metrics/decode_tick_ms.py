"""Layer: decode plane. Mean wall time of one decode tick over the window:
dl4j_decode_phase_seconds{phase="decode"}, sum over count, between the
window's two ends."""


def compute(env):
    a, b = env.facts.get("counters_before"), env.facts.get("counters_after")
    if not a or not b:
        return None
    n = b["decode_count"] - a["decode_count"]
    if n <= 0:
        return None
    return 1e3 * (b["decode_sum"] - a["decode_sum"]) / n
