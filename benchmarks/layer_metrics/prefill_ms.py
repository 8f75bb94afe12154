"""Layer: decode plane. Mean wall time of one prefill over the window: the
growth of dl4j_decode_phase_seconds{phase="prefill"}'s sum over the growth of
its count, read over /metrics at the window's two ends."""


def compute(env):
    a, b = env.facts.get("counters_before"), env.facts.get("counters_after")
    if not a or not b:
        return None
    n = b["prefill_count"] - a["prefill_count"]
    if n <= 0:
        return None
    return 1e3 * (b["prefill_sum"] - a["prefill_sum"]) / n
