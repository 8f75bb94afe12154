"""Layer: decode plane. Tokens sampled inside the window by the program's own
count: the growth of dl4j_decode_tokens_total between the window's two ends,
read over /metrics, over the window. Exact at both ends, where the end-to-end
rate is taken over whole requests on the clients' clocks."""


def compute(env):
    a, b = env.facts.get("counters_before"), env.facts.get("counters_after")
    if not a or not b or b["tokens_total"] <= a["tokens_total"]:
        return None
    return (b["tokens_total"] - a["tokens_total"]) / env.facts["window_s"]
