"""Layer: whole step. Required operations of the requests sent inside the
window (harness/flops_nemotron_h.py serve_flops: the mixers' and the
LatentMoE layers' dense products, the recurrence and the attention from
shapes, each client's over its own span as generate_tokens_per_s counts its
tokens, the clients added; the routed experts' from the (token, held
expert) pairs the window's prefills and ticks counted, `moe_held` on the
engine's fetch spans, over the window), over the chip's bf16 peak, in
percent."""
from harness import flops_nemotron_h as flops
from harness import spanlog, spanlog_moe


def compute(env):
    clients = env.facts.get("clients")
    counts = spanlog_moe.window_counts(spanlog.records(), env.facts)
    if env.peak is None or not clients or counts is None:
        return None
    rate = sum(flops.serve_flops(env.config, c["prompt_lens"], c["generated"])
               / c["span_s"] for c in clients)
    held = sum(c["moe_held"] for c in counts["tick"] + counts["prefill"])
    rate += held * flops.expert_pair_flops(env.config) / env.facts["window_s"]
    return 100.0 * rate / env.peak["bf16_flops_per_s"]
