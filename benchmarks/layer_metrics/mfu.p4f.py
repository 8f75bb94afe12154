"""Layer: whole step. Required operations of the requests sent inside the
window (harness/flops_phi4flash.py serve_flops: the products from shapes, the
prefill as the skip leaves it, the scan at its per-element operations, the
window layers' and the shared cache's attention from the counted contexts;
each client's over its own span as generate_tokens_per_s counts its tokens,
the clients added), over the chip's bf16 peak, in percent."""
from harness import flops_phi4flash as flops


def compute(env):
    clients = env.facts.get("clients")
    if env.peak is None or not clients:
        return None
    rate = sum(flops.serve_flops(env.config, c["prompt_lens"], c["generated"])
               / c["span_s"] for c in clients)
    return 100.0 * rate / env.peak["bf16_flops_per_s"]
