"""Layer: whole step. Required operations of the requests sent inside the
window (harness/flops.py lm_serve_flops: every prompt token and every token
fed back passes the blocks at its own context, the head only where a token is
sampled), each client's over its own span (first send to last reply, as
generate_tokens_per_s counts its tokens), the clients added, over the chip's
bf16 peak, in percent."""


def compute(env):
    clients = env.facts.get("clients")
    if env.peak is None or not clients:
        return None
    rate = sum(env.flops.lm_serve_flops(env.config, c["prompt_lens"],
                                        c["generated"]) / c["span_s"]
               for c in clients)
    return 100.0 * rate / env.peak["bf16_flops_per_s"]
