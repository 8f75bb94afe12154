"""Layer: kernels. The least time the chip could take for the traced
`grouped_experts` calls of Nemotron 3 Super's LatentMoE layers, relu^2
experts of two matrices in the 1,024-wide latent (each call reads the
weights of the held experts some row picked, once: the window ticks' mean
`moe_held_hit` a layer, from their `tick.fetch` spans as
`moe_held_experts_hit` reads it, x 2 x latent x expert width x the weights'
bytes: `harness/flops_nemotron_h.py` expert_bytes; the kernel's operations,
2 a multiply-add over a tick's 128 rows, are under its bytes' time) over
the HBM bandwidth, over the calls' device time in the trace by the kernel's
device-op name, in percent. `grouped_experts_roofline` counts three
matrices of the model's width and does not list this cell. Nothing where
the trace holds no such call (a program whose experts run under
conditionals, a CPU run) or the spans carry no counts."""
from harness import flops_nemotron_h as flops
from harness import spanlog, spanlog_moe

KERNEL = "grouped_experts"
WEIGHT_BYTES = {"bfloat16": 2, "float32": 4}


def compute(env):
    if env.trace is None or env.peak is None:
        return None
    stats = env.xplane.kernel_stats(env.trace, [KERNEL])
    hit = spanlog_moe.held_experts_hit(spanlog.records(), env.facts)
    if KERNEL not in stats or hit is None:
        return None
    seconds, calls = stats[KERNEL]
    expert = flops.expert_bytes(
        env.config, WEIGHT_BYTES[env.config["precision"]["weights"]])
    least = calls * hit * expert / env.peak["hbm_bytes_per_s"]
    return 100.0 * least / seconds
