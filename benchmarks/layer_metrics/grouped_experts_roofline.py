"""Layer: kernels. The least time the chip could take for the traced
`grouped_experts` calls (each reads the weights of the held experts some row
picked, once: the window ticks' mean `moe_held_hit` a layer, from their
`tick.fetch` spans as `moe_held_experts_hit` reads it, x 3 x hidden x expert
width x the weights' bytes; the kernel's operations, 2 a multiply-add over a
tick's few dozen rows, are far under its bytes' time) over the HBM
bandwidth, over the calls' device time in the trace by the kernel's
device-op name, in percent. Nothing where the trace holds no such call (a
program whose experts run under conditionals, a CPU run) or the spans carry
no counts."""
from harness import spanlog, spanlog_moe

KERNEL = "grouped_experts"
WEIGHT_BYTES = {"bfloat16": 2, "float32": 4}


def expert_bytes(config) -> int:
    """One held expert's three matrices: W_g, W_u [d, h] and W_d [h, d]."""
    h = config.get("expert_ffn_hidden_size") or config["intermediate_size"]
    return 3 * int(config["hidden_size"]) * int(h) \
        * WEIGHT_BYTES[config["precision"]["weights"]]


def compute(env):
    if env.trace is None or env.peak is None:
        return None
    stats = env.xplane.kernel_stats(env.trace, [KERNEL])
    hit = spanlog_moe.held_experts_hit(spanlog.records(), env.facts)
    if KERNEL not in stats or hit is None:
        return None
    seconds, calls = stats[KERNEL]
    least = calls * hit * expert_bytes(env.config) \
        / env.peak["hbm_bytes_per_s"]
    return 100.0 * least / seconds
