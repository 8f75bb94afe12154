"""Layer: model layers. Of the experts held here (16 a layer), how many some
row of a tick picked: mean over the window's ticks and expert layers. An
expert no row picked is not computed and its weights are not read."""
from harness import spanlog, spanlog_moe


def compute(env):
    return spanlog_moe.held_experts_hit(spanlog.records(), env.facts)
