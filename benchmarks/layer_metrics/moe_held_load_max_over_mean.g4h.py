"""Layer: model layers. Over the window's prefills: the largest load of a held
expert (tokens that picked it; mean over the expert layers) over the mean load
of the held experts, `num_local_experts` of them in this configuration. 1 is
even routing; at 10 picks of 72 the grouped products give each expert a
quarter of the tokens, 1.8 times its even share, before the layer falls back
to every expert over every token."""
from harness import spanlog, spanlog_moe


def compute(env):
    return spanlog_moe.held_load_max_over_mean(
        spanlog.records(), env.facts, env.config.get("num_local_experts"))
