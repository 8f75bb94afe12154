"""Layer: model layers. Percent of the router's picks (12 a live token and
expert layer), over the window's ticks and prefills, that fell on identity
(zero-compute) experts; 256 of 768 routes are, so seeded weights read near
33%. From the `moe_*` counts on the engine's fetch spans."""
from harness import spanlog, spanlog_moe


def compute(env):
    return spanlog_moe.identity_pick_share(spanlog.records(), env.facts)
