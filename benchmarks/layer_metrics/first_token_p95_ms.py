"""Layer: decode plane. 95th percentile of `first_token_s` (submit to the end of
the first token's sampling) over the window's `dl4j/sched/admit` spans, from
the program's span log."""
from harness import spanlog


def compute(env):
    return spanlog.admit_p95_ms(spanlog.records(), env.facts, "first_token_s")
