"""Layer: decode plane. 95th percentile of `queue_wait_s` (last enqueue to the
scheduler taking the sequence off the queue) over the window's
`dl4j/sched/admit` spans, from the program's span log."""
from harness import spanlog


def compute(env):
    return spanlog.admit_p95_ms(spanlog.records(), env.facts, "queue_wait_s")
