"""Layer: decode plane. Mean per tick of its `dl4j/sched/sample`: sampling a token
for each row and finishing the rows that end, from the program's span log."""
from harness import spanlog


def compute(env):
    return spanlog.tick_child_ms(spanlog.records(), env.facts, spanlog.SAMPLE)
