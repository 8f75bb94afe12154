"""Layer: decode plane. Mean duration of one iteration of the scheduler loop
(`dl4j/sched/loop`: resolve the version, admit, tick) over the loops that hold
the window's ticks, from the program's span log."""
from harness import spanlog


def compute(env):
    return spanlog.sched_loop_ms(spanlog.records(), env.facts)
