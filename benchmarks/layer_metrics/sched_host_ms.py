"""Layer: decode plane. Mean per scheduler loop of its duration less its
`*.fetch` descendants: the scheduler thread's time a loop in which it is not
waiting for the device, from the program's span log."""
from harness import spanlog


def compute(env):
    return spanlog.sched_host_ms(spanlog.records(), env.facts)
