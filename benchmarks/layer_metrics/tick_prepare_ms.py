"""Layer: decode plane. Mean per tick of `dl4j/engine/tick.prepare`: the version
check, the host arrays and the executable lookup, from the program's span log."""
from harness import spanlog


def compute(env):
    return spanlog.tick_child_ms(spanlog.records(), env.facts,
                                 spanlog.ENGINE_TICK + "prepare")
