"""Layer: decode plane. Mean per tick of `dl4j/engine/tick.dispatch`: the three
uploads and the executable call until it returns, from the program's span log."""
from harness import spanlog


def compute(env):
    return spanlog.tick_child_ms(spanlog.records(), env.facts,
                                 spanlog.ENGINE_TICK + "dispatch")
