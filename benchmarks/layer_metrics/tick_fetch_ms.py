"""Layer: decode plane. Mean per tick of `dl4j/engine/tick.fetch`: the wait for
the device and the copy of the logits to the host, from the program's span log."""
from harness import spanlog


def compute(env):
    return spanlog.tick_child_ms(spanlog.records(), env.facts,
                                 spanlog.ENGINE_TICK + "fetch")
