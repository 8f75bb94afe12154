"""Layer: decode plane. Mean `rows` of the window's ticks (of the largest batch
bucket), from the program's span log."""
from harness import spanlog


def compute(env):
    return spanlog.decode_rows_per_tick(spanlog.records(), env.facts)
