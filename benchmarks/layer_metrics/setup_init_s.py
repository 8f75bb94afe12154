"""Layer: set-up. Summed wall time of the program's `dl4j/nn/init` spans
before the window: a model's parameters made from the seed, or taken where
they were given (`given` 1), from the program's span log."""
from harness import spanlog, spanlog_setup


def compute(env):
    return spanlog_setup.init_s(spanlog.records(), env.facts)
