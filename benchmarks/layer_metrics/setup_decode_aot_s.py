"""Layer: set-up. Summed wall time of the `dl4j/registry/compile` spans with
`plane` "decode" before the window: the decode plane's prefill and tick
executables, lowered and compiled or loaded from the persistent cache; from
the program's span log."""
from harness import spanlog, spanlog_setup


def compute(env):
    return spanlog_setup.aot_s(spanlog.records(), env.facts, "decode")
