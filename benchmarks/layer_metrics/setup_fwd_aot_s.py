"""Layer: set-up. Summed wall time of the `dl4j/registry/compile` spans with
`plane` "fwd" before the window: the serving registry's lower and compile of
the stateless forward, a bucket each, which a generate-only cell never calls;
from the program's span log."""
from harness import spanlog, spanlog_setup


def compute(env):
    return spanlog_setup.aot_s(spanlog.records(), env.facts, "fwd")
