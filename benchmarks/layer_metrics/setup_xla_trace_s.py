"""Layer: set-up. Tracing and lowering before the window: on each thread the
union of the `xla/trace` and `xla/lower` spans (an inner jit traces inside an
outer one and counts once), summed over the threads; from the program's span
log."""
from harness import spanlog, spanlog_setup


def compute(env):
    return spanlog_setup.xla_trace_s(spanlog.records(), env.facts)
