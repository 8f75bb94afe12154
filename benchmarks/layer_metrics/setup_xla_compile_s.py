"""Layer: set-up. Backend compiles before the window, less the cache loads
inside them: compiling proper, and hashing the persistent cache's key. On
each thread the union of the `xla/compile` spans less the `xla/cache_load`
spans; from the program's span log."""
from harness import spanlog, spanlog_setup


def compute(env):
    return spanlog_setup.xla_compile_s(spanlog.records(), env.facts)
