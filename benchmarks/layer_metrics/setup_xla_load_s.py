"""Layer: set-up. Executables loaded from the persistent compilation cache
before the window: on each thread the union of the `xla/cache_load` spans
(each inside an `xla/compile` with `cache` "hit"); from the program's span
log."""
from harness import spanlog, spanlog_setup


def compute(env):
    return spanlog_setup.xla_load_s(spanlog.records(), env.facts)
