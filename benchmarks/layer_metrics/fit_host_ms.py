"""Layer: entry points (fit). Mean per step of `dl4j/fit/step` less
`dl4j/fit/listeners` (where the score read blocks): the host's own work a step,
from the program's span log."""
from harness import spanlog


def compute(env):
    return spanlog.fit_host_ms(spanlog.records(), env.facts)
