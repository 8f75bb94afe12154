"""Layer: entry points (fit). 95th percentile, over the window's groups of
steps between two score reads, of the group's time per step (host clock of
the driver; a group is some 10 steps, well over the clock's half millisecond)."""
import statistics


def compute(env):
    groups = env.facts.get("group_step_ms")
    if not groups or len(groups) < 2:
        return None
    return statistics.quantiles(groups, n=20, method="inclusive")[18]
