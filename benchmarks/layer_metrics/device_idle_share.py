"""Layer: device. 1 - (union of the device's operation intervals) / (traced
window), in percent. One reader for `device_idle_share.train` and
`device_idle_share.serve`: the quantity is the same, the names differ because
the cells report different end-to-end metrics."""


def compute(env):
    if env.trace is None:
        return None
    busy, window = env.xplane.busy_seconds(env.trace)
    return 100.0 * (1.0 - busy / window)
