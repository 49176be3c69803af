"""Device idle share of the traced part of the window: 1 minus the union
of device-operation intervals over the traced time, averaged over the
traced devices (profiler trace)."""


def read(r):
    t = r.trace
    if t is None or not t.ops or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.mean_busy_s() / t.window_s)
