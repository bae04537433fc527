"""device.idle_share: 1 - (union of the device's operation intervals) /
(traced window), from the profiler trace, in percent."""


def read(rec):
    t = rec.trace
    if t is None or t["window_s"] <= 0 or t["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
