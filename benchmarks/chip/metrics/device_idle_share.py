"""Share of the traced window in which no operation ran on the device:
1 - (union of the device-op intervals / traced window), averaged over the
chips used, from the profiler trace of a ``--trace 1`` run, in percent."""


def read(r):
    if r.trace is None or not r.trace["window_s"]:
        return None
    return 100.0 * (1.0 - r.trace["busy_s"] / r.trace["window_s"])
