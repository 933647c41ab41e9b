"""device_idle_share: 1 - (union of device-op intervals / traced window),
from the profiler trace of the window's first passes."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["window_s"] or not t["busy_s"]:
        return None
    return 1.0 - t["busy_s"] / t["window_s"]
