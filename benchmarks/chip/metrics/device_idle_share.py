"""Device: 1 - (union of device-operation intervals / traced window),
from the profiler trace. The suffix names the cell's kind only."""


def read(run, suffix):
    t = run.trace_summary
    if not t or t["window_s"] <= 0:
        return None
    return 1.0 - t["busy_s"] / t["window_s"]
