"""Device: the share of the chips' busy time spent in the exchange
between them, the collectives of the traced window."""

from benchmarks.chip import trace


def read(run, suffix):
    t = run.trace_summary
    if not t or t["busy_s"] <= 0:
        return None
    secs, _ = trace.collectives(t)
    return secs / t["busy_s"] if secs > 0 else None
