"""device_idle_share.paced: 100 * (1 - device busy / traced span) over
the last seconds of an open-loop window (traffic trace_s), from the
profiler trace (trace_reduce.py)."""


def read(r):
    if r.trace is None or r.cell["traffic"]["loop"] != "open":
        return None
    return 100.0 * (1.0 - r.trace["busy_s"] / r.trace["window_s"])
