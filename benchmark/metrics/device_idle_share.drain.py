"""device_idle_share.drain: 100 * (1 - device busy / traced span) over
one whole round of a drain, from the profiler trace (trace_reduce.py:
busy is the union of the device's op intervals)."""


def read(r):
    if r.trace is None or r.cell["traffic"]["loop"] != "closed":
        return None
    return 100.0 * (1.0 - r.trace["busy_s"] / r.trace["window_s"])
