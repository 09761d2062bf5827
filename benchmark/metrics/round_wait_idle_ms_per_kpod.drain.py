"""round_wait_idle_ms_per_kpod.drain: device idle time (trace_reduce's
idle gaps) inside the program's pipeline/executed spans, where the host
waits in block_until_ready for the round, in the traced round, per
thousand pods that round bound (program_trace.py)."""

import program_trace


def read(r):
    if r.cell["traffic"]["loop"] != "closed" or not r.traced_binds:
        return None
    s = program_trace.step_idle_s(r, "pipeline/executed")
    if s is None:
        return None
    return 1000.0 * s / (r.traced_binds / 1000.0)
