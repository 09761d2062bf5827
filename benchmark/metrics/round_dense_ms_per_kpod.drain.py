"""round_dense_ms_per_kpod.drain: device time of the round program's
ops under the `wave_dense` scope (each wave's static masks, affinity and
topology statics and static score planes, ahead of the scan) and the
`taint_ports` scope (the hoisted Pallas taint/port pass), ops/kernel.py,
in the traced round, per thousand pods that round bound
(program_trace.py)."""

import program_trace

SCOPES = ("wave_dense", "taint_ports")


def read(r):
    if r.cell["traffic"]["loop"] != "closed" or not r.traced_binds:
        return None
    by_scope = program_trace.round_scopes(r)
    if by_scope is None or not any(s in by_scope for s in SCOPES):
        return None
    s = sum(by_scope.get(x, 0.0) for x in SCOPES)
    return 1000.0 * s / (r.traced_binds / 1000.0)
