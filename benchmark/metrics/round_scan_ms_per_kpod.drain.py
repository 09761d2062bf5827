"""round_scan_ms_per_kpod.drain: device time of the round program's ops
under the `pod_scan` scope (the serial per-pod lax.scan of _wave_body,
ops/kernel.py) in the traced round, per thousand pods that round bound
(program_trace.py)."""

import program_trace

SCOPES = ("pod_scan",)


def read(r):
    if r.cell["traffic"]["loop"] != "closed" or not r.traced_binds:
        return None
    by_scope = program_trace.round_scopes(r)
    if by_scope is None or not any(s in by_scope for s in SCOPES):
        return None
    s = sum(by_scope.get(x, 0.0) for x in SCOPES)
    return 1000.0 * s / (r.traced_binds / 1000.0)
