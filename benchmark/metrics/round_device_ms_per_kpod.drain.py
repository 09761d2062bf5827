"""round_device_ms_per_kpod.drain: device time of the round program
(XLA module jit__schedule_round, ops/kernel.py _schedule_round) in the
traced round, per thousand pods that round bound."""

MODULE = "jit__schedule_round"


def read(r):
    if r.trace is None or not r.traced_binds:
        return None
    s = r.trace["modules"].get(MODULE)
    if s is None:
        return None
    return 1000.0 * s / (r.traced_binds / 1000.0)
