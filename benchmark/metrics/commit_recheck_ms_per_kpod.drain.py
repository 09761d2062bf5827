"""commit_recheck_ms_per_kpod.drain: host time of the exact int64 recheck
(NodeInfo.fits_exactly) in Scheduler._commit, the step profiler's
commit/recheck (timed per pipeline round while the profiler is on),
accrued inside the window, per thousand pods bound in it. Window delta
of the step profiler."""

STEP = "commit/recheck"


def read(r):
    n = r.window_binds()
    if r.cell["traffic"]["loop"] != "closed" or not n or STEP not in r.step_delta:
        return None
    return 1000.0 * r.step_delta[STEP] / (n / 1000.0)
