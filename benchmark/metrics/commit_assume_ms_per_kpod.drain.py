"""commit_assume_ms_per_kpod.drain: host time of the assume
(cache.assume_pod, Snapshot.refresh_node_resources, Snapshot.add_pod) in
Scheduler._commit, the step profiler's commit/assume (timed per pipeline
round while the profiler is on), accrued inside the window, per thousand
pods bound in it. Window delta of the step profiler."""

STEP = "commit/assume"


def read(r):
    n = r.window_binds()
    if r.cell["traffic"]["loop"] != "closed" or not n or STEP not in r.step_delta:
        return None
    return 1000.0 * r.step_delta[STEP] / (n / 1000.0)
