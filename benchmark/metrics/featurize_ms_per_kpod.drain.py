"""featurize_ms_per_kpod.drain: host time of the program's
pipeline/featurized+staged step (state/featurize.py, Snapshot.
stage_pending) accrued inside the window, per thousand pods bound in
it. Window delta of the step profiler."""

STEP = "pipeline/featurized+staged"


def read(r):
    n = r.window_binds()
    if r.cell["traffic"]["loop"] != "closed" or not n or STEP not in r.step_delta:
        return None
    return 1000.0 * r.step_delta[STEP] / (n / 1000.0)
