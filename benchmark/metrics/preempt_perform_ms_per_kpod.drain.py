"""preempt_perform_ms_per_kpod.drain: host time of the preempt chunks'
perform part: the nomination and eviction writes of
Scheduler._perform_preemption, and the preemptor's park in backoff. The
step profiler's preempt/perform (Scheduler._preempt_chunk, timed while
the profiler is on), accrued inside the window, per thousand pods bound
in it. Window delta of the step profiler."""

STEP = "preempt/perform"


def read(r):
    n = r.window_binds()
    if r.cell["traffic"]["loop"] != "closed" or not n or STEP not in r.step_delta:
        return None
    return 1000.0 * r.step_delta[STEP] / (n / 1000.0)
