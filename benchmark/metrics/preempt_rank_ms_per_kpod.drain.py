"""preempt_rank_ms_per_kpod.drain: host time of the preempt chunks' rank
part: ordering each preemptor's device candidates (the sort of the
what-if's ranking). The step profiler's preempt/rank
(Scheduler._preempt_chunk, timed while the profiler is on), accrued
inside the window, per thousand pods bound in it. Window delta of the
step profiler."""

STEP = "preempt/rank"


def read(r):
    n = r.window_binds()
    if r.cell["traffic"]["loop"] != "closed" or not n or STEP not in r.step_delta:
        return None
    return 1000.0 * r.step_delta[STEP] / (n / 1000.0)
