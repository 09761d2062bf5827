"""preempt_validate_ms_per_kpod.drain: host time of the preempt chunks'
validate part: selectVictimsOnNode over each preemptor's candidates,
with the walk past nodes claimed or exhausted, and
pickOneNodeForPreemption. The step profiler's preempt/validate
(Scheduler._preempt_chunk, timed while the profiler is on), accrued
inside the window, per thousand pods bound in it. Window delta of the
step profiler."""

STEP = "preempt/validate"


def read(r):
    n = r.window_binds()
    if r.cell["traffic"]["loop"] != "closed" or not n or STEP not in r.step_delta:
        return None
    return 1000.0 * r.step_delta[STEP] / (n / 1000.0)
