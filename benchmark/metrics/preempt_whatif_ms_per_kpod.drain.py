"""preempt_whatif_ms_per_kpod.drain: host time of the preempt chunks'
device what-if (Scheduler._preempt_chunk: featurize and upload, dispatch
of ops/preempt.py preemption_stats, fetch of its planes), the step
profiler's preempt chunk/featurized+uploaded, /dispatched and /fetched,
accrued inside the window, per thousand pods bound in it. Window delta
of the step profiler."""

STEPS = ("preempt chunk/featurized+uploaded", "preempt chunk/dispatched",
         "preempt chunk/fetched")


def read(r):
    n = r.window_binds()
    got = [r.step_delta[s] for s in STEPS if s in r.step_delta]
    if r.cell["traffic"]["loop"] != "closed" or not n or not got:
        return None
    return 1000.0 * sum(got) / (n / 1000.0)
