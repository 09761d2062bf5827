"""Device-dispatch watchdog: a deadline budget around jitted dispatches.

The device-path circuit breaker (sched/breaker.py) only trips on RAISED
exceptions — a dispatch that silently never returns (a hung device or
runtime call) would wedge the scheduling loop forever with the breaker
still CLOSED. The watchdog closes that gap:
each dispatch through the ops/kernel.py `record_dispatch` seam runs on
a worker thread with a deadline; a dispatch that exceeds it is
ABANDONED — the thread keeps running against the wedged runtime (a
thread cannot be killed, and the runtime owns the hang), but the
scheduling loop gets `DispatchTimeout` immediately, feeds the breaker,
and the round completes through the numpy hostwave twin. Scheduling
never stalls behind a wedged dispatch.

Abandoned-but-still-running dispatches are tracked: while any is
outstanding the scheduler refuses to dispatch AT ALL (including the
breaker's half-open probe — see Scheduler._device_admitted), because a
runtime with a wedged wave in flight would eat the probe the same way.

Cold compiles are not hangs: a first dispatch at a new shape bucket
legitimately takes seconds to tens of seconds, so unwarmed dispatches get the
deadline scaled by `compile_scale`.

Results of an abandoned dispatch are discarded when the thread finally
returns — kernel dispatches are pure functions over device arrays; all
scheduler state mutation happens host-side after a successful fetch,
so nothing partial can escape an abandoned wave.
"""

from __future__ import annotations

import atexit
import threading
import time
import weakref
from typing import Callable, List, Optional

# Live watchdogs, weakly held: ONE module-level atexit hook drains
# whatever is still alive at interpreter exit. Weak refs so the hook
# never pins a discarded watchdog's whole object graph (on_abandon is
# typically a bound Scheduler method -> store -> HBM mirrors).
_LIVE: List["weakref.ref"] = []


def _drain_all() -> None:
    for ref in list(_LIVE):
        wd = ref()
        if wd is not None:
            wd.drain()


atexit.register(_drain_all)


class DispatchTimeout(RuntimeError):
    """A device dispatch exceeded its watchdog deadline and was
    abandoned. The dispatch may still complete eventually; its result
    is discarded either way."""

    def __init__(self, program: str, deadline_s: float):
        super().__init__(
            f"device dispatch {program!r} exceeded its "
            f"{deadline_s:.3f}s deadline and was abandoned")
        self.program = program
        self.deadline_s = deadline_s


class DispatchWatchdog:
    """Deadline harness for device dispatches. `deadline_s` <= 0
    disarms it entirely (run() degenerates to fn()). One worker thread
    per guarded dispatch — ~50-100us of overhead against the ~50ms
    fixed cost of a device program execution."""

    def __init__(self, deadline_s: float, compile_scale: float = 20.0,
                 on_abandon: Optional[Callable[[str, float], None]] = None):
        self.deadline_s = float(deadline_s)
        # unwarmed shape buckets compile inside the dispatch: scale the
        # budget rather than charging a legitimate 10-40s TPU compile
        # as a hang
        self.compile_scale = float(compile_scale)
        # fired (program, deadline_s) on every abandonment — feeds
        # scheduler_wave_deadline_overruns_total{stage=dispatch} and
        # the flight recorder
        self.on_abandon = on_abandon
        self.abandoned_total = 0
        # completion events of abandoned dispatches still in flight;
        # pruned on read (list, not set: determinism rule)
        self._inflight: List[threading.Event] = []
        self._lock = threading.Lock()
        # exit-time drain (module-level hook, weakly registered): a
        # daemon worker still blocked inside native XLA code while the
        # interpreter tears the runtime down aborts the whole process
        # (C++ terminate -> SIGABRT, exit 134) — a successful run that
        # once hit a wedged dispatch would read as a crash to any
        # supervisor. Bounded wait, best effort.
        _LIVE[:] = [r for r in _LIVE if r() is not None]
        _LIVE.append(weakref.ref(self))

    def armed(self) -> bool:
        return self.deadline_s > 0

    def outstanding(self) -> int:
        """Abandoned dispatches whose worker threads are STILL blocked
        in the runtime. While this is non-zero the runtime is presumed
        wedged and no new dispatch should be issued."""
        with self._lock:
            self._inflight = [e for e in self._inflight if not e.is_set()]
            return len(self._inflight)

    def drain(self, timeout: float = 30.0) -> bool:
        """Wait (bounded) for every abandoned dispatch to return.
        Registered at exit; also useful for tests that must not leak a
        still-running dispatch into the next scenario. True when the
        runtime is quiet again."""
        deadline = time.monotonic() + timeout
        with self._lock:
            pending = list(self._inflight)
        for e in pending:
            remaining = deadline - time.monotonic()
            if remaining <= 0 or not e.wait(remaining):
                return False
        return True

    def run(self, fn: Callable, program: str = "wave",
            warm: bool = True):
        """Run one dispatch under the deadline. Raises DispatchTimeout
        on abandonment; re-raises fn's own exception otherwise."""
        if not self.armed():
            return fn()
        deadline = self.deadline_s * (1.0 if warm else self.compile_scale)
        done = threading.Event()
        box: dict = {}

        def _worker():
            try:
                box["out"] = fn()
            except BaseException as e:  # re-raised on the caller below
                box["exc"] = e
            finally:
                done.set()

        t = threading.Thread(target=_worker, daemon=True,
                             name=f"dispatch-{program}")
        t.start()
        if not done.wait(deadline):
            with self._lock:
                self.abandoned_total += 1
                self._inflight.append(done)
            if self.on_abandon is not None:
                self.on_abandon(program, deadline)
            raise DispatchTimeout(program, deadline)
        if "exc" in box:
            raise box["exc"]
        return box["out"]
