"""Step tracing (analog of apiserver/pkg/util/trace/trace.go:33 utiltrace).

The scheduler wraps every cycle in a Trace and logs it when it exceeds a
threshold (reference: generic_scheduler.go:108-160, 100ms).

While a JAX profiler session records, a Trace that declares its `steps`
also puts every interval on the profiler's host plane: the interval from
the start (or the previous step) to a step is a span named
"<phase>/<step>", exactly the key utils/profiling.Profiler.step_totals()
gives that step, inside one span named "<phase>" for the whole trace
(<phase> is the trace name up to " of ", as the step profiler cuts it). A
profiler span's name is fixed when it opens, so each interval opens under
the declared step expected to end it. An interval that another step ends
(a retry, a fallback) keeps the name it opened with and carries
`ended_by=<step>`; one still open when the trace is dropped carries
`abandoned=1`. With no session recording, a Trace opens no span."""

from __future__ import annotations

import logging
import time
from typing import List, Sequence, Tuple

log = logging.getLogger("kubernetes_tpu")


class Trace:
    def __init__(self, name: str, clock=time.monotonic,
                 steps: Sequence[str] = ()):
        self._whole = self._span = None
        self.name = name
        self.clock = clock
        self.start = clock()
        self.steps: List[Tuple[float, str]] = []
        self.phase = name.split(" of ")[0]
        self._plan = tuple(steps)
        self._next = 0  # index in _plan of the step the open span expects
        if self._plan:
            from jax.profiler import TraceAnnotation

            if TraceAnnotation.is_enabled():
                self._ann = TraceAnnotation
                self._whole = TraceAnnotation(self.phase)
                self._span = TraceAnnotation(
                    f"{self.phase}/{self._plan[0]}")

    def annotate(self, **meta):
        """Metadata on the span of the whole trace, if one is recording."""
        if self._whole is not None:
            self._whole.set_metadata(**meta)

    def step(self, msg: str, **meta):
        """Close the interval ending now under `msg`; `meta` goes on its
        profiler span, if one is recording."""
        now = self.clock()
        self.steps.append((now, msg))
        # the spans close before the step profiler hears of the step: a
        # profiler hook may stop the session in record_step
        if self._span is not None:
            self._close_span(msg, meta)
        # feed the step profiler when enabled (utils/profiling.py): the
        # traces the scheduler already emits become the pprof-style
        # where-did-the-time-go breakdown with no extra instrumentation
        from . import profiling

        prof = profiling.active()
        if prof is not None:
            last = self.steps[-2][0] if len(self.steps) > 1 else self.start
            prof.record_step(self.name, msg, now - last)

    def _close_span(self, msg: str, meta: dict):
        if msg != self._plan[self._next]:
            meta["ended_by"] = msg
        if meta:
            self._span.set_metadata(**meta)
        self._span.__exit__(None, None, None)
        self._span = None
        nxt = (self._plan.index(msg) if msg in self._plan
               else self._next) + 1
        if nxt < len(self._plan):
            self._next = nxt
            self._span = self._ann(f"{self.phase}/{self._plan[nxt]}")
        else:
            self._whole.__exit__(None, None, None)
            self._whole = None

    def __del__(self):
        for span in (self._span, self._whole):
            if span is not None:
                span.set_metadata(abandoned=1)
                span.__exit__(None, None, None)

    def total(self) -> float:
        return self.clock() - self.start

    def log_if_long(self, threshold: float = 0.1):
        total = self.total()
        if total >= threshold:
            last = self.start
            lines = [f"Trace {self.name!r} (total {total*1e3:.1f}ms):"]
            for t, msg in self.steps:
                lines.append(f"  +{(t-last)*1e3:.1f}ms {msg}")
                last = t
            log.info("\n".join(lines))
        return total
