"""The device-dispatch path of the scheduler's four sites: the round
(_run_pipeline), the per-wave path (_wave), the gang (_place_gang) and
the warm-up (warm_pipeline). Each builds its batch and commits its own
way; between, Scheduler._dispatch runs the program under its
Formulation, Scheduler._classify turns a failure into a Verdict, and
Scheduler._salvage does what the site's row of SALVAGE says."""

from __future__ import annotations

import logging
from typing import Callable, NamedTuple, Optional

import jax

from ..ops import kernel
from ..utils import tracing
from ..utils.watchdog import DispatchTimeout

PROGRAMS = ("round", "wave", "gang")

# verdicts, in the order the classifier tries them
CAPACITY = "capacity"    # the scheduler's own footprint outgrew the device
INPUT = "input"          # the work is at fault (poison): convict it
TRANSIENT = "transient"  # a host-side seam failed, and its replay ran clean
REFORMED = "reformed"    # a mesh device was lost and the mesh reformed
HUNG = "hung"            # the watchdog abandoned the dispatch: breaker open
FAILED = "failed"        # any other device failure, counted by the breaker

# what a site does with its pods: RETRY the site (capacity: while the
# strike ladder allows, else DEGRADE), DEGRADE through the host twin
# now, REQUEUE for the drain's next pass, PARK with backoff. An INPUT
# verdict always convicts: the round and the wave bisect, the gang
# quarantines whole.
RETRY, DEGRADE, REQUEUE, PARK = "retry", "degrade", "requeue", "park"
SALVAGE = {
    # The round is a drain's first pass: the per-wave path takes what
    # it hands back within the same drain, one more device attempt
    # before the twin. A reformed mesh or a hung runtime has no device
    # attempt to offer, so those degrade at once.
    "round": {CAPACITY: RETRY, TRANSIENT: REQUEUE, FAILED: REQUEUE,
              REFORMED: DEGRADE, HUNG: DEGRADE},
    # The per-wave path is the drain's last rung: a pod it requeued
    # would be popped again at once. A device fault costs a slower wave
    # through the twin; a seam fault with nothing to convict parks.
    "wave": {CAPACITY: RETRY, TRANSIENT: PARK, FAILED: DEGRADE,
             REFORMED: DEGRADE, HUNG: DEGRADE},
    # A gang is one PodGroup placed all or nothing. After compaction
    # the twin's all-or-nothing plane places it at once; a failed
    # joint assignment parks the gang whole, its members placing
    # nothing, and the breaker routes later gangs to the twin once it
    # trips.
    "gang": {CAPACITY: DEGRADE, TRANSIENT: PARK, FAILED: PARK,
             REFORMED: DEGRADE, HUNG: DEGRADE},
}


class Verdict(NamedTuple):
    kind: str
    # the verdict exception for INPUT (it names the culprits), else the
    # failure itself
    exc: BaseException
    # the round ledger's record of the failure: error type or poison count
    ledger: dict


class Formulation:
    """The Pallas/XLA choice of each device program, resolved to
    pallas_default() on its first dispatch. A failed Pallas dispatch is
    retried once on XLA and the program stays demoted, unless XLA fails
    too (the fault was never Pallas's). A first Pallas run given a
    `same` check is compared on the device with XLA; a mismatch demotes.
    Under a multi-device mesh all are XLA: GSPMD cannot shard a
    pallas_call."""

    def __init__(self, metrics, multi_device: bool = False):
        self.metrics = metrics
        self._pallas = {p: False if multi_device else None
                        for p in PROGRAMS}
        self._checked = set()
        # what the most recently executed program used: "pallas" or
        # "xla" on the device, "vector" for the host twin — what
        # wave_path() reports, never a prediction
        self.last_path: Optional[str] = None

    def pallas(self, program: str) -> bool:
        if self._pallas[program] is None:
            self._pallas[program] = kernel.pallas_default()
        return self._pallas[program]

    def demote(self, program: str, why: str,
               exc: Optional[BaseException] = None) -> None:
        """Demote a program to XLA, visibly: scheduling_errors_total
        {stage=pallas}, a logged traceback and a flight-recorder
        event."""
        self._pallas[program] = False
        self.metrics.scheduling_errors.labels(stage="pallas").inc()
        logging.getLogger(__name__).error(
            "pallas %s demoted to the XLA formulation: %s", program, why,
            exc_info=exc)
        tracing.event("pallas_demoted", program=program, why=why,
                      error=type(exc).__name__ if exc is not None else "")

    def run(self, program: str, attempt: Callable[[bool], object],
            same: Optional[Callable[[object, object], bool]] = None):
        """(result, path) of attempt(use_pallas), waited for: dispatch
        is async, and a program that compiles but faults at execution
        raises only when its result is consumed."""
        use_p = self.pallas(program)
        try:
            out = jax.block_until_ready(attempt(use_p))
        except Exception as e:
            # a wedged runtime is not a Pallas failure: retrying on XLA
            # would dispatch at it again and burn another deadline
            if not use_p or isinstance(e, DispatchTimeout):
                raise
            self.demote(program, f"{type(e).__name__}: {e}", e)
            try:
                out = jax.block_until_ready(attempt(False))
            except Exception:
                self._pallas[program] = True
                raise
            use_p = False
        if use_p and same is not None and program not in self._checked:
            want = jax.block_until_ready(attempt(False))
            if not same(out, want):
                self.demote(program, "MISMATCHES the XLA formulation on "
                            "this backend")
                out, use_p = want, False
            self._checked.add(program)
        return out, "pallas" if use_p else "xla"
