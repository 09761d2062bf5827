"""Device-path circuit breaker.

The wave pipeline's device step can fail persistently, not just
transiently: a wedged XLA runtime, a kernel OOM at this cluster's
shapes, a device that dropped off the host. The per-call fallbacks in
the scheduler (pallas -> XLA retry, round -> per-wave) handle one
failure; a PERSISTENT fault would otherwise pay a doomed device attempt
— compile time, dispatch, the exception unwind — on every single wave,
forever. The breaker is the standard remedy (the same shape as
client-go's backoff-on-connection-storms, applied to an accelerator):

  closed     normal operation; consecutive-failure count resets on any
             device success.
  open       `threshold` consecutive device failures trip it; every
             wave routes through the exact host path
             (`_schedule_host_path`) — scheduling NEVER stops, it
             degrades — until `cooldown` elapses.
  half-open  after the cooldown one probe wave is re-admitted to the
             device path. Success closes the breaker (firing
             `on_recover`, which the scheduler uses to force a full
             snapshot rebuild — nothing incremental is trusted across a
             device fault); failure re-opens with a fresh cooldown.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Optional

CLOSED = "closed"
OPEN = "open"
HALF_OPEN = "half-open"

# gauge encoding for device_path_breaker_state (utils/metrics.py):
# operators alert on >0 (scheduling currently degraded)
STATE_CODES = {CLOSED: 0, HALF_OPEN: 1, OPEN: 2}


class DevicePathBreaker:
    def __init__(self, threshold: int = 3, cooldown: float = 30.0,
                 clock: Callable[[], float] = time.monotonic,
                 on_recover: Optional[Callable[[], None]] = None,
                 on_trip: Optional[Callable[[], None]] = None,
                 on_state: Optional[Callable[[str], None]] = None):
        self.threshold = max(int(threshold), 1)
        self.cooldown = cooldown
        self.clock = clock
        self.on_recover = on_recover
        self.on_trip = on_trip
        # fired on EVERY transition (trip, half-open probe admission,
        # recovery) with the new state — feeds the breaker-state gauge
        # and the flight recorder's span events
        self.on_state = on_state
        self.state = CLOSED
        self.failures = 0  # consecutive, since the last success
        self.trips = 0
        self.opened_at = 0.0

    def _transition(self, state: str) -> None:
        self.state = state
        if self.on_state is not None:
            self.on_state(state)

    def allow(self) -> bool:
        """May this wave take the device path? Open + cooldown elapsed
        transitions to half-open and admits the probe."""
        if self.state == OPEN:
            if self.clock() - self.opened_at >= self.cooldown:
                self._transition(HALF_OPEN)
                return True
            return False
        return True  # closed, or half-open (the probe itself)

    def record_failure(self) -> None:
        self.failures += 1
        if self.state == HALF_OPEN or (
                self.state == CLOSED and self.failures >= self.threshold):
            self._trip()

    def record_hang(self) -> None:
        """A dispatch the watchdog ABANDONED (utils/watchdog.py): trip
        immediately, ignoring the consecutive-failure threshold. The
        threshold exists to tolerate transient exceptions that cost
        milliseconds each; a hang costs a full wave_deadline_s per
        retry and signals a wedged runtime that won't heal by retrying
        — the cooldown probe is the right (and only) way back."""
        self.failures += 1
        if self.state != OPEN:
            self._trip()

    def record_success(self) -> None:
        self.failures = 0
        if self.state != CLOSED:
            self._transition(CLOSED)
            if self.on_recover is not None:
                self.on_recover()

    def _trip(self) -> None:
        self._transition(OPEN)
        self.opened_at = self.clock()
        self.trips += 1
        if self.on_trip is not None:
            self.on_trip()


# ---------------------------------------------------------------------------
# Per-device attribution: the mesh rungs ABOVE the whole-path breaker.
#
# The DevicePathBreaker above is binary: any device-path failure counts
# against the WHOLE accelerator plane, and tripping it abandons every
# chip for the numpy twin — losing 1 of 8 devices used to cost 8/8 of
# device throughput. With a multi-device mesh (parallel/mesh.py) the
# right remedy for a single sick chip is a *reform*: quarantine the
# culprit, rebuild a smaller valid mesh from the survivors, and keep
# dispatching. The MeshFaultManager owns that per-device state; the
# classic breaker remains the FINAL rung of the ladder (mesh exhausted,
# or no mesh at all).
# ---------------------------------------------------------------------------


class DeviceLost(RuntimeError):
    """A specific mesh device failed. Raised by the `device.lost` fault
    point in chaos tests (utils/faultpoints.py), and the shape an
    XLA/runtime error that names a device is normalized to by
    MeshFaultManager.attribute."""

    def __init__(self, device: str):
        super().__init__(f"device {device!r} lost")
        self.device = device


def lost_device_fault(device: str):
    """corrupt-mode fn for the `device.lost` fault point, arming chaos
    for ONE device: raises DeviceLost(device) when the guarded action
    involves it — the dispatch seam (ops/kernel.py record_dispatch)
    passes the active device-name tuple as payload, the recovery probe
    (sched/scheduler.py _probe_device) passes the probed device's name.
    Probes of innocent devices and dispatches on a mesh reformed past
    the victim proceed untouched, so one activation models exactly one
    lost chip:

        faultpoints.activate("device.lost", "corrupt",
                             fn=lost_device_fault(str(dev)))

    A None payload (no device registration — a dispatch from a
    scheduler built after another cleared the process-global
    set_devices) is a no-op: the fn models a MESH device loss, and
    killing dispatches whose device set is unknown would keep failing
    meshes already reformed past the victim.
    """

    def fn(payload):
        if payload is None:
            return
        if isinstance(payload, str):
            if payload == device:
                raise DeviceLost(device)
            return
        if device in payload:  # dispatch seam: active device names
            raise DeviceLost(device)

    return fn


class ResourceExhausted(RuntimeError):
    """Device allocation failure — the capacity-fault class. Raised by
    the `device.oom` fault point in chaos tests, and the shape a real
    XLA RESOURCE_EXHAUSTED / allocation-site MemoryError is classified
    into by is_capacity_error. NOT a device fault: no device is sick,
    the working set is too big — the remedy is compaction, a smaller
    wave, or the host twin, never quarantine or a mesh reform."""


def oom_fault(message: str = "RESOURCE_EXHAUSTED: out of memory "
                             "while trying to allocate"):
    """corrupt-mode fn for the `device.oom` fault point — the
    lost_device_fault analog for capacity faults: raises
    ResourceExhausted at the dispatch seam (ops/kernel.py
    record_dispatch passes the active device-name tuple as payload).
    A None payload (no device registration) is a no-op, matching
    lost_device_fault's contract:

        faultpoints.activate("device.oom", "corrupt", fn=oom_fault())
    """

    def fn(payload):
        if payload is None:
            return
        raise ResourceExhausted(message)

    return fn


# markers an XLA/runtime allocation failure embeds in its message; the
# gRPC status name is what real TPU runtimes surface. "device.oom"
# covers the raise-mode FaultInjected of that point ("fault injected at
# 'device.oom'"), so KTPU_FAULTPOINTS="device.oom=raise" is a
# paste-able capacity-chaos reproducer without a custom corrupt fn.
_CAPACITY_MARKERS = ("RESOURCE_EXHAUSTED", "resource exhausted",
                     "out of memory", "OOM when allocating",
                     "device.oom")


def is_capacity_error(exc: BaseException) -> bool:
    """True when the exception chain is a capacity miss — an
    allocation-site MemoryError, a ResourceExhausted, or an error whose
    text carries an XLA RESOURCE_EXHAUSTED marker. Walks __cause__/
    __context__ like MeshFaultManager.attribute: jax wraps backend
    errors, and the classification must see through the wrapping."""
    seen = set()
    e: Optional[BaseException] = exc
    while e is not None and id(e) not in seen:
        seen.add(id(e))
        if isinstance(e, (MemoryError, ResourceExhausted)):
            return True
        text = str(e)
        if any(m in text for m in _CAPACITY_MARKERS):
            return True
        e = e.__cause__ or e.__context__
    return False


def device_name_hits(names, text: str):
    """Device names appearing in `text` as exact tokens — a name
    followed by another digit is a DIFFERENT device's id ('TPU_1'
    inside 'TPU_10'), not a hit; plain substring matching would turn
    an unambiguous attribution into a 2-hit ambiguity on meshes of 10+
    devices."""
    hits = []
    for n in names:
        if not n:
            continue
        idx = text.find(n)
        while idx != -1:
            end = idx + len(n)
            if end == len(text) or not text[end].isdigit():
                hits.append(n)
                break
            idx = text.find(n, idx + 1)
    return hits


HEALTHY = "healthy"
QUARANTINED = "quarantined"


class MeshFaultManager:
    """Per-device health for the mesh rungs of the degradation ladder.

    Tracks which of the configured mesh's devices are healthy vs
    quarantined, attributes dispatch failures to a culprit device (the
    exception names one — DeviceLost, or an XLA error mentioning the
    device — else quarantine-and-probe bisection: half the healthy set
    is quarantined on suspicion and recovery probes re-admit the
    innocent), and schedules those probes on a cooldown. The scheduler
    consults `healthy()` to reform the mesh after each change
    (parallel/mesh.py reform_mesh) and re-forms UPWARD when probes
    re-admit devices.

    Thread-safety: mutations run under `_lock`; the scheduler calls in
    while holding Scheduler._mu (the reform must be atomic w.r.t. the
    device upload), so the static lock graph carries the
    Scheduler._mu -> MeshFaultManager._lock edge (analysis/lockgraph)."""

    def __init__(self, devices, clock: Callable[[], float] = time.monotonic,
                 probe_cooldown: float = 30.0):
        self._lock = threading.Lock()
        self.clock = clock
        self.probe_cooldown = float(probe_cooldown)
        # original mesh order, preserved: reform keeps the leading
        # survivors, so which devices serve after a loss is deterministic
        self.devices: List[str] = [str(d) for d in devices]
        self._objs: Dict[str, object] = {str(d): d for d in devices}
        # name -> quarantined_at (dict-as-ordered-set: deterministic
        # iteration for probes and ledger records)
        self._quarantined: Dict[str, float] = {}
        self.quarantines = 0  # cumulative, for tests/ledger

    # -- queries -------------------------------------------------------------

    def healthy(self) -> List[object]:
        """Surviving device objects, original mesh order."""
        with self._lock:
            return [self._objs[n] for n in self.devices
                    if n not in self._quarantined]

    def healthy_names(self) -> List[str]:
        with self._lock:
            return [n for n in self.devices if n not in self._quarantined]

    def quarantined_names(self) -> List[str]:
        with self._lock:
            return list(self._quarantined)

    def attribute(self, exc: BaseException) -> Optional[str]:
        """Name the culprit device, if the exception does. DeviceLost
        carries it; otherwise the error text is scanned for exactly one
        currently-healthy device name (XLA runtime errors usually embed
        the failing device's id). Ambiguous or silent errors return
        None — the bisection path."""
        seen = set()
        e: Optional[BaseException] = exc
        while e is not None and id(e) not in seen:
            seen.add(id(e))
            dev = getattr(e, "device", None)
            if isinstance(dev, str):
                with self._lock:
                    if dev in self._objs and dev not in self._quarantined:
                        return dev
            e = e.__cause__ or e.__context__
        text = str(exc)
        with self._lock:
            live = [n for n in self.devices if n not in self._quarantined]
        hits = device_name_hits(live, text)
        return hits[0] if len(hits) == 1 else None

    # -- mutations -----------------------------------------------------------

    def quarantine(self, name: str) -> bool:
        """Mark one device quarantined; True if it was healthy."""
        with self._lock:
            if name not in self._objs or name in self._quarantined:
                return False
            self._quarantined[name] = self.clock()
            self.quarantines += 1
            return True

    def quarantine_suspects(self) -> List[str]:
        """Unattributed failure: bisection step. Quarantine the TRAILING
        half of the healthy set on suspicion (the leading half keeps
        serving — reform keeps leading survivors, so this halves the
        mesh exactly one ladder rung); recovery probes re-admit the
        innocent. A repeat failure halves again, converging on the
        culprit in log2(devices) rounds."""
        with self._lock:
            healthy = [n for n in self.devices if n not in self._quarantined]
            if len(healthy) <= 1:
                return []
            now = self.clock()
            suspects = healthy[len(healthy) // 2:]
            for n in suspects:
                self._quarantined[n] = now
                self.quarantines += 1
            return suspects

    def due_probes(self, now: Optional[float] = None) -> List[object]:
        """Quarantined devices whose cooldown elapsed — probe these."""
        if now is None:
            now = self.clock()
        with self._lock:
            return [self._objs[n] for n, t in self._quarantined.items()
                    if now - t >= self.probe_cooldown]

    def reprobe_later(self, name: str) -> None:
        """A probe failed: restart the device's cooldown."""
        with self._lock:
            if name in self._quarantined:
                self._quarantined[name] = self.clock()

    def readmit(self, name: str) -> bool:
        """A probe succeeded: the device rejoins the healthy set (the
        caller re-forms the mesh upward)."""
        with self._lock:
            return self._quarantined.pop(name, None) is not None
