"""The scheduler: wave loop, assume/bind pipeline, failure handling.

Behavioral port of the reference's Scheduler.scheduleOne cycle
(pkg/scheduler/scheduler.go:438) restructured around the TPU wave model:

  reference                          this framework
  ---------                          --------------
  NextPod (queue.Pop)           ->   queue.pop_wave(W)
  schedule (filter+score 1 pod) ->   ops.kernel.schedule_wave (W pods)
  assume + async bind           ->   exact host recheck -> assume -> bind
  preempt on FitError           ->   sched.preemption over mask reasons
  error -> backoff requeue      ->   same (utils.backoff)

Informer wiring mirrors factory.NewConfigFactory's handler sets
(pkg/scheduler/factory/factory.go:191-295): assigned pods feed the cache
+ snapshot, pending pods feed the queue, node events refresh the tensor
mirror and flush the unschedulable queue.

Placement-quality note: the wave scan commits pods in priority order and
each pod sees all earlier commitments (resources/pod counts on device,
exactly; spreading counts refresh between waves), so results match
one-pod-at-a-time scheduling except for intra-wave spreading/affinity
visibility — SURVEY.md §7 hard part (c); interpod-affinity pods bypass
the wave batch in later rounds.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from ..api import labels as lbl
from ..api import types as api
from ..ops import encoding as enc
from ..ops.kernel import schedule_wave
from ..plugins import golden
from ..plugins.registry import Profile, default_profile
from ..runtime.informer import SharedInformer
from ..runtime.store import ObjectStore
from ..state.cache import SchedulerCache
from ..state.featurize import PodFeaturizeError, PodFeaturizer
from ..state.scrubber import SnapshotScrubber
from ..state.snapshot import Snapshot
from ..utils import (Metrics, PodBackoff, Trace, bounded_label, faultpoints,
                     profiling, tracing)
from ..utils.watchdog import DispatchTimeout
from ..utils.feature_gates import FeatureGates
from . import breaker as breaker_mod
from .breaker import STATE_CODES, DevicePathBreaker, is_capacity_error
from .dispatch import (CAPACITY, DEGRADE, FAILED, HUNG, INPUT, PARK,
                       REFORMED, SALVAGE, TRANSIENT, Formulation, Verdict)
from .equivalence import EquivalenceCache, equivalence_class
from .errors import (REASON_KEYS, REASONS, FitError, PoisonError,
                     insufficient_resource_reason)
from .extender import ExtenderError
from .gang import GangDirectory
from .preemption import (GangGuard, PreemptionResult,
                         get_lower_priority_nominated_pods, pick_one_node,
                         pod_eligible_to_preempt_others, preempt,
                         process_preemption_with_extenders,
                         select_victims_on_node)
from .queue import SchedulingQueue
from .reconciler import BOUND, CONFIRMED, GONE, ORPHANED, BindReconciler
from .storehealth import DISCONNECTED as STORE_DISCONNECTED
from .storehealth import STATE_CODES as STORE_STATE_CODES
from .storehealth import StorePathBreaker
from ..state.journal import BindJournal


# Max chained waves per device-resident round; rounds compile per
# power-of-two wave-count bucket (a fixed W would make small rounds pay
# for 128 scan iterations). Longer backlogs run multiple rounds. The
# inter-pod-affinity variant is capped lower. The cap dates from a
# remote runtime whose worker crashed on the W=128 ipa scan at full caps
# (M=32k, E=8k, N=8k); on a directly attached v5e that round compiles,
# runs and places like W=64 (CHANGES.md PR 21). Whether to lift it is
# a measurement, not a correctness, question.
PIPELINE_MAX_WAVES = 128
PIPELINE_MAX_WAVES_IPA = 64
# device-side preemption (ops/preempt.py): priority-threshold levels per
# what-if program, and how many device-ranked candidate nodes get the
# exact host validation (selectVictimsOnNode) per failed pod
PREEMPT_LEVELS = 8
PREEMPT_HOST_CANDIDATES = 8
# the steps each Trace takes, in order: each interval between them is a
# profiler span named "<phase>/<step>" (utils/trace.py)
PIPELINE_STEPS = ("featurized+staged", "uploaded", "dispatched", "executed",
                  "fetched", "committed")
WAVE_STEPS = ("featurized", "device wave", "committed")
HOST_WAVE_STEPS = ("featurized", "host wave", "committed")
PREEMPT_STEPS = ("featurized+uploaded", "dispatched", "fetched",
                 "validated+performed")
PREEMPT_HOST_STEPS = ("host what-if", "fetched", "validated+performed")
# the parts of _commit timed per pipeline round while the step profiler
# is on, recorded as steps of the "commit" phase
COMMIT_PARTS = ("recheck", "assume", "bind")
# the parts of a preempt chunk's host loop (its validated+performed
# step) timed the same way, as steps of the "preempt" phase: ordering
# the device candidates, selectVictimsOnNode over them, and the
# nomination and eviction writes
PREEMPT_PARTS = ("rank", "validate", "perform")


def _accrue(parts: List[float], i: int, since: float) -> float:
    """Add the perf-counter seconds since `since` to parts[i]; returns
    now."""
    now = time.perf_counter()
    parts[i] += now - since
    return now


def pipeline_bucket(n_waves: int, lo: int = 4,
                    hi: int = PIPELINE_MAX_WAVES) -> int:
    """Smallest power-of-two wave-count >= n_waves (ceiling at hi) — the
    static W of the round program."""
    b = lo
    while b < n_waves and b < hi:
        b *= 2
    return b


def _pod_has_ipa_terms(pod: api.Pod) -> bool:
    aff = pod.spec.affinity
    return aff is not None and (aff.pod_affinity is not None
                                or aff.pod_anti_affinity is not None)


def assemble_round(pbs, waves, pm_rows_all, term_rows_all, wbucket, tpp):
    """Stack per-wave PodBatches + staged row ids into the fixed-shape
    inputs of ops.kernel.schedule_round: batches padded to the bucket
    with zeroed (valid=False) waves, row ids padded with -1. ONE
    assembly used by both warm_pipeline and _run_pipeline — the warm-up
    must compile byte-identical program shapes to the measured run."""
    P = pbs[0].req.shape[0]
    pad_pb = enc.PodBatch(*[np.zeros_like(a) for a in pbs[0]])
    pbs_padded = list(pbs) + [pad_pb] * (wbucket - len(pbs))
    pbs_stacked = enc.PodBatch(*[np.stack(arrs)
                                 for arrs in zip(*pbs_padded)])
    pm_rows = np.full((wbucket, P), -1, np.int32)
    term_rows = np.full((wbucket, P, tpp), -1, np.int32)
    cursor = 0
    for wi, wv in enumerate(waves):
        n = len(wv)
        pm_rows[wi, :n] = pm_rows_all[cursor:cursor + n]
        term_rows[wi, :n] = term_rows_all[cursor:cursor + n]
        cursor += n
    return pbs_stacked, pm_rows, term_rows


class GroupLister:
    """Selectors of services/RCs/RSs/StatefulSets that select a pod
    (reference: priorities metadata getSelectors,
    algorithm/priorities/metadata.go + selector_spreading.go:230)."""

    def __init__(self, store: ObjectStore):
        self.store = store

    def __call__(self, pod: api.Pod) -> List[lbl.Selector]:
        out: List[lbl.Selector] = []
        for svc in self.store.list("services", pod.namespace):
            if svc.selector and lbl.Selector.from_set(svc.selector).matches(pod.metadata.labels):
                out.append(lbl.Selector.from_set(svc.selector))
        for rc in self.store.list("replicationcontrollers", pod.namespace):
            if rc.selector and lbl.Selector.from_set(rc.selector).matches(pod.metadata.labels):
                out.append(lbl.Selector.from_set(rc.selector))
        for rs in self.store.list("replicasets", pod.namespace):
            if rs.selector is not None:
                sel = rs.selector.to_selector()
                if sel.requirements and sel.matches(pod.metadata.labels):
                    out.append(sel)
        for ss in self.store.list("statefulsets", pod.namespace):
            if ss.selector is not None:
                sel = ss.selector.to_selector()
                if sel.requirements and sel.matches(pod.metadata.labels):
                    out.append(sel)
        return out


class Scheduler:
    # idle backoff entries are swept on this cadence (2x the backoff
    # ceiling matches the reference Gc()'s retention window)
    BACKOFF_GC_PERIOD = 120.0

    def __init__(self, store: ObjectStore, profile: Optional[Profile] = None,
                 wave_size: int = 128, features: Optional[FeatureGates] = None,
                 clock: Callable[[], float] = time.monotonic,
                 assume_ttl: float = 30.0, caps=None, mesh=None,
                 bind_workers: int = 4,
                 scrub_interval: Optional[float] = None,
                 compact_interval: Optional[float] = None,
                 hbm_budget_bytes: int = 0,
                 breaker_threshold: int = 3, breaker_cooldown: float = 30.0,
                 store_breaker_threshold: int = 3,
                 store_breaker_cooldown: float = 30.0,
                 bind_journal_path: Optional[str] = None,
                 bind_journal_max_bytes: int = -1,
                 spool_watermark: int = 0,
                 metrics: Optional[Metrics] = None,
                 bind_max_attempts: int = 3,
                 racecheck: bool = False,
                 shed_watermark: int = 0,
                 shed_priority_threshold: Optional[int] = None,
                 shed_age_s: float = 30.0,
                 wave_deadline_s: float = 0.0,
                 shadow_exact_interval: int = 0,
                 mesh_min_devices: int = 1,
                 poison_backoff_s: float = 5.0,
                 invariants: bool = False):
        self.store = store
        # jax.sharding.Mesh with ("wave", "nodes") axes: wave inputs are
        # committed to NamedShardings before each device step and GSPMD
        # inserts the ICI collectives (parallel/mesh.py). None = single
        # device. This replaces the reference's fixed 16-goroutine fan-out
        # (generic_scheduler.go:378) as the scale-out mechanism.
        self.mesh = mesh
        self.profile = profile or default_profile(store)
        self.wave_size = wave_size
        self.features = features or FeatureGates()
        self.clock = clock
        # Guards cache + snapshot against concurrent informer delivery:
        # with RemoteStore, handlers fire on reflector threads while the
        # wave runs (reference: schedulerCache's mutex, cache.go:42; here
        # coarser because snapshot mutations must be atomic w.r.t. the
        # device upload). RLock: in-process stores deliver bind events
        # re-entrantly on the committing thread.
        self._mu = threading.RLock()
        self.cache = SchedulerCache(ttl=assume_ttl, clock=clock)
        self.snapshot = Snapshot(caps=caps)
        # HBM budget governor: 0 = unlimited (no budget). When set, any
        # _grow that would push the projected device footprint over the
        # budget demands a compaction (the kubelet eviction-manager
        # analog for the scheduler's own memory plane) instead of
        # letting XLA throw RESOURCE_EXHAUSTED mid-wave.
        self.snapshot.hbm_budget_bytes = int(hbm_budget_bytes)
        self.featurizer = PodFeaturizer(self.snapshot, GroupLister(store))
        # overload control: the queue's priority-aware shed plane
        # (sched/queue.py "Overload control") — watermark 0 keeps it off
        from .queue import HIGH_PRIORITY_BAND

        self.queue = SchedulingQueue(
            pod_priority_enabled=self.features.enabled("PodPriority"),
            clock=clock,
            shed_watermark=shed_watermark,
            shed_priority_threshold=(HIGH_PRIORITY_BAND
                                     if shed_priority_threshold is None
                                     else shed_priority_threshold),
            shed_age_s=shed_age_s)
        self.queue.on_shed = self._pod_shed
        # --racecheck: wrap the scheduling-plane locks in the runtime
        # LockOrderWatcher (utils/racecheck.py), the `go test -race`
        # analog. Lock names match the STATIC lock graph's ids
        # (analysis/lockgraph.py), so observed edges are directly
        # comparable: tests assert runtime edges ⊆ static graph. Must
        # run before anything captures the raw lock objects — the
        # scrubber below closes over _mu, and a component holding the
        # unwrapped lock would silently bypass mutual exclusion with
        # proxy holders. The cache carries no lock of its own: it is
        # guarded by Scheduler._mu (see the _mu comment above), so
        # instrumenting _mu covers cache+snapshot access too.
        self.racecheck_watcher = None
        if racecheck:
            from ..utils.racecheck import LockOrderWatcher, instrument

            self.racecheck_watcher = LockOrderWatcher()
            instrument(self.racecheck_watcher, self, "_mu", "Scheduler._mu")
            instrument(self.racecheck_watcher, self.queue, "_lock",
                       "SchedulingQueue._lock")
        # metrics may be a SHARED registry (cli/kube_scheduler.py hands
        # the same one to the RemoteStore's reflectors so control-plane
        # series land on the same /metrics endpoint as scheduling ones)
        self.metrics = metrics or Metrics()
        # an assumed-pod expiry means a bind confirmation was lost —
        # count it (cache logs the warning)
        self.cache.on_expired = (
            lambda pod: self.metrics.cache_assumed_expired.inc())
        # bind reconciler: per-attempt-bounded jittered retries on the
        # bind POST, then GET-against-API-truth resolution of the
        # succeeded-but-response-lost ambiguity (sched/reconciler.py)
        self.reconciler = BindReconciler(
            self._pod_truth, metrics=self.metrics,
            max_attempts=bind_max_attempts,
            on_transport_error=self._store_bind_failed,
            on_transport_ok=self._store_bind_ok)
        # dormant = leadership lost: waves stop, binds drained, informers
        # stay warm; recover_leadership() reconciles + resumes
        self._dormant = False
        # gang (PodGroup) coscheduling: the queue parks incomplete gangs
        # and the wave path routes complete ones through the
        # joint-assignment kernel (ops/gang.py). Costs non-gang pods one
        # annotation lookup at enqueue and one per wave partition.
        self.gangs = GangDirectory(store)
        self.queue.gang_lookup = self.gangs.lookup
        self.queue.on_gang_released = self._gang_released
        self.backoff = PodBackoff(clock=clock)
        self._next_backoff_gc = 0.0
        # poison-work isolation: capped re-probe backoff for CONVICTED
        # pods (sched/queue.py quarantine area). Deliberately separate
        # from the scheduling backoff: a poison conviction is a
        # different fault class (the spec needs an EDIT, not a cluster
        # event), its ladder starts higher and caps far higher, and it
        # only clears on a successful bind or pod deletion.
        self.poison_backoff = PodBackoff(
            initial=max(float(poison_backoff_s), 0.001),
            maximum=max(float(poison_backoff_s), 0.001) * 64,
            clock=clock)
        # cumulative convictions — schedule_pending treats a conviction
        # as progress (the survivors re-run the pipeline), and tests /
        # bench assert on it
        self.poison_convictions = 0
        # snapshot scrubber (state/scrubber.py): audits the HBM mirror
        # against the host cache on SIGUSR2 / the periodic cadence and
        # repairs divergent rows in place. Shares _mu so a scrub can
        # never interleave with a wave's upload.
        self.scrubber = SnapshotScrubber(
            self.cache, self.snapshot, metrics=self.metrics, clock=clock,
            period=scrub_interval, lock=self._mu,
            compact_period=compact_interval)
        # capacity-fault strike ladder (RESOURCE_EXHAUSTED / MemoryError
        # at the device boundary — never a device conviction, never a
        # mesh reform, never a pod conviction): strike 1 compacts and
        # retries, strike 2 additionally halves the adaptive wave cap,
        # strike 3 salvages the round through the host twin. Reset on
        # any successful device round.
        self._capacity_strikes = 0
        # device-path circuit breaker: consecutive device failures route
        # whole waves through the exact host path until a half-open
        # probe succeeds; recovery forces a full snapshot rebuild
        # (nothing incremental is trusted across a device fault)
        self.breaker = DevicePathBreaker(
            threshold=breaker_threshold, cooldown=breaker_cooldown,
            clock=clock, on_recover=self.scrubber.rebuild,
            on_trip=self.metrics.device_path_trips.inc,
            on_state=self._breaker_state_changed)
        self.metrics.breaker_state.set(STATE_CODES[self.breaker.state])
        # store-path circuit breaker (sched/storehealth.py): consecutive
        # transport failures across bind/GET/LIST trip disconnected-mode
        # scheduling — waves keep scoring against the informer cache,
        # binds spool into the durable intent journal, and the oldest
        # spooled intent's own POST serves as the jittered half-open
        # probe. Fed by the reconciler's per-attempt callbacks, the
        # truth-GET seam (_pod_truth) and — for RemoteStore — the
        # reflector relist path (set_health below).
        self.storehealth = StorePathBreaker(
            threshold=store_breaker_threshold,
            cooldown=store_breaker_cooldown, clock=clock,
            on_trip=self.metrics.store_breaker_trips.inc,
            on_state=self._store_state_changed,
            on_reconnect=self._store_reconnected)
        self.metrics.store_breaker_state.set(
            STORE_STATE_CODES[self.storehealth.state])
        set_health = getattr(store, "set_health", None)
        if set_health is not None:
            set_health(self.storehealth)
        # disconnected-mode bind spool: arrival-ordered
        # (pod, bound, node_name, vol_rollback, journal_seq) intents
        # whose POST is deferred until the store heals. The pod STAYS
        # assumed (capacity held; post-heal placements bit-identical to
        # an outage-free run) and the journal holds the durable copy
        # for crash-restart replay. Guarded by _mu.
        self._spool: List[tuple] = []
        self._spool_uids: set = set()
        self._spool_drain_due = False
        self.spool_watermark = int(spool_watermark)
        self.journal = (BindJournal(bind_journal_path,
                                    max_bytes=bind_journal_max_bytes)
                        if bind_journal_path else None)
        # admission hold: while DISCONNECTED with the spool at its
        # watermark, sheddable arrivals park in the shed area (the PR 11
        # overload machinery) instead of growing assumed capacity —
        # the spool stays bounded by watermark + in-queue backlog
        self.queue.hold_admissions = self._admissions_held
        # device telemetry: kernel dispatches account jit cache events
        # into this scheduler's registry; snapshot upload bytes are
        # drained into counters by export_queue_gauges
        from ..ops import kernel as _kernel

        _kernel.set_telemetry(self.metrics)
        # device-dispatch watchdog (utils/watchdog.py): with
        # wave_deadline_s > 0 every dispatch through the record_dispatch
        # seam runs under a deadline budget; an abandoned (wedged)
        # dispatch trips the breaker immediately and the round salvages
        # through the hostwave twin. Registered process-globally like
        # the telemetry hook — None (the default) disarms it, so a
        # later deadline-free scheduler also clears a predecessor's.
        self.wave_deadline_s = float(wave_deadline_s)
        self.watchdog = None
        if self.wave_deadline_s > 0:
            from ..utils.watchdog import DispatchWatchdog

            self.watchdog = DispatchWatchdog(
                self.wave_deadline_s, on_abandon=self._dispatch_abandoned)
        _kernel.set_watchdog(self.watchdog)
        # per-round deadline accounting: host-stage (featurize+upload)
        # overruns degrade the wave size before they degrade latency —
        # _wave_cap halves on overrun (floor MIN_ADAPTIVE_WAVE) and
        # recovers toward wave_size on comfortably-fast rounds
        self._wave_cap = wave_size
        # class-depth gauge cadence: class_counts() walks every pending
        # pod under the queue lock — O(1) area gauges export per wave,
        # the per-class walk at most once per second
        self._next_class_export = 0.0
        self._upload_bytes_seen = 0
        from .volume_binder import VolumeBinder

        self.volume_binder = VolumeBinder(store)
        self._rr = None  # round-robin counter, device i32
        # host-side MIRROR of the logical round-robin counter. Degraded
        # waves must never touch the device-resident _rr (fetching it
        # dispatches to the very runtime the breaker just tripped), so
        # the host tracks it exactly: the device counter advances by one
        # per placement, so a successful device round adds its
        # chosen>=0 count here; twin waves advance it directly and
        # null _rr, so a later device round re-seeds from the mirror.
        # This keeps tie-breaks bit-equal to a clean run ACROSS a
        # device->twin->device transition (breaker recovery, mesh
        # reform salvage) instead of rewinding the counter to 0.
        self._host_rr = 0
        # the Pallas/XLA choice of the round, wave and gang programs,
        # their demotion, and what wave_path() reports (sched/dispatch.py)
        self.formulation = Formulation(
            self.metrics,
            multi_device=mesh is not None and mesh.devices.size > 1)
        # telemetry gauge children exported last traced round
        # ({resource names}, {(zone, resource)}) — pruned when the
        # subject disappears so /metrics never freezes a dead series
        self._tele_exported: Tuple[set, set] = (set(), set())
        # the mesh actually used by the last _to_device upload (None when
        # caps.N doesn't divide the nodes axis — inputs ran unsharded)
        self._active_mesh = None
        # -- mesh fault tolerance (sched/breaker.py MeshFaultManager) --
        # With a multi-device mesh, a device-path failure first walks
        # the degradation LADDER: attribute the culprit device (or
        # bisect), quarantine it, reform a smaller mesh
        # (parallel/mesh.py reform_mesh: 8 -> 4 -> 2 -> 1), salvage the
        # in-flight round through the hostwave twin, and dispatch the
        # next round on the reformed mesh. Only when fewer than
        # mesh_min_devices survive does the failure fall through to the
        # classic whole-path breaker (the host-twin rung). Recovery
        # probes (breaker_cooldown cadence) re-admit healed devices and
        # reform UPWARD. All mesh swaps happen under _mu.
        self.mesh_min_devices = max(int(mesh_min_devices), 1)
        self.meshfaults = None
        if mesh is not None and mesh.devices.size > 1:
            from .breaker import MeshFaultManager

            self.meshfaults = MeshFaultManager(
                list(mesh.devices.flat), clock=clock,
                probe_cooldown=breaker_cooldown)
            _kernel.set_devices([str(d) for d in mesh.devices.flat])
        else:
            _kernel.set_devices(())
        self.metrics.mesh_devices.set(
            int(mesh.devices.size) if mesh is not None else 1)
        # preemptions performed by the batched pipeline path (tests +
        # bench assert the pipeline handled them, not per-wave fallback);
        # device_preemption=False routes the batched what-if through the
        # vectorized numpy twin (ops/hostwave.py preemption_stats_host)
        # instead of the device kernel — the bench's host baseline
        self.pipeline_preemptions = 0
        self.device_preemption = True
        self.ecache = (EquivalenceCache()
                       if self.features.enabled("EnableEquivalenceClassCache")
                       else None)
        # Async bind pipeline (reference scheduler.go:491 `go sched.bind`):
        # assume reserves capacity under _mu, the bind POST runs from this
        # pool OUTSIDE _mu so wave N+1's featurize/device step overlaps
        # wave N's binding. Only enabled for stores that dispatch watch
        # events outside their own lock (RemoteStore via reflector
        # threads, NativeObjectStore) — the in-process ObjectStore
        # delivers events synchronously UNDER its lock by contract, so a
        # binder thread dispatching there while the wave thread (holding
        # _mu) touches the store would deadlock on lock-order inversion;
        # it also has no I/O latency worth hiding. bind_workers=0 forces
        # inline binds everywhere.
        self._bind_pool = None
        if bind_workers > 0 and getattr(store, "async_bind_safe", False):
            from concurrent.futures import ThreadPoolExecutor

            self._bind_pool = ThreadPoolExecutor(
                max_workers=bind_workers, thread_name_prefix="binder")
        self._inflight_mu = threading.Lock()
        self._inflight: set = set()
        self.bind_overlap_hwm = 0  # high-water mark of concurrent binds
        # per-part seconds of the pipeline round's commits, while the
        # step profiler is on (see _commit); None otherwise
        self._commit_parts: Optional[List[float]] = None
        # live weight profiles + the shadow-scoring observatory
        # (sched/weights.py): the production weight vector is served
        # from here as a TRACED array (hot-swap/rollback between rounds,
        # no recompile); candidate profiles are re-scored against every
        # traced wave's decomposition on host. shadow_exact_interval > 0
        # additionally replays the first wave of every Nth traced round
        # through the numpy twin under each candidate — exact
        # divergence, closing the top-K lower bound on samples.
        from .weights import WeightBook

        self.weightbook = WeightBook(self.profile.weights())
        self.shadow_exact_interval = int(shadow_exact_interval)
        self._shadow_rounds = 0
        # the autopilot controller (autopilot/controller.py) registers
        # itself here; the HealthServer serves it at /debug/autopilot
        self.autopilot = None
        # continuously-checked cluster invariants (chaos/invariants.py):
        # opt-in post-round observer; None costs one attribute check per
        # round (the tracing pattern). A checker can also be attached
        # externally (strict=False for end-of-run gating — bench.py).
        self.invariants = None
        if invariants:
            from ..chaos.invariants import InvariantChecker

            self.invariants = InvariantChecker(metrics=self.metrics)
        # gang-commit rollback test hook: the chaos campaign's
        # deliberately-broken-build acceptance check flips this False to
        # prove a partial gang commit without rollback is caught by the
        # conservation/gang_atomic invariants. NEVER disable outside a
        # test.
        self._gang_rollback_enabled = True
        # crash-journal replay test hook: the chaos campaign's
        # broken-build acceptance flips this False to prove that a
        # build which neither drains the spool nor replays the journal
        # is caught by the conservation invariant's
        # spool-outlived-the-outage rule. NEVER disable outside a test.
        self._journal_replay_enabled = True
        self._wire_informers()
        # a warm store (crash restart / failover) backfills bound pods
        # BEFORE their nodes above, so the per-event snapshot adds can
        # land against absent node rows — rebuild the mirror from host
        # truth exactly like recover_leadership does, before the first
        # wave ever reads it
        if any(ni.pods for ni in self.cache.node_infos.values()):
            self.scrubber.rebuild()
        # after informer backfill (which re-queues Pending pods a prior
        # process had claimed) so replay can retire journal-claimed pods
        # from the queue before the first wave
        self.recover_from_journal()

    # -- informer handlers (reference: factory.go:191-295) --------------------

    def _wire_informers(self):
        name = self.profile.scheduler_name
        self.pod_informer = SharedInformer(self.store, "pods")
        self.pod_informer.add_event_handler(
            on_add=self._on_pod_add, on_update=self._on_pod_update,
            on_delete=self._on_pod_delete)
        self.node_informer = SharedInformer(self.store, "nodes")
        self.node_informer.add_event_handler(
            on_add=self._on_node_add, on_update=lambda o, n: self._on_node_add(n),
            on_delete=self._on_node_delete)
        for kind in ("services", "replicationcontrollers", "replicasets",
                     "statefulsets"):
            SharedInformer(self.store, kind).add_event_handler(
                on_add=lambda o: self._invalidate_features(),
                on_update=lambda o, n: self._invalidate_features(),
                on_delete=lambda o: self._invalidate_features())
        # a PodGroup created/updated AFTER its pods may complete a gang
        # that was parked against a higher annotation-derived minMember
        SharedInformer(self.store, "podgroups").add_event_handler(
            on_add=lambda o: self.queue.gang_reevaluate(),
            on_update=lambda o, n: self.queue.gang_reevaluate())
        # live weight profiles: the watch IS the hot-swap/rollback path
        # — promoting a candidate to role=live (or demoting/deleting the
        # live one) takes effect on the next round, under _mu so a swap
        # never interleaves with a wave
        SharedInformer(self.store, "weightprofiles").add_event_handler(
            on_add=self._on_weight_profile,
            on_update=lambda o, n: self._on_weight_profile(n),
            on_delete=self._on_weight_profile_delete)
        if self.ecache is not None:
            # targeted ecache invalidation (factory.go:191-295 wiring).
            # Must serialize with _run_wave under _mu like the pod/node
            # handlers: an invalidation racing a wave would otherwise be
            # overwritten by the wave's stale ecache.update, resurrecting
            # the entry the event just killed.
            def _vol_event(*_):
                with self._mu:
                    self.ecache.on_volume_event()

            def _svc_event(*_):
                with self._mu:
                    self.ecache.on_service_event()

            for kind in ("persistentvolumes", "persistentvolumeclaims"):
                SharedInformer(self.store, kind).add_event_handler(
                    on_add=_vol_event, on_update=_vol_event,
                    on_delete=_vol_event)
            SharedInformer(self.store, "services").add_event_handler(
                on_add=_svc_event, on_update=_svc_event,
                on_delete=_svc_event)

    def _responsible(self, pod: api.Pod) -> bool:
        return pod.spec.scheduler_name == self.profile.scheduler_name

    def _on_pod_add(self, pod: api.Pod):
        with self._mu:
            if pod.spec.node_name:
                if self.ecache is not None:
                    self.ecache.on_assigned_pod_event(pod.spec.node_name)
                self.cache.add_pod(pod)
                ni = self.cache.node_infos.get(pod.spec.node_name)
                if ni is not None:
                    self.snapshot.refresh_node_resources(ni)
                self.snapshot.add_pod(pod)
                self.queue.assigned_pod_added(pod)
            elif self._responsible(pod) and pod.status.phase in ("", "Pending"):
                self.queue.add(pod)

    def _on_pod_update(self, old: api.Pod, new: api.Pod):
        with self._mu:
            if new.spec.node_name:
                if self.ecache is not None:
                    self.ecache.on_assigned_pod_event(new.spec.node_name)
                if old.spec.node_name:
                    self.cache.update_pod(old, new)
                else:
                    self.cache.add_pod(new)  # bind confirmation
                ni = self.cache.node_infos.get(new.spec.node_name)
                if ni is not None:
                    self.snapshot.refresh_node_resources(ni)
                self.snapshot.add_pod(new)
                self.queue.assigned_pod_added(new)
            elif self._responsible(new):
                self.queue.update(old, new)

    def _on_pod_delete(self, pod: api.Pod):
        with self._mu:
            if pod.spec.node_name:
                if self.ecache is not None:
                    self.ecache.on_assigned_pod_event(pod.spec.node_name)
                self.cache.remove_pod(pod)
                ni = self.cache.node_infos.get(pod.spec.node_name)
                if ni is not None:
                    self.snapshot.refresh_node_resources(ni)
                self.snapshot.remove_pod(pod)
                # a BOUND gang member leaving must shrink its gang's
                # member count, or a stale uid would open the admission
                # gate for a sub-minMember gang
                self.queue.gang_forget(pod)
                self.queue.move_all_to_active()
            else:
                self.queue.delete(pod)

    def _on_node_add(self, node: api.Node):
        with self._mu:
            if self.ecache is not None:
                self.ecache.on_node_event(node.name)
            self.cache.add_node(node)
            self.snapshot.set_node(self.cache.node_infos[node.name])
            self.queue.move_all_to_active()

    def _on_node_delete(self, node: api.Node):
        with self._mu:
            if self.ecache is not None:
                self.ecache.on_node_event(node.name)
            self.cache.remove_node(node)
            self.snapshot.remove_node(node.name)

    def _invalidate_features(self):
        # group membership may have changed -> equivalence rows are stale
        self.featurizer._cache.clear()

    # -- live weight profiles --------------------------------------------------

    def _on_weight_profile(self, obj):
        with self._mu:
            before = self.weightbook.live_version()
            try:
                self.weightbook.on_profile(obj)
            except ValueError as e:
                # a typo'd weight table must not take down the watch —
                # the previous table stays in force, the error is loud
                logging.getLogger(__name__).error(
                    "rejecting WeightProfile %s: %s",
                    obj.metadata.name, e)
                return
            after = self.weightbook.live_version()
        if after != before:
            logging.getLogger(__name__).info(
                "weight vector hot-swapped: %s -> %s", before, after)
            tracing.event("weights_swapped", before=before, after=after)

    def _on_weight_profile_delete(self, obj):
        with self._mu:
            before = self.weightbook.live_version()
            self.weightbook.on_profile_delete(obj)
            after = self.weightbook.live_version()
        if after != before:
            logging.getLogger(__name__).info(
                "weight vector rolled back: %s -> %s", before, after)
            tracing.event("weights_swapped", before=before, after=after)

    def _weights_kw(self):
        """(gating Weights, f32 [S] live vector, version string) for one
        round: the static arg gates which score planes compile, the
        vector — passed traced as the kernel's weight_vec — supplies the
        multipliers (so hot-swapping values never recompiles), and the
        version is what the round's ledger record and decision entries
        report. Resolved under ONE WeightBook lock hold
        (dispatch_view), so a swap or rollback landing mid-round can
        never split the vector a round dispatched under from the
        version it claims."""
        return self.weightbook.dispatch_view(self.profile.weights())

    def _golden_reasons(self, pods: List[api.Pod]) -> Dict[str, int]:
        """{reason: count} of pods routed to the exact golden path —
        the pods with NO ScoreDeco, i.e. the shadow observatory's
        per-round coverage gap."""
        counts: Dict[str, int] = {}
        for p in pods:
            r = (self.featurizer.golden_reason(p)
                 if _pod_has_ipa_terms(p) or self.featurizer.needs_host_path(p)
                 else "nominated")
            counts[r] = counts.get(r, 0) + 1
        return counts

    def _split_golden(self, pods: List[api.Pod]):
        """(pods for the exact golden path, the rest). Golden takes what
        the device cannot encode (needs_host_path) and, while the queue
        holds nominations, what the device's nomination term does not
        count: it adds a nominated pod's requests to its node, not its
        inter-pod (anti)affinity terms or labels. So then the pods with
        such terms go to golden, and every pod where a nominated pod
        carries them."""
        nominated = [p for p, _ in self.queue.nominated_pods()]
        every = any(_pod_has_ipa_terms(p) for p in nominated)
        host, rest = [], []
        for p in pods:
            if (every or self.featurizer.needs_host_path(p)
                    or (nominated and _pod_has_ipa_terms(p))):
                host.append(p)
            else:
                rest.append(p)
        return host, rest

    # -- observability hooks ---------------------------------------------------

    def _breaker_state_changed(self, state: str) -> None:
        """Every breaker transition lands on the state gauge (0=closed,
        1=half-open, 2=open) and, when tracing, as a span event — the
        trips counter alone can't tell an operator whether scheduling is
        degraded RIGHT NOW."""
        self.metrics.breaker_state.set(STATE_CODES[state])
        rec = tracing.active()
        if rec is not None:
            rec.event("breaker", state=state,
                      failures=self.breaker.failures)

    def _store_state_changed(self, state: str) -> None:
        """Store-path breaker transitions land on the state gauge
        (0=connected, 1=degraded, 2=disconnected) and as a span event —
        like the device breaker, operators need to see the DEGRADED
        window, not only the trip counter."""
        self.metrics.store_breaker_state.set(STORE_STATE_CODES[state])
        rec = tracing.active()
        if rec is not None:
            rec.event("store_breaker", state=state,
                      failures=self.storehealth.failures,
                      spool=len(self._spool))

    def _store_reconnected(self) -> None:
        """record_success fires this from whatever thread observed the
        heal (a binder, the reflector, a recovery GET) — draining
        inline there could re-enter the reconciler from its own
        callback, so only flag it; the next housekeeping pass drains on
        the scheduling thread."""
        self._spool_drain_due = True

    def _store_bind_failed(self) -> None:
        # reconciler on_transport_error: one failed bind POST attempt
        self.metrics.store_errors.labels(op="bind").inc()
        self.storehealth.record_failure()

    def _store_bind_ok(self) -> None:
        self.storehealth.record_success()

    def _admissions_held(self) -> bool:
        """queue.hold_admissions hook — outage with the spool at its
        watermark: park sheddable arrivals in the shed area until the
        store heals (system/high classes are never held, exactly like
        overload shedding)."""
        return (self.spool_watermark > 0
                and self.storehealth.state == STORE_DISCONNECTED
                and len(self._spool) >= self.spool_watermark)

    def spool_count(self) -> int:
        with self._mu:
            return len(self._spool)

    def spool_uids(self) -> frozenset:
        """UIDs currently spooled — the invariant checker's legal
        assumed-but-unbound set for the duration of an outage."""
        with self._mu:
            return frozenset(self._spool_uids)

    def store_debug(self) -> Dict[str, object]:
        """The /debug/store payload: breaker snapshot, spool depth,
        journal stats, per-op store error counters."""
        out = self.storehealth.snapshot()
        with self._mu:
            out["spool"] = {
                "depth": len(self._spool),
                "watermark": self.spool_watermark,
                "oldest_seq": self._spool[0][4] if self._spool else None,
                "drain_due": self._spool_drain_due,
            }
        out["journal"] = (self.journal.stats()
                          if self.journal is not None else None)
        out["errors"] = {
            op: self.metrics.store_errors.value(op=op)
            for op in ("get", "list", "bind", "create", "update",
                       "delete", "watch")}
        return out

    def _pod_shed(self, cls: str) -> None:
        """Queue shed hook: one increment per shed decision, labelled
        by priority class (sheds of system/high are the SLO violation
        the storm gates hold at zero)."""
        self.metrics.shed_total.labels(**{"class": cls}).inc()
        rec = tracing.active()
        if rec is not None:
            rec.event("pod_shed", cls=cls)

    def _dispatch_abandoned(self, program: str, deadline: float) -> None:
        """Watchdog abandonment hook: the overrun counter's dispatch
        stage, a span event, and a log line — the wave itself raises
        DispatchTimeout into the normal device-failure path."""
        self.metrics.wave_deadline_overruns.labels(stage="dispatch").inc()
        logging.getLogger(__name__).error(
            "device dispatch %s abandoned after %.3fs deadline; runtime "
            "presumed wedged until it returns", program, deadline)
        rec = tracing.active()
        if rec is not None:
            rec.event("dispatch_abandoned", program=program,
                      deadline_s=round(deadline, 3))

    # floor of the adaptive wave cap: below this the per-wave fixed
    # costs dominate and halving further only multiplies round count
    MIN_ADAPTIVE_WAVE = 16

    def _account_host_overrun(self, host_seconds: float) -> None:
        """Per-round deadline accounting for the HOST stages
        (featurize/stage/upload): a round whose host side alone exceeds
        wave_deadline_s halves the adaptive wave cap — smaller waves
        bound per-round latency at the cost of more rounds — and
        comfortably-fast rounds (under a quarter of the budget) double
        it back toward wave_size. No-op while wave_deadline_s is 0."""
        if self.wave_deadline_s <= 0:
            return
        if host_seconds > self.wave_deadline_s:
            self.metrics.wave_deadline_overruns.labels(stage="host").inc()
            # floor clamped to wave_size: a scheduler configured BELOW
            # the adaptive floor must never have overload RAISE its wave
            self._wave_cap = max(self._wave_cap // 2,
                                 min(self.MIN_ADAPTIVE_WAVE,
                                     self.wave_size))
        elif (host_seconds <= self.wave_deadline_s / 4
                and self._wave_cap < self.wave_size):
            self._wave_cap = min(self._wave_cap * 2, self.wave_size)
        self.metrics.effective_wave_size.set(self._wave_cap)

    def _runtime_wedged(self) -> bool:
        """Is a watchdog-abandoned dispatch still in flight? The
        runtime is presumed wedged until that thread returns."""
        return self.watchdog is not None and bool(
            self.watchdog.outstanding())

    def _device_admitted(self) -> bool:
        """May this wave/round dispatch to the device? False while the
        runtime is wedged: even the breaker's half-open probe must not
        be spent on it — allow() is deliberately not consulted, so the
        OPEN -> HALF_OPEN transition (and the probe it admits) is
        deferred until the wedge clears."""
        if self._runtime_wedged():
            return False
        return self.breaker.allow()

    def _gang_released(self, key: str, waited: float) -> None:
        self.metrics.gang_wait_seconds.observe(waited)
        rec = tracing.active()
        if rec is not None:
            now = rec.now()
            rec.add_span("gang_wait", now - waited, now, cat="gang",
                         gang=key, waited_s=round(waited, 6))

    def _begin_round(self, kind: str, pods: List[api.Pod], wver: str,
                     golden: Optional[Dict[str, int]] = None, **meta):
        """(recorder, round trace) of a traced round, (None, None) when
        tracing is off. The trace carries each pod's queue_wait span
        and, where golden-path pods were scheduled beside the round,
        their count by reason: they have no ScoreDeco, the shadow
        observatory's coverage gap."""
        rec = tracing.active()
        if rec is None:
            return None, None
        rt = rec.begin_round(kind, pending=len(pods), **meta,
                             weights_version=wver)
        self._trace_queue_waits(rt, pods)
        if golden:
            rt.ledger["golden"] = dict(golden)
        return rec, rt

    def _host_planes(self, pods: List[api.Pod], P: int):
        """(extra mask, extra scores) of the host plugins and extenders
        for a batch, or None when a non-ignorable extender is
        unreachable: then only this attempt fails, and the batch parks
        for retry on the next cluster event (reference: scheduleOne
        records the error and MakeDefaultErrorFunc requeues with
        backoff)."""
        try:
            return (self._host_plugin_mask(pods, P),
                    self._host_score_matrix(pods, P))
        except ExtenderError:
            self.metrics.scheduling_errors.labels(stage="extender").inc()
            for p in pods:
                self._park_with_backoff(p)
            return None

    def _has_ipa(self, pbs) -> bool:
        """The has_ipa static of a program over these batches:
        inter-pod (anti)affinity already placed, or asked for by a pod."""
        return bool(self.snapshot.has_affinity_terms
                    or any(pb.ra_has.any() or pb.rn_has.any()
                           or (pb.pa_w != 0).any() for pb in pbs))

    def _trace_queue_waits(self, rt, pods: List[api.Pod]) -> None:
        """Per-pod queue_wait spans (first enqueue -> popped into this
        round), keyed by UID; added_at survives until bind so reading it
        here consumes nothing."""
        now = self.clock()
        added_at = self.queue.added_at
        for p in pods:
            added = added_at.get(p.uid)
            if added is not None:
                rt.pod_span(p.uid, "queue_wait", now - added)

    def _round_snapshot_shape(self) -> Dict[str, int]:
        c = self.snapshot.caps
        return {"nodes": int(np.sum(self.snapshot.valid)),
                "N": c.N, "M": c.M, "E": c.E}

    def _record_decisions(self, rec, pods: List[api.Pod], chosen,
                          cparts, tidx, tvals, tparts,
                          committed: Optional[set] = None,
                          wvec=None, wver: Optional[str] = None):
        """Consume one fetched ScoreDeco slice ([P, ...] numpy arrays
        aligned with `pods`): per-pod decision entries into the
        recorder's observatory (/debug/score), margin observations into
        scheduler_score_margin, weighted per-priority contributions into
        scheduler_score_priority_points_total, the counterfactual
        shadow pass over every candidate WeightProfile, and a
        (scores, shadow) pair of per-round aggregates for the ledger.
        Tracing-only by construction — callers gate on the recorder.

        committed: uids whose exact-recheck commit succeeded. A device
        choice the int64 recheck rejected never became a placement —
        recording it would have /debug/score claim a binding that
        never happened.

        wvec/wver: the dispatch-time weight view (_weights_kw) — the
        weights this round ACTUALLY dispatched under. /debug/score and
        the ledger breakdown must describe the decision that happened,
        so a live re-read (the None fallback, for direct callers only)
        would mislabel a round raced by a swap or rollback."""
        from ..ops.scores import SCORE_STACK

        w = wvec if wvec is not None else self.weightbook.live_vector()
        if wver is None:
            wver = self.weightbook.live_version()
        shadow = self.weightbook.score_wave(
            pods, chosen, self.snapshot.node_names, cparts, tidx, tvals,
            tparts, committed=committed, metrics=self.metrics)
        margins: List[float] = []
        totals: List[float] = []
        contrib = np.zeros(len(SCORE_STACK), np.float64)
        names = self.snapshot.node_names
        placed = 0
        for i, pod in enumerate(pods):
            c = int(chosen[i])
            if c < 0 or c >= len(names):
                continue
            if committed is not None and pod.uid not in committed:
                continue
            placed += 1
            total = float(tvals[i][0])  # argmax total == top-1 value
            totals.append(total)
            # runner-up: best-scoring DIFFERENT feasible node (the
            # chosen node usually occupies rank 0; round-robin
            # tie-breaks can place it deeper, so scan)
            runner = None
            for j in range(tidx[i].shape[0]):
                if int(tidx[i][j]) != c and float(tvals[i][j]) >= 0:
                    runner = j
                    break
            margin = (total - float(tvals[i][runner])
                      if runner is not None else None)
            if margin is not None:
                margins.append(margin)
                self.metrics.score_margin.observe(margin)
            wparts = w.astype(np.float64) * cparts[i]
            contrib += wparts
            parts = {}
            for s, name in enumerate(SCORE_STACK):
                parts[name] = {
                    "weight": float(w[s]),
                    "chosen": round(float(cparts[i][s]), 4),
                    "runner_up": (round(float(tparts[i][s][runner]), 4)
                                  if runner is not None else None)}
            top = [{"node": names[int(tidx[i][j])],
                    "total": round(float(tvals[i][j]), 4)}
                   for j in range(tidx[i].shape[0])
                   if float(tvals[i][j]) >= 0 and int(tidx[i][j]) < len(names)]
            rec.record_decision(pod.uid, {
                "pod": pod.full_name(),
                "node": names[c],
                "round": rec.current().rid,
                "total": round(total, 4),
                "margin": None if margin is None else round(margin, 4),
                "runner_up": (names[int(tidx[i][runner])]
                              if runner is not None else None),
                "weights_version": wver,
                "weights": [float(x) for x in w],
                "parts": parts,
                "top": top,
            })
        if not placed:
            return None, shadow
        for s, name in enumerate(SCORE_STACK):
            if contrib[s]:
                self.metrics.score_priority_points.labels(
                    priority=name).inc(float(contrib[s]))
        # schema note: parts/breakdown/weights are keyed (and ordered) by
        # SCORE_STACK, so growing the stack — e.g. the TopologySpread /
        # TopologyCompactness planes — extends these records in place.
        # Readers must key by plane NAME, never by position or a fixed
        # plane count; that is what makes stack growth version-bump-free.
        out: Dict = {
            "min": round(min(totals), 4), "max": round(max(totals), 4),
            "mean": round(sum(totals) / len(totals), 4),
            "breakdown": {name: round(float(contrib[s]) / placed, 4)
                          for s, name in enumerate(SCORE_STACK)
                          if contrib[s]},
        }
        if margins:
            out["margin"] = {
                "min": round(min(margins), 4),
                "mean": round(sum(margins) / len(margins), 4),
                "max": round(max(margins), 4)}
        return out, shadow

    def _shadow_exact_sample(self, wave_pods, pb, chosen_row, rr_start,
                             has_ipa: bool, gating,
                             nom=None) -> Optional[Dict]:
        """Opt-in exact shadow mode (shadow_exact_interval > 0): every
        Nth traced round replays its FIRST wave through the numpy host
        twin under each candidate vector — exact candidate placements,
        calibrating the top-K lower bound on samples. Must run before
        any commit mutates the snapshot. Costs one host wave per
        candidate plus one scalar rr fetch per sampled round. The twin
        carries the inter-pod affinity plane too, so affinity rounds
        sample exactly like any other."""
        if (self.shadow_exact_interval <= 0
                or not self.weightbook.has_candidates()):
            return None
        self._shadow_rounds += 1
        if self._shadow_rounds % self.shadow_exact_interval:
            return None
        from ..ops import hostwave
        from .weights import gate_weights

        nt, pm, tt = self.snapshot.host_tensors()
        P = pb.req.shape[0]
        extra = np.ones((P, nt.valid.shape[0]), bool)
        rr0 = 0 if rr_start is None else int(np.asarray(rr_start))
        n = len(wave_pods)
        chosen_dev = np.asarray(chosen_row)[:n]
        out: Dict[str, Dict] = {}
        for name, vec in self.weightbook.candidate_vectors().items():
            res, _u = hostwave.schedule_wave_host(
                nt, pm, tt, pb, extra, rr0, None,
                weights=gate_weights(gating, vec),
                num_zones=self.snapshot.caps.Z,
                num_label_values=self.snapshot.num_label_values,
                has_ipa=has_ipa,
                weight_vec=vec, nom=nom)
            flips = int(np.sum(np.asarray(res.chosen)[:n] != chosen_dev))
            self.weightbook.record_exact(name, n, flips)
            out[name] = {"pods": n, "flips": flips}
        return out or None

    @staticmethod
    def _merge_exact(shadow: Optional[Dict],
                     exact_info: Optional[Dict]) -> Optional[Dict]:
        """Fold a sampled exact-mode result into the round's shadow
        ledger record (creating profile entries the lower-bound pass
        produced nothing for)."""
        if not exact_info:
            return shadow
        shadow = shadow or {}
        for name, ex in exact_info.items():
            shadow.setdefault(
                name, {"pods": 0, "flips": 0,
                       "lower_bound": True})["exact"] = ex
        return shadow

    def _resource_names(self) -> List[str]:
        """Column -> resource name for the telemetry exports (core
        columns by convention, extended ones from the resource vocab)."""
        from ..ops.telemetry import CORE_RESOURCE_NAMES

        names = list(CORE_RESOURCE_NAMES)
        for c in range(enc.RES_FIXED, self.snapshot.caps.R):
            try:
                names.append(self.snapshot.extended.string(
                    c - enc.RES_FIXED + 1))
            except Exception:
                names.append(f"ext{c}")
        return names

    def _emit_telemetry(self, rt, device_ok: bool = True) -> None:
        """One cluster-state reduction for a TRACED round (rt is the
        round trace; callers gate on it, so tracing off costs nothing):
        the jitted on-device kernel over the resident planes while the
        breaker allows, the numpy twin otherwise — gauges refreshed,
        the round-ledger record extended, the stage span marked.

        device_ok: False from degraded rounds — they are entered either
        with the breaker open or as the immediate fallback after a
        device failure the breaker hasn't tripped on yet; either way
        the runtime just misbehaved and a telemetry dispatch could hang
        the loop where the scheduling path deliberately stepped away."""
        from ..ops import telemetry as tele

        Z = self.snapshot.caps.Z
        R = self.snapshot.caps.R
        packed = None
        backend = "host"
        # passive breaker check: allow() would consume the half-open
        # probe (OPEN -> HALF_OPEN after cooldown) and dispatch an
        # upload+fetch to a possibly-wedged runtime — the probe belongs
        # to a scheduling wave, telemetry only rides a CLOSED breaker
        # (and never a runtime with a watchdog-abandoned wave in flight)
        if (device_ok and self.breaker.state == breaker_mod.CLOSED
                and not self._runtime_wedged()):
            try:
                nt, _pm, _tt = self._to_device()
                packed = np.asarray(tele.cluster_telemetry(nt, num_zones=Z))
                self.metrics.device_fetch_bytes.inc(packed.nbytes)
                backend = "device"
            except Exception:
                # telemetry must never fail a scheduling round; the
                # twin serves it from the host planes instead
                self.metrics.scheduling_errors.labels(
                    stage="telemetry").inc()
                packed = None
        if packed is None:
            from ..ops import hostwave

            nt, _pm, _tt = self.snapshot.host_tensors()
            packed = hostwave.cluster_telemetry_host(nt, num_zones=Z)
        ct = tele.ClusterTelemetry(packed, R, Z)
        res_names = self._resource_names()
        util = ct.utilization()
        frag = ct.fragmentation()
        m = self.metrics
        seen_res: set = set()
        for c, name in enumerate(res_names):
            if not (ct.alloc_total[c] or ct.req_total[c]):
                continue
            seen_res.add(name)
            m.cluster_requested.labels(resource=name).set(
                float(ct.req_total[c]))
            m.cluster_allocatable.labels(resource=name).set(
                float(ct.alloc_total[c]))
            m.cluster_free_largest.labels(resource=name).set(
                float(ct.free_max[c]))
            m.cluster_fragmentation.labels(resource=name).set(
                float(frag[c]))
        for k, (sname, _cpu, _mem) in enumerate(tele.CANONICAL_SHAPES):
            m.feasibility_headroom.labels(shape=sname).set(
                int(ct.headroom[k]))
        zones = {}
        seen_zone: set = set()
        # zone slot 0 is "no zone key" (the vocab pad) — real zones only
        for z in range(1, Z):
            if not np.any(ct.zone_alloc[z]):
                continue
            try:
                zname = self.snapshot.vocabs.zones.string(z)
            except Exception:
                zname = str(z)
            zu = {}
            for c, name in enumerate(res_names):
                if ct.zone_alloc[z][c]:
                    u = float(ct.zone_req[z][c] / ct.zone_alloc[z][c])
                    zu[name] = round(u, 4)
                    seen_zone.add((zname, name))
                    m.zone_utilization.labels(zone=zname,
                                              resource=name).set(u)
            zones[zname] = zu
        # a zone or resource that disappeared must stop exporting, not
        # freeze at its last value on /metrics forever
        prev_res, prev_zone = self._tele_exported
        for name in sorted(prev_res - seen_res):
            for fam in (m.cluster_requested, m.cluster_allocatable,
                        m.cluster_free_largest, m.cluster_fragmentation):
                fam.remove(resource=name)
        for zname, name in sorted(prev_zone - seen_zone):
            m.zone_utilization.remove(zone=zname, resource=name)
        self._tele_exported = (seen_res, seen_zone)
        summary = {
            "backend": backend,
            "nodes": ct.nodes_valid,
            "schedulable": ct.nodes_schedulable,
            "util": {n: round(float(util[c]), 4)
                     for c, n in enumerate(res_names)
                     if ct.alloc_total[c]},
            "frag": {n: round(float(frag[c]), 4)
                     for c, n in enumerate(res_names)
                     if ct.free_total[c]},
            "headroom": {sname: int(ct.headroom[k])
                         for k, (sname, _c, _m2) in
                         enumerate(tele.CANONICAL_SHAPES)},
            "free_hist": {n: ct.free_hist[c].tolist()
                          for c, n in enumerate(res_names)
                          if ct.alloc_total[c]},
        }
        if zones:
            summary["zones"] = zones
        rt.ledger["telemetry"] = summary
        rt.mark("telemetry", backend=backend)

    def _count_unschedulable(self, err: FitError) -> None:
        """scheduler_unschedulable_reasons_total{predicate}: one
        increment per (failed pod, first-fail predicate) — the FitError
        text's attribution, finally visible to dashboards."""
        for reason, count in err.failed_predicates.items():
            if not count:
                continue
            if reason.startswith("Insufficient "):
                pred = "PodFitsResources"
            else:
                # free-text reasons (filter extenders, host plugins)
                # would mint an unbounded, unescaped label value per
                # unique message — bucket them into "Other"; the exact
                # text still reaches events via the FitError
                pred = bounded_label(REASON_KEYS.get(reason, reason),
                                     REASONS)
            self.metrics.unschedulable_reasons.labels(predicate=pred).inc()

    def _to_device(self) -> Tuple[enc.NodeTensors, enc.PodMatrix,
                                  enc.TermTable]:
        """Snapshot upload honoring the scheduler's mesh: node tensors
        sharded on the "nodes" axis, pod/term tables replicated — or
        plain single-device when no mesh is configured / the N bucket
        doesn't divide the nodes axis (capacity buckets are powers of
        two, so with a power-of-two mesh this only happens while the
        cluster is smaller than the mesh). Records the mesh actually
        used in self._active_mesh so callers shard the remaining wave
        inputs consistently."""
        mesh = self.mesh
        if mesh is not None:
            from ..parallel.mesh import nodes_divide

            if not nodes_divide(mesh, self.snapshot.caps.N):
                mesh = None
        self._active_mesh = mesh
        return self.snapshot.to_device(mesh=mesh)

    def _max_waves(self, pods: List[api.Pod]) -> int:
        """The round's wave cap: ipa anywhere in the backlog (or already
        placed) caps it at the ipa-safe count, even for ipa-free leading
        rounds."""
        return (PIPELINE_MAX_WAVES_IPA
                if (self.snapshot.has_affinity_terms
                    or any(_pod_has_ipa_terms(p) for p in pods))
                else PIPELINE_MAX_WAVES)

    def _device_inputs(self, pbs, wvec, nom=None, rows=None, wave=None):
        """(has_ipa, wv, nom, rows, wave): what a device program takes
        beside the snapshot, for the round, the wave, the gang and the
        warm-up. Under a mesh the rr carry, the weights, the nominations
        and `rows` (the round's stacked batch and staged row ids)
        replicate, and `wave` (nt, pm, tt, pb, extra, extra_scores of the
        wave and the gang) shards where the mesh divides it."""
        import jax.numpy as jnp

        has_ipa = self._has_ipa(pbs)
        if self._rr is None:
            # re-seed from the host mirror: a twin-salvaged round nulls
            # _rr after advancing _host_rr, so device resumption keeps
            # the logical counter continuous (bit-equal tie-breaks)
            self._rr = jnp.asarray(self._host_rr, jnp.int32)
        wv = jnp.asarray(wvec)
        mesh = self._active_mesh
        if mesh is not None:
            from ..parallel.mesh import (mesh_divides, replicate,
                                         shard_extra, shard_inputs)

            # every input carries the same commitment on every path:
            # shardings are part of the jit cache key, and the rr carry
            # may still sit on one device from rounds run before the
            # cluster grew to divide the mesh
            self._rr = replicate(mesh, self._rr)
            wv = replicate(mesh, wv)
            if nom is not None:
                nom = replicate(mesh, nom)
            if rows is not None:
                rows = replicate(mesh, rows)
            if wave is not None and mesh_divides(
                    mesh, wave[0].valid.shape[0], wave[3].req.shape[0]):
                # re-putting nt/pm/tt to their identical shardings
                # transfers nothing; this shards the pod batch and masks
                extra_scores = wave[5]
                wave = shard_inputs(mesh, *wave[:5]) + (
                    None if extra_scores is None
                    else shard_extra(mesh, extra_scores),)
        return has_ipa, wv, nom, rows, wave

    def wave_path(self) -> str:
        """Which formulation the most recently executed program actually
        used: 'pallas' or 'xla' on the device path, 'vector' for the
        numpy host twin (degraded waves), or 'unresolved' before any
        wave or round has run. This reports executions, not intent — the
        round, wave and gang programs resolve their formulation
        independently."""
        return self.formulation.last_path or "unresolved"

    # -- the wave cycle --------------------------------------------------------

    def schedule_pending(self, max_waves: Optional[int] = None) -> int:
        """Run waves until the active queue drains, then drain in-flight
        binds so the store state is settled on return. Returns pods
        placed (assumed + bind dispatched).

        EVERY backlog takes the device-resident round first (see
        _schedule_pipelined), bucketed to its wave count
        (pipeline_bucket). Stragglers, extenders and host plugins fall
        through to the per-wave loop below."""
        placed = 0
        waves = 0
        allow_pipeline = True
        while not self._dormant:
            if self.queue.active_count() == 0:
                # a failed async bind may requeue a pod: settle and recheck
                self.wait_for_binds()
                if self.queue.active_count() == 0:
                    break
            # extenders / policy host priorities force per-wave host
            # evaluation anyway — attempting the pipeline first would
            # double every extender webhook call just to bail out.
            # A configured mesh runs the pipeline too: the round program
            # is partitionable XLA and _run_pipeline commits its inputs
            # to the mesh shardings (GSPMD inserts the collectives).
            if (allow_pipeline and max_waves is None
                    and not self.profile.extenders
                    and not self.profile.host_scores):
                pre = self.pipeline_preemptions
                pre_poison = self.poison_convictions
                n = self._schedule_pipelined()
                self._check_invariants()
                placed += n
                if (n > 0 or self.pipeline_preemptions > pre
                        or self.poison_convictions > pre_poison):
                    # preemptions and poison convictions are progress
                    # too: victims were evicted / culprits quarantined,
                    # and the survivors should re-run the PIPELINE (so
                    # their placements stay bit-equal a clean run's)
                    continue
                # zero progress is systemic (host plugins/extenders in
                # play, or an unplaceable backlog): disable the pipeline
                # for the rest of this drain — re-attempting it before
                # every per-wave step would re-pop and re-stage the whole
                # remaining backlog each time, O(waves^2) work
                allow_pipeline = False
            placed += self.run_once()
            waves += 1
            if max_waves is not None and waves >= max_waves:
                break
        self.wait_for_binds()
        self.export_queue_gauges()
        self._check_invariants()
        return placed

    def _housekeep(self) -> None:
        """Per-cycle maintenance: expire assumed pods, sweep idle
        backoff entries (PodBackoff.gc, reference backoff_utils.go Gc —
        previously never invoked, so every pod that EVER failed held an
        entry forever), refresh the queue-depth gauges, and run the
        snapshot scrubber if its signal or cadence fired."""
        with self._mu:
            self.cache.cleanup_expired()
        # disconnected-mode spool: drain when the store path is healthy
        # again (reconnect flagged by the breaker), or use the oldest
        # spooled intent as the half-open probe once the jittered
        # cooldown elapses (allow() admits exactly one). Gated on the
        # replay hook so the chaos broken-build acceptance can model a
        # build that never drains.
        if (self._journal_replay_enabled and self._spool
                and (self.storehealth.state != STORE_DISCONNECTED
                     or self.storehealth.allow())):
            self._drain_spool()
        now = self.clock()
        if now >= self._next_backoff_gc:
            self._next_backoff_gc = now + self.BACKOFF_GC_PERIOD
            self.backoff.gc()
            self.poison_backoff.gc()
        self.export_queue_gauges()
        self.scrubber.maybe_scrub()
        # memory governance: compact when the HBM governor demanded it
        # (an over-budget _grow) or the cadence elapsed with removals
        # outstanding — the vocab mark-and-sweep + bucket shrink that
        # bounds a long-lived scheduler's footprint under churn. A
        # compaction crash (the snapshot.compact chaos point) costs the
        # compaction, never the housekeeping pass: the live snapshot
        # only swaps in after the scratch rebuild fully succeeds.
        try:
            self.scrubber.maybe_compact()
        except Exception as ce:
            logging.getLogger(__name__).error(
                "housekeeping compaction failed (live snapshot "
                "unchanged): %s: %s", type(ce).__name__, ce)
        # mesh fault plane: probe quarantined devices past their
        # cooldown and reform upward when one heals
        self._maybe_heal_mesh()

    def export_queue_gauges(self) -> None:
        """Refresh scheduler_pending_pods{queue=...} — queue depth was
        invisible before this gauge; the cluster autoscaler's demand
        signal and the operator's backlog dashboard both read it. Called
        from housekeeping AND after a drain settles (the final parks of
        a wave land after its housekeeping pass ran)."""
        g = self.metrics.pending_pods
        g.labels(queue="active").set(self.queue.active_count())
        g.labels(queue="backoff").set(self.queue.backoff_count())
        g.labels(queue="unschedulable").set(self.queue.unschedulable_count())
        g.labels(queue="gang_waiting").set(self.queue.gang_waiting_count())
        # overload control: the load-shedding parking area, plus depth
        # banded by priority class (the client-go workqueue-depth
        # signal, made class-aware so a storm's bulk never hides a
        # starving high class). The class walk is O(total pending)
        # under the queue lock, so it runs on a 1s cadence, not per
        # wave — dashboards scrape slower than that anyway.
        g.labels(queue="shed").set(self.queue.shed_count())
        # poison-work isolation: convicted pods awaiting their re-probe
        g.labels(queue="quarantine").set(self.queue.quarantine_count())
        # control-plane outage: bind intents spooled for the store heal
        g.labels(queue="spool").set(self.spool_count())
        now = self.clock()
        if now >= self._next_class_export:
            self._next_class_export = now + 1.0
            for cls, n in self.queue.class_counts().items():
                self.metrics.queue_class_pods.labels(**{"class": cls}).set(n)
        # device telemetry: HBM footprint of the resident mirror — the
        # TRUE per-shard sum across devices (node groups tile the mesh's
        # "nodes" axis, pod/term replicas cost full size per device) —
        # plus a per-device gauge under sharding, and the upload bytes
        # accrued since the last export (snapshot counts, the registry
        # exposes)
        self.metrics.snapshot_hbm_bytes.set(self.snapshot.hbm_bytes())
        # memory governance: budget headroom (only meaningful with a
        # budget configured — without one the gauge stays 0) and the
        # per-interner vocabulary sizes the soak gate watches for leaks
        headroom = self.snapshot.hbm_headroom_bytes()
        if headroom is not None:
            self.metrics.hbm_headroom_bytes.set(headroom)
        for vocab, size in self.snapshot.vocabs.sizes().items():
            self.metrics.snapshot_vocab_size.labels(vocab=vocab).set(size)
        per_dev = self.snapshot.hbm_bytes_per_device()
        for dev, b in per_dev.items():
            self.metrics.snapshot_hbm_device_bytes.labels(device=dev).set(b)
        # falling back to unsharded (mesh no longer divides the grown N
        # bucket) empties the map — zero the stale device children so
        # per-device series keep summing to the unlabeled total instead
        # of exporting their last sharded values forever
        if not per_dev:
            for child in self.metrics.snapshot_hbm_device_bytes.children():
                child.set(0)
        up = self.snapshot.upload_bytes_total
        if up > self._upload_bytes_seen:
            self.metrics.snapshot_upload_bytes.inc(up - self._upload_bytes_seen)
            self._upload_bytes_seen = up

    def run_once(self, timeout: float = 0.0) -> int:
        """Schedule one wave. Returns the number of pods assumed with a
        bind dispatched (a failed async bind requeues its pod, which then
        counts again on the successful retry)."""
        if self._dormant:
            return 0  # not the leader: informers stay warm, waves don't run
        self._housekeep()
        pods = self.queue.pop_wave(self._wave_cap, timeout=timeout)
        if not pods:
            return 0
        with self._mu:
            n = self._run_wave(pods)
        self._check_invariants()
        return n

    def _check_invariants(self) -> None:
        """Post-round invariant check (chaos/invariants.py) — runs at
        every round boundary when a checker is armed (--invariants /
        Scheduler(invariants=True)); one attribute check when off.
        Holds _mu so informer delivery and the check see a consistent
        cache/snapshot, exactly like a wave."""
        chk = self.invariants
        if chk is None:
            return
        with self._mu:
            chk.check(self)

    def _schedule_pipelined(self) -> int:
        """Device-resident scheduling round over the whole backlog: every
        wave chains on the device and results are fetched ONCE at the
        end, instead of a host round trip per wave during which the
        device waits. Staged PodMatrix/TermTable rows (state/snapshot.py
        stage_pending) flip on the device as waves place, keeping
        inter-wave visibility; the host then replays the placements
        through the exact recheck + assume + bind path."""
        self._housekeep()
        all_pods: List[api.Pod] = []
        while True:
            batch = self.queue.pop_wave(self._wave_cap, timeout=0.0)
            if not batch:
                break
            all_pods.extend(batch)
        if not all_pods:
            return 0
        with self._mu:
            return self._route(all_pods, self._run_pipeline)

    def _route(self, pods: List[api.Pod], run) -> int:
        """Route a batch the way the round and the wave both do: gangs
        to the joint-assignment path, pods the device can't encode to
        the golden path, the rest to run(pods, golden). While the
        breaker is open (or a wedged dispatch is outstanding) the batch
        takes the host path instead — degraded but never stopped."""
        if not self._device_admitted():
            return self._schedule_degraded(pods)
        # gangs bypass the round and the wave: their placements must be
        # all-or-nothing per group, which the staged-commit carry can't
        # express — the joint-assignment kernel (ops/gang.py) owns them
        placed, pods = self._gangs_first(pods, self._schedule_one_gang)
        # pods whose required pod-(anti)affinity spans >1 topology key,
        # or that nominated pods' affinity terms bear on, take the exact
        # host path (_split_golden); with no ScoreDeco they are counted
        # by reason, so the round record shows the shadow observatory's
        # coverage gap alongside the shadow divergence itself
        host_path, pods = self._split_golden(pods)
        golden = self._golden_reasons(host_path)
        placed += self._schedule_host_batch(host_path)
        if not pods:
            if golden:
                tracing.event("golden_gap", **golden)
            return placed
        # RE-check admission: a gang dispatch above may have been
        # watchdog-abandoned (breaker now open, wedge outstanding), and
        # nothing may follow it onto that runtime; the golden coverage
        # gap travels with the fallback
        if not self._device_admitted():
            return placed + self._schedule_degraded(pods, golden=golden)
        return placed + run(pods, golden)

    def warm_pipeline(self, pods: List[api.Pod],
                      n_waves: Optional[int] = None) -> None:
        """Compile and run the round program for this cluster's shapes
        on `pods`, committing nothing. n_waves selects the wave-count
        bucket to compile (default: one bucket covering len(pods)/wave).
        The pods are left unscheduled; staged rows are released. A
        failure other than a Pallas demotion is raised, not salvaged:
        the warm-up places nothing, so it has nothing to salvage."""
        from ..ops.kernel import schedule_round

        with self._mu:
            pods = [p for p in pods
                    if not self.featurizer.needs_host_path(p)][:self.wave_size]
            if not pods:
                return
            # guarded: a poison pod in the warm batch convicts here
            # instead of crashing the warm-up (the warm-up must never
            # be the thing a bad spec takes down)
            _pb0, pods = self._featurize_guarded(pods)
            if not pods:
                return
            pm_rows, term_rows = self.snapshot.stage_pending(pods)
            pb = self.featurizer.featurize(pods)
            nt, pm, tt = self._to_device()
            usage = (nt.requested, nt.nonzero, nt.pod_count)
            gating, wvec, _wver = self._weights_kw()
            wbucket = pipeline_bucket(n_waves if n_waves is not None else 1,
                                      hi=self._max_waves(pods))
            has_ipa, wv, _nom, rows, _ = self._device_inputs(
                [pb], wvec, rows=assemble_round(
                    [pb], [pods], pm_rows, term_rows, wbucket,
                    term_rows.shape[1]))
            # compile the SAME collect_scores variant the measured
            # rounds will dispatch: with tracing on they run the
            # decomposition-carrying program, and warming the other one
            # would leave a full round compile inside the window this
            # warm-up exists to protect
            collect = tracing.active() is not None

            def _warm(use_p: bool):
                out = schedule_round(
                    nt, pm, tt, rows[0], usage, self._rr, rows[1], rows[2],
                    weights=gating,
                    num_zones=self.snapshot.caps.Z,
                    num_label_values=self.snapshot.num_label_values,
                    has_ipa=has_ipa, use_pallas=use_p,
                    collect_scores=collect, weight_vec=wv)
                # fetch the placements: the first Pallas round's
                # cross-check compares them, and an execution fault
                # surfaces here instead of in the first real round
                return np.asarray(out[0])

            try:
                # a Pallas fault or mismatch demotes the round HERE, so
                # the measured run compiles the same program the warm-up
                # ran (the cross-check's compile lands in this window)
                self.formulation.run("round", _warm, same=np.array_equal)
            finally:
                for p in pods:
                    self.snapshot.unstage(p)

    def _run_pipeline(self, pods: List[api.Pod],
                      golden: Optional[Dict[str, int]] = None) -> int:
        import jax

        from ..ops.kernel import schedule_round

        trace = Trace(f"pipeline of {len(pods)}", clock=self.clock,
                      steps=PIPELINE_STEPS)
        start = self.clock()
        # the ADAPTIVE cap, not wave_size: host-stage overruns under
        # wave_deadline_s shrink it (see _account_host_overrun); they
        # are the same number whenever no deadline is configured
        W = self._wave_cap
        max_waves = self._max_waves(pods)
        waves = [pods[i:i + W] for i in range(0, len(pods), W)]
        if len(waves) > max_waves:
            # bound the round (fixed program size); the leftover goes back
            # to the queue and the next schedule_pending iteration runs
            # another round
            keep = max_waves * W
            for p in pods[keep:]:
                self.queue.add_if_not_present(p)
            pods, waves = pods[:keep], waves[:max_waves]
        # flight recorder (utils/tracing.py): one round trace whose marks
        # tile the wall time — featurize / upload / device_wave / fetch /
        # commit / preempt — plus per-pod queue_wait spans keyed by UID
        # ONE weight view per round: dispatch, decision recording, and
        # the ledger's weights_version all come from this triple
        gating, wvec, wver = self._weights_kw()
        rec, rt = self._begin_round("pipeline", pods, wver, golden,
                                    waves=len(waves))
        # pass 1: grow every vocab/cap to its final size so pass 2 emits
        # uniform shapes (one compiled program, not one per growth step).
        # When nothing grew — the steady state once caps are pre-sized —
        # pass 1's batches already have the final shapes and pass 2 is
        # skipped (featurize was ~25% of round wall time when run twice).
        # A PodFeaturizeError mid-pass is a DIRECT poison conviction
        # (typed, uid-carrying — no bisection): quarantine the culprit,
        # re-chunk the survivors, and featurize again.
        import dataclasses

        while True:
            try:
                sig0 = (self.featurizer.vocabs.version(),
                        dataclasses.astuple(self.snapshot.caps))
                pbs = [self.featurizer.featurize(wv) for wv in waves]
                if (self.featurizer.vocabs.version(),
                        dataclasses.astuple(self.snapshot.caps)) != sig0:
                    pbs = [self.featurizer.featurize(wv) for wv in waves]
                break
            except PodFeaturizeError as e:
                pods = self._convict_featurize_victim(e, pods)
                if not pods:
                    self._ledger(rt, rec, outcome="input_fault")
                    return 0
                waves = [pods[i:i + W] for i in range(0, len(pods), W)]
            except Exception as e:
                # an allocation-site MemoryError (state/featurize.py
                # deliberately propagates it raw — environmental, not
                # spec-caused) is a CAPACITY fault at the round
                # boundary: compact and retry rather than crash the
                # scheduling loop or convict the pod that happened to
                # be featurizing when memory ran out
                return self._salvage("round", pods, self._classify(
                    pods, e, "featurize"), rt, rec)
        try:
            for wv, pb_w in zip(waves, pbs):
                P = pb_w.req.shape[0]
                extra = self._host_plugin_mask(wv, P)
                if (not extra.all()
                        or self._host_score_matrix(wv, P) is not None):
                    # host plugin predicates / extender priorities are in
                    # play: those need per-wave host evaluation against
                    # fresh state — the per-wave loop owns that path
                    for p in pods:
                        self.queue.add_if_not_present(p)
                    if rt is not None:
                        rec.end_round(rt, outcome="host_fallback")
                    return 0
        except ExtenderError:
            self.metrics.scheduling_errors.labels(stage="extender").inc()
            for p in pods:
                self._park_with_backoff(p)
            if rt is not None:
                rec.end_round(rt, outcome="extender_error")
            return 0
        try:
            # chaos seam, per wave, while the batches are still host-side
            # numpy (pre-stack, pre-upload): a crash-kind poison here
            # reproduces on the attribution replay (same seam) and
            # classifies as an input fault; nan-kind corrupts the
            # victim's row for the sentinel path
            for wv_pods, pb_w in zip(waves, pbs):
                self._wave_poison_seam(wv_pods, pb_w)
        except Exception as e:
            return self._salvage("round", pods,
                                 self._classify(pods, e, "seam"), rt, rec)
        pm_rows_all, term_rows_all = self.snapshot.stage_pending(pods)
        tpp = term_rows_all.shape[1]
        nw = len(waves)
        wbucket = pipeline_bucket(nw, hi=max_waves)
        nom = self._nominations(waves, pbs[0].req.shape[0], wbucket)
        trace.step("featurized+staged")
        if rt is not None:
            rt.mark("featurize", pods=len(pods))
            up0 = self.snapshot.upload_bytes_total
        nt, pm, tt = self._to_device()
        trace.step("uploaded")
        if rt is not None:
            rt.mark("upload", cat="device",
                    bytes=self.snapshot.upload_bytes_total - up0,
                    shards=(1 if self._active_mesh is None
                            else int(self._active_mesh.shape["nodes"])))
        # per-round deadline accounting: featurize+stage+upload overruns
        # degrade the wave size BEFORE they degrade latency
        self._account_host_overrun(self.clock() - start)
        usage = (nt.requested, nt.nonzero, nt.pod_count)
        has_ipa, wv, nom, (pbs_stacked, pm_rows, term_rows), _ = \
            self._device_inputs(pbs, wvec, nom=nom, rows=assemble_round(
                pbs, waves, pm_rows_all, term_rows_all, wbucket, tpp))
        trace.annotate(pods=len(pods), waves=nw, bucket=wbucket)
        # score decomposition rides along EXACTLY when tracing: the
        # compiled program (and its jit cache bucket) is byte-identical
        # to the pre-observatory kernel otherwise
        collect = rt is not None

        def _attempt(use_p: bool):
            # the Pallas taint/port kernel is HOISTED out of the round's
            # lax.scan (ops/kernel.py schedule_round: one call covering
            # all waves) — under the scan it faults on Mosaic
            (chosen_d, fail_d, _usage_end, rr_end, deco_d,
             fin_d) = schedule_round(
                nt, pm, tt, pbs_stacked, usage, self._rr, pm_rows,
                term_rows, weights=gating,
                num_zones=self.snapshot.caps.Z,
                num_label_values=self.snapshot.num_label_values,
                has_ipa=has_ipa, use_pallas=use_p,
                collect_scores=collect, weight_vec=wv, nom=nom)
            trace.step("dispatched")
            # wait for the round before fetching, so the trace's
            # "executed" and "fetched" steps split device time from
            # transfer time
            jax.block_until_ready(chosen_d)
            trace.step("executed")
            if rt is not None:
                rt.mark("device_wave", cat="device", waves=nw,
                        path="pallas" if use_p else "xla")
            chosen = np.asarray(chosen_d)
            # the numeric-integrity sentinel planes ride the SAME fetch
            fin = np.asarray(fin_d)
            fetched = chosen.nbytes + fin.nbytes
            deco = None
            if deco_d is not None:
                # the [W, P, S(+K)] decomposition planes are the round's
                # only extra fetch, bounded by SCORE_TOPK — tracing-only
                deco = tuple(np.asarray(a) for a in deco_d)
                fetched += sum(a.nbytes for a in deco)
            self.metrics.device_fetch_bytes.inc(fetched)
            trace.step("fetched")
            if rt is not None:
                rt.mark("fetch", cat="device", bytes=int(fetched))
            return chosen, rr_end, deco, fin

        out, verdict = self._dispatch(
            "round", pods, _attempt,
            finite=lambda o: [o[3][wi, i] for wi, wv_pods in
                              enumerate(waves) for i in range(len(wv_pods))],
            same=lambda a, b: np.array_equal(a[0], b[0]))
        if verdict is not None:
            for p in pods:
                self.snapshot.unstage(p)
            # golden is NOT re-passed to a salvage: this round's (failed)
            # record already ledgered it at begin_round
            return self._salvage("round", pods, verdict, rt, rec)
        chosen_all, rr_end, deco_all, _fin = out
        # exact shadow sampling runs BEFORE any commit mutates the
        # snapshot: the twin must replay the identical pre-round state
        # the device program scored
        exact_info = None
        if rt is not None and deco_all is not None:
            exact_info = self._shadow_exact_sample(
                waves[0], pbs[0], chosen_all[0], self._rr, has_ipa, gating,
                None if nom is None
                else enc.Nominations(*(np.asarray(a) for a in nom[:3]),
                                     np.asarray(nom.own)[0]))
        self._rr = rr_end
        # mirror: the round's scan advanced rr once per placement
        self._host_rr += int(np.sum(chosen_all >= 0))
        placed = 0
        committed: set = set()
        retry: List[api.Pod] = []
        prof = profiling.active()
        parts = [0.0] * len(COMMIT_PARTS) if prof is not None else None
        self._commit_parts = parts
        try:
            for wi, wv in enumerate(waves):
                for i, pod in enumerate(wv):
                    self.metrics.schedule_attempts.inc()
                    node_idx = int(chosen_all[wi, i])
                    if node_idx >= 0:
                        node_name = self.snapshot.node_names[node_idx]
                        if self._commit(pod, node_name):
                            placed += 1
                            committed.add(pod.uid)
                            continue
                    # device placement rejected by the exact recheck, or
                    # the pod failed on device: batched device preemption
                    # handles resource-starved failures below; everything
                    # else goes back through the per-wave path for exact
                    # attribution
                    self.snapshot.unstage(pod)
                    retry.append(pod)
        finally:
            self._commit_parts = None
        if rt is not None:
            rt.mark("commit", placed=placed)
        handled = self._pipeline_preempt(retry) if retry else set()
        for pod in retry:
            if pod.uid not in handled:
                self.queue.add_if_not_present(pod)
        commit_meta = {}
        if parts is not None:
            # recorded before the round's last step, so a window the step
            # profiler's hook closes at that step holds them
            for part, s in zip(COMMIT_PARTS, parts):
                prof.record_step("commit", part, s)
                commit_meta[part + "_s"] = s
        trace.step("committed", **commit_meta)
        self.metrics.e2e_scheduling_latency.observe(self.clock() - start)
        self.metrics.waves_total.labels(path="device").inc(len(waves))
        if rt is not None:
            if retry:
                rt.mark("preempt", candidates=len(retry),
                        handled=len(handled))
            scores = shadow = None
            if deco_all is not None:
                # flatten the [W, P, ...] planes down to the real pods
                # (pad waves and pad rows carry no pods by construction)
                sel = [(wi, i) for wi, wv in enumerate(waves)
                       for i in range(len(wv))]
                wi_idx = np.asarray([s[0] for s in sel], np.int64)
                i_idx = np.asarray([s[1] for s in sel], np.int64)
                scores, shadow = self._record_decisions(
                    rec, pods, chosen_all[wi_idx, i_idx],
                    deco_all[0][wi_idx, i_idx], deco_all[1][wi_idx, i_idx],
                    deco_all[2][wi_idx, i_idx], deco_all[3][wi_idx, i_idx],
                    committed=committed, wvec=wvec, wver=wver)
                shadow = self._merge_exact(shadow, exact_info)
            self._emit_telemetry(rt)
            rec.end_round(
                rt, outcome="ok", placed=placed, retried=len(retry),
                preempted=len(handled), scores=scores, shadow=shadow,
                path=self.formulation.last_path or "unresolved",
                snapshot=self._round_snapshot_shape(),
                breaker=self.breaker.state, mesh=self._mesh_ledger())
        trace.log_if_long(0.5)
        return placed

    def _pipeline_preempt(self, pods: List[api.Pod],
                          host: bool = False) -> set:
        """Batched preemption for round failures (SURVEY §7 step 6;
        VERDICT r3 item 3). One program computes the what-if stats for
        EVERY failed pod x node — the XLA kernel (ops/preempt.py) on the
        device path, its numpy twin (ops/hostwave.py) when `host` is set
        or device preemption is off — then the host runs the exact
        selectVictimsOnNode + pickOneNodeForPreemption tie-breaks only
        on the few ranked candidates. Returns the uids handled
        (nominated + parked); the rest fall back to the per-wave path
        for failure attribution."""
        if not (self.features.enabled("PodPriority")
                and not self.profile.disable_preemption):
            return set()
        if not self.device_preemption:
            # device what-ifs disabled: the numpy twin carries the same
            # batched pipeline, instead of a per-pod host what-if for
            # every failed pod on every node
            host = True
        cands = [p for p in pods
                 if pod_eligible_to_preempt_others(p, self.cache)]
        if not cands:
            return set()
        # chunk at wave_size: growing the P bucket would retrace the
        # round program itself, and later chunks then see earlier
        # chunks' evictions through the refreshed snapshot; the claimed
        # map spans chunks so freed capacity is never double-counted
        handled: set = set()
        claimed: Dict[str, List[api.Pod]] = {}
        exhausted: Dict[str, int] = {}
        for i in range(0, len(cands), self.wave_size):
            handled |= self._preempt_chunk(cands[i:i + self.wave_size],
                                           claimed, exhausted, host=host)
        return handled

    def _nominations(self, waves: List[List[api.Pod]], P: int,
                     W: Optional[int] = None) -> Optional[enc.Nominations]:
        """The queue's nominations as the fit of a round's waves (or, W
        None, of one wave) counts them (Snapshot.stage_nominations);
        None when the queue holds none. A nominated pod the cache holds
        as assumed is left out: its requests already count where it was
        assumed."""
        nominated = [(p, name) for p, name in self.queue.nominated_pods()
                     if not self.cache.is_assumed(p)]
        if not nominated:
            return None
        nom = self.snapshot.stage_nominations(nominated, waves, P, W)
        if nom is not None:
            self.metrics.nominated_pods_staged.inc(int(nom.count[0].sum()))
        return nom

    def _preempt_gang_weights(self):
        """Victim-gang disruption weights for the what-if stats: 1 for
        placed members of gangs with no slack above minMember (any
        eviction breaks them). Returns (guard, f32 [M] weights or None)."""
        guard, placed_gangs, gang_mins = self._gang_state()
        if guard is None:
            return None, None
        w = np.zeros((self.snapshot.caps.M,), np.float32)
        for gkey, gmembers in placed_gangs.items():
            if len(gmembers) <= gang_mins[gkey]:
                for gp in gmembers:
                    slot = self.snapshot.pod_slot.get(gp.uid)
                    if slot is not None:
                        w[slot] = 1.0
        return guard, (w if w.any() else None)

    def _preempt_chunk(self, cands: List[api.Pod],
                       claimed: Dict[str, List[api.Pod]],
                       exhausted: Dict[str, int],
                       host: bool = False) -> set:
        from ..ops.hostwave import victim_levels
        from ..ops.preempt import PreemptStats

        t0 = self.clock()
        trace = Trace(f"preempt chunk of {len(cands)}", clock=self.clock,
                      steps=PREEMPT_HOST_STEPS if host else PREEMPT_STEPS)
        pb, cands = self._featurize_guarded(cands)
        if not cands:
            return set()
        # candidate thresholds: distinct priorities of live existing pods
        # (+1 so "< level" removes that class); always keep the HIGHEST
        # so the remove-all-lower option survives the level cap
        live = self.snapshot.ep_valid & self.snapshot.ep_alive
        levels = victim_levels(self.snapshot.ep_prio, live, PREEMPT_LEVELS)
        if levels is None:
            return set()
        # victim-gang awareness: the per-class segment sum ranks
        # gang-sparing nodes first. None for gang-free clusters — same
        # compiled program as before.
        guard, gang_w = self._preempt_gang_weights()
        def _host_whatif():
            from ..ops.hostwave import preemption_stats_host

            nt_h, pm_h, _tt = self.snapshot.host_tensors()
            out = preemption_stats_host(
                nt_h, pm_h, pb, np.asarray(levels, np.int32),
                num_levels=PREEMPT_LEVELS, gang_w=gang_w)
            trace.step("host what-if")
            return out

        if not host and not self._device_admitted():
            # the breaker opened (or the runtime wedged) mid-round — a
            # preempt chunk must not follow the wave onto a bad runtime
            host = True
        if host:
            packed = _host_whatif()
        else:
            import jax.numpy as jnp

            from ..ops.preempt import preemption_stats

            try:
                nt, pm, tt = self._to_device()
                pb_dev = pb
                if self._active_mesh is not None:
                    # what-if stats partition along the node axis like
                    # the wave kernels; the failed-pod batch replicates
                    from ..parallel.mesh import replicate

                    pb_dev = enc.PodBatch(
                        *replicate(self._active_mesh, tuple(pb)))
                trace.step("featurized+uploaded")
                packed_d = preemption_stats(
                    nt, pm, pb_dev, jnp.asarray(levels, jnp.int32),
                    num_levels=PREEMPT_LEVELS,
                    gang_w=None if gang_w is None else jnp.asarray(gang_w))
                trace.step("dispatched")
                # the fetch surfaces execution faults too — keep it
                # inside the try
                packed = np.asarray(packed_d)
            except Exception as e:
                # mid-preempt-chunk device loss: reform (or feed the
                # breaker) and salvage THIS chunk through the numpy
                # twin — preemption survives the ladder like waves do
                self._device_failure(e)
                packed = _host_whatif()
        st = PreemptStats(np.asarray(packed))  # ONE fetch for all planes
        ok, victims_n = st.ok, st.victims
        psum, pmax = st.prio_sum, st.prio_max
        gviol = st.gang_viol
        trace.step("fetched")
        pdbs = self._pdbs()
        handled: set = set()
        prof = profiling.active()
        parts = [0.0] * len(PREEMPT_PARTS) if prof is not None else None
        # `claimed` = capacity claimed by earlier pods in this batch (the
        # host analog of the reference's nominated-pod accounting in
        # podFitsOnNode's two-pass logic): without it, one freed node
        # would absorb every later candidate's validation and the batch
        # would degenerate to one eviction per round
        for i, pod in enumerate(cands):
            t = time.perf_counter() if parts is not None else 0.0
            cand_nodes = np.nonzero(ok[i])[0]
            if cand_nodes.size == 0:
                if parts is not None:
                    _accrue(parts, 0, t)
                continue
            self.metrics.total_preemption_attempts.inc()
            # device ranking approximates the reference's tie-breaks to
            # pick the TOP-K; the exact criteria (incl. PDB violations)
            # re-rank the validated candidates below
            order = sorted(
                cand_nodes.tolist(),
                key=lambda n: (float(gviol[i, n]), float(pmax[i, n]),
                               float(psum[i, n]), float(victims_n[i, n])))
            if parts is not None:
                t = _accrue(parts, 0, t)
            aff = pod.spec.affinity
            with_aff = bool(self.snapshot.has_affinity_terms
                            or (aff is not None
                                and (aff.pod_affinity is not None
                                     or aff.pod_anti_affinity is not None))
                            # spread's what-if reads cluster-wide domain
                            # counts through the view, like affinity
                            or golden.has_hard_spread(pod))
            node_infos = self.cache.node_infos if with_aff else None
            validated = {}
            tried = 0
            for n in order:
                if tried >= PREEMPT_HOST_CANDIDATES:
                    break
                name = self.snapshot.node_names[n]
                ni = self.cache.node_infos.get(name)
                if ni is None or ni.node is None:
                    continue
                # a node that already FAILED validation at (or below)
                # its current claim count can't absorb another preemptor
                # — skip it WITHOUT spending a validation slot. Identical
                # failed pods all rank the same few nodes first; without
                # this the batch exhausts its top-K on claimed nodes and
                # the round degenerates to one preemption chunk per
                # device round-trip. Marking on observed failure (not a
                # predicted victim count) keeps both directions honest:
                # a claimed node that can evict FURTHER victims, or
                # whose earlier eviction freed surplus capacity, still
                # gets validated once before being written off.
                if (name in exhausted
                        and len(claimed.get(name, ())) >= exhausted[name]):
                    continue
                tried += 1
                if claimed.get(name):
                    ni = ni.clone()
                    for cp in claimed[name]:
                        ni.add_pod(cp)
                sel = select_victims_on_node(pod, ni, pdbs, node_infos,
                                             self._host_extra_fit, guard)
                if sel is not None:
                    validated[name] = sel
                elif claimed.get(name):
                    # validation failures on an UNclaimed node are pod-
                    # specific (PDB, affinity) — don't block other pods
                    exhausted[name] = len(claimed[name])
            if self.profile.extenders:
                validated = process_preemption_with_extenders(
                    pod, validated, self.profile.extenders, pdbs)
            chosen = pick_one_node(validated)
            rec = tracing.active()
            if rec is not None:
                rec.event("preempt_whatif", pod=pod.uid,
                          device_candidates=int(cand_nodes.size),
                          validated=len(validated),
                          chosen=chosen or "")
            if chosen is None:
                if parts is not None:
                    _accrue(parts, 1, t)
                continue
            victims, nviol = validated[chosen]
            claimed.setdefault(chosen, []).append(pod)
            if parts is not None:
                t = _accrue(parts, 1, t)
            if not victims:
                # an earlier eviction already freed this node: the pod
                # fits WITHOUT preempting — requeue and let the next
                # round place it (the claim above stops later batch
                # members from also counting on this capacity)
                continue
            self._perform_preemption(
                pod, PreemptionResult(chosen, victims, nviol))
            self._park_with_backoff(pod)
            self.pipeline_preemptions += 1
            handled.add(pod.uid)
            if parts is not None:
                _accrue(parts, 2, t)
        trace.step("validated+performed")
        if parts is not None:
            for part, s in zip(PREEMPT_PARTS, parts):
                prof.record_step("preempt", part, s)
        trace.log_if_long(0.5)
        self.metrics.preemption_evaluation.observe(self.clock() - t0)
        return handled

    def _count_degraded_golden(self, pods: List[api.Pod], rt=None) -> None:
        """Degraded-mode visibility: pods the hostwave twin can't encode
        drain through the exact per-pod golden path at a fraction of the
        twin's rate — count them by reason
        (scheduler_degraded_golden_pods_total{reason=affinity|multi_tk})
        and tag the round-ledger entry, so the untwinned inter-pod
        affinity plane shows up on dashboards instead of silently
        dragging degraded throughput."""
        counts = self._golden_reasons(pods)
        for r, n in counts.items():
            self.metrics.degraded_golden_pods.labels(reason=r).inc(n)
        if rt is not None:
            g = rt.ledger.setdefault("degraded_golden", {})
            for r, n in counts.items():
                g[r] = g.get(r, 0) + n

    def _schedule_degraded(self, pods: List[api.Pod],
                           golden: Optional[Dict[str, int]] = None) -> int:
        """Degraded mode: the backlog drains through the vectorized numpy
        host twin (ops/hostwave.py) — one batched wave per wave_size
        chunk, batched twin preemption for its failures, all-or-nothing
        gangs through the twin's count-feasibility plane, and the exact
        golden path for what the twin can't encode."""
        # ONE weight view per round (see _run_pipeline); every twin
        # chunk below dispatches under it
        gating, wvec, wver = self._weights_kw()
        # the coverage gap counted by the caller BEFORE it fell back here
        # (golden-path pods it already scheduled) must not vanish just
        # because the round went degraded
        rec, rt = self._begin_round("degraded", pods, wver, golden)
        # gangs stay atomic in degraded mode: the twin's count
        # feasibility IS the joint-assignment proof (host twin). Gangs
        # with golden-only members still place individually — atomicity
        # is not offered for that combination on either backend.
        placed, pods = self._gangs_first(
            pods, lambda key, members: self._schedule_degraded_gang(
                key, members, rt))
        # the twin, like the device kernel, does not carry multi-
        # topology-key required affinity (needs_host_path); its
        # inter-pod affinity plane is twinned (ops/hostwave.py
        # incoming_statics_host), so affinity pods keep batched
        # throughput here, routed as on the device path
        needs_golden = self.featurizer.needs_host_path
        golden_pods = [p for p in pods if needs_golden(p)]
        if golden_pods:
            pods = [p for p in pods if not needs_golden(p)]
            self._count_degraded_golden(golden_pods, rt)
            placed += self._schedule_host_batch(golden_pods)
        # chunk at wave_size: featurize buckets caps.P by batch length,
        # and a 10k-pod degraded backlog must not balloon the P bucket
        # every later DEVICE wave would recompile under
        deco_acc: Optional[List] = [] if rt is not None else None
        committed: set = set()
        for i in range(0, len(pods), self.wave_size):
            placed += self._host_wave(pods[i:i + self.wave_size], rt,
                                      deco_acc=deco_acc,
                                      committed=committed,
                                      weights_view=(gating, wvec))
        if rt is not None:
            scores = shadow = None
            if deco_acc:
                # one decision-recording pass over every twin chunk's
                # decomposition (the twin computes it in-place — no
                # fetch; golden-path pods have no decomposition)
                all_pods = [p for ps, _c, _d in deco_acc for p in ps]
                chosen_cat = np.concatenate([c for _p, c, _d in deco_acc])
                planes = [np.concatenate([d[k] for _p, _c, d in deco_acc])
                          for k in range(4)]
                scores, shadow = self._record_decisions(
                    rec, all_pods, chosen_cat, *planes,
                    committed=committed, wvec=wvec, wver=wver)
            self._emit_telemetry(rt, device_ok=False)
            rec.end_round(rt, outcome="ok", placed=placed, path="host",
                          scores=scores, shadow=shadow,
                          breaker=self.breaker.state,
                          snapshot=self._round_snapshot_shape(),
                          mesh=self._mesh_ledger())
        return placed

    def _host_wave(self, pods: List[api.Pod], rt=None,
                   deco_acc: Optional[List] = None,
                   committed: Optional[set] = None,
                   weights_view=None) -> int:
        """One batched host-twin wave over the snapshot's host planes (no
        device touch: a wedged runtime must not be dispatched to), then
        the device path's exact recheck -> assume -> bind commit.
        Failures take ONE batched host-twin preemption pass, then park
        with FitError attribution from the twin's mask stack. deco_acc
        collects (pods, chosen, deco) for the degraded round's one
        decision-recording pass."""
        from ..ops import hostwave

        if not pods:
            return 0
        trace = Trace(f"host wave of {len(pods)}", clock=self.clock,
                      steps=HOST_WAVE_STEPS)
        start = self.clock()
        for _p in pods:
            self.metrics.schedule_attempts.inc()
        runner = (lambda ps: self._host_wave(
            ps, rt, deco_acc=deco_acc, committed=committed,
            weights_view=weights_view))
        pb, pods = self._featurize_guarded(pods)
        if not pods:
            return 0  # the whole chunk was convicted at featurize time
        P = pb.req.shape[0]
        planes = self._host_planes(pods, P)
        if planes is None:
            return 0
        extra, extra_scores = planes
        trace.step("featurized")
        if rt is not None:
            rt.mark("featurize", pods=len(pods))
        nt, pm, tt = self.snapshot.host_tensors()
        # the enclosing degraded round's weight view, or (direct calls)
        # a fresh one — same triple source either way
        gating, wvec = (weights_view if weights_view is not None
                        else self._weights_kw()[:2])
        # the same has_ipa resolution as the device path: the twin
        # carries the full inter-pod affinity plane
        has_ipa = self._has_ipa([pb])
        try:
            self._wave_poison_seam(pods, pb)
            res, _usage = hostwave.schedule_wave_host(
                nt, pm, tt, pb, extra, self._host_rr, extra_scores,
                weights=gating,
                num_zones=self.snapshot.caps.Z,
                num_label_values=self.snapshot.num_label_values,
                has_ipa=has_ipa,
                collect_scores=deco_acc is not None,
                weight_vec=wvec, nom=self._nominations([pods], P))
        except Exception as e:
            # a crash on the HOST path follows the data by construction
            # (no runtime to blame): input fault — bisect to the
            # culprit. Known infrastructure errors are exempt (store /
            # REST / OS — never the work's fault); a deterministic twin
            # BUG does still convict the batch pod by pod, a deliberate
            # tradeoff: each conviction logs loudly and re-probes on the
            # capped ladder, where the pre-isolation behavior crashed
            # the scheduling loop outright.
            if self._infra_error(e):
                self.metrics.scheduling_errors.labels(stage="wave").inc()
                logging.getLogger(__name__).error(
                    "host wave failed on infrastructure, parking %d "
                    "pods", len(pods), exc_info=e)
                for p in pods:
                    self._park_with_backoff(p)
                return 0
            verdict = (e if isinstance(e, (PoisonError, PodFeaturizeError))
                       else PoisonError(f"host twin pass failed: "
                                        f"{type(e).__name__}: {e}"))
            return self._isolate_poison(pods, verdict, runner)
        # numeric-integrity sentinel: discard the chunk, convict the
        # flagged pods, re-run the survivors (host rr not advanced)
        verdict = self._sentinel(pods, np.asarray(res.finite))
        if verdict is not None:
            return self._isolate_poison(pods, verdict.exc, runner)
        if deco_acc is not None and res.deco is not None:
            # slice off featurize's P-bucket pad rows: the degraded round
            # concatenates chunks, so a padded chunk would shift every
            # later chunk's rows off its pods
            n = len(pods)
            deco_acc.append((list(pods), np.asarray(res.chosen[:n]),
                             tuple(np.asarray(a)[:n] for a in res.deco)))
        self._host_rr = int(res.rr_end)
        self._rr = None  # device resumption re-seeds from the mirror
        self.formulation.last_path = "vector"
        trace.step("host wave")
        if rt is not None:
            rt.mark("host_wave", cat="host", backend="vector",
                    pods=len(pods))
        placed = 0
        failed: List[Tuple[int, api.Pod]] = []
        for i, pod in enumerate(pods):
            node_idx = int(res.chosen[i])
            if node_idx >= 0:
                if self._commit(pod, self.snapshot.node_names[node_idx]):
                    placed += 1
                    if committed is not None:
                        committed.add(pod.uid)
                    continue
                # exact recheck lost a race with f32 arithmetic: retry
                self.queue.add_if_not_present(pod)
                continue
            failed.append((i, pod))
        trace.step("committed")
        if rt is not None:
            rt.mark("commit", placed=placed)
        handled: set = set()
        if failed:
            handled = self._pipeline_preempt([p for _, p in failed],
                                             host=True)
            for i, pod in failed:
                self.metrics.pods_failed.inc()
                err = self._fit_error(pod, i, res.fail_counts, res)
                self._count_unschedulable(err)
                if pod.uid not in handled:
                    self._park_with_backoff(pod)
                self.store.set_pod_condition(
                    pod, ("PodScheduled", "False:" + err.message()))
            if rt is not None:
                rt.mark("preempt", candidates=len(failed),
                        handled=len(handled))
        self.metrics.e2e_scheduling_latency.observe(self.clock() - start)
        self.metrics.waves_total.labels(path="host").inc()
        trace.log_if_long(0.5)
        return placed

    # -- the dispatch path (sched/dispatch.py) ---------------------------------

    def _dispatch(self, program: str, pods: List[api.Pod], attempt,
                  finite, same=None):
        """Run one device program of the round, the wave or the gang:
        attempt(use_pallas) dispatches it, finite(result) is its
        numeric-integrity plane, one flag per pod. Returns (result,
        None), (None, verdict) on a failure, or (result, verdict) when
        the sentinel flagged pods (see _sentinel)."""
        try:
            out, path = self.formulation.run(program, attempt, same)
        except Exception as e:
            return None, self._classify(pods, e)
        self.breaker.record_success()
        self._capacity_strikes = 0
        self.formulation.last_path = path
        return out, self._sentinel(pods, finite(out))

    @staticmethod
    def _sentinel(pods: List[api.Pod], finite) -> Optional[Verdict]:
        """The numeric-integrity verdict, None when every pod is finite.
        A non-finite row means a poison pod contaminated the scan's
        shared carries: the whole result is discarded (a NaN carry
        silently shifts innocent pods' placements) and rr is not
        advanced; the flagged pods convict and the survivors re-run,
        placing bit-equal a clean run."""
        bad = [p.uid for p, ok in zip(pods, finite) if not ok]
        if not bad:
            return None
        return Verdict(INPUT, PoisonError("numeric-integrity sentinel",
                                          uids=bad), {"poison": len(bad)})

    def _classify(self, pods: List[api.Pod], exc: BaseException,
                  stage: str = "device") -> Verdict:
        """The one ordered classifier of a failure at a site: capacity,
        input fault, then the device (sched/dispatch.py). `stage`
        "featurize" lets any other error out (a featurizer bug is
        neither the work's nor the device's); "seam", before any
        dispatch, charges the device nothing when the replay is clean."""
        # capacity first: an OOM replays clean on the twin, so the input
        # verdict would blame the device, and the scheduler's own
        # footprint must never convict a device, reform the mesh or
        # convict a pod; then the input replay, as bad work must never
        # blame or reform the runtime
        ledger = {"error": type(exc).__name__}
        if is_capacity_error(exc):
            return Verdict(CAPACITY, exc, ledger)
        if stage == "featurize" and not isinstance(exc, PodFeaturizeError):
            raise exc
        verdict = self._input_fault_verdict(pods, exc)
        if verdict is not None:
            return Verdict(INPUT, verdict, ledger)
        if stage == "seam":
            return Verdict(TRANSIENT, exc, ledger)
        if self._device_failure(exc):
            return Verdict(REFORMED, exc, ledger)
        return Verdict(HUNG if isinstance(exc, DispatchTimeout) else FAILED,
                       exc, ledger)

    def _salvage(self, site: str, pods: List[api.Pod], verdict: Verdict,
                 rt=None, rec=None, degrade=None) -> int:
        """The one salvage ladder: what happens to a site's pods under a
        verdict, by the site's row of SALVAGE. The round and the wave
        retry and degrade through themselves and _schedule_degraded;
        the gang passes `degrade` (its all-or-nothing twin) and ledgers
        into the round its caller ends. Returns pods placed."""
        if site == "gang" and verdict.kind == INPUT:
            self._gang_input_fault(pods, verdict.exc, rt)
            return 0
        retry = self._run_pipeline if site == "round" else self._run_wave
        degrade = degrade or self._schedule_degraded
        if verdict.kind == INPUT:
            self._ledger(rt, rec, outcome="input_fault", **verdict.ledger)
            return self._isolate_poison(pods, verdict.exc, retry)
        if verdict.kind == CAPACITY:
            return self._capacity_fault(site, pods, verdict.exc, rt, rec,
                                        retry, degrade)
        self._ledger(rt, rec, outcome="device_failure", **verdict.ledger,
                     mesh=(None if verdict.kind == TRANSIENT
                           else self._mesh_ledger()))
        then = SALVAGE[site][verdict.kind]
        if then == DEGRADE:
            return degrade(pods)
        for p in pods:
            if then == PARK:
                self._park_with_backoff(p)
            else:
                self.queue.add_if_not_present(p)
        return 0

    @staticmethod
    def _ledger(rt, rec, **fields) -> None:
        """Ledger a site's failure: the round and the wave end their
        round here, the gang (rec None) writes the fields into the round
        _schedule_one_gang ends."""
        if rt is None:
            return
        if rec is not None:
            rec.end_round(rt, **fields)
        else:
            rt.ledger.update({k: v for k, v in fields.items()
                              if v is not None})

    def _device_failure(self, exc: BaseException) -> bool:
        """Account one device failure: with a multi-device mesh, first
        one step down the reform ladder (_maybe_reform), leaving the
        breaker untouched — losing 1 of 8 chips must cost 1/8 of device
        throughput, not 8/8. Otherwise the breaker counts it, and a
        watchdog abandonment trips it at once: a wedged runtime won't
        heal by retrying. True when the mesh reformed."""
        self.metrics.scheduling_errors.labels(stage="wave").inc()
        reformed = self._maybe_reform(exc)
        if not reformed:
            if isinstance(exc, DispatchTimeout):
                self.breaker.record_hang()
            else:
                self.breaker.record_failure()
        logging.getLogger(__name__).error(
            "device wave failed (%s consecutive, breaker %s%s): %s: %s",
            self.breaker.failures, self.breaker.state,
            ", mesh reformed" if reformed else "",
            type(exc).__name__, exc, exc_info=exc)
        return reformed

    def _capacity_fault(self, site: str, pods: List[api.Pod],
                        exc: BaseException, rt, rec, retry, degrade) -> int:
        """The capacity strike ladder: strike 1 compacts the snapshot
        (vocab mark-and-sweep + bucket shrink, state/scrubber.py) and
        retries, strike 2 also halves the adaptive wave cap, strike 3
        degrades through the twin, which needs no device memory; a site
        whose SALVAGE row says DEGRADE does so after compacting. The
        breaker counts it only when compaction cannot restore headroom.
        Strikes reset on the next device success."""
        self._capacity_strikes += 1
        strike = self._capacity_strikes
        self.metrics.capacity_faults.inc()
        logging.getLogger(__name__).warning(
            "capacity fault (strike %d), compacting: %s: %s", strike,
            type(exc).__name__, exc)
        summary = self._compact_guarded(trigger="oom")
        if strike >= 2:
            # same floor discipline as _account_host_overrun: a
            # scheduler configured below the adaptive floor must never
            # have a fault RAISE its wave
            self._wave_cap = max(self._wave_cap // 2,
                                 min(self.MIN_ADAPTIVE_WAVE,
                                     self.wave_size))
            self.metrics.effective_wave_size.set(self._wave_cap)
        headroom = self.snapshot.hbm_headroom_bytes()
        exhausted = headroom is not None and headroom < 0
        if exhausted:
            # compaction could not restore headroom: only now does the
            # fault feed the breaker — threshold trips route waves
            # through the host twin until a half-open probe clears
            self.breaker.record_failure()
        self._ledger(rt, rec, outcome="capacity_fault",
                     error=type(exc).__name__, memory=self._memory_ledger())
        if (SALVAGE[site][CAPACITY] == DEGRADE or strike >= 3
                or summary is None or exhausted):
            # third strike, compaction deferred (staged rows held by a
            # concurrent round), or budget still exceeded: salvage the
            # round host-side instead of burning another dispatch
            return degrade(pods)
        return retry(pods)

    def _compact_guarded(self, trigger: str):
        """scrubber.compact hardened for the scheduling loop: a crash
        inside compaction (the `snapshot.compact` chaos point, or a
        real bug) must cost the compaction, never the round — the live
        snapshot is untouched until the scratch rebuild fully succeeds
        (state/snapshot.py _compact swaps in at the end), so failure
        here just means no shrink happened. Returns the summary, or
        None when compaction failed or was deferred."""
        try:
            return self.scrubber.compact(trigger=trigger, force=True)
        except Exception as ce:
            logging.getLogger(__name__).error(
                "snapshot compaction failed (live snapshot unchanged): "
                "%s: %s", type(ce).__name__, ce)
            return None

    def _memory_ledger(self) -> Dict:
        """Round-ledger `memory` record: {hbm_bytes, budget, headroom,
        vocabs, compactions, capacity_strikes}. headroom is None when
        no budget is configured."""
        return {
            "hbm_bytes": int(self.snapshot.projected_hbm_bytes()),
            "budget": int(self.snapshot.hbm_budget_bytes),
            "headroom": self.snapshot.hbm_headroom_bytes(),
            "vocabs": self.snapshot.vocabs.sizes(),
            "compactions": int(
                self.metrics.snapshot_compactions_total.total()),
            "capacity_strikes": int(self._capacity_strikes),
        }

    def _maybe_reform(self, exc: BaseException) -> bool:
        """One ladder step down, under _mu: attribute the failure to a
        device (named by the exception, else bisection), quarantine it
        and rebuild a smaller mesh from the survivors. False when there
        is nothing to reform (no multi-device mesh, the
        --mesh-min-devices floor, or a failed reform): the breaker owns
        the failure then."""
        from ..parallel.mesh import reform_mesh

        mf = self.meshfaults
        if (mf is None or self.mesh is None
                or int(self.mesh.devices.size) <= 1):
            return False
        culprit = mf.attribute(exc)
        if culprit is not None:
            mf.quarantine(culprit)
            newly = [culprit]
        else:
            newly = mf.quarantine_suspects()
        if not newly:
            return False
        for name in newly:
            self.metrics.device_quarantined.labels(device=name).set(1)
            tracing.event("device_quarantined", device=name,
                          attributed=culprit is not None)
        logging.getLogger(__name__).warning(
            "mesh device(s) quarantined (%s): %s",
            "attributed" if culprit is not None else "bisection",
            ", ".join(newly))
        try:
            faultpoints.fire("mesh.reform")
            new_mesh = reform_mesh(mf.healthy(),
                                   min_devices=self.mesh_min_devices)
        except Exception as reform_exc:
            logging.getLogger(__name__).error(
                "mesh reform failed, falling through to the breaker: %s",
                reform_exc)
            new_mesh = None
        if new_mesh is None:
            # below the floor: the quarantines stand (probes may still
            # heal them) but the failure feeds the classic breaker
            return False
        self._swap_mesh(new_mesh, direction="down")
        return True

    def _swap_mesh(self, new_mesh, direction: str) -> None:
        """Install a reformed mesh (under _mu): the next _to_device
        re-commits every node-tensor group to its sharding (a full
        re-upload); the in-flight round is salvaged host-side, so no
        dispatch happens in between."""
        from ..ops import kernel as _kernel

        _kernel.set_devices([str(d) for d in new_mesh.devices.flat])
        self.mesh = new_mesh
        self._active_mesh = None
        ndev = int(new_mesh.devices.size)
        self.metrics.mesh_reforms.labels(direction=direction).inc()
        self.metrics.mesh_devices.set(ndev)
        tracing.event("mesh_reform", direction=direction, devices=ndev)
        logging.getLogger(__name__).warning(
            "mesh reformed %s to %d device(s)", direction, ndev)

    def _mesh_ledger(self) -> Optional[Dict]:
        """Round-ledger `mesh` record ({devices, reforms, quarantined});
        None (dropped by end_round) when no mesh fault plane exists."""
        mf = self.meshfaults
        if mf is None:
            return None
        return {
            "devices": (int(self.mesh.devices.size)
                        if self.mesh is not None else 1),
            "reforms": int(self.metrics.mesh_reforms.total()),
            "quarantined": mf.quarantined_names(),
        }

    # one process-global jitted probe program: compiled once per device
    # it runs on, reused across probes (a fresh jax.jit per probe would
    # recompile every cooldown tick)
    _PROBE_FN = None

    def _probe_device(self, dev) -> bool:
        """Recovery probe for one quarantined device: a trivial jitted
        op pinned to it, fetched. Runs OUTSIDE _mu (a probe is a device
        dispatch; lock-discipline forbids blocking device work under
        the scheduler lock from housekeeping) and never while the
        runtime is wedged. The `device.lost` fault point fires with the
        device's name as payload so per-device chaos
        (lost_device_fault) fails exactly its victim's probes."""
        import jax
        import jax.numpy as jnp

        try:
            if faultpoints.fire("device.lost", payload=str(dev)):
                return False  # drop mode: the probe was lost
            if Scheduler._PROBE_FN is None:
                Scheduler._PROBE_FN = jax.jit(lambda a: a + jnp.float32(1.0))
            x = jax.device_put(np.float32(1.0), dev)
            out = Scheduler._PROBE_FN(x)
            return float(np.asarray(out)) == 2.0
        except Exception:
            return False

    def _maybe_heal_mesh(self) -> None:
        """Probe quarantined devices whose cooldown elapsed; re-admit
        the healed and reform UPWARD (4 -> 8) so a recovered chip
        rejoins the serving mesh. Called from housekeeping."""
        from ..parallel.mesh import reform_mesh

        mf = self.meshfaults
        if mf is None or not mf.quarantined_names():
            return
        if self._runtime_wedged():
            return  # no probes at a wedged runtime
        healed = False
        for dev in mf.due_probes(self.clock()):
            name = str(dev)
            if self._probe_device(dev):
                mf.readmit(name)
                self.metrics.device_quarantined.remove(device=name)
                tracing.event("device_readmitted", device=name)
                logging.getLogger(__name__).warning(
                    "quarantined device %s probed healthy; re-admitted",
                    name)
                healed = True
            else:
                mf.reprobe_later(name)
        if not healed:
            return
        with self._mu:
            cur = (int(self.mesh.devices.size)
                   if self.mesh is not None else 0)
            new_mesh = reform_mesh(mf.healthy(), min_devices=1)
            if new_mesh is not None and int(new_mesh.devices.size) > cur:
                self._swap_mesh(new_mesh, direction="up")

    # -- poison-work isolation (input-fault attribution) -----------------------
    #
    # Batching Filter+Score into one (pods x nodes) device computation
    # collapsed the per-pod error isolation 1.11's genericScheduler got
    # for free: one pod whose spec crashes the featurizer — or whose
    # NaN request poisons the scan's shared usage carry — used to look
    # exactly like a device fault, so the breaker blamed the runtime,
    # the reform ladder quarantined innocent DEVICES, the hostwave
    # salvage crashed on the same input, and the pods requeued into the
    # same wave forever. This plane restores the isolation: classify
    # every failure as device-fault vs INPUT-fault before any breaker /
    # reform accounting (replay through the numpy twin — a runtime
    # fault cannot follow the data onto the host), attribute directly
    # when the evidence names a pod (typed featurizer errors, the
    # kernel's numeric-integrity sentinel), BISECT the wave along the
    # pod axis otherwise (the PR 14 device-bisection mirror), and park
    # convicted pods in the queue's quarantine area with a capped
    # re-probe backoff. Breaker and mesh never move for bad work.

    # attribution-replay bound, in waves (see _input_fault_verdict):
    # enough to cover every pipeline round shape the tests and the
    # acceptance proof exercise while keeping the failure path's twin
    # cost bounded on huge backlogs
    ATTRIBUTION_REPLAY_MAX_WAVES = 4

    def _wave_poison_seam(self, pods: List[api.Pod], pb) -> None:
        """The `wave.poison` chaos seam: fired before EVERY batched pass
        over a pod list — device round/wave/gang dispatches, degraded
        host-twin waves, and the input-fault attribution replay — with
        (pods, host-side PodBatch) as payload, so an injected poison
        follows the DATA across backends (state/featurize.py
        poison_pod_fault). One dict check when unarmed."""
        faultpoints.fire("wave.poison", payload=(pods, pb))

    def _featurize_guarded(self, pods: List[api.Pod]):
        """(PodBatch, survivors): featurize a batch, convicting pods
        whose spec crashes (or numerically poisons) the featurizer —
        PodFeaturizeError carries the culprit UID, so attribution is
        direct and the innocent podmates featurize clean on the retry.
        Returns (None, []) when every pod was convicted."""
        pods = list(pods)
        while pods:
            try:
                return self.featurizer.featurize(pods), pods
            except PodFeaturizeError as e:
                pods = self._convict_featurize_victim(e, pods)
        return None, []

    def _convict_featurize_victim(self, e: PodFeaturizeError,
                                  pods: List[api.Pod]) -> List[api.Pod]:
        """The convict-and-filter step of a guarded featurize retry
        (shared by _featurize_guarded and _run_pipeline's two-pass
        loop): quarantine the pod the typed error names, return the
        survivors. Re-raises when the error names a pod outside the
        batch — that is a bug, not poison."""
        victims = [p for p in pods if p.uid == e.uid]
        if not victims:
            raise e
        self._convict(victims, reason="featurize", error=str(e),
                      cohort=pods)
        return [p for p in pods if p.uid != e.uid]

    def _input_fault_verdict(self, pods: List[api.Pod],
                             exc: BaseException):
        """Fault attribution: replay the failed batch through the numpy
        twin over the host planes (commits discarded, rr untouched). A
        runtime fault cannot follow the data onto the host, so a replay
        that fails too, or that its sentinel flags, convicts the WORK:
        returns the verdict exception (with uids when attribution is
        direct). None when the replay is clean, or for a wedge, which
        is the runtime's alone."""
        if isinstance(exc, DispatchTimeout):
            return None
        if isinstance(exc, (PoisonError, PodFeaturizeError)):
            return exc
        from ..ops import hostwave

        # the replay is a FAILURE-path cost paid before a genuine
        # device fault's salvage re-runs the same twin waves: bound it.
        # A poison beyond the cap is not lost — misclassifying it as a
        # device fault routes the batch to the degraded/salvage path,
        # whose own host waves carry the identical sentinel + crash
        # isolation and convict it there (at the price of one wrongly
        # charged breaker count).
        replay = pods[:self.ATTRIBUTION_REPLAY_MAX_WAVES * self.wave_size]
        gating, wvec, _wver = self._weights_kw()
        try:
            for s in range(0, len(replay), self.wave_size):
                chunk = replay[s:s + self.wave_size]
                pb = self.featurizer.featurize(chunk)
                self._wave_poison_seam(chunk, pb)
                nt, pm, tt = self.snapshot.host_tensors()
                extra = np.ones((pb.req.shape[0], nt.valid.shape[0]), bool)
                has_ipa = self._has_ipa([pb])
                res, _usage = hostwave.schedule_wave_host(
                    nt, pm, tt, pb, extra, self._host_rr, None,
                    weights=gating, num_zones=self.snapshot.caps.Z,
                    num_label_values=self.snapshot.num_label_values,
                    has_ipa=has_ipa, weight_vec=wvec)
                flagged = self._sentinel(chunk, np.asarray(res.finite))
                if flagged is not None:
                    return flagged.exc
        except PodFeaturizeError as fe:
            return fe
        except Exception as replay_exc:
            if self._infra_error(replay_exc):
                # the REPLAY itself failed on infrastructure (store /
                # OS), which proves nothing about the work — fall back
                # to the device-fault path rather than convicting
                # innocents on a broken jury
                return None
            return PoisonError(
                f"twin replay reproduced the failure: "
                f"{type(replay_exc).__name__}: {replay_exc}")
        return None

    def _isolate_poison(self, pods: List[api.Pod], verdict,
                        runner: Callable[[List[api.Pod]], int]) -> int:
        """Input-fault isolation: direct conviction when the verdict
        names UIDs, the survivors requeued. Otherwise bisection along
        the pod axis: each half re-runs through `runner` in order, so
        the clean half places bit-equal a clean run and the poisoned
        half recurses, converging on the culprit in log2(wave) rounds.
        Returns pods placed by the retries."""
        self.metrics.scheduling_errors.labels(stage="poison").inc()
        victims, reason = self._verdict_attribution(verdict, pods)
        if victims:
            vuids = {p.uid for p in victims}
            self._convict(victims, reason=reason, error=str(verdict),
                          cohort=pods)
            for p in pods:
                if p.uid not in vuids:
                    self.queue.add_if_not_present(p)
            return 0
        if len(pods) <= 1:
            self._convict(list(pods), reason="bisect", error=str(verdict),
                          cohort=pods)
            return 0
        mid = (len(pods) + 1) // 2
        tracing.event("poison_bisect", pods=len(pods))
        logging.getLogger(__name__).warning(
            "input fault with no direct attribution: bisecting a "
            "%d-pod wave (%s)", len(pods), verdict)
        return runner(pods[:mid]) + runner(pods[mid:])

    def _convict(self, pods: List[api.Pod], reason: str, error: str = "",
                 cohort=()) -> None:
        """Quarantine convicted poison work. Gang-atomic: a poisoned
        member convicts its WHOLE gang — pending members are pulled
        from every queue area (and from `cohort`, the in-hand wave
        mates) and quarantined together, because a sub-minMember
        remnant would wedge against its own admission gate forever.
        Every conviction gets a FitError-style condition/event, the
        scheduler_poison_pods_total{reason} increment, and a capped-
        backoff re-probe deadline (specs get edited; a spec EDIT
        releases immediately via the queue's update path)."""
        # dict-as-ordered-set: conviction order follows victim order
        victims: Dict[str, tuple] = {}
        for p in pods:
            victims[p.uid] = (p, reason)
        if self.gangs.active:
            keys: Dict[str, None] = {}
            for p in pods:
                k = self.gangs.key(p)
                if k is not None:
                    keys[k] = None
            for k in keys:
                for mate in self.queue.gang_pending_pods(k):
                    victims.setdefault(mate.uid, (mate, "gang"))
                for mate in cohort:
                    if (mate.uid not in victims
                            and self.gangs.key(mate) == k):
                        victims[mate.uid] = (mate, "gang")
        n_nodes = int(np.sum(self.snapshot.valid))
        log = logging.getLogger(__name__)
        for uid, (pod, r) in victims.items():
            d = self.poison_backoff.bump(uid)
            until = self.clock() + d
            if not self.queue.quarantine(pod, until):
                # queue.quarantine drop-mode chaos: a lost conviction —
                # degrade to the plain backoff park so the pod still
                # leaves the wave (pre-isolation behavior, never a wedge)
                self._park_with_backoff(pod)
                continue
            self.poison_convictions += 1
            self.metrics.pods_failed.inc()
            self.metrics.poison_pods.labels(reason=r).inc()
            err = FitError(pod.full_name(), n_nodes,
                           {REASONS["Poisoned"]: 1})
            self.store.set_pod_condition(
                pod, ("PodScheduled", "False:" + err.message()))
            tracing.event("pod_quarantined", pod=uid, reason=r,
                          reprobe_s=round(d, 3))
            log.error(
                "poison pod %s quarantined (%s; re-probe in %.1fs): %s",
                pod.full_name(), r, d, error or reason)

    def _gang_input_fault(self, members: List[api.Pod], verdict,
                          rt=None) -> None:
        """Gang flavor of _isolate_poison: no bisection WITHIN a gang —
        one poisoned member quarantines the group atomically (the
        culprit keeps its direct reason when the verdict names it, the
        mates are booked under reason=gang)."""
        self.metrics.scheduling_errors.labels(stage="poison").inc()
        culprits, reason = self._verdict_attribution(verdict, members)
        if not culprits:
            culprits = list(members)
        self._convict(culprits, reason=reason, error=str(verdict),
                      cohort=members)
        if rt is not None:
            rt.ledger["outcome"] = "input_fault"

    @staticmethod
    def _verdict_attribution(verdict, pods: List[api.Pod]):
        """(culprits, reason) for one input-fault verdict: the pods it
        names directly — a typed featurizer error's uid or the
        sentinel's uids — with the matching conviction reason, or
        ([], "bisect") when attribution is indirect."""
        uids = set(getattr(verdict, "uids", ()) or ())
        one = getattr(verdict, "uid", None)
        if one:
            uids.add(one)
        culprits = [p for p in pods if p.uid in uids]
        if not culprits:
            return [], "bisect"
        return culprits, ("featurize"
                          if isinstance(verdict, PodFeaturizeError)
                          else "sentinel")

    def _run_wave(self, pods: List[api.Pod]) -> int:
        return self._route(pods, self._wave)

    def _wave(self, pods: List[api.Pod],
              golden: Optional[Dict[str, int]] = None) -> int:
        trace = Trace(f"wave of {len(pods)}", clock=self.clock,
                      steps=WAVE_STEPS)
        start = self.clock()
        # ONE weight view per round (see _run_pipeline)
        gating, wvec, wver = self._weights_kw()
        rec, rt = self._begin_round("wave", pods, wver, golden)
        try:
            pb, pods = self._featurize_guarded(pods)
        except Exception as e:
            # allocation-site MemoryError routed into the capacity
            # verdict (see _run_pipeline's featurize loop) instead of
            # propagating raw out of the scheduling loop
            return self._salvage("wave", pods, self._classify(
                pods, e, "featurize"), rt, rec)
        if not pods:
            # the whole wave was convicted at featurize time
            self._ledger(rt, rec, outcome="input_fault")
            return 0
        planes = self._host_planes(pods, pb.req.shape[0])
        if planes is None:
            self._ledger(rt, rec, outcome="extender_error")
            return 0
        extra, extra_scores = planes
        trace.step("featurized")
        if rt is not None:
            rt.mark("featurize", pods=len(pods))
            up0 = self.snapshot.upload_bytes_total
        try:
            # chaos seam, fired while pb is still the host-side batch:
            # a crash-kind poison here reproduces on the attribution
            # replay (which fires the same seam) and classifies as an
            # input fault; nan-kind corrupts the row pre-upload for the
            # sentinel path
            self._wave_poison_seam(pods, pb)
        except Exception as e:
            return self._salvage(
                "wave", pods, self._classify(pods, e, "seam"), rt, rec)
        nt, pm, tt = self._to_device()
        if rt is not None:
            rt.mark("upload", cat="device",
                    bytes=self.snapshot.upload_bytes_total - up0)
        # per-wave deadline accounting, same as the round path: the
        # live CLI loop runs run_once -> HERE, and host-stage overruns
        # must shrink the wave there too, not only under the pipeline
        self._account_host_overrun(self.clock() - start)
        has_ipa, wv, nom, _, (nt, pm, tt, pb, extra, extra_scores) = \
            self._device_inputs(
                [pb], wvec, nom=self._nominations([pods], pb.req.shape[0]),
                wave=(nt, pm, tt, pb, extra, extra_scores))
        kw = dict(weights=gating, weight_vec=wv, nom=nom,
                  num_zones=self.snapshot.caps.Z,
                  num_label_values=self.snapshot.num_label_values,
                  has_ipa=has_ipa,
                  # decomposition rides along exactly when tracing; off,
                  # the compiled program is byte-identical to before
                  collect_scores=rt is not None)
        res, verdict = self._dispatch(
            "wave", pods, lambda use_p: schedule_wave(
                nt, pm, tt, pb, extra, self._rr, extra_scores,
                use_pallas=use_p, **kw),
            finite=lambda r: np.asarray(r.finite))
        if verdict is not None:
            return self._salvage("wave", pods, verdict, rt, rec)
        chosen = np.asarray(res.chosen)
        fin = np.asarray(res.finite)
        self._rr = res.rr_end
        if rt is not None:
            rt.mark("device_wave", cat="device",
                    path=self.formulation.last_path)
        # mirror: one rr advance per placement (see _host_rr)
        self._host_rr += int(np.sum(chosen >= 0))
        fetched = chosen.nbytes + fin.nbytes
        deco = None
        if res.deco is not None:
            deco = tuple(np.asarray(a) for a in res.deco)
            fetched += sum(a.nbytes for a in deco)
        self.metrics.device_fetch_bytes.inc(fetched)
        trace.step("device wave")
        if rt is not None:
            rt.mark("fetch", cat="device", bytes=int(fetched))
        placed = 0
        committed: set = set()
        fail_counts = None
        for i, pod in enumerate(pods):
            self.metrics.schedule_attempts.inc()
            node_idx = int(chosen[i])
            if node_idx >= 0:
                node_name = self.snapshot.node_names[node_idx]
                if self._commit(pod, node_name):
                    placed += 1
                    committed.add(pod.uid)
                    continue
                # exact recheck lost a race with device f32 arithmetic:
                # retry next wave without counting it unschedulable
                self.queue.add_if_not_present(pod)
                continue
            if fail_counts is None:
                fail_counts = np.asarray(res.fail_counts)
            self._handle_failure(pod, i, fail_counts, res)
        trace.step("committed")
        self.metrics.e2e_scheduling_latency.observe(self.clock() - start)
        self.metrics.waves_total.labels(path="device").inc()
        if rt is not None:
            rt.mark("commit", placed=placed)
            # scores summary over the wave's placed pods: the round
            # ledger's (state, placement, outcome) record carries the
            # per-priority breakdown + margin-over-runner-up for
            # offline scoring-weight analysis
            # a traced wave always carries its decomposition: summary
            # only over placements that actually committed
            scores, shadow = self._record_decisions(
                rec, pods, chosen, *deco, committed=committed,
                wvec=wvec, wver=wver)
            self._emit_telemetry(rt)
            rec.end_round(
                rt, outcome="ok", placed=placed,
                failed=len(pods) - placed, path=self.formulation.last_path,
                scores=scores, shadow=shadow,
                snapshot=self._round_snapshot_shape(),
                breaker=self.breaker.state, mesh=self._mesh_ledger())
        trace.log_if_long(0.1)
        return placed

    def _extender_node_labels(self) -> Optional[Dict[str, dict]]:
        """Full node -> labels map for non-cache-capable filter
        extenders, built ONCE per round/wave and passed down — the
        per-pod golden path used to rebuild this dict per call."""
        if not any(e.filter_verb and not e.node_cache_capable
                   for e in self.profile.extenders):
            return None
        return {n: (ni.node.metadata.labels or {})
                for n, ni in self.cache.node_infos.items()
                if ni.node is not None}

    def _schedule_host_batch(self, pods: List[api.Pod]) -> int:
        """Golden path for a batch: the ClusterView and the extender
        node-labels map are built ONCE for the round and shared across
        every pod's pass (they read live cache state, so commits and
        evictions inside the loop stay visible). The per-pod loop IS
        the fault domain here, so a spec that crashes the golden pass
        gets attribution for free: convict just that pod and keep
        draining the batch."""
        if not pods:
            return 0
        view = golden.ClusterView(self.cache.node_infos)
        node_labels = self._extender_node_labels()
        placed = 0
        crashed: List[Tuple[api.Pod, BaseException]] = []
        for p in pods:
            try:
                placed += self._schedule_host_path(p, view=view,
                                                   node_labels=node_labels)
            except Exception as e:
                if self._infra_error(e):
                    # the golden pass also preempts and commits: a
                    # transient store/REST failure there is NOT the
                    # pod's fault — plain backoff park, never a
                    # conviction (a poison verdict escalates a x2..x64
                    # ladder an innocent pod would have to re-probe
                    # down)
                    self.metrics.scheduling_errors.labels(
                        stage="bind").inc()
                    logging.getLogger(__name__).error(
                        "golden pass failed on infrastructure, "
                        "parking %s", p.full_name(), exc_info=e)
                    self._park_with_backoff(p)
                    continue
                crashed.append((p, e))
        if crashed and len(crashed) == len(pods) and len(pods) > 1:
            # EVERY pod in the batch crashed the golden pass: that is a
            # systemic fault (a buggy host plugin, corrupt shared
            # state), not per-pod poison — park the batch instead of
            # quarantining an entire innocent class behind Poisoned
            # conditions. A single-pod batch can't be disambiguated and
            # keeps the conviction (the re-probe ladder bounds a wrong
            # call).
            self.metrics.scheduling_errors.labels(stage="wave").inc()
            logging.getLogger(__name__).error(
                "golden pass crashed for ALL %d pods (systemic, not "
                "poison); parking batch", len(pods),
                exc_info=crashed[0][1])
            for p, _e in crashed:
                self._park_with_backoff(p)
            return placed
        for p, e in crashed:
            self._convict([p], reason="golden",
                          error=f"{type(e).__name__}: {e}")
        return placed

    @staticmethod
    def _infra_error(exc: BaseException) -> bool:
        """Is this exception an infrastructure failure (store/REST/OS)
        rather than something the pod's own spec can cause? Conviction
        paths that wrap phases with side effects (commit, preemption)
        must not misattribute these to the work."""
        from ..runtime.store import Conflict

        if isinstance(exc, (OSError, TimeoutError, Conflict, KeyError)):
            return True
        try:
            from ..client.rest import APIStatusError

            if isinstance(exc, APIStatusError):
                return True
        except Exception:
            pass
        return False

    def _schedule_host_path(self, pod: api.Pod, view=None,
                            node_labels=None) -> int:
        """Exact one-pod golden pass for pods the wave kernel (and its
        numpy twin) can't encode — inter-pod affinity and
        multi-topology-key required affinity. Mirrors the reference's
        single-pod cycle over the golden predicates/priorities. `view`
        and `node_labels` are per-round shared state (see
        _host_path_inner); omitted, they're built per call."""
        self.metrics.schedule_attempts.inc()
        self.metrics.waves_total.labels(path="host").inc()
        rec = tracing.active()
        if rec is None:
            return self._host_path_inner(pod, view, node_labels)
        t0 = rec.now()
        try:
            return self._host_path_inner(pod, view, node_labels)
        finally:
            # backend attribution: Perfetto traces must distinguish the
            # exact per-pod golden fallback from the vectorized twin
            rec.add_span("host_wave", t0, rec.now(), cat="host",
                         pod=pod.uid, backend="golden")

    def _host_path_inner(self, pod: api.Pod, view=None,
                         node_labels=None) -> int:
        if view is None:
            view = golden.ClusterView(self.cache.node_infos)
        feasible: List[str] = []
        reasons: Dict[str, int] = {}
        failed: Dict[str, List[str]] = {}
        for name, ni in self.cache.node_infos.items():
            ok, rs = golden.pod_fits_on_node(
                pod, ni, view=view,
                nominated=self.queue.waiting_pods_for_node(name))
            if ok:
                for fname, fn in self.profile.host_filters.items():
                    if getattr(fn, "relevant", None) is not None and not fn.relevant(pod):
                        continue
                    ok2, rs2 = fn(pod, ni)
                    if not ok2:
                        ok, rs = False, rs2
                        break
            if ok:
                feasible.append(name)
            else:
                for r in rs[:1]:
                    reasons[r] = reasons.get(r, 0) + 1
                failed[name] = rs[:1]
        try:
            for ext in self.profile.extenders:
                if ext.filter_verb and feasible:
                    if ext.node_cache_capable:
                        labels_arg = None
                    elif node_labels is not None:
                        labels_arg = {n: node_labels[n] for n in feasible
                                      if n in node_labels}
                    else:
                        labels_arg = {
                            n: (self.cache.node_infos[n].node.metadata.labels or {})
                            for n in feasible
                            if self.cache.node_infos[n].node is not None}
                    feasible, ext_failed = ext.filter(
                        pod, feasible, node_labels=labels_arg)
                    for n, r in ext_failed.items():
                        reasons[r] = reasons.get(r, 0) + 1
                        failed[n] = ["ExtenderFilter"]
        except ExtenderError:
            self.metrics.scheduling_errors.labels(stage="extender").inc()
            self._park_with_backoff(pod)
            return 0
        if not feasible:
            self.metrics.pods_failed.inc()
            err = FitError(pod.full_name(), len(self.cache.node_infos), reasons)
            self._count_unschedulable(err)
            if (self.features.enabled("PodPriority")
                    and not self.profile.disable_preemption):
                # map reason strings back to predicate names for the
                # unresolvable filter
                fp = {n: [REASON_KEYS.get(r, r) for r in rs]
                      for n, rs in failed.items()}
                pr = preempt(pod, self.cache, fp, self._pdbs(), with_affinity=True,
                             extenders=self.profile.extenders,
                             extra_fit=self._host_extra_fit,
                             gang_guard=self._gang_guard(),
                             snapshot=self.snapshot,
                             featurizer=self.featurizer)
                if pr is not None:
                    self._perform_preemption(pod, pr)
            self._park_with_backoff(pod)
            self.store.set_pod_condition(pod, ("PodScheduled", "False:" + err.message()))
            return 0
        # score: golden interpod priority + least-requested tie-breaking.
        # The interpod weight follows the LIVE vector (a hot-swapped
        # profile applies to golden-path pods too); lr/ba stay
        # implicitly weight-1 here — the golden path has always been an
        # approximation of the full stack, and its pods carry no
        # ScoreDeco either way (see the round ledger's `golden` field)
        from ..ops.scores import W_INTERPOD

        w = self.profile.weights()
        w_interpod = float(self.weightbook.live_vector()[W_INTERPOD])
        ipa_scores = golden.interpod_affinity_priority(
            pod, [self.cache.node_infos[n] for n in feasible], view,
            hard_weight=int(w.hard_pod_affinity))
        host_scores: Dict[str, float] = {}
        for _name, (fn, weight) in self.profile.host_scores.items():
            for node, s in fn(pod, self.cache.node_infos).items():
                host_scores[node] = host_scores.get(node, 0.0) + weight * s
        try:
            for ext in self.profile.extenders:
                for node, s in ext.prioritize(pod, feasible).items():
                    host_scores[node] = host_scores.get(node, 0.0) + s
        except ExtenderError:
            self.metrics.scheduling_errors.labels(stage="extender").inc()
            self._park_with_backoff(pod)
            return 0
        best_name, best_score = None, None
        for name in feasible:
            ni = self.cache.node_infos[name]
            s = (w_interpod * ipa_scores.get(name, 0)
                 + golden.least_requested_map(pod, ni)
                 + golden.balanced_allocation_map(pod, ni)
                 + host_scores.get(name, 0.0))
            if best_score is None or s > best_score:
                best_name, best_score = name, s
        if best_name is not None and self._commit(pod, best_name):
            return 1
        self.queue.add_if_not_present(pod)
        return 0

    # -- gang (PodGroup) scheduling --------------------------------------------

    def _gangs_first(self, pods: List[api.Pod], place):
        """Place a batch's gangs through place(key, members), one
        PodGroup at a time; returns (placed, the other pods). Gangs
        commit one group at a time so the second gang's pass sees the
        first gang's assumed usage: two gangs contending for the same
        nodes can never interleave partial placements, the loser fails
        whole. pop_wave delivers gangs whole; a batch with no gang pod
        costs one annotation lookup per pod."""
        groups: Dict[str, List[api.Pod]] = {}
        rest: List[api.Pod] = []
        for p in pods:
            key = self.gangs.key(p)
            if key is None:
                rest.append(p)
            else:
                groups.setdefault(key, []).append(p)
        return sum(place(k, m) for k, m in groups.items()), rest

    def _schedule_one_gang(self, key: str, members: List[api.Pod]) -> int:
        self.metrics.gang_schedule_attempts.inc()
        for _p in members:
            self.metrics.schedule_attempts.inc()
        rec, rt = self._begin_round("gang", members,
                                    self.weightbook.live_version(), gang=key)
        try:
            placed = self._place_gang(key, members, rt)
        finally:
            if rt is not None and rt.t1 is None:
                rec.end_round(rt, snapshot=self._round_snapshot_shape(),
                              breaker=self.breaker.state,
                              mesh=self._mesh_ledger())
        return placed

    def _schedule_degraded_gang(self, key: str, members: List[api.Pod],
                                rt=None) -> int:
        """Degraded-mode gang placement: all or nothing through the host
        twin (see _place_gang)."""
        self.metrics.gang_schedule_attempts.inc()
        for _p in members:
            self.metrics.schedule_attempts.inc()
        return self._place_gang(key, members, rt, host=True)

    def _place_gang(self, key: str, members: List[api.Pod], rt=None,
                    host: bool = False) -> int:
        """All-or-nothing placement of one PodGroup: the joint-assignment
        program on the device or, with `host`, the host twin's
        count-feasibility plane (ops/hostwave.py schedule_gang_host).
        Either minMember members hold capacity at once or nothing
        commits. Members the device can't encode (multi-topology-key
        required affinity) take the exact golden path one at a time,
        where atomicity is not offered; on the twin one such member
        sends the whole gang there."""
        import jax.numpy as jnp

        from ..ops import hostwave
        from ..ops.gang import schedule_gang

        # per-gang admission: an earlier gang in this very batch may
        # have been watchdog-abandoned — each remaining gang must
        # re-check before dispatching (and must not burn another full
        # wave_deadline_s against a runtime already presumed wedged)
        if not host and not self._device_admitted():
            return self._schedule_degraded_gang(key, members, rt)
        min_member = self.gangs.min_member(members[0])
        bound = self.gangs.bound_count(self.cache, key,
                                       exclude={p.uid for p in members})
        # members already holding capacity (earlier rounds, or a bind
        # retry straggler) count toward minMember: the program only
        # needs to place the remainder
        need = max(min_member - bound, 0)
        placed = 0
        needs_golden = self.featurizer.needs_host_path
        golden_members = [p for p in members if needs_golden(p)]
        if golden_members:
            if host:
                self._count_degraded_golden(golden_members, rt)
                return self._schedule_host_batch(members)
            placed += self._schedule_host_batch(golden_members)
            members = [p for p in members if not needs_golden(p)]
            if not members:
                return placed

        def salvage(verdict):
            # a poisoned member quarantines the whole group (a
            # sub-minMember remnant would wedge against its own
            # admission gate forever); the twin salvages it whole
            return placed + self._salvage(
                "gang", members, verdict, rt, degrade=lambda ms: self
                ._schedule_degraded_gang(key, ms, rt))

        try:
            pb = self.featurizer.featurize(members)
        except Exception as e:
            if host and not isinstance(e, PodFeaturizeError):
                raise
            return salvage(self._classify(members, e, "featurize"))
        planes = self._host_planes(members, pb.req.shape[0])
        if planes is None:
            self._ledger(rt, None, outcome="extender_error")
            return placed
        extra, extra_scores = planes
        gating, wvec, _wver = self._weights_kw()
        if host:
            nt, pm, tt = self.snapshot.host_tensors()
            try:
                self._wave_poison_seam(members, pb)
                res = hostwave.schedule_gang_host(
                    nt, pm, tt, pb, extra, self._host_rr, extra_scores,
                    need, weights=gating, num_zones=self.snapshot.caps.Z,
                    num_label_values=self.snapshot.num_label_values,
                    has_ipa=self._has_ipa([pb]), weight_vec=wvec)
            except Exception as e:
                # a host-path crash follows the data: the gang convicts
                return salvage(Verdict(INPUT, e if isinstance(
                    e, (PoisonError, PodFeaturizeError)) else PoisonError(
                    f"host twin gang pass failed: {type(e).__name__}: {e}"),
                    {}))
            self.formulation.last_path = "vector"
            if rt is not None:
                rt.mark("host_wave", cat="host", backend="vector", gang=key,
                        pods=len(members))
            # the twin discards nothing on its own (count feasibility
            # may even have passed): the gang convicts before any commit
            verdict = self._sentinel(members, np.asarray(res.finite))
        else:
            if rt is not None:
                rt.mark("featurize", pods=len(members))
            try:
                # chaos seam while pb is still host-side (see _wave)
                self._wave_poison_seam(members, pb)
            except Exception as e:
                return salvage(self._classify(members, e, "seam"))
            nt, pm, tt = self._to_device()
            if rt is not None:
                rt.mark("upload", cat="device")
            # joint assignment runs under the mesh like a wave: node
            # tensors stay sharded, the member batch shards on the wave
            # axis (replicated at wave_parallel=1)
            has_ipa, wv, _nom, _, (nt, pm, tt, pb, extra, extra_scores) = \
                self._device_inputs([pb], wvec, wave=(nt, pm, tt, pb, extra,
                                                      extra_scores))
            res, verdict = self._dispatch(
                "gang", members, lambda use_p: schedule_gang(
                    nt, pm, tt, pb, extra, self._rr, extra_scores,
                    jnp.asarray(need, jnp.int32), weights=gating,
                    weight_vec=wv, num_zones=self.snapshot.caps.Z,
                    num_label_values=self.snapshot.num_label_values,
                    has_ipa=has_ipa, use_pallas=use_p),
                finite=lambda r: np.asarray(r.finite))
            if res is not None:
                # counted before the sentinel: a discarded gang still ran
                self.metrics.waves_total.labels(path="device").inc()
                if rt is not None:
                    rt.mark("device_wave", cat="device",
                            path=self.formulation.last_path)
                self.metrics.device_fetch_bytes.inc(
                    np.asarray(res.chosen).nbytes
                    + np.asarray(res.finite).nbytes)
        if verdict is not None:
            return salvage(verdict)
        if not bool(np.asarray(res.ok)):
            if rt is not None and not host:
                rt.ledger.update(outcome="gang_unplaceable",
                                 path=self.formulation.last_path)
            self._fail_gang(key, members, need, res)
            return placed
        chosen = np.asarray(res.chosen)
        if host:
            self._host_rr = int(res.rr_end)
            self._rr = None  # device resumption re-seeds from the mirror
        else:
            self._rr = res.rr_end
            self._host_rr += int(np.sum(chosen >= 0))  # see _host_rr
        pairs: List = []
        leftover: List = []
        for i, pod in enumerate(members):
            n = int(chosen[i])
            if n >= 0:
                pairs.append((pod, self.snapshot.node_names[n]))
            else:
                leftover.append((i, pod))
        if not self._commit_gang(pairs):
            # exact int64 recheck lost a race with device f32 arithmetic:
            # retry the whole gang next wave, not unschedulable
            for pod in members:
                self.queue.add_if_not_present(pod)
            if rt is not None and not host:
                rt.ledger["outcome"] = "recheck_race"
            return placed
        self.backoff.clear("gang:" + key)
        if host:
            self.metrics.waves_total.labels(path="host").inc()
        elif rt is not None:
            rt.mark("commit", placed=len(pairs))
            rt.ledger.update(outcome="ok", placed=len(pairs),
                             path=self.formulation.last_path)
        # surplus members beyond minMember that didn't fit park
        # individually with normal per-pod attribution
        if leftover:
            fail_counts = np.asarray(res.fail_counts)
            for i, pod in leftover:
                self._handle_failure(pod, i, fail_counts, res)
        return placed + len(pairs)

    def _fail_gang(self, key: str, members: List[api.Pod], need: int, res):
        """minMember pods can't hold capacity simultaneously: no member
        commits (the device already discarded the scan's placements),
        every member parks with ONE shared backoff deadline — the gang
        fails, waits, and retries as a unit — and gang-aware preemption
        runs so a higher-priority gang can evict its way in."""
        n_nodes = int(np.sum(self.snapshot.valid))
        short = max(need - int(np.asarray(res.placed)), 1)
        tracing.event("gang_failed", gang=key, need=need, short=short)
        err = FitError(key, n_nodes, {REASONS["Gang"]: short})
        # park FIRST: the preemption below emits store events (nominated-
        # node writes, victim deletes) whose queue.update would re-add a
        # not-yet-parked member to the ACTIVE heap — the gang would then
        # retry as shrinking subsets instead of waiting out its backoff
        until = self.clock() + self.backoff.bump("gang:" + key)
        for pod in members:
            self.metrics.pods_failed.inc()
            self.queue.set_backoff(pod.uid, until)
            self.queue.add_unschedulable_if_not_present(pod)
            self.store.set_pod_condition(
                pod, ("PodScheduled", "False:" + err.message()))
        if (self.features.enabled("PodPriority")
                and not self.profile.disable_preemption):
            t0 = self.clock()
            guard = self._gang_guard()
            # claimed: nodes earlier members already nominated — each
            # member must free a DIFFERENT node or the gang re-fails with
            # one slot freed (the host analog of _preempt_chunk's claim
            # accounting, scoped to this gang)
            claimed: set = set()
            for i, pod in enumerate(members):
                self.metrics.total_preemption_attempts.inc()
                fp = {n: preds for n, preds in
                      self._failed_predicates_by_node(res, i).items()
                      if n not in claimed}
                pr = preempt(pod, self.cache, fp, self._pdbs(),
                             with_affinity=self.snapshot.has_affinity_terms
                             or _pod_has_ipa_terms(pod),
                             extenders=self.profile.extenders,
                             extra_fit=self._host_extra_fit,
                             gang_guard=guard,
                             snapshot=self.snapshot,
                             featurizer=self.featurizer)
                if pr is not None:
                    claimed.add(pr.node_name)
                    self._perform_preemption(pod, pr)
            self.metrics.preemption_evaluation.observe(self.clock() - t0)

    def _commit_gang(self, pairs) -> bool:
        """Group-wide exact commit: EVERY member passes the int64
        recheck and assumes before any bind dispatches; one failure
        rolls the entire group back (forget + snapshot restore + volume
        rollback) so a partially-bound gang can never reach the store.
        Per-member mechanics mirror _commit."""
        assumed: List = []  # (pod, bound, node_name, vol_rollback)
        ok = True
        for pod, node_name in pairs:
            ni = self.cache.node_infos.get(node_name)
            if ni is None or not ni.fits_exactly(pod):
                ok = False
                break
            vol_rollback = None
            if (self.features.enabled("VolumeScheduling")
                    and self.volume_binder.pod_has_claims(pod)):
                got, vol_rollback = self.volume_binder.bind_pod_volumes(
                    pod, ni.node)
                if not got:
                    ok = False
                    break
            bound = api.with_node_name(pod, node_name)
            self.cache.assume_pod(bound)
            self.snapshot.refresh_node_resources(
                self.cache.node_infos[node_name])
            self.snapshot.add_pod(bound)
            assumed.append((pod, bound, node_name, vol_rollback))
        if not ok:
            if not self._gang_rollback_enabled:
                # test hook (see __init__): leave the partial commit in
                # place — the invariant checker must catch the orphaned
                # assumed members (conservation) and the split gang
                # (gang_atomic)
                return False
            for pod, bound, node_name, vol_rollback in reversed(assumed):
                try:
                    self.cache.forget_pod(bound)
                except KeyError:
                    pass
                ni = self.cache.node_infos.get(node_name)
                if ni is not None:
                    self.snapshot.refresh_node_resources(ni)
                self.snapshot.remove_pod(bound)
                if vol_rollback is not None:
                    vol_rollback()
            return False
        for pod, bound, node_name, vol_rollback in assumed:
            if self._bind_pool is None:
                self._bind_and_finish(pod, bound, node_name, vol_rollback)
                continue
            fut = self._bind_pool.submit(self._bind_and_finish, pod, bound,
                                         node_name, vol_rollback)
            with self._inflight_mu:
                self._inflight.add(fut)
                self.bind_overlap_hwm = max(self.bind_overlap_hwm,
                                            len(self._inflight))
            fut.add_done_callback(self._bind_done)
        return True

    def _gang_state(self):
        """(GangGuard, placed-members map, minMember map) from ONE cache
        scan, or (None, {}, {}) when the cluster has never seen a gang
        pod — the flag check keeps gang-free preemption paths at zero
        added cost."""
        if not self.gangs.active:
            return None, {}, {}
        placed = self.gangs.placed_by_gang(self.cache)
        if not placed:
            return None, {}, {}
        mins = {key: self.gangs.min_member_by_key(key, sample=members[0])
                for key, members in placed.items()}
        slack = {key: max(len(members) - mins[key], 0)
                 for key, members in placed.items()}
        return GangGuard(self.gangs.key, slack), placed, mins

    def _gang_guard(self) -> Optional[GangGuard]:
        return self._gang_state()[0]

    # -- commit path -----------------------------------------------------------

    def _commit(self, pod: api.Pod, node_name: str) -> bool:
        """Exact int64 re-verification then assume; the bind posts from
        the worker pool outside _mu (reference: scheduler.go:486 assume ->
        :491 `go sched.bind`). True means the pod is assumed and its bind
        dispatched — a failed bind forgets the assume and requeues.

        With the VolumeScheduling gate on, the pod's unbound PVCs are
        bound to node-compatible PVs first (scheduler.go:268
        assumeAndBindVolumes); a later bind failure rolls them back.

        While a pipeline round sets _commit_parts, the perf-counter
        seconds of the recheck, the assume (volumes, cache and snapshot)
        and the bind (the inline bind with whatever the store runs
        inline, or the hand-off to the bind pool) are added to its
        entries, in COMMIT_PARTS order."""
        parts = self._commit_parts
        timed = parts is not None
        t = time.perf_counter() if timed else 0.0
        ni = self.cache.node_infos.get(node_name)
        fits = ni is not None and ni.fits_exactly(pod)
        if timed:
            t = _accrue(parts, 0, t)
        if not fits:
            return False
        vol_rollback = None
        if (self.features.enabled("VolumeScheduling")
                and self.volume_binder.pod_has_claims(pod)):
            ok, vol_rollback = self.volume_binder.bind_pod_volumes(
                pod, ni.node)
            if not ok:
                if timed:
                    _accrue(parts, 1, t)
                return False
        bound = api.with_node_name(pod, node_name)
        self.cache.assume_pod(bound)
        self.snapshot.refresh_node_resources(self.cache.node_infos[node_name])
        self.snapshot.add_pod(bound)
        if timed:
            t = _accrue(parts, 1, t)
        if self._bind_pool is None:
            ok = self._bind_and_finish(pod, bound, node_name, vol_rollback)
        else:
            fut = self._bind_pool.submit(self._bind_and_finish, pod, bound,
                                         node_name, vol_rollback)
            with self._inflight_mu:
                self._inflight.add(fut)
                self.bind_overlap_hwm = max(self.bind_overlap_hwm,
                                            len(self._inflight))
            fut.add_done_callback(self._bind_done)
            ok = True
        if timed:
            _accrue(parts, 2, t)
        return ok

    def _bind_done(self, fut):
        with self._inflight_mu:
            self._inflight.discard(fut)
        exc = fut.exception()
        if exc is not None:
            # nothing awaits these futures for a value; without the
            # counter an exception escaping _bind_and_finish would only
            # ever reach stderr — invisible to /metrics and dashboards
            self.metrics.scheduling_errors.labels(stage="bind").inc()
            logging.getLogger(__name__).error(
                "bind worker raised", exc_info=exc)

    def _bind_attempt(self, pod: api.Pod, node_name: str):
        """One bind POST as a closure — shared by the live bind path
        and the spool drain, so both replay through identical fault
        seams and extender routing."""

        def _attempt():
            # chaos seam: a raise here exercises retry, then the full
            # rollback/confirm resolution path
            faultpoints.fire("bind.post", payload=pod)
            # store-path outage seam: covers the ObjectStore and
            # RemoteStore bind paths exactly once per attempt
            # (RemoteStore.bind deliberately does NOT fire it — doubling
            # would double-count breaker failures and burn injected
            # `times` budgets twice)
            if faultpoints.fire("store.outage", payload=("bind", pod.uid)):
                raise ConnectionError("store.outage: bind request dropped")
            # reference scheduler.go:409 GetBinder: an extender with a bind
            # verb performs the binding; the in-process store is then updated
            # so informers observe the placement either way
            binder = next((e for e in self.profile.extenders if e.bind_verb),
                          None)
            if binder is not None:
                binder.bind(pod, node_name)
            self.store.bind(pod, node_name)

        return _attempt

    def _bind_and_finish(self, pod: api.Pod, bound: api.Pod,
                         node_name: str, vol_rollback=None) -> bool:
        """The bind POST + cache confirmation; runs outside _mu. The
        POST goes through the bind reconciler (sched/reconciler.py):
        jittered retries first, then GET-against-API-truth resolution —
        so a lost bind RESPONSE confirms the assumption while a lost
        bind REQUEST rolls it back (forget + PVC rollback +
        backoff-requeue; reference forget-on-failure, scheduler.go:
        409-432, which tolerated the ambiguity this resolves).

        Disconnected mode changes exactly two things here: a POST is
        not even attempted while the store-path breaker is dark
        (allow() False -> spool the intent straight away), and the
        retries-exhausted-AND-truth-unreachable resolution — which the
        reconciler reports as (ORPHANED, None) — spools instead of
        forgetting: that signature is a store outage, not a placement
        problem, and forgetting would re-place the pod post-heal,
        breaking placement parity with an outage-free run."""
        t0 = self.clock()
        if not self.storehealth.allow():
            return self._spool_bind(pod, bound, node_name, vol_rollback)
        outcome, truth = self.reconciler.reconcile(
            pod, node_name, self._bind_attempt(pod, node_name))
        rec = tracing.active()
        if rec is not None:
            # per-pod async bind span (UID-keyed); retries inside the
            # reconciler already emitted bind_retry events
            rec.pod_span(pod.uid, "bind", self.clock() - t0,
                         node=node_name, outcome=outcome)
            if outcome != BOUND:
                # ambiguity resolution is exactly what a pod's trace
                # must surface: the bind POST's fate was only resolved
                # against API truth
                rec.event("bind_resolution", pod=pod.uid, outcome=outcome,
                          node=node_name)
        if outcome == ORPHANED and truth is None:
            return self._spool_bind(pod, bound, node_name, vol_rollback)
        return self._apply_bind_outcome(pod, bound, node_name, vol_rollback,
                                        outcome, truth, t0)

    def _apply_bind_outcome(self, pod: api.Pod, bound: api.Pod,
                            node_name: str, vol_rollback,
                            outcome: str, truth, t0: float) -> bool:
        """The cache/queue consequences of one reconciled bind outcome —
        shared by the live bind path and the spool drain."""
        if outcome == CONFIRMED:
            # the bind landed server-side and only the response was
            # lost: adopt API truth instead of rolling back. add_pod
            # confirms the assumption (and moves it if truth names a
            # different node); a duplicate informer confirmation later
            # is a no-op by the cache's state machine.
            with self._mu:
                self.cache.add_pod(truth)
                if truth.spec.node_name != node_name:
                    # adopted onto a DIFFERENT node (another actor's bind
                    # won): the snapshot row written at assume time still
                    # charges the assumed node — move it, or that node
                    # holds phantom capacity until the next scrub
                    self.snapshot.remove_pod(bound)
                    ni = self.cache.node_infos.get(node_name)
                    if ni is not None:
                        self.snapshot.refresh_node_resources(ni)
                    nb = self.cache.node_infos.get(truth.spec.node_name)
                    if nb is not None:
                        self.snapshot.refresh_node_resources(nb)
                        self.snapshot.add_pod(truth)
                    if vol_rollback is not None and \
                            not self.volume_binder.volumes_admit_node(
                                pod, nb.node if nb is not None else None):
                        # our PVC pre-binding chose PVs for the node WE
                        # assumed; they cannot serve where the pod really
                        # landed — free the claims so the winning
                        # leader's commit / the PV controller rebinds
                        vol_rollback()
        elif outcome in (ORPHANED, GONE):
            # never landed (or the pod was deleted): roll the assume
            # back. The rollback itself must not raise into the pool: if
            # an informer confirmation consumed the assume concurrently,
            # forget_pod raises KeyError — the pod IS bound and no
            # rollback is wanted.
            self.metrics.scheduling_errors.labels(stage="bind").inc()
            with self._mu:
                try:
                    self.cache.forget_pod(bound)
                except KeyError:
                    return True  # confirmed by informer: bind succeeded
                ni = self.cache.node_infos.get(node_name)
                if ni is not None:
                    self.snapshot.refresh_node_resources(ni)
                self.snapshot.remove_pod(bound)
            if vol_rollback is not None:
                vol_rollback()
            if outcome == ORPHANED:
                # backoff-requeue: a bind that just failed repeatedly
                # should not re-enter the very next wave at full speed
                self._park_with_backoff(truth if truth is not None else pod)
            return False
        with self._mu:
            self.cache.finish_binding(bound)
        self.metrics.binding_latency.observe(self.clock() - t0)
        # per-pod e2e: first enqueue -> bind POST landed. Observed (and
        # the timestamp consumed) only HERE so a failed bind's requeue
        # keeps the original enqueue time and the pod counts once
        added = self.queue.added_at.pop(pod.uid, None)
        if added is not None:
            self.metrics.pod_scheduling_latency.observe(self.clock() - added)
        self.metrics.pods_scheduled.inc()
        self.backoff.clear(pod.uid)
        # a successful bind clears the poison ladder too: an edited
        # (recovered) spec starts fresh on any future conviction
        self.poison_backoff.clear(pod.uid)
        self.queue.clear_backoff(pod.uid)
        self.queue.update_nominated_pod(pod, "")
        return True

    # -- disconnected-mode bind spool + durable intent journal -----------------

    def _spool_bind(self, pod: api.Pod, bound: api.Pod, node_name: str,
                    vol_rollback=None, seq: Optional[int] = None) -> bool:
        """Disconnected-mode bind: keep the assumption (capacity stays
        held, so post-heal placements are bit-identical to an
        outage-free run), append the intent to the durable journal, and
        park the POST in the in-memory spool in arrival order. The
        reconnect drain replays it through the full reconciler
        ambiguity path. Returns True — the pod IS placed; only the
        store write is deferred."""
        with self._mu:
            if pod.uid in self._spool_uids:
                return True
            if seq is None and self.journal is not None:
                try:
                    seq = self.journal.append_intent(bound, node_name)
                except Exception:
                    # full disk / IO fault at the worst moment: the
                    # intent still spools in memory (a crash now loses
                    # it — exactly the reference's pre-journal exposure)
                    logging.getLogger(__name__).exception(
                        "bind journal append failed; intent for %s/%s "
                        "spools in memory only", pod.namespace, pod.name)
            self._spool.append((pod, bound, node_name, vol_rollback, seq))
            self._spool_uids.add(pod.uid)
            depth = len(self._spool)
        self.metrics.binds_spooled.inc()
        rec = tracing.active()
        if rec is not None:
            rec.event("bind_spooled", pod=pod.uid, node=node_name,
                      seq=seq if seq is not None else -1, depth=depth)
        return True

    def _drain_spool(self) -> Dict[str, int]:
        """Replay spooled bind intents head-first (arrival order)
        through the reconciler. Stops at the first intent whose store
        path is still dark — that entry stays at the head for the next
        probe window and the breaker has already re-tripped via the
        per-attempt callbacks. Every resolved intent is removed from
        the spool and marked resolved in the journal."""
        stats = {"bound": 0, "confirmed": 0, "orphaned": 0, "gone": 0}
        while True:
            with self._mu:
                if not self._spool:
                    break
                entry = self._spool[0]
            if not self._flush_intent(entry, stats):
                break
        self._spool_drain_due = False
        if any(stats.values()):
            logging.getLogger(__name__).info(
                "bind spool drained: %(bound)d bound, %(confirmed)d "
                "confirmed, %(orphaned)d orphaned+requeued, "
                "%(gone)d gone", stats)
        return stats

    def _flush_intent(self, entry, stats) -> bool:
        """POST one spooled intent and apply its outcome. False = the
        store is still dark (entry stays spooled at the head)."""
        pod, bound, node_name, vol_rollback, seq = entry
        t0 = self.clock()
        outcome, truth = self.reconciler.reconcile(
            pod, node_name, self._bind_attempt(pod, node_name))
        if outcome == ORPHANED and truth is None:
            return False  # still unreachable: keep the intent spooled
        with self._mu:
            try:
                self._spool.remove(entry)
            except ValueError:
                pass
            self._spool_uids.discard(pod.uid)
        self._apply_bind_outcome(pod, bound, node_name, vol_rollback,
                                 outcome, truth, t0)
        if seq is not None and self.journal is not None:
            self.journal.resolve(
                seq, CONFIRMED if outcome in (BOUND, CONFIRMED) else outcome)
        stats[outcome if outcome != BOUND else "bound"] += 1
        rec = tracing.active()
        if rec is not None:
            rec.event("bind_despooled", pod=pod.uid, node=node_name,
                      outcome=outcome)
        return True

    def recover_from_journal(self) -> Dict[str, int]:
        """Crash-restart replay: re-own every unresolved bind intent in
        the journal before the first wave. API truth decides each one:
        already bound -> adopt (the crash lost only the confirmation);
        still pending -> re-assume and re-spool (the POST never got
        out, or its fate was lost with the process); deleted or
        recreated under a new UID -> resolve as gone; truth unreachable
        (the outage outlived the crash) -> re-assume from the local
        mirror and re-spool for the post-heal drain. Runs at
        construction (AFTER informer backfill, so journal-claimed pods
        can be retired from the pending queue) and again on
        recover_leadership()."""
        stats = {"adopted": 0, "respooled": 0, "requeued": 0, "gone": 0,
                 "unreachable": 0}
        if self.journal is None or not self._journal_replay_enabled:
            return stats

        class _PodRef:
            # pod-shaped stub for the truth GET: namespace/name/uid are
            # all the journal recorded
            def __init__(self, ns, name, uid):
                self.namespace, self.name, self.uid = ns, name, uid
                self.metadata = type("M", (), {"name": name})()

        for it in self.journal.unresolved():
            uid, node, seq = it.get("uid"), it.get("node"), it.get("seq")
            ns, name = it.get("ns"), it.get("name")
            with self._mu:
                if uid in self._spool_uids:
                    continue  # the live spool owns it (leadership
                    #           bounce, not a crash)
            local = self.store.get("pods", ns, name)
            reachable = True
            try:
                truth = self._pod_truth(local if local is not None
                                        else _PodRef(ns, name, uid))
            except Exception:
                truth, reachable = None, False
            if not reachable:
                # outage persists across the restart: re-own the intent
                # from the mirror copy so capacity is held and the
                # post-heal drain resolves it; without a mirror copy the
                # intent stays unresolved for the next replay
                if local is not None and self._respool_local(local, node,
                                                             seq):
                    stats["respooled"] += 1
                else:
                    stats["unreachable"] += 1
                continue
            if truth is None or truth.uid != uid:
                # deleted (or the name reused by a NEW pod) while down
                self.journal.resolve(seq, GONE)
                stats["gone"] += 1
            elif truth.spec.node_name:
                # the bind landed before the crash; adopt it and retire
                # the pod from the queue informer backfill re-added
                with self._mu:
                    self.cache.add_pod(truth)  # insert-or-confirm
                    self.queue.remove_if_pending(uid)
                    self.queue.assigned_pod_added(truth)
                self.journal.resolve(seq, CONFIRMED)
                stats["adopted"] += 1
            else:
                # still Pending in API truth: the intent never landed.
                # Re-assume onto the journaled node and re-spool under
                # the SAME seq (the drain POSTs it as soon as the path
                # is confirmed healthy — which this GET just did).
                if self._respool_local(truth, node, seq):
                    stats["respooled"] += 1
                else:
                    # node vanished while down: the pod stays queued
                    # (informer backfill already re-added it) and
                    # schedules fresh
                    self.journal.resolve(seq, ORPHANED)
                    stats["requeued"] += 1
        if any(stats.values()):
            logging.getLogger(__name__).info(
                "bind-journal replay: %(adopted)d adopted, %(respooled)d "
                "re-spooled, %(requeued)d requeued fresh, %(gone)d gone, "
                "%(unreachable)d unreachable (kept for next replay)",
                stats)
            self.export_queue_gauges()
        return stats

    def _respool_local(self, pod: api.Pod, node_name: str,
                       seq: Optional[int]) -> bool:
        """Re-own one journaled intent: assume the pod onto its
        journaled node (if that node still exists) and re-spool it."""
        bound = api.with_node_name(pod, node_name)
        with self._mu:
            ni = self.cache.node_infos.get(node_name)
            if ni is None:
                return False
            try:
                self.cache.assume_pod(bound)
            except KeyError:
                pass  # already assumed/known — capacity already held
            else:
                self.snapshot.refresh_node_resources(
                    self.cache.node_infos[node_name])
                self.snapshot.add_pod(bound)
            self.queue.remove_if_pending(bound.uid)
        self._spool_bind(pod, bound, node_name, None, seq=seq)
        return True

    def wait_for_binds(self) -> None:
        """Drain all in-flight binds (callers that need settled store
        state: end of schedule_pending, tests, shutdown)."""
        import concurrent.futures

        while True:
            with self._inflight_mu:
                # ktpu: allow[determinism] wait-on-ALL; order irrelevant
                pending = list(self._inflight)
            if not pending:
                return
            concurrent.futures.wait(pending)

    def close(self) -> None:
        """Settle in-flight binds and release the binder pool's threads.
        The scheduler object stays queryable but schedules no more."""
        self.wait_for_binds()
        if self._bind_pool is not None:
            self._bind_pool.shutdown(wait=True)
            self._bind_pool = None

    # -- cluster-autoscaler hooks ----------------------------------------------

    def pending_unschedulable(self) -> List[api.Pod]:
        """Snapshot of the unschedulable map — the cluster autoscaler's
        demand feed: pods that failed on every node and wait for the
        cluster to change."""
        return self.queue.unschedulable_pods()

    def shadow_featurizer(self, snapshot: Snapshot) -> PodFeaturizer:
        """Pending-pod featurization over a scratch snapshot (the
        autoscaler's what-if hook, ops/simulate.py): shares the live
        GroupLister so spreading selectors encode exactly as they would
        on the live path. The scratch snapshot must share the live
        vocabularies (shadow_snapshot guarantees it) so interned ids
        line up."""
        return PodFeaturizer(snapshot, self.featurizer.group_selectors)

    # -- leadership lifecycle (warm restart) -----------------------------------

    @property
    def dormant(self) -> bool:
        return self._dormant

    def enter_dormant(self) -> None:
        """Leadership lost: stop scheduling waves and DRAIN in-flight
        binds — a demoted leader finishing a POST it already sent is
        safe (the new leader sees the binding through its informers; the
        server 409s any conflict), but dispatching NEW work is not.
        Informers keep running so the cache stays warm for
        recover_leadership(). Idempotent. Taking _mu to set the flag
        orders dormancy AFTER any wave already executing on another
        thread, so once this returns no further binds can be dispatched;
        call it from the scheduling loop, not the elector callback — the
        drain blocks for as long as in-flight binds take to settle."""
        if self._dormant:
            return
        with self._mu:
            self._dormant = True
        self.wait_for_binds()
        logging.getLogger(__name__).info(
            "scheduler dormant: leadership lost; binds drained, %d assumed "
            "pods held for reconciliation, informers stay warm",
            len(self.cache.assumed_pods()))

    def recover_leadership(self) -> Dict[str, int]:
        """Leadership re-acquired after a dormant spell: reconcile every
        assumed pod against API truth (adopt confirmed bindings, forget
        orphans and release their capacity), force a full HBM snapshot
        rebuild (nothing incremental is trusted across a leadership
        gap — another leader may have scheduled through it), and resume
        waves. Returns the reconciliation tally."""
        self.wait_for_binds()
        stats = {"confirmed": 0, "orphaned": 0, "unresolved": 0}

        # phase 1, OUTSIDE _mu: one capped GET per assumed pod (truth,
        # not the mirror) — informers must stay live while a flapping
        # apiserver stretches these round trips. The binder pool (idle:
        # binds just drained) fans the GETs out so a full wave of
        # assumed pods resolves in ~one round trip, not wave_size of
        # them serially.
        def _fetch(pod):
            try:
                return (pod, self._pod_truth(pod), True)
            except Exception as e:
                # truth unreachable for THIS pod: keep the assumption —
                # holding capacity briefly beats double-placing; the
                # assume TTL (cleanup_expired) is the backstop
                logging.getLogger(__name__).warning(
                    "recovery: could not resolve assumed pod %s/%s "
                    "against API truth (%s: %s); keeping the assumption",
                    pod.namespace, pod.name, type(e).__name__, e)
                return (pod, None, False)

        assumed = self.cache.assumed_pods()
        if self._bind_pool is not None and len(assumed) > 1:
            resolved = list(self._bind_pool.map(_fetch, assumed))
        else:
            resolved = [_fetch(p) for p in assumed]
        # phase 2, under _mu: apply, then rebuild the snapshot wholesale
        # (so no per-pod snapshot surgery here — the rebuild is the
        # recovery analog of the device-path breaker's on_recover)
        with self._mu:
            for pod, truth, ok in resolved:
                if not self.cache.is_assumed(pod):
                    continue  # an informer event settled it while we fetched
                if not ok:
                    stats["unresolved"] += 1
                elif truth is not None and truth.spec.node_name:
                    self.cache.add_pod(truth)  # adopt the confirmed binding
                    # the informer events that would normally retire it
                    # from the pending queue may be exactly what was lost
                    self.queue.remove_if_pending(pod.uid)
                    self.queue.assigned_pod_added(truth)
                    stats["confirmed"] += 1
                else:
                    try:
                        self.cache.forget_pod(pod)
                    except KeyError:
                        pass
                    stats["orphaned"] += 1
                    if truth is not None:
                        # still pending in the API: schedule it fresh
                        self.queue.add_if_not_present(truth)
                    else:
                        # deleted while we weren't looking (the DELETED
                        # event may have been lost too)
                        self.queue.delete(pod)
            # crash-journal replay re-runs on every leadership
            # recovery: a prior incarnation (or the dormant spell's
            # binds) may have left unresolved intents behind; anything
            # the live spool already owns is skipped
            self.recover_from_journal()
            self.scrubber.rebuild()
            self._dormant = False
        # anything another leader failed to place may be schedulable
        # now; give every parked pod a fresh look in the first wave
        self.queue.move_all_to_active()
        logging.getLogger(__name__).info(
            "scheduler resumed leadership: %(confirmed)d assumed pods "
            "confirmed, %(orphaned)d orphans forgotten+requeued, "
            "%(unresolved)d unresolved (TTL backstop)", stats)
        return stats

    # per-attempt deadline on truth GETs: reconciliation runs on binder
    # threads and (for the recovery pass) under _mu — a hung round trip
    # must fail fast, like the bind POST's own bind_timeout
    TRUTH_GET_TIMEOUT = 5.0

    def _pod_truth(self, pod: api.Pod) -> Optional[api.Pod]:
        """One pod from API truth. Goes through the REST client when the
        store is a RemoteStore — its get() serves the reflector mirror,
        whose staleness is exactly what bind reconciliation and the
        recovery pass must not trust. None = deleted; raises when truth
        is unreachable.

        This is also the store-path breaker's GET feed: a transport
        failure counts against the consecutive-failure ladder (op=get),
        any ANSWER — including 404/409 — counts as the store being
        reachable. The `store.outage` fault point fires here so chaos
        can sever the truth path together with the bind path."""
        try:
            if faultpoints.fire("store.outage", payload=("get", pod.uid)):
                raise ConnectionError("store.outage: truth GET dropped")
            client = getattr(self.store, "client", None)
            if client is not None:
                from ..client.rest import APIStatusError
                try:
                    truth = client.get("pods", pod.namespace,
                                       pod.metadata.name,
                                       timeout=self.TRUTH_GET_TIMEOUT)
                except APIStatusError as e:
                    self.storehealth.record_success()  # the store ANSWERED
                    if e.code == 404:
                        return None
                    raise
            else:
                truth = self.store.get("pods", pod.namespace, pod.name)
        except Exception as e:
            from ..client.rest import APIStatusError as _APIErr
            if not isinstance(e, _APIErr):
                self.metrics.store_errors.labels(op="get").inc()
                self.storehealth.record_failure()
            raise
        self.storehealth.record_success()
        return truth

    # -- failure path ----------------------------------------------------------

    def _fit_error(self, pod: api.Pod, idx: int, fail_counts,
                   res=None) -> FitError:
        reasons: Dict[str, int] = {}
        for q, name in enumerate(enc.MASK_STACK_NAMES):
            c = int(fail_counts[q, idx])
            if not c:
                continue
            if name == "PodFitsResources":
                reasons[insufficient_resource_reason("resources")] = c
            elif name == "HostPlugins":
                # real per-node reasons recorded by _host_plugin_mask —
                # counted only for nodes whose FIRST failure was the host
                # stack (short-circuit attribution, like the device rows)
                fails = getattr(self, "_wave_host_fails", {}).get(idx, {})
                if fails and res is not None:
                    col = np.asarray(res.masks[:, idx, :])  # [Q, N]
                    valid = self.snapshot.valid
                    for n, nname in enumerate(self.snapshot.node_names):
                        if (n < col.shape[1] and valid[n] and not col[q, n]
                                and col[:q, n].all()):
                            key = fails.get(nname, "NoDiskConflict")
                            r = REASONS.get(key, key)
                            reasons[r] = reasons.get(r, 0) + 1
                else:
                    reasons[REASONS["NoDiskConflict"]] = c
            elif name == "CheckNodeCondition":
                reasons[REASONS["NodeNotReady"]] = c
            elif name == "CheckNodeUnschedulable":
                reasons[REASONS["NodeUnschedulable"]] = c
            elif name == "CheckNodeMemoryPressure":
                reasons[REASONS["NodeUnderMemoryPressure"]] = c
            elif name == "CheckNodeDiskPressure":
                reasons[REASONS["NodeUnderDiskPressure"]] = c
            elif name == "CheckNodePIDPressure":
                reasons[REASONS["NodeUnderPIDPressure"]] = c
            else:
                reasons[REASONS.get(name, name)] = c
        return FitError(pod.full_name(), int(np.sum(self.snapshot.valid)), reasons)

    def _failed_predicates_by_node(self, res, idx: int) -> Dict[str, List[str]]:
        """First-failing predicate per node for one failed pod, from the
        device mask stack (short-circuit attribution)."""
        col = np.asarray(res.masks[:, idx, :])  # [Q, N]
        out: Dict[str, List[str]] = {}
        valid = self.snapshot.valid
        host_fails = getattr(self, "_wave_host_fails", {}).get(idx, {})
        for n, name in enumerate(self.snapshot.node_names):
            if n < col.shape[1] and valid[n]:
                fails = np.flatnonzero(~col[:, n])
                if fails.size:
                    pred = enc.MASK_STACK_NAMES[fails[0]]
                    if pred == "HostPlugins":
                        out[name] = [host_fails.get(name, "NoDiskConflict")]
                        continue
                    if pred == "CheckNodeCondition":
                        # distinguish sub-reasons host-side for the
                        # unresolvable filter
                        ni = self.cache.node_infos.get(name)
                        if ni is not None and ni.node is not None:
                            _, rs = golden.check_node_condition(None, ni)
                            out[name] = ["NodeNotReady" if r == REASONS["NodeNotReady"]
                                         else "NodeNetworkUnavailable" if r == REASONS["NodeNetworkUnavailable"]
                                         else "NodeUnschedulable" if r == REASONS["NodeUnschedulable"]
                                         else "NodeOutOfDisk" for r in rs] or ["NodeNotReady"]
                            continue
                    out[name] = [pred]
        return out

    def _handle_failure(self, pod: api.Pod, idx: int, fail_counts, res):
        self.metrics.pods_failed.inc()
        err = self._fit_error(pod, idx, fail_counts, res)
        self._count_unschedulable(err)
        if (self.features.enabled("PodPriority")
                and not self.profile.disable_preemption):
            t0 = self.clock()
            self.metrics.total_preemption_attempts.inc()
            aff = pod.spec.affinity
            pod_has_ipa = aff is not None and (
                aff.pod_affinity is not None or aff.pod_anti_affinity is not None)
            pr = preempt(pod, self.cache, self._failed_predicates_by_node(res, idx),
                         self._pdbs(),
                         with_affinity=self.snapshot.has_affinity_terms or pod_has_ipa,
                         extenders=self.profile.extenders,
                         extra_fit=self._host_extra_fit,
                         gang_guard=self._gang_guard(),
                         snapshot=self.snapshot,
                         featurizer=self.featurizer)
            self.metrics.preemption_evaluation.observe(self.clock() - t0)
            if pr is not None and pr.victims:
                self._perform_preemption(pod, pr)
            # a zero-victim candidate means the what-if thinks the pod
            # fits as-is (a racing eviction freed capacity, or the host
            # fit diverged from the device mask): same discipline as
            # _preempt_chunk — don't nominate, just park and retry. The
            # nomination's store write echoes through the informer and
            # re-activates the pod BEFORE the park below, so a divergent
            # zero-victim nominate becomes a backoff-less hot loop.
        self._park_with_backoff(pod)
        self.store.set_pod_condition(pod, ("PodScheduled", "False:" + err.message()))

    def _park_with_backoff(self, pod: api.Pod):
        """Failure-path requeue: compute the pod's next backoff duration
        and park it unschedulable; the queue keeps it ineligible for the
        active heap until the deadline even if cluster events move it
        (reference: util/backoff_utils.go:97-112, enforced by the factory
        error func's delayed requeue)."""
        d = self.backoff.bump(pod.uid)
        self.queue.set_backoff(pod.uid, self.clock() + d)
        self.queue.add_unschedulable_if_not_present(pod)

    def _pdbs(self) -> List[api.PodDisruptionBudget]:
        return list(self.store.list("poddisruptionbudgets"))

    def _perform_preemption(self, pod: api.Pod, pr):
        """Reference: scheduler.go:233-256 — nominate, evict victims, clear
        lower nominations. Gang extension: when the evictions drop a
        victim gang below its minMember, the gang's REMAINING members are
        evicted too (cluster-wide) — a sub-minMember gang holds capacity
        while doing no useful work, the exact deadlock gang scheduling
        exists to prevent; its controller recreates the pods and the gang
        re-forms through the waiting area."""
        tracing.event("preemption", pod=pod.uid, node=pr.node_name,
                      victims=len(pr.victims),
                      pdb_violations=pr.num_pdb_violations)
        pod.status.nominated_node_name = pr.node_name
        self.store.set_nominated_node(pod, pr.node_name)
        self.queue.update_nominated_pod(pod, pr.node_name)
        # dict-as-ordered-set (the PR 8 rule): broken-gang teardown below
        # deletes pods in this iteration order, which must follow victim
        # order, not the gang keys' hash order
        victim_gangs: Dict[str, None] = {}
        for victim in pr.victims:
            if self.gangs.active:
                k = self.gangs.key(victim)
                if k is not None:
                    victim_gangs[k] = None
            self.metrics.pod_preemption_victims.inc()
            try:
                self.store.delete("pods", victim.namespace, victim.metadata.name)
            except KeyError:
                pass
        victim_uids = {v.uid for v in pr.victims}
        for gkey in victim_gangs:
            remaining = [p for p in self.gangs.placed_members(self.cache, gkey)
                         if p.uid not in victim_uids]
            if not remaining:
                continue
            m = self.gangs.min_member_by_key(gkey, sample=remaining[0])
            if len(remaining) >= m:
                continue
            for p in remaining:
                self.metrics.pod_preemption_victims.inc()
                try:
                    self.store.delete("pods", p.namespace, p.metadata.name)
                except KeyError:
                    pass
        for lower in get_lower_priority_nominated_pods(pod, pr.node_name, self.queue):
            lower.status.nominated_node_name = ""
            self.queue.update_nominated_pod(lower, "")

    # -- host plugin mask ------------------------------------------------------

    def _host_plugin_mask(self, pods: List[api.Pod], P: int) -> np.ndarray:
        """Evaluate non-tensorized predicates host-side, only for pods that
        can possibly fail them: each host plugin may carry a `relevant(pod)`
        gate (e.g. volume predicates only fire for pods with PVC/special
        volumes), mirroring how the reference orders cheap checks first
        (predicates.go:133).

        Side effect: records the first-failing predicate key per (pod,
        node) in self._wave_host_fails so FitError reporting and the
        preemption unresolvable filter see the real reason behind the
        device mask stack's "HostPlugins" pseudo-predicate."""
        N = self.snapshot.caps.N
        mask = np.ones((P, N), bool)
        self._wave_host_fails: Dict[int, Dict[str, str]] = {}
        if not self.profile.host_filters and not self.profile.extenders:
            return mask
        for i, pod in enumerate(pods):
            fails: Dict[str, str] = {}
            fns = [(pname, fn) for pname, fn in self.profile.host_filters.items()
                   if getattr(fn, "relevant", None) is None or fn.relevant(pod)]
            eclass = (equivalence_class(pod) if self.ecache is not None
                      else None)
            if fns:
                for name, ni_idx in self.snapshot.node_index.items():
                    ni = self.cache.node_infos.get(name)
                    if ni is None:
                        continue
                    for pname, fn in fns:
                        cached = (self.ecache.lookup(eclass, name, pname)
                                  if self.ecache is not None else None)
                        if cached is not None:
                            ok, rs = cached
                        else:
                            ok, rs = fn(pod, ni)
                            if self.ecache is not None:
                                self.ecache.update(eclass, name, pname, ok, rs)
                        if not ok:
                            mask[i, ni_idx] = False
                            fails[name] = REASON_KEYS.get(rs[0], pname) if rs else pname
                            break
            for ext in self.profile.extenders:
                if not ext.filter_verb:
                    continue
                feasible, _failed = ext.filter(
                    pod, list(self.snapshot.node_index),
                    node_labels=None if ext.node_cache_capable else {
                        n: (ni.node.metadata.labels or {})
                        for n, ni in self.cache.node_infos.items()
                        if ni.node is not None})
                keep = {self.snapshot.node_index[n] for n in feasible
                        if n in self.snapshot.node_index}
                for name, ni_idx in self.snapshot.node_index.items():
                    if ni_idx not in keep and mask[i, ni_idx]:
                        mask[i, ni_idx] = False
                        fails[name] = "ExtenderFilter"
            if fails:
                self._wave_host_fails[i] = fails
        return mask

    def _host_extra_fit(self, pod: api.Pod, ni) -> bool:
        """Host filters as a single fit check for preemption's what-if
        simulation (victim removal can resolve NoDiskConflict /
        MaxVolumeCount, so the simulation must re-run them)."""
        for fn in self.profile.host_filters.values():
            if getattr(fn, "relevant", None) is not None and not fn.relevant(pod):
                continue
            ok, _ = fn(pod, ni)
            if not ok:
                return False
        return True

    def _host_score_matrix(self, pods: List[api.Pod], P: int) -> Optional[np.ndarray]:
        """Host-side Score contributions ([P, N] f32, pre-weighted) from
        policy host priorities and extender Prioritize webhooks — the
        kernel's extra_scores input (reference: generic_scheduler.go:615
        Reduce goroutines + :650 extender prioritize goroutines)."""
        if not self.profile.host_scores and not any(
                ext.prioritize_verb for ext in self.profile.extenders):
            return None
        N = self.snapshot.caps.N
        out = np.zeros((P, N), np.float32)
        idx = self.snapshot.node_index
        for i, pod in enumerate(pods):
            for name, (fn, weight) in self.profile.host_scores.items():
                for node, s in fn(pod, self.cache.node_infos).items():
                    j = idx.get(node)
                    if j is not None:
                        out[i, j] += weight * s
            for ext in self.profile.extenders:
                for node, s in ext.prioritize(pod, list(idx)).items():
                    j = idx.get(node)
                    if j is not None:
                        out[i, j] += s
        return out
