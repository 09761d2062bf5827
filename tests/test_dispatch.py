"""The device-dispatch matrix: every site that runs a device program
(the round, the per-wave path, the gang and the warm-up) under every
fault class the dispatch path classifies. Each case reads the fate of
each pod and the counters of every fault plane, so a change to the
dispatch path shows here as a changed row, not as a silent drift
between sites.

Fates, one letter per pod in creation order: P placed (bound), Q still
in the active queue, K parked with backoff, X quarantined.

Two rows pin rules the sites share: warm-up × pallas (a warm-up
demotion counts on scheduling_errors{pallas} like every other), and
round × both (a round whose Pallas and XLA dispatches both fail keeps
Pallas, as the wave and the gang do, so the next round tries it again).
"""

import re

import pytest

from kubernetes_tpu.ops import kernel
from kubernetes_tpu.parallel.mesh import make_mesh
from kubernetes_tpu.runtime.store import ObjectStore
from kubernetes_tpu.sched.breaker import lost_device_fault
from kubernetes_tpu.sched.scheduler import Scheduler
from kubernetes_tpu.state.featurize import poison_pod_fault
from kubernetes_tpu.utils import faultpoints, tracing

from helpers import make_node, make_pod

pytestmark = pytest.mark.faults

SITES = ("round", "wave", "gang", "warm")
FAULTS = ("kernel", "oom", "crash", "nan", "hang", "lost", "pallas")
# the kernel.* fault point each site's program fires
KERNEL_POINT = {"round": "kernel.round", "wave": "kernel.wave",
                "gang": "kernel.gang", "warm": "kernel.round"}


class FakeClock:
    def __init__(self, t: float = 1000.0):
        self.t = t

    def __call__(self) -> float:
        return self.t


@pytest.fixture(autouse=True)
def _tracing_on():
    """Every case reads the round ledger, so the recorder is on; it is
    process-global, so it never outlives the test."""
    tracing.disable()
    yield tracing.enable()
    tracing.disable()


def _world(site, fault):
    store = ObjectStore()
    kw = {}
    if fault == "lost":
        kw["mesh"] = make_mesh(8)
    if fault == "hang":
        kw["wave_deadline_s"] = 0.15
    sched = Scheduler(store, wave_size=8, clock=FakeClock(), **kw)
    for i in range(16):
        store.create("nodes", make_node(
            f"n{i}", cpu="4", memory="8Gi",
            labels={"kubernetes.io/hostname": f"n{i}"}))
    return store, sched, _add_pods(store, site, 0)


def _add_pods(store, site, first):
    pods = []
    for i in range(first, first + (2 if site == "gang" else 4)):
        p = make_pod(f"p{i}", cpu="100m", memory="128Mi")
        if site == "gang":
            p.metadata.annotations = {
                "pod-group.scheduling.k8s.io/name": "g",
                "pod-group.scheduling.k8s.io/min-available": "2"}
        store.create("pods", p)
        pods.append(p)
    return pods


def _run_site(site, sched, pods):
    """Drive the site; returns the exception's type name when the site
    let one out."""
    try:
        if site == "wave":
            sched.run_once()
        elif site == "warm":
            sched.warm_pipeline(pods)
        else:
            sched.schedule_pending()
    except Exception as e:
        return type(e).__name__
    return None


def _arm(site, fault, sched, pods, monkeypatch):
    victim = pods[1].uid
    if fault in ("pallas", "both"):
        # Pallas chosen on the CPU, where it lowers only in interpret
        # mode: every Pallas dispatch fails as a real lowering fault
        monkeypatch.setattr(kernel, "pallas_default", lambda: True)
    if fault in ("kernel", "both"):
        faultpoints.activate(KERNEL_POINT[site], "raise")
    elif fault == "oom":
        faultpoints.activate("device.oom", "raise", times=1)
    elif fault in ("crash", "nan"):
        faultpoints.activate("wave.poison", "corrupt",
                             fn=poison_pod_fault(victim, fault))
    elif fault == "hang":
        faultpoints.activate("kernel.hang", "latency", arg=1.0, times=1)
    elif fault == "lost":
        faultpoints.activate("device.lost", "corrupt", fn=lost_device_fault(
            str(sched.mesh.devices.flat[3])))


def _observe(store, sched, rec, raised):
    m = sched.metrics
    quarantined = {p.uid for p in sched.queue.quarantined_pods()}
    parked = {p.uid for p in sched.queue.unschedulable_pods()}
    fates = "".join(
        "P" if p.spec.node_name else "X" if p.uid in quarantined
        else "K" if p.uid in parked else "Q"
        for p in sorted(store.list("pods"), key=lambda p: p.metadata.name))
    errors = {}
    for c in m.scheduling_errors.children():
        stage = re.search(r'stage="([^"]*)"', c.name).group(1)
        errors[stage] = errors.get(stage, 0) + int(c.value)
    return dict(
        fates=fates, errors=errors,
        capacity_faults=int(m.capacity_faults.value),
        waves=(int(m.waves_total.value(path="device")),
               int(m.waves_total.value(path="host"))),
        reforms=int(m.mesh_reforms.total()),
        breaker=(sched.breaker.state, sched.breaker.trips),
        outcomes=[r.get("outcome") for r in rec.ledger_rows()],
        path=sched.wave_path(), raised=raised)


def run_case(site, fault, monkeypatch, rec):
    if fault == "hang":
        # the watchdog grants a first compile twenty deadlines, so a
        # clean run of the same shapes, with no deadline to abandon its
        # compile, warms the program first
        store, sched, pods = _world(site, "none")
        assert _run_site(site, sched, pods) is None
        sched.close()
        rec.rounds.clear()
    store, sched, pods = _world(site, fault)
    _arm(site, fault, sched, pods, monkeypatch)
    raised = _run_site(site, sched, pods)
    faultpoints.reset()
    if fault == "both":
        # the kernel fault was never Pallas's, so the round kept Pallas:
        # a clean second drain tries it again, and demotes it again
        raised = _run_site(site, sched, _add_pods(store, site, len(pods)))
    if sched.watchdog is not None:
        # settle the abandoned dispatch before the next test
        assert sched.watchdog.drain(5.0)
    got = _observe(store, sched, rec, raised)
    sched.close()
    return got


def _row(fates, errors, outcomes, path, waves, capacity=0, reforms=0,
         breaker=("closed", 0), raised=None):
    """One expected observation; `waves` is (device, host) and
    `errors` the scheduling_errors_total count of each stage."""
    return dict(fates=fates, errors=errors, capacity_faults=capacity,
                waves=waves, reforms=reforms, breaker=breaker,
                outcomes=outcomes, path=path, raised=raised)


EXPECTED = {
    ("round", "kernel"): _row(
        "PPPP", {"wave": 1}, ["device_failure", "ok"], "xla", (1, 0)),
    ("round", "oom"): _row(
        "PPPP", {"dispatch": 1}, ["capacity_fault", "ok"], "xla", (1, 0),
        capacity=1),
    ("round", "crash"): _row(
        "PXPP", {"poison": 3}, ["input_fault", "input_fault", "ok",
        "input_fault", "ok"], "xla", (2, 0)),
    ("round", "nan"): _row(
        "PXPP", {"poison": 1}, ["input_fault", "ok"], "xla", (1, 0)),
    ("round", "hang"): _row(
        "PPPP", {"dispatch": 1, "wave": 1}, ["device_failure", "ok"], "vector",
        (0, 1), breaker=("open", 1)),
    ("round", "lost"): _row(
        "PPPP", {"dispatch": 1, "wave": 1}, ["device_failure", "ok"], "vector",
        (0, 1), reforms=1),
    ("round", "pallas"): _row(
        "PPPP", {"dispatch": 1, "pallas": 1}, ["ok"], "xla", (1, 0)),
    ("wave", "kernel"): _row(
        "PPPP", {"wave": 1}, ["device_failure", "ok"], "vector", (0, 1)),
    ("wave", "oom"): _row(
        "PPPP", {"dispatch": 1}, ["capacity_fault", "ok"], "xla", (1, 0),
        capacity=1),
    ("wave", "crash"): _row(
        "PXPP", {"poison": 3}, ["input_fault", "input_fault", "ok",
        "input_fault", "ok"], "xla", (2, 0)),
    ("wave", "nan"): _row(
        "QXQQ", {"poison": 1}, ["input_fault"], "xla", (0, 0)),
    ("wave", "hang"): _row(
        "PPPP", {"dispatch": 1, "wave": 1}, ["device_failure", "ok"], "vector",
        (0, 1), breaker=("open", 1)),
    ("wave", "lost"): _row(
        "PPPP", {"dispatch": 1, "wave": 1}, ["device_failure", "ok"], "vector",
        (0, 1), reforms=1),
    ("wave", "pallas"): _row(
        "PPPP", {"dispatch": 1, "pallas": 1}, ["ok"], "xla", (1, 0)),
    ("gang", "kernel"): _row(
        "KK", {"wave": 1}, ["device_failure"], "unresolved", (0, 0)),
    ("gang", "oom"): _row(
        "PP", {"dispatch": 1}, ["capacity_fault"], "vector", (0, 1),
        capacity=1),
    ("gang", "crash"): _row(
        "XX", {"poison": 1}, ["input_fault"], "unresolved", (0, 0)),
    ("gang", "nan"): _row("XX", {"poison": 1}, ["input_fault"], "xla", (1, 0)),
    ("gang", "hang"): _row(
        "PP", {"dispatch": 1, "wave": 1}, ["device_failure"], "vector", (0, 1),
        breaker=("open", 1)),
    ("gang", "lost"): _row(
        "PP", {"dispatch": 1, "wave": 1}, ["device_failure"], "vector", (0, 1),
        reforms=1),
    ("gang", "pallas"): _row(
        "PP", {"dispatch": 1, "pallas": 1}, ["ok"], "xla", (1, 0)),
    ("warm", "kernel"): _row(
        "QQQQ", {}, [], "unresolved", (0, 0), raised="FaultInjected"),
    ("warm", "oom"): _row(
        "QQQQ", {"dispatch": 1}, [], "unresolved", (0, 0),
        raised="FaultInjected"),
    ("warm", "crash"): _row("QQQQ", {}, [], "unresolved", (0, 0)),
    ("warm", "nan"): _row("QQQQ", {}, [], "unresolved", (0, 0)),
    ("warm", "hang"): _row(
        "QQQQ", {"dispatch": 1}, [], "unresolved", (0, 0),
        raised="DispatchTimeout"),
    ("warm", "lost"): _row(
        "QQQQ", {"dispatch": 1}, [], "unresolved", (0, 0),
        raised="DeviceLost"),
    ("warm", "pallas"): _row(
        "QQQQ", {"dispatch": 1, "pallas": 1}, [], "unresolved", (0, 0)),
    ("round", "both"): _row(
        "PPPPPPPP", {"pallas": 3, "wave": 1, "dispatch": 2},
        ["device_failure", "ok", "ok"], "xla", (2, 0)),
}


@pytest.mark.parametrize("site,fault", [(s, f) for s in SITES for f in FAULTS]
                         + [("round", "both")])
def test_dispatch_matrix(site, fault, monkeypatch, _tracing_on):
    assert run_case(site, fault, monkeypatch, _tracing_on) \
        == EXPECTED[(site, fault)]


# -- the formulation object ---------------------------------------------------


def _formulation(monkeypatch, default=True, multi_device=False):
    from kubernetes_tpu.sched.dispatch import Formulation
    from kubernetes_tpu.utils.metrics import Metrics

    monkeypatch.setattr(kernel, "pallas_default", lambda: default)
    return Formulation(Metrics(), multi_device=multi_device)


def _attempt(calls, fails=None, results=None):
    """attempt(use_pallas) recording its calls; raises fails[use_pallas]
    where given, else returns results[use_pallas] or "out"."""
    def attempt(use_p):
        calls.append(use_p)
        if use_p in (fails or {}):
            raise fails[use_p]
        return (results or {}).get(use_p, "out")
    return attempt


def _case_default(monkeypatch):
    # off the TPU the default is XLA for every program
    from kubernetes_tpu.sched.dispatch import PROGRAMS, Formulation
    from kubernetes_tpu.utils.metrics import Metrics

    assert kernel.pallas_default() is False  # tests run on the CPU
    f = Formulation(Metrics())
    assert [f.pallas(p) for p in PROGRAMS] == [False, False, False]


def _case_mesh(monkeypatch):
    # GSPMD cannot shard a pallas_call: XLA up front under a mesh
    from kubernetes_tpu.sched.dispatch import PROGRAMS

    f = _formulation(monkeypatch, multi_device=True)
    assert [f.pallas(p) for p in PROGRAMS] == [False, False, False]


def _case_demote(monkeypatch):
    f, calls = _formulation(monkeypatch), []
    assert f.run("wave", _attempt(calls, fails={True: RuntimeError()})) \
        == ("out", "xla")
    assert f.run("wave", _attempt(calls)) == ("out", "xla")
    assert calls == [True, False, False]
    assert f.metrics.scheduling_errors.value(stage="pallas") == 1
    # each program fails on its own: the round still tries Pallas
    assert f.pallas("round") is True


def _case_restore(monkeypatch):
    # both formulations failed: the fault was never Pallas's
    f, calls = _formulation(monkeypatch), []
    with pytest.raises(RuntimeError):
        f.run("round", _attempt(calls, fails={True: RuntimeError(),
                                              False: RuntimeError()}))
    assert calls == [True, False] and f.pallas("round") is True
    assert f.metrics.scheduling_errors.value(stage="pallas") == 1


def _case_timeout(monkeypatch):
    # a wedged runtime is not a Pallas failure: no retry at it
    from kubernetes_tpu.utils.watchdog import DispatchTimeout

    f, calls = _formulation(monkeypatch), []
    with pytest.raises(DispatchTimeout):
        f.run("gang", _attempt(
            calls, fails={True: DispatchTimeout("gang", 0.1)}))
    assert calls == [True] and f.pallas("gang") is True
    assert f.metrics.scheduling_errors.value(stage="pallas") == 0


def _case_crosscheck(monkeypatch):
    # the first Pallas run is compared with XLA; a mismatch demotes
    f, calls = _formulation(monkeypatch), []
    got = f.run("round", _attempt(calls, results={True: 1, False: 2}),
                same=lambda a, b: a == b)
    assert got == (2, "xla") and f.pallas("round") is False
    assert f.metrics.scheduling_errors.value(stage="pallas") == 1


def _case_checked_once(monkeypatch):
    f, calls = _formulation(monkeypatch), []
    for _ in range(2):
        assert f.run("round", _attempt(calls), same=lambda a, b: a == b) \
            == ("out", "pallas")
    assert calls == [True, False, True]


FORMULATION_CASES = {
    "default": _case_default, "mesh": _case_mesh, "demote": _case_demote,
    "restore": _case_restore, "timeout": _case_timeout,
    "crosscheck": _case_crosscheck, "checked_once": _case_checked_once}


@pytest.mark.parametrize("case", sorted(FORMULATION_CASES))
def test_formulation(case, monkeypatch):
    FORMULATION_CASES[case](monkeypatch)
