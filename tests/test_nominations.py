"""Nominated pods in the fit (1.11 addNominatedPods): a pod's resource
fit on a node counts every pod nominated there with priority >= its
own, other than itself; a nominated pod that places leaves the count
for the pods after it. The device round (ops/kernel.py), its numpy twin
(ops/hostwave.py) and golden (plugins/golden.py) must agree, and the
scheduler must bind each preemptor to the node it was nominated to."""

import re

import jax.numpy as jnp
import numpy as np
import pytest

from kubernetes_tpu.api import types as api
from kubernetes_tpu.ops import encoding as enc
from kubernetes_tpu.ops import hostwave
from kubernetes_tpu.ops.kernel import Weights, _schedule_round, schedule_round
from kubernetes_tpu.plugins import golden
from kubernetes_tpu.runtime.store import ObjectStore
from kubernetes_tpu.sched.scheduler import Scheduler, assemble_round
from kubernetes_tpu.utils import profiling

from helpers import make_node, make_pod
from test_scheduler_e2e import FakeClock

PRIOS = (0, 5, 10, 20)
WAVE = 4


def nominated_world(seed: int):
    """Ten nodes with running pods of mixed priorities, a round of
    pending pods of which some are nominated, and nominated pods outside
    the round. Node n9 has 1 CPU free; pod `x` (1500m) is nominated
    there and cannot fit, so it places elsewhere, and `y` (500m, lower
    priority, after it) may then take n9."""
    rng = np.random.default_rng(seed)
    store = ObjectStore()
    sched = Scheduler(store, wave_size=WAVE)
    for i in range(10):
        store.create("nodes", make_node(
            f"n{i}", cpu=str(int(rng.choice([2, 3, 4]))) if i < 9 else "2",
            memory="8Gi", pods=6 if i == 3 else 110))
    k = 0
    for i in range(9):
        for _ in range(int(rng.integers(1, 3))):
            store.create("pods", make_pod(
                f"run-{k}", cpu=f"{int(rng.integers(2, 8)) * 100}m",
                memory="256Mi", priority=int(rng.choice(PRIOS)),
                node_name=f"n{i}"))
            k += 1
    store.create("pods", make_pod("run-full", cpu="1", memory="256Mi",
                                  priority=0, node_name="n9"))
    pending = [make_pod("x", cpu="1500m", memory="256Mi", priority=20),
               make_pod("y", cpu="500m", memory="256Mi", priority=5)]
    for j in range(10):
        pending.append(make_pod(
            f"p{j}", cpu=f"{int(rng.choice([5, 10, 15]))}00m",
            memory="256Mi", priority=int(rng.choice(PRIOS))))
    # the queue's order: priority descending, x ahead of y
    pending.sort(key=lambda p: -api.pod_priority(p))
    outside = [make_pod(f"o{j}", cpu=f"{int(rng.choice([5, 10]))}00m",
                        memory="256Mi", priority=PRIOS[j % 4])
               for j in range(4)]
    nominations = [(pending.index(next(p for p in pending
                                       if p.metadata.name == "x")), "n9")]
    for j, p in enumerate(pending):
        if p.metadata.name not in ("x", "y") and j % 3 == 0:
            nominations.append((j, f"n{int(rng.integers(0, 9))}"))
    for j, p in enumerate(outside):
        sched.queue.update_nominated_pod(p, f"n{int(rng.integers(0, 10))}")
    for j, name in nominations:
        sched.queue.update_nominated_pod(pending[j], name)
    return store, sched, pending


def run_round(sched, pending):
    """The round program on `pending` with the queue's nominations, and
    its twin chained wave by wave. Returns (device chosen [W, P], device
    fail_counts, twin results per wave, waves, nominations staged)."""
    snap, feat = sched.snapshot, sched.featurizer
    waves = [pending[i:i + WAVE] for i in range(0, len(pending), WAVE)]
    [feat.featurize(wv) for wv in waves]
    pbs = [feat.featurize(wv) for wv in waves]
    P = pbs[0].req.shape[0]
    nom = snap.stage_nominations(sched.queue.nominated_pods(), waves, P, 4)
    assert nom is not None
    nt_h, pm_h, tt_h = snap.host_tensors()
    twin = []
    usage = (nt_h.requested, nt_h.nonzero, nt_h.pod_count,
             nom.req, nom.count)
    rr = 0
    kw = dict(weights=Weights(), num_zones=snap.caps.Z,
              num_label_values=snap.num_label_values, has_ipa=False)
    for w, pb in enumerate(pbs):
        res, usage = hostwave.schedule_wave_host(
            nt_h, pm_h, tt_h, pb, np.ones((P, nt_h.valid.shape[0]), bool),
            rr, usage_in=usage[:3],
            nom=enc.Nominations(usage[3], usage[4], nom.prio, nom.own[w]),
            **kw)
        rr = int(res.rr_end)
        twin.append(res)
    pm_rows, term_rows = snap.stage_pending(pending)
    nt, pm, tt = snap.to_device()
    pbs_stacked, rows, trows = assemble_round(
        pbs, waves, pm_rows, term_rows, 4, term_rows.shape[1])
    out = schedule_round(nt, pm, tt, pbs_stacked,
                         (nt.requested, nt.nonzero, nt.pod_count),
                         jnp.asarray(0, jnp.int32), rows, trows, nom=nom,
                         **kw)
    for p in pending:
        snap.unstage(p)
    return np.asarray(out[0]), np.asarray(out[1]), twin, waves, nom


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4, 5])
def test_round_twin_and_golden_agree_on_nominations(seed):
    store, sched, pending = nominated_world(seed)
    chosen, fails, twin, waves, nom = run_round(sched, pending)
    levels = set(nom.prio[nom.prio != enc.NOM_PAD_PRIO].tolist())
    assert len(levels) >= 3
    names = sched.snapshot.node_names
    res_i = enc.PRED_IDX["PodFitsResources"]
    for w, res in enumerate(twin):
        np.testing.assert_array_equal(chosen[w], res.chosen)
        np.testing.assert_array_equal(fails[w], res.fail_counts)
    # golden, pod by pod: the state the pods before it left, and the
    # nominations still in force
    noms = {p.uid: name for p, name in sched.queue.nominated_pods()}
    nominated = {p.uid: p for p, _ in sched.queue.nominated_pods()}
    placed = {}
    off_node = on_node = 0
    for w, wv in enumerate(waves):
        for j, pod in enumerate(wv):
            for n, name in enumerate(names):
                if not name:
                    continue
                ni = sched.cache.node_infos[name].clone()
                for q, c in placed.values():
                    if c == name:
                        ni.add_pod(q)
                here = [nominated[u] for u, m in noms.items() if m == name]
                ok, _ = golden.pod_fits_on_node(pod, ni, nominated=here)
                assert ok == bool(twin[w].masks[res_i][j][n]), \
                    (seed, pod.metadata.name, name)
            c = int(chosen[w, j])
            if c >= 0:
                placed[pod.uid] = (pod, names[c])
                if pod.uid in noms:
                    if noms.pop(pod.uid) == names[c]:
                        on_node += 1
                    else:
                        off_node += 1
    x = next(p for p in pending if p.metadata.name == "x")
    assert x.uid in placed and placed[x.uid][1] != "n9"
    assert off_node >= 1


def test_placed_nomination_frees_its_node_for_later_pods():
    """y (after x, lower priority) fits n9 only once x, nominated there
    and placed elsewhere, no longer counts; with x's nomination held
    (x not in the round) n9 stays closed to y."""
    store, sched, pending = nominated_world(0)
    names = sched.snapshot.node_names
    n9 = names.index("n9")
    x = next(p for p in pending if p.metadata.name == "x")
    y = next(p for p in pending if p.metadata.name == "y")
    res_i = enc.PRED_IDX["PodFitsResources"]

    def y_fits_n9(round_pods):
        _, _, twin, waves, _ = run_round(sched, round_pods)
        for w, wv in enumerate(waves):
            if y in wv:
                return bool(twin[w].masks[res_i][wv.index(y)][n9])

    assert y_fits_n9([x, y]) is True
    assert y_fits_n9([y]) is False


def test_golden_counts_higher_and_equal_priority_nominations_only():
    node = make_node("n0", cpu="4", memory="8Gi")
    from kubernetes_tpu.state.node_info import NodeInfo

    ni = NodeInfo(node)
    ni.add_pod(make_pod("run", cpu="900m", priority=0, node_name="n0"))
    pod = make_pod("me", cpu="3", priority=10)
    peer = make_pod("peer", cpu="3", priority=10)
    low = make_pod("low", cpu="3", priority=5)
    assert golden.pod_fits_on_node(pod, ni)[0]
    assert not golden.pod_fits_on_node(pod, ni, nominated=[peer])[0]
    assert golden.pod_fits_on_node(pod, ni, nominated=[low])[0]
    assert golden.pod_fits_on_node(pod, ni, nominated=[pod])[0]


def _lowered(nom):
    store, sched, pending = nominated_world(1)
    snap, feat = sched.snapshot, sched.featurizer
    waves = [pending[i:i + WAVE] for i in range(0, len(pending), WAVE)]
    [feat.featurize(wv) for wv in waves]
    pbs = [feat.featurize(wv) for wv in waves]
    staged = snap.stage_nominations(sched.queue.nominated_pods(), waves,
                                    pbs[0].req.shape[0], 4)
    pm_rows, term_rows = snap.stage_pending(pending)
    nt, pm, tt = snap.to_device()
    pbs_stacked, rows, trows = assemble_round(
        pbs, waves, pm_rows, term_rows, 4, term_rows.shape[1])
    args = (nt, pm, tt, pbs_stacked, (nt.requested, nt.nonzero, nt.pod_count),
            jnp.asarray(0, jnp.int32), rows, trows)
    kw = dict(weights=Weights(), num_zones=snap.caps.Z,
              num_label_values=snap.num_label_values, has_ipa=False)
    if nom == "absent":
        return _schedule_round.lower(*args, **kw)
    return _schedule_round.lower(*args, nom=staged if nom else None, **kw)


def _main_inputs(lowered) -> int:
    main = next(line for line in lowered.as_text().splitlines()
                if "@main(" in line)
    return len(re.findall(r"%arg\d+:", main))


def test_round_without_nominations_takes_no_nomination_inputs():
    plain = _lowered("absent")
    none = _lowered(False)
    with_nom = _lowered(True)
    assert none.as_text() == plain.as_text()
    # the nomination rows (req, count, prio) and own are four inputs
    # more, and only where the round is given them
    assert _main_inputs(with_nom) == _main_inputs(plain) + 4
    assert with_nom.as_text() != plain.as_text()


def test_round_without_nominations_reuses_the_warmed_program():
    """warm_pipeline passes no nominations; a round whose queue holds
    none runs the program it compiled, with nothing compiled again."""
    from kubernetes_tpu.ops.encoding import Caps

    store = ObjectStore()
    for i in range(8):
        store.create("nodes", make_node(f"w{i}", cpu="4", memory="8Gi"))
    sched = Scheduler(store, wave_size=8, caps=Caps(M=128, P=8))
    sched.warm_pipeline([make_pod(f"warm-{j}", cpu="100m")
                         for j in range(8)], n_waves=2)
    compiled = _schedule_round._cache_size()
    for j in range(16):
        store.create("pods", make_pod(f"p-{j}", cpu="100m"))
    assert sched.schedule_pending() == 16
    assert _schedule_round._cache_size() == compiled


def sixteen_node_world(clock):
    """PreemptionBasic's shapes on 16 nodes: four 900m low pods fill
    each 4-CPU node, so each 3-CPU high pod evicts three. A small pod
    bound first moves the round-robin counter one step, so a round that
    ignored nominations would put the preemptors on other freed nodes."""
    store = ObjectStore()
    sched = Scheduler(store, wave_size=8, clock=clock)
    for i in range(16):
        store.create("nodes", make_node(f"n{i}", cpu="4", memory="32Gi"))
    for j in range(64):
        store.create("pods", make_pod(f"low-{j}", cpu="900m",
                                      memory="500Mi", priority=0,
                                      node_name=f"n{j % 16}"))
    store.create("pods", make_pod("tiny", cpu="10m", priority=0))
    sched.schedule_pending()
    for k in range(16):
        store.create("pods", make_pod(f"high-{k}", cpu="3", memory="500Mi",
                                      priority=10))
    return store, sched


def test_preemptors_bind_to_their_nominated_nodes():
    clock = FakeClock()
    store, sched = sixteen_node_world(clock)
    prof = profiling.enable()
    try:
        sched.schedule_pending()
        nominated = {k: store.get("pods", "default",
                                  f"high-{k}").status.nominated_node_name
                     for k in range(16)}
        assert sched.pipeline_preemptions == 16
        assert len(set(nominated.values())) == 16
        assert sum(1 for p in store.list("pods")
                   if p.metadata.name.startswith("low-")) == 16
        clock.advance(5.0)
        sched.schedule_pending()
        bound = {k: store.get("pods", "default", f"high-{k}").spec.node_name
                 for k in range(16)}
        assert bound == nominated
        steps = prof.step_totals()
    finally:
        profiling.disable()
    # the preempt chunk's host loop, split, and the nominations the
    # binding round counted
    assert {"preempt/rank", "preempt/validate", "preempt/perform"} \
        <= set(steps)
    assert sched.metrics.nominated_pods_staged.value == 16
    assert sched.queue.nominated_pods() == []


def _guarded_world(guard_priority: int):
    """Two empty 4-CPU nodes; `guard`, nominated to n0 and not pending,
    carries hostname anti-affinity against app=web; `web` (priority 5)
    is pending. Returns the store and the scheduler."""
    from kubernetes_tpu.api.labels import LabelSelector

    store = ObjectStore()
    sched = Scheduler(store, wave_size=WAVE)
    for i in range(2):
        store.create("nodes", make_node(
            f"n{i}", cpu="4", memory="8Gi",
            labels={"kubernetes.io/hostname": f"n{i}"}))
    aff = api.Affinity(pod_anti_affinity=api.PodAntiAffinity(
        required=[api.PodAffinityTerm(
            label_selector=LabelSelector(match_labels={"app": "web"}),
            topology_key="kubernetes.io/hostname")]))
    guard = make_pod("guard", cpu="100m", priority=guard_priority,
                     affinity=aff)
    sched.queue.update_nominated_pod(guard, "n0")
    store.create("pods", make_pod("web", cpu="100m", labels={"app": "web"},
                                  priority=5))
    return store, sched


@pytest.mark.parametrize("guard_priority,node", [(10, "n1"), (0, "n0")])
def test_nominated_anti_affinity_routes_the_round_to_golden(guard_priority,
                                                            node):
    """The device's nomination term adds requests only, so while a
    nominated pod carries inter-pod terms the round's pods take golden,
    which adds the nominated pod to its node for pods of equal or lower
    priority: web keeps off n0 only where guard outranks it."""
    store, sched = _guarded_world(guard_priority)
    web = store.get("pods", "default", "web")
    host, rest = sched._split_golden([web])
    assert (host, rest) == ([web], [])
    assert sched.schedule_pending() == 1
    assert store.get("pods", "default", "web").spec.node_name == node


def test_no_nominations_leave_the_round_on_the_device():
    store, sched = _guarded_world(10)
    sched.queue.update_nominated_pod(
        next(p for p, _ in sched.queue.nominated_pods()), "")
    web = store.get("pods", "default", "web")
    assert sched._split_golden([web]) == ([], [web])
