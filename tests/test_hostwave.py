"""Vectorized numpy host twin (ops/hostwave.py) — ISSUE 7.

Two properties under test:

  1. PARITY — over randomized snapshots, the twin's feasibility masks,
     scores, placements, and preemption stat planes are bit-for-bit
     identical to the jit kernels', and its combined feasibility agrees
     with the golden oracle (plugins/golden.py) per (pod, node). The
     golden comparison runs over a shared-vocab scratch Snapshot (the
     scrubber's trick, via ops/simulate.shadow_snapshot) so interned ids
     line up without touching the live mirror.
  2. DEGRADED MODE — with every device kernel entry faulted
     (breaker-open), the scheduler drains whole backlogs through the
     twin: placements match an identical un-faulted device scheduler,
     preemption stays batched, gang atomicity holds, and inter-pod
     affinity pods ride the twin's batched affinity plane
     (incoming_statics_host) instead of draining through the per-pod
     golden path; only multi-topology-key pods still route golden,
     exactly like the device path.
"""

import numpy as np
import pytest

import kubernetes_tpu.api.types as api
from kubernetes_tpu.ops import hostwave
from kubernetes_tpu.ops.encoding import Caps
from kubernetes_tpu.runtime.store import ObjectStore
from kubernetes_tpu.sched.breaker import OPEN
from kubernetes_tpu.sched.scheduler import Scheduler
from kubernetes_tpu.utils import faultpoints

from helpers import make_node, make_pod

pytestmark = pytest.mark.hostpath


def _weights(sched):
    return dict(weights=sched.profile.weights(),
                num_zones=sched.snapshot.caps.Z,
                num_label_values=sched.snapshot.num_label_values)


def random_world(seed, n_nodes=8, n_existing=10, n_pending=12):
    """Randomized cluster + pending batch over the twin-encodable
    feature set (no inter-pod affinity — those pods take the golden
    path on both backends by design)."""
    rng = np.random.RandomState(seed)
    store = ObjectStore()
    sched = Scheduler(store, wave_size=16)
    for i in range(n_nodes):
        labels = {"zone": f"z{rng.randint(3)}",
                  "kubernetes.io/hostname": f"n{i}"}
        if rng.rand() < 0.5:
            labels["disk"] = rng.choice(["ssd", "hdd"])
        if rng.rand() < 0.3:
            labels["gen"] = str(rng.randint(1, 4))
        taints = []
        if rng.rand() < 0.25:
            taints.append(api.Taint(key="dedicated",
                                    value=rng.choice(["a", "b"]),
                                    effect=rng.choice(
                                        ["NoSchedule", "PreferNoSchedule"])))
        conds = [api.NodeCondition(api.NODE_READY,
                                   api.COND_TRUE if rng.rand() < 0.9
                                   else api.COND_FALSE)]
        store.create("nodes", make_node(
            f"n{i}", cpu=str(rng.randint(2, 9)),
            memory=f"{rng.randint(2, 9)}Gi", labels=labels, taints=taints,
            unschedulable=bool(rng.rand() < 0.1), conditions=conds))
    for i in range(n_existing):
        store.create("pods", make_pod(
            f"ex-{i}", cpu=str(rng.randint(1, 3)),
            priority=int(rng.choice([0, 1, 5, 50])),
            labels={"app": rng.choice(["a", "b", "c"])},
            ports=[int(9000 + rng.randint(4))] if rng.rand() < 0.3 else None))
    sched.schedule_pending()
    pending = []
    for i in range(n_pending):
        kw = {}
        if rng.rand() < 0.3:
            kw["node_selector"] = {"disk": rng.choice(["ssd", "hdd", "nvme"])}
        if rng.rand() < 0.3:
            kw["tolerations"] = [api.Toleration(
                key="dedicated", operator="Exists",
                effect=rng.choice(["NoSchedule", ""]))]
        if rng.rand() < 0.3:
            kw["ports"] = [int(9000 + rng.randint(4))]
        pending.append(make_pod(
            f"pend-{i}", cpu=str(rng.randint(1, 4)),
            priority=int(rng.choice([5, 10, 100])),
            labels={"app": rng.choice(["a", "b", "c"])}, **kw))
    return store, sched, pending


class TestWaveParity:
    @pytest.mark.parametrize("seed", [0, 1, 2, 3, 4])
    def test_device_host_bitwise_parity(self, seed):
        """Every WaveResult plane — masks, chosen, scores, fail counts,
        feasible counts, round-robin — identical between the jit wave
        kernel and the numpy twin on a randomized snapshot."""
        import jax.numpy as jnp

        from kubernetes_tpu.ops.kernel import schedule_wave

        store, sched, pending = random_world(seed)
        pb = sched.featurizer.featurize(pending)
        P = pb.req.shape[0]
        extra = np.ones((P, sched.snapshot.caps.N), bool)
        nt_d, pm_d, tt_d = sched.snapshot.to_device()
        res_d = schedule_wave(nt_d, pm_d, tt_d, pb, extra,
                              jnp.asarray(3, jnp.int32), None,
                              has_ipa=False, **_weights(sched))
        nt, pm, tt = sched.snapshot.host_tensors()
        res_h, _usage = hostwave.schedule_wave_host(
            nt, pm, tt, pb, extra, 3, None, **_weights(sched))
        assert np.array_equal(np.asarray(res_d.masks), res_h.masks)
        assert np.array_equal(np.asarray(res_d.chosen), res_h.chosen)
        assert np.array_equal(np.asarray(res_d.score), res_h.score)
        assert np.array_equal(np.asarray(res_d.fail_counts),
                              res_h.fail_counts)
        assert np.array_equal(np.asarray(res_d.feasible_count),
                              res_h.feasible_count)
        assert int(res_d.rr_end) == int(res_h.rr_end)

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_twin_matches_golden_oracle(self, seed):
        """Per (pod, node) combined feasibility of the twin equals the
        golden predicates, evaluated over a shared-vocab scratch
        Snapshot (the scrubber trick) so the live mirror stays
        untouched."""
        from kubernetes_tpu.ops.simulate import shadow_snapshot
        from kubernetes_tpu.plugins import golden

        store, sched, pending = random_world(seed, n_pending=6)
        shadow, n_real = shadow_snapshot(sched.cache, sched.snapshot)
        feat = sched.shadow_featurizer(shadow)
        for pod in pending:
            pb = feat.featurize([pod])
            nt, pm, tt = shadow.host_tensors()
            extra = np.ones((pb.req.shape[0], shadow.caps.N), bool)
            res, _ = hostwave.schedule_wave_host(
                nt, pm, tt, pb, extra, 0, None,
                weights=sched.profile.weights(), num_zones=shadow.caps.Z,
                num_label_values=shadow.num_label_values)
            combined = res.masks.all(axis=0)[0]  # [N]
            for name, idx in shadow.node_index.items():
                ni = sched.cache.node_infos.get(name)
                if ni is None or ni.node is None:
                    continue
                ok, _reasons = golden.pod_fits_on_node(pod, ni)
                assert bool(combined[idx]) == ok, \
                    f"pod {pod.name} node {name}: twin={bool(combined[idx])} golden={ok}"


class TestPreemptionParity:
    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_stats_bitwise_parity(self, seed):
        """The packed [5, P, N] what-if stat stack — ok, victim count,
        priority max, bitcast priority sum, bitcast gang weight —
        byte-identical between the device kernel and the twin."""
        import jax.numpy as jnp

        from kubernetes_tpu.ops.preempt import preemption_stats

        store, sched, pending = random_world(seed, n_existing=14)
        vips = [make_pod(f"vip-{i}", cpu="2", priority=100)
                for i in range(4)]
        pb = sched.featurizer.featurize(vips)
        live = sched.snapshot.ep_valid & sched.snapshot.ep_alive
        levels = hostwave.victim_levels(sched.snapshot.ep_prio, live, 8)
        assert levels is not None
        gang_w = np.zeros((sched.snapshot.caps.M,), np.float32)
        gang_w[:3] = 1.0  # arbitrary disruption weights exercise plane 4
        nt_d, pm_d, tt_d = sched.snapshot.to_device()
        pk_d = np.asarray(preemption_stats(
            nt_d, pm_d, pb, jnp.asarray(levels, jnp.int32), num_levels=8,
            gang_w=jnp.asarray(gang_w)))
        nt, pm, tt = sched.snapshot.host_tensors()
        pk_h = hostwave.preemption_stats_host(
            nt, pm, pb, np.asarray(levels, np.int32), num_levels=8,
            gang_w=gang_w)
        assert np.array_equal(pk_d, pk_h)

    def test_prune_preserves_preempt_choice(self):
        """preempt() with the vectorized candidate prune picks the same
        node and victim set as the unpruned validate-everything loop."""
        from kubernetes_tpu.sched.preemption import preempt

        store = ObjectStore()
        sched = Scheduler(store, wave_size=4)
        for i in range(6):
            store.create("nodes", make_node(f"n{i}", cpu="2"))
        for i in range(6):
            store.create("pods", make_pod(f"hog-{i}", cpu="2",
                                          priority=1 if i % 2 else 50))
        assert sched.schedule_pending() == 6
        vip = make_pod("vip", cpu="2", priority=100)
        failed = {f"n{i}": ["PodFitsResources"] for i in range(6)}
        exact = preempt(vip, sched.cache, failed, [])
        pruned = preempt(vip, sched.cache, failed, [],
                         snapshot=sched.snapshot,
                         featurizer=sched.featurizer)
        assert exact is not None and pruned is not None
        # the prune ranks odd-numbered nodes (priority-1 victims) ahead
        # of the priority-50 ones — same lexicographic criteria the
        # exact pick applies after validating everything
        assert {v.uid for v in pruned.victims} == \
            {v.uid for v in exact.victims}
        assert api.pod_priority(pruned.victims[0]) == 1

    def test_prune_drops_hopeless_nodes(self):
        """A node that cannot fit the pod even with EVERY lower-priority
        pod removed is pruned before any clone/reprieve work."""
        from kubernetes_tpu.sched.preemption import vector_candidate_order

        store = ObjectStore()
        sched = Scheduler(store, wave_size=4)
        store.create("nodes", make_node("big", cpu="4"))
        store.create("nodes", make_node("small", cpu="1"))
        store.create("pods", make_pod("hog-big", cpu="4", priority=1))
        store.create("pods", make_pod("hog-small", cpu="1", priority=1))
        assert sched.schedule_pending() == 2
        vip = make_pod("vip", cpu="3", priority=100)
        order = vector_candidate_order(vip, sched.snapshot,
                                       sched.featurizer)
        assert order == ["big"]  # "small" can never host a 3-cpu pod


def _faulted(n_nodes=4, cpu="4", wave=8, threshold=2):
    """Cluster whose device path faults at every kernel entry — after
    `threshold` failures the breaker opens and the twin carries."""
    store = ObjectStore()
    sched = Scheduler(store, wave_size=wave, breaker_threshold=threshold)
    for i in range(n_nodes):
        store.create("nodes", make_node(f"n{i}", cpu=cpu))
    faultpoints.activate("kernel.round", "raise")
    faultpoints.activate("kernel.wave", "raise")
    faultpoints.activate("kernel.gang", "raise")
    return store, sched


class TestDegradedVectorWave:
    def test_breaker_open_placements_match_device_path(self):
        """End-to-end device==host: an identical workload placed by a
        clean device scheduler and by a breaker-open (twin) scheduler
        lands every pod on the same node."""
        def build(faulted):
            store = ObjectStore()
            sched = Scheduler(store, wave_size=8, breaker_threshold=1)
            for i in range(5):
                store.create("nodes", make_node(f"n{i}", cpu="4"))
            if faulted:
                faultpoints.activate("kernel.round", "raise")
                faultpoints.activate("kernel.wave", "raise")
            for i in range(12):
                store.create("pods", make_pod(f"p{i}", cpu="1"))
            assert sched.schedule_pending() == 12
            return store, sched

        store_d, sched_d = build(False)
        want = {p.metadata.name: p.spec.node_name
                for p in store_d.list("pods")}
        faultpoints.reset()
        store_h, sched_h = build(True)
        got = {p.metadata.name: p.spec.node_name
               for p in store_h.list("pods")}
        assert sched_h.breaker.state == OPEN
        assert got == want
        assert sched_h.metrics.waves_total.value(path="host") >= 1
        # degraded waves ran the VECTOR backend, not the golden loop
        assert sched_h.wave_path() == "vector"

    def test_degraded_preemption_is_batched(self):
        """Breaker open + saturated cluster + high-priority backlog:
        evictions happen through the batched twin what-if (pipeline
        accounting), not the per-pod cascade, and the vips land."""
        store, sched = _faulted(n_nodes=4, cpu="2", wave=4)
        for i in range(4):
            store.create("pods", make_pod(f"hog-{i}", cpu="2", priority=1))
        assert sched.schedule_pending() == 4
        for i in range(4):
            store.create("pods", make_pod(f"vip-{i}", cpu="2",
                                          priority=100))
        sched.schedule_pending()
        assert sched.breaker.state == OPEN
        assert sched.pipeline_preemptions == 4
        assert all(store.get("pods", "default", f"hog-{i}") is None
                   for i in range(4))
        import time

        deadline = time.monotonic() + 10.0
        placed = 0
        while placed < 4 and time.monotonic() < deadline:
            time.sleep(0.01)
            placed += sched.schedule_pending()
        vips = [store.get("pods", "default", f"vip-{i}") for i in range(4)]
        assert all(v.spec.node_name for v in vips)

    def test_degraded_gang_atomicity_restored(self):
        """Gangs stay all-or-nothing in degraded mode through the twin's
        count-feasibility plane: a fitting gang fully places, an
        unfittable one places NOTHING (PR 2 suspended this; the twin
        restores it)."""
        store, sched = _faulted(n_nodes=3, cpu="2", wave=8)
        # trip the breaker with plain pods first: a gang arriving while
        # the breaker is CLOSED parks on the device failure (atomicity:
        # nothing placed) rather than degrading mid-attempt
        for i in range(2):
            store.create("pods", make_pod(f"filler-{i}", cpu="100m"))
        assert sched.schedule_pending() == 2
        assert sched.breaker.state == OPEN
        for i in range(2):
            store.delete("pods", "default", f"filler-{i}")

        def gang(name, size, cpu):
            out = []
            for j in range(size):
                p = make_pod(f"{name}-{j}", cpu=cpu)
                p.metadata.annotations = {
                    "pod-group.scheduling.k8s.io/name": name,
                    "pod-group.scheduling.k8s.io/min-available": str(size)}
                out.append(p)
            return out

        for p in gang("fits", 3, "2"):
            store.create("pods", p)
        assert sched.schedule_pending() == 3
        assert sched.breaker.state == OPEN
        for p in gang("toobig", 4, "2"):
            store.create("pods", p)
        assert sched.schedule_pending() == 0
        assert all(not store.get("pods", "default", f"toobig-{j}").spec.node_name
                   for j in range(4))

    def test_degraded_affinity_pods_take_the_twin(self):
        """The inter-pod affinity plane IS twinned: breaker-open
        placement of anti-affine pods stays on the batched numpy twin
        (no per-pod golden routing — reason=affinity stays zero) and
        still honors the constraint exactly."""
        from kubernetes_tpu.api.labels import LabelSelector

        store, sched = _faulted(n_nodes=3, cpu="4", wave=8)
        aff = api.Affinity(pod_anti_affinity=api.PodAntiAffinity(
            required=[api.PodAffinityTerm(
                label_selector=LabelSelector(match_labels={"g": "x"}),
                topology_key="kubernetes.io/hostname")]))
        for i in range(3):
            store.create("pods", make_pod(f"anti-{i}", cpu="1",
                                          labels={"g": "x"}, affinity=aff))
        assert sched.schedule_pending() == 3
        assert sched.breaker.state == OPEN
        nodes = {store.get("pods", "default", f"anti-{i}").spec.node_name
                 for i in range(3)}
        assert len(nodes) == 3  # one per host, exactly
        # the affinity coverage gap is CLOSED: no pod went golden for
        # reason=affinity — the twin carried the whole wave batched
        assert sched.metrics.degraded_golden_pods.value(
            reason="affinity") == 0
        assert sched.metrics.degraded_golden_pods.value(
            reason="multi_tk") == 0
        # and the twin actually ran (host waves, not golden pods/s)
        assert sched.metrics.waves_total.value(path="host") >= 1

    def test_degraded_golden_reasons_and_ledger_tag(self):
        """multi-topology-key pods count under reason=multi_tk, and the
        degraded round's ledger entry carries the per-reason tally."""
        from kubernetes_tpu.api.labels import LabelSelector
        from kubernetes_tpu.utils import tracing

        store, sched = _faulted(n_nodes=4, cpu="8", wave=8)
        rec = tracing.enable()
        try:
            # required anti-affinity over TWO topology keys -> the
            # multi-tk encoding limit (needs_host_path), not just the
            # untwinned-affinity plane
            aff = api.Affinity(pod_anti_affinity=api.PodAntiAffinity(
                required=[
                    api.PodAffinityTerm(
                        label_selector=LabelSelector(
                            match_labels={"g": "y"}),
                        topology_key="kubernetes.io/hostname"),
                    api.PodAffinityTerm(
                        label_selector=LabelSelector(
                            match_labels={"g": "y"}),
                        topology_key=api.LABEL_ZONE),
                ]))
            # trip the breaker with plain pods FIRST: only pods that
            # arrive while it's open take the DEGRADED golden route
            for i in range(3):
                store.create("pods", make_pod(f"plain-{i}", cpu="1"))
            assert sched.schedule_pending() == 3
            assert sched.breaker.state == OPEN
            store.create("pods", make_pod("multi-tk", cpu="1",
                                          labels={"g": "y"}, affinity=aff))
            assert sched.schedule_pending() == 1
            assert sched.metrics.degraded_golden_pods.value(
                reason="multi_tk") == 1
            ledgers = [r for r in rec.ledger_rows()
                       if r.get("degraded_golden")]
            assert ledgers, "degraded round ledger entry not tagged"
            assert ledgers[-1]["degraded_golden"] == {"multi_tk": 1}
        finally:
            tracing.disable()

    def test_simulate_host_backend_matches_device(self):
        """The autoscaler what-if's host backend returns the same
        verdict planes as the device pass on the same shadow."""
        from kubernetes_tpu.ops import simulate

        store, sched, pending = random_world(7, n_pending=5)
        shadow, n_real = simulate.shadow_snapshot(sched.cache,
                                                  sched.snapshot)
        feat = sched.shadow_featurizer(shadow)
        pb = feat.featurize(pending)
        kw = dict(weights=sched.profile.weights(),
                  num_zones=shadow.caps.Z,
                  num_label_values=shadow.num_label_values)
        v_d = simulate.simulate_placements(shadow, pb, **kw)
        v_h = simulate.simulate_placements(shadow, pb, backend="host", **kw)
        assert np.array_equal(v_d.chosen, v_h.chosen)
        assert np.array_equal(v_d.feasible, v_h.feasible)


class TestInterPodAffinityTwin:
    """Bitwise parity of the twinned inter-pod affinity plane
    (ops/hostwave.py incoming_statics_host + the has_ipa commit loop)
    against the device kernel — the coverage gap the degraded path used
    to pay for with per-pod golden routing."""

    @staticmethod
    def _ipa_world(seed, n_nodes=10, n_existing=8, n_pods=12):
        """Randomized world that is GUARANTEED affinity-rich: required
        (anti)affinity, preferred terms, and existing pods carrying
        required anti terms (the symmetry plane)."""
        import random

        from kubernetes_tpu.api.labels import LabelSelector
        from test_parity import build

        rng = random.Random(seed)
        nodes = [make_node(f"n{i}", cpu="8", memory="16Gi",
                           labels={"kubernetes.io/hostname": f"n{i}",
                                   api.LABEL_ZONE: f"z{i % 3}"})
                 for i in range(n_nodes)]
        existing = []
        for i in range(n_existing):
            aff = None
            if rng.random() < 0.5:
                aff = api.Affinity(pod_anti_affinity=api.PodAntiAffinity(
                    required=[api.PodAffinityTerm(
                        label_selector=LabelSelector(
                            match_labels={"grp": f"g{i % 3}"}),
                        topology_key="kubernetes.io/hostname")]))
            existing.append(make_pod(
                f"ex-{i}", cpu="200m", memory="256Mi",
                labels={"grp": f"g{i % 3}", "app": "web"},
                node_name=f"n{i % n_nodes}", affinity=aff))
        pods = []
        for i in range(n_pods):
            r = rng.random()
            aff = None
            if r < 0.3:
                aff = api.Affinity(pod_anti_affinity=api.PodAntiAffinity(
                    required=[api.PodAffinityTerm(
                        label_selector=LabelSelector(
                            match_labels={"grp": f"g{i % 3}"}),
                        topology_key="kubernetes.io/hostname")]))
            elif r < 0.5:
                aff = api.Affinity(pod_affinity=api.PodAffinity(
                    required=[api.PodAffinityTerm(
                        label_selector=LabelSelector(
                            match_labels={"grp": f"g{(i + 1) % 3}"}),
                        topology_key=api.LABEL_ZONE)]))
            elif r < 0.7:
                aff = api.Affinity(pod_affinity=api.PodAffinity(
                    preferred=[api.WeightedPodAffinityTerm(
                        weight=rng.randint(1, 100),
                        pod_affinity_term=api.PodAffinityTerm(
                            label_selector=LabelSelector(
                                match_labels={"app": "web"}),
                            topology_key=api.LABEL_ZONE))]))
            pods.append(make_pod(
                f"p{i}", cpu=f"{rng.randint(1, 8) * 100}m", memory="128Mi",
                labels={"grp": f"g{i % 3}", "app": "web"}, affinity=aff))
        cache, snap = build(nodes, existing)
        return rng, snap, pods

    @staticmethod
    def _anchor_world(seed, n_nodes=12, n_existing=40, n_pods=16):
        """World for the node-first anchoring of the statics: matching
        existing pods stacked several to a node, deleted pods whose freed
        pod-matrix rows keep their stale node, nodes without the zone
        key, and required and preferred terms on both the hostname and
        the zone key."""
        import random

        from kubernetes_tpu.api.labels import LabelSelector
        from test_parity import build

        rng = random.Random(seed)
        nodes = []
        for i in range(n_nodes):
            labels = {api.LABEL_HOSTNAME: f"n{i}"}
            if rng.random() < 0.7:
                labels[api.LABEL_ZONE] = f"z{rng.randint(0, 2)}"
            nodes.append(make_node(f"n{i}", cpu="32", memory="64Gi",
                                   labels=labels))
        # a third of the nodes hold nearly all the existing pods
        hot = [f"n{i}" for i in rng.sample(range(n_nodes), n_nodes // 3)]
        existing = [make_pod(
            f"ex-{i}", cpu="100m", memory="64Mi",
            labels={"grp": f"g{rng.randint(0, 2)}", "app": "web"},
            node_name=(rng.choice(hot) if rng.random() < 0.8
                       else f"n{rng.randrange(n_nodes)}"))
            for i in range(n_existing)]
        cache, snap = build(nodes, existing)
        for p in rng.sample(existing, n_existing // 3):
            cache.remove_pod(p)
            snap.remove_pod(p)

        def term(key):
            return api.PodAffinityTerm(
                label_selector=LabelSelector(
                    match_labels={"grp": f"g{rng.randint(0, 2)}"}),
                topology_key=key)

        keys = (api.LABEL_HOSTNAME, api.LABEL_ZONE)
        pods = []
        for i in range(n_pods):
            key = keys[i % 2]
            kind = rng.randrange(4)
            if kind == 0:
                aff = api.Affinity(pod_affinity=api.PodAffinity(
                    required=[term(key)]))
            elif kind == 1:
                aff = api.Affinity(pod_anti_affinity=api.PodAntiAffinity(
                    required=[term(key)]))
            elif kind == 2:
                aff = api.Affinity(pod_affinity=api.PodAffinity(
                    preferred=[api.WeightedPodAffinityTerm(
                        weight=rng.randint(1, 100),
                        pod_affinity_term=term(key))]))
            else:
                aff = api.Affinity(pod_anti_affinity=api.PodAntiAffinity(
                    preferred=[api.WeightedPodAffinityTerm(
                        weight=rng.randint(1, 100),
                        pod_affinity_term=term(key))]))
            pods.append(make_pod(
                f"p{i}", cpu="100m", memory="64Mi",
                labels={"grp": f"g{i % 3}", "app": "web"}, affinity=aff))
        return snap, pods

    @pytest.mark.parametrize("seed", range(3))
    def test_incoming_statics_bitwise_parity(self, seed):
        """Every IncomingStatics plane of the device's node-first
        anchoring equals the twin's per-pod domain reduction bit for bit,
        on a world where node counts exceed 1, freed rows keep a stale
        node and some nodes lack the zone key."""
        from kubernetes_tpu.ops.affinity import incoming_statics
        from kubernetes_tpu.state.featurize import PodFeaturizer

        snap, pods = self._anchor_world(seed)
        pb = PodFeaturizer(snap, group_selectors=lambda p: []).featurize(pods)
        nth, pmh, tth = snap.host_tensors()
        live = np.asarray(pmh.valid)
        assert np.any(~live & (np.arange(live.size) < snap._next_slot))
        assert np.bincount(np.asarray(pmh.node)[live]).max() > 1
        assert np.any(np.asarray(nth.valid)
                      & (np.asarray(nth.labels)[
                          :, snap.label_key_col(api.LABEL_ZONE)] == 0))
        lv, hw = snap.num_label_values, 1.0
        nt, pm, tt = snap.to_device()
        dev = incoming_statics(nt, pm, tt, pb, lv, hw)
        host = hostwave.incoming_statics_host(nth, pmh, tth, pb, lv, hw)
        assert np.any(host.ok_aff) and np.any(host.counts != 0)
        for f in dev._fields:
            np.testing.assert_array_equal(np.asarray(getattr(dev, f)),
                                          np.asarray(getattr(host, f)),
                                          err_msg=f)

    @pytest.mark.parametrize("seed", range(6))
    def test_ipa_wave_bitwise_parity(self, seed):
        """Device kernel == numpy twin on affinity-rich worlds: chosen,
        score, rr, fail counts, the FULL mask stack (incl. the
        MatchInterPodAffinity row), and the score decomposition."""
        import jax.numpy as jnp

        from kubernetes_tpu.ops.kernel import Weights, schedule_wave
        from kubernetes_tpu.state.featurize import PodFeaturizer

        rng, snap, pods = self._ipa_world(seed)
        feat = PodFeaturizer(snap, group_selectors=lambda p: [])
        pb = feat.featurize(pods)
        nt, pm, tt = snap.to_device()
        P = pb.req.shape[0]
        extra = np.ones((P, snap.caps.N), bool)
        kw = dict(weights=Weights(), num_zones=snap.caps.Z,
                  num_label_values=snap.num_label_values, has_ipa=True)
        rr0 = rng.randint(0, 5)
        dev = schedule_wave(nt, pm, tt, pb, extra,
                            jnp.asarray(rr0, jnp.int32),
                            collect_scores=True, **kw)
        nth, pmh, tth = snap.host_tensors()
        host, _u = hostwave.schedule_wave_host(
            nth, pmh, tth, pb, extra, rr0, None, collect_scores=True, **kw)
        # the statics twin actually saw affinity programs
        assert (np.any(pb.ra_has) or np.any(pb.rn_has)
                or np.any(pb.pa_w != 0) or np.any(np.asarray(tth.valid)))
        np.testing.assert_array_equal(np.asarray(dev.chosen), host.chosen)
        np.testing.assert_array_equal(np.asarray(dev.score), host.score)
        np.testing.assert_array_equal(np.asarray(dev.fail_counts),
                                      host.fail_counts)
        np.testing.assert_array_equal(np.asarray(dev.masks), host.masks)
        assert int(np.asarray(dev.rr_end)) == int(host.rr_end)
        for a, b in zip(dev.deco, host.deco):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("seed", range(3))
    def test_ipa_gang_bitwise_parity(self, seed):
        """The all-or-nothing gang wrapper under has_ipa: device ==
        twin on ok / chosen / placed / rr."""
        import jax.numpy as jnp

        from kubernetes_tpu.ops.gang import schedule_gang
        from kubernetes_tpu.ops.kernel import Weights
        from kubernetes_tpu.state.featurize import PodFeaturizer

        rng, snap, pods = self._ipa_world(seed + 100, n_pods=6)
        feat = PodFeaturizer(snap, group_selectors=lambda p: [])
        pb = feat.featurize(pods)
        nt, pm, tt = snap.to_device()
        P = pb.req.shape[0]
        extra = np.ones((P, snap.caps.N), bool)
        kw = dict(weights=Weights(), num_zones=snap.caps.Z,
                  num_label_values=snap.num_label_values, has_ipa=True)
        need = rng.randint(1, len(pods))
        dev = schedule_gang(nt, pm, tt, pb, extra,
                            jnp.asarray(0, jnp.int32), None,
                            jnp.asarray(need, jnp.int32), **kw)
        nth, pmh, tth = snap.host_tensors()
        host = hostwave.schedule_gang_host(
            nth, pmh, tth, pb, extra, 0, None, need, **kw)
        assert bool(np.asarray(dev.ok)) == bool(host.ok)
        np.testing.assert_array_equal(np.asarray(dev.chosen), host.chosen)
        assert int(np.asarray(dev.placed)) == int(host.placed)
        assert int(np.asarray(dev.rr_end)) == int(host.rr_end)

    def test_degraded_affinity_e2e_matches_clean_device_run(self):
        """Breaker-open end-to-end with required (anti)affinity, a
        preferred term, and symmetry from existing pods: the degraded
        scheduler's placements equal a clean device scheduler's exactly
        — and no pod was routed golden for reason=affinity."""
        from kubernetes_tpu.api.labels import LabelSelector

        def world(store):
            for i in range(8):
                store.create("nodes", make_node(
                    f"n{i}", cpu="8", memory="16Gi",
                    labels={"kubernetes.io/hostname": f"n{i}",
                            api.LABEL_ZONE: f"z{i % 2}"}))
            for i in range(12):
                aff = None
                labels = {"app": "w"}
                if i % 4 == 0:
                    labels = {"anti": "a", "app": "w"}
                    aff = api.Affinity(
                        pod_anti_affinity=api.PodAntiAffinity(
                            required=[api.PodAffinityTerm(
                                label_selector=LabelSelector(
                                    match_labels={"anti": "a"}),
                                topology_key="kubernetes.io/hostname")]))
                elif i % 4 == 1:
                    aff = api.Affinity(pod_affinity=api.PodAffinity(
                        preferred=[api.WeightedPodAffinityTerm(
                            weight=10,
                            pod_affinity_term=api.PodAffinityTerm(
                                label_selector=LabelSelector(
                                    match_labels={"app": "w"}),
                                topology_key=api.LABEL_ZONE))]))
                store.create("pods", make_pod(
                    f"p{i}", cpu="500m", memory="128Mi", labels=labels,
                    affinity=aff))

        ref_store = ObjectStore()
        ref = Scheduler(ref_store, wave_size=8)
        world(ref_store)
        assert ref.schedule_pending() == 12

        store, sched = _faulted(n_nodes=0, wave=8)
        world(store)
        assert sched.schedule_pending() == 12
        assert sched.breaker.state == OPEN
        want = sorted((p.metadata.name, p.spec.node_name)
                      for p in ref_store.list("pods"))
        got = sorted((p.metadata.name, p.spec.node_name)
                     for p in store.list("pods"))
        assert got == want
        assert sched.metrics.degraded_golden_pods.value(
            reason="affinity") == 0
