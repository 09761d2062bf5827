"""Topology & heterogeneity subsystem (ops/topology.py) — dense
rack/superpod/accel-gen columns, the forward-ported PodTopologySpread
kernels, and gang compactness scoring.

Properties under test:

  1. PARITY — topo_statics/topo_statics_host and every new plane
     (PodTopologySpread mask row, TopologySpread + TopologyCompactness
     scores) are bit-for-bit identical between the jit kernel and the
     numpy twin over randomized topology worlds, including the
     mesh-sharded and breaker-open degraded paths.
  2. ENFORCEMENT — DoNotSchedule constraints hold EXACTLY against a
     host-side oracle reading the store's final bindings (the stepwise
     skew check implies the final per-domain skew bound), including
     wave-internal placements and key-less nodes failing hard.
  3. PLUMBING — the topo columns ride the scrubber (corrupt
     rack_id/accel_gen detected + repaired) and the delta-upload path
     (label churn scatter == full upload, incl. 8-device mesh and
     post-reform), weight swaps on the new planes stay recompile-free,
     and kubemark's HollowCluster stamps the labels.
"""

import jax.numpy as jnp
import numpy as np
import pytest

import kubernetes_tpu.api.types as api
from kubernetes_tpu.api.labels import LabelSelector
from kubernetes_tpu.ops import hostwave
from kubernetes_tpu.ops.hostwave import topo_statics_host
from kubernetes_tpu.ops.kernel import schedule_wave
from kubernetes_tpu.ops.topology import topo_statics
from kubernetes_tpu.runtime.store import ObjectStore
from kubernetes_tpu.sched.breaker import OPEN
from kubernetes_tpu.sched.scheduler import Scheduler
from kubernetes_tpu.utils import faultpoints

from helpers import make_node, make_pod

pytestmark = pytest.mark.topology


def _weights(sched):
    return dict(weights=sched.profile.weights(),
                num_zones=sched.snapshot.caps.Z,
                num_label_values=sched.snapshot.num_label_values)


def _spread(max_skew=1, key=None, when=None, match=None):
    return api.TopologySpreadConstraint(
        max_skew=max_skew, topology_key=key or api.LABEL_ZONE,
        when_unsatisfiable=when or api.DO_NOT_SCHEDULE,
        label_selector=(LabelSelector(match_labels=match)
                        if match is not None else None))


def topo_world(seed, n_nodes=8, n_existing=6, n_pending=10):
    """Randomized cluster with the full topology label set and a pending
    batch mixing spread-constrained (zone + rack keys, both
    whenUnsatisfiable modes), priority-bearing, and plain pods."""
    rng = np.random.RandomState(seed)
    store = ObjectStore()
    sched = Scheduler(store, wave_size=16)
    for i in range(n_nodes):
        labels = {"kubernetes.io/hostname": f"n{i}"}
        if rng.rand() < 0.8:
            labels[api.LABEL_ZONE] = f"z{rng.randint(3)}"
        if rng.rand() < 0.8:
            rack = rng.randint(4)
            labels[api.LABEL_RACK] = f"r{rack}"
            labels[api.LABEL_SUPERPOD] = f"sp{rack // 2}"
        if rng.rand() < 0.7:
            labels[api.LABEL_ACCEL_GEN] = str(rng.randint(1, 4))
        store.create("nodes", make_node(
            f"n{i}", cpu=str(rng.randint(4, 9)),
            memory=f"{rng.randint(4, 9)}Gi", labels=labels))
    for i in range(n_existing):
        store.create("pods", make_pod(
            f"ex-{i}", cpu="500m", labels={"app": rng.choice(["a", "b"])}))
    sched.schedule_pending()
    pending = []
    for i in range(n_pending):
        app = rng.choice(["a", "b"])
        tsc = []
        if rng.rand() < 0.7:
            tsc.append(_spread(
                max_skew=int(rng.randint(1, 3)),
                when=(api.DO_NOT_SCHEDULE if rng.rand() < 0.7
                      else api.SCHEDULE_ANYWAY),
                match={"app": app}))
        if rng.rand() < 0.3:
            tsc.append(_spread(key=api.LABEL_RACK, max_skew=2,
                               when=api.SCHEDULE_ANYWAY, match={"app": app}))
        p = make_pod(f"pend-{i}", cpu="200m",
                     priority=int(rng.choice([0, 5])), labels={"app": app})
        p.spec.topology_spread_constraints = tsc
        pending.append(p)
    return store, sched, pending


# ---------------------------------------------------------------------------
# parity: device == twin, bit for bit


def anchor_world(seed, n_nodes=12, n_existing=40, n_pending=10):
    """World for the node-first anchoring of the spread counts: matching
    pods stacked several to a node, deleted pods whose freed pod-matrix
    rows keep their stale node, nodes without the zone key, and
    constraints on both the hostname and the zone key."""
    rng = np.random.RandomState(seed)
    store = ObjectStore()
    sched = Scheduler(store, wave_size=16)
    for i in range(n_nodes):
        labels = {api.LABEL_HOSTNAME: f"n{i}"}
        if rng.rand() < 0.7:
            labels[api.LABEL_ZONE] = f"z{rng.randint(3)}"
        store.create("nodes", make_node(f"n{i}", cpu="32", memory="64Gi",
                                        labels=labels))
    hot = rng.choice(n_nodes, n_nodes // 3, replace=False)
    existing = [make_pod(
        f"ex-{i}", cpu="100m", labels={"app": rng.choice(["a", "b"])},
        node_name=f"n{rng.choice(hot) if rng.rand() < 0.8 else i % n_nodes}")
        for i in range(n_existing)]
    for p in existing:
        store.create("pods", p)
    for i in rng.choice(n_existing, n_existing // 3, replace=False):
        store.delete("pods", existing[i].namespace, existing[i].name)
    pending = []
    for i in range(n_pending):
        app = rng.choice(["a", "b"])
        p = make_pod(f"pend-{i}", cpu="100m", labels={"app": app})
        p.spec.topology_spread_constraints = [
            _spread(key=api.LABEL_HOSTNAME, max_skew=2, match={"app": app}),
            _spread(when=api.SCHEDULE_ANYWAY, match={"app": app})]
        pending.append(p)
    snap = sched.snapshot
    valid = snap.ep_valid[:snap._next_slot]
    assert not valid.all(), "deleted pods must leave freed rows"
    assert np.bincount(snap.ep_node[:snap._next_slot][valid]).max() > 1
    zone = snap.labels[:, snap.label_key_col(api.LABEL_ZONE)]
    assert np.any(snap.valid & (zone == 0)), "some node must lack the zone"
    return store, sched, pending


def heavy_world(seed, n_nodes=12):
    """World for exact counts past bf16's integer range: 301 matching pods
    on one node, 333 over the nodes of another zone, each below 256 (odd
    counts past 256, which bf16 rounds), and constraints on the
    hostname, zone and rack keys in one wave."""
    rng = np.random.RandomState(seed)
    store = ObjectStore()
    sched = Scheduler(store, wave_size=16)
    for i in range(n_nodes):
        store.create("nodes", make_node(
            f"n{i}", cpu="64", memory="256Gi", pods=1000,
            labels={api.LABEL_HOSTNAME: f"n{i}", api.LABEL_ZONE: f"z{i % 3}",
                    api.LABEL_RACK: f"r{i % 4}"}))
    z1 = [f"n{i}" for i in range(1, n_nodes, 3)]
    homes = ["n0"] * 301 + [z1[i % len(z1)] for i in range(333)]
    for i, node in enumerate(homes):
        store.create("pods", make_pod(f"ex-{i}", labels={"app": "a"},
                                      node_name=node))
    snap = sched.snapshot
    live = snap.ep_valid[:snap._next_slot]
    per_node = np.bincount(snap.ep_node[:snap._next_slot][live])
    assert per_node.max() == 301 and per_node[per_node < 301].max() < 256
    keys = (api.LABEL_HOSTNAME, api.LABEL_ZONE, api.LABEL_RACK)
    pending = []
    for i in range(9):
        p = make_pod(f"pend-{i}", cpu="100m",
                     labels={"app": rng.choice(["a", "b"])})
        p.spec.topology_spread_constraints = [
            _spread(key=keys[i % 3], max_skew=400, match={"app": "a"}),
            _spread(key=keys[(i + 1) % 3], when=api.SCHEDULE_ANYWAY,
                    match={"app": "a"})]
        pending.append(p)
    return store, sched, pending


class TestStaticsParity:
    @pytest.mark.parametrize("seed,world", [
        *(pytest.param(s, topo_world, id=str(s)) for s in range(3)),
        pytest.param(7, anchor_world, id="anchor-7"),
        pytest.param(11, heavy_world, id="heavy-11")])
    def test_topo_statics_matches_host(self, seed, world):
        """The wave-start spread statics — per-pod node domains, resident
        counts per domain value, domain presence, wave match matrix, self
        matches — bitwise identical between topo_statics (device) and
        topo_statics_host (twin)."""
        store, sched, pending = world(seed)
        pb = sched.featurizer.featurize(pending)
        lv = sched.snapshot.num_label_values
        nt_d, pm_d, _ = sched.snapshot.to_device()
        dev = topo_statics(nt_d, pm_d, pb, lv)
        nt_h, pm_h, _ = sched.snapshot.host_tensors()
        host = topo_statics_host(nt_h, pm_h, pb, lv)
        for f in dev._fields:
            assert np.array_equal(np.asarray(getattr(dev, f)),
                                  np.asarray(getattr(host, f))), f


class TestWaveParity:
    @pytest.mark.parametrize("seed", range(5))
    def test_spread_compact_bitwise_parity(self, seed):
        """Every WaveResult plane — the 13-row mask stack including the
        PodTopologySpread row, chosen, total scores (TopologySpread +
        TopologyCompactness folded in), fail counts — identical between
        the jit kernel and the numpy twin on a topology world."""
        store, sched, pending = topo_world(seed)
        pb = sched.featurizer.featurize(pending)
        assert bool(np.any(np.asarray(pb.ts_valid))), "world must spread"
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

    @pytest.mark.parametrize("seed", range(3))
    def test_mesh_sharded_matches_unsharded(self, seed):
        """The new planes under GSPMD node-axis sharding: the per-domain
        segment-sums and compactness scatter are integer-valued f32, so
        the sharded wave stays BITWISE equal, not just close."""
        import jax

        from kubernetes_tpu.parallel.mesh import make_mesh, shard_inputs

        assert jax.device_count() >= 8, "conftest must force 8 CPU devices"
        store, sched, pending = topo_world(seed)
        pb = sched.featurizer.featurize(pending)
        P = pb.req.shape[0]
        extra = np.ones((P, sched.snapshot.caps.N), bool)
        nt, pm, tt = sched.snapshot.to_device()
        rr = jnp.asarray(0, jnp.int32)
        ref = schedule_wave(nt, pm, tt, pb, extra, rr, None,
                            has_ipa=False, **_weights(sched))
        mesh = make_mesh(8)
        nt_s, pm_s, tt_s, pb_s, extra_s = shard_inputs(
            mesh, nt, pm, tt, pb, extra)
        res = schedule_wave(nt_s, pm_s, tt_s, pb_s, extra_s, rr, None,
                            has_ipa=False, **_weights(sched))
        assert np.array_equal(np.asarray(res.chosen), np.asarray(ref.chosen))
        assert np.array_equal(np.asarray(res.score), np.asarray(ref.score))
        assert np.array_equal(np.asarray(res.masks), np.asarray(ref.masks))

    def test_degraded_breaker_open_enforces_spread(self):
        """Breaker-open degraded mode: with every device kernel entry
        faulted the backlog drains through the twin, and the twin's
        spread plane enforces DoNotSchedule exactly like the device."""
        for point in ("kernel.round", "kernel.wave", "kernel.gang"):
            faultpoints.activate(point, "raise")
        store = ObjectStore()
        sched = Scheduler(store, wave_size=8, breaker_threshold=1,
                          breaker_cooldown=300.0)
        for i in range(4):
            store.create("nodes", make_node(
                f"n{i}", cpu="8",
                labels={"kubernetes.io/hostname": f"n{i}",
                        api.LABEL_ZONE: f"z{i % 2}",
                        api.LABEL_RACK: f"r{i}"}))
        for i in range(8):
            p = make_pod(f"sp-{i}", cpu="100m", labels={"grp": "g"})
            p.spec.topology_spread_constraints = [
                _spread(match={"grp": "g"})]
            store.create("pods", p)
        placed = 0
        for _ in range(6):
            placed += sched.schedule_pending()
            if placed >= 8:
                break
        assert placed == 8
        assert sched.breaker.state == OPEN
        assert sched.wave_path() == "vector"
        zone = {n.metadata.name: n.metadata.labels[api.LABEL_ZONE]
                for n in store.list("nodes")}
        counts = {"z0": 0, "z1": 0}
        for p in store.list("pods"):
            if p.spec.node_name:
                counts[zone[p.spec.node_name]] += 1
        assert abs(counts["z0"] - counts["z1"]) <= 1, counts


# ---------------------------------------------------------------------------
# enforcement: the host oracle over the store's final bindings


class TestSpreadEnforcement:
    @pytest.mark.parametrize("seed", range(5))
    def test_randomized_donotschedule_skew_oracle(self, seed):
        """Randomized world + full scheduler drain: per-zone counts of
        the constrained group must end within maxSkew (the kernel's
        stepwise `cand - min <= maxSkew` implies the final bound: min
        only grows, so each domain's last placement certifies it)."""
        rng = np.random.RandomState(seed + 100)
        store = ObjectStore()
        sched = Scheduler(store, wave_size=8)
        zones = int(rng.randint(2, 4))
        for i in range(6):
            store.create("nodes", make_node(
                f"n{i}", cpu="16",
                labels={"kubernetes.io/hostname": f"n{i}",
                        api.LABEL_ZONE: f"z{i % zones}"}))
        skew = int(rng.randint(1, 3))
        n_pods = int(rng.randint(5, 14))
        for i in range(n_pods):
            p = make_pod(f"sp-{i}", cpu="100m", labels={"grp": "g"})
            p.spec.topology_spread_constraints = [
                _spread(max_skew=skew, match={"grp": "g"})]
            store.create("pods", p)
        assert sched.schedule_pending() == n_pods
        zone = {n.metadata.name: n.metadata.labels[api.LABEL_ZONE]
                for n in store.list("nodes")}
        counts = {f"z{z}": 0 for z in range(zones)}
        for p in store.list("pods"):
            if p.spec.node_name and p.metadata.labels.get("grp") == "g":
                counts[zone[p.spec.node_name]] += 1
        assert max(counts.values()) - min(counts.values()) <= skew, counts

    def test_wave_internal_placements_counted(self):
        """4 identical maxSkew=1 pods landing in ONE wave across 2
        single-node zones must split 2/2 — only the scan carry's
        wave-internal counting can see the first placements."""
        store = ObjectStore()
        sched = Scheduler(store, wave_size=16)
        for i in range(2):
            store.create("nodes", make_node(
                f"n{i}", cpu="16",
                labels={"kubernetes.io/hostname": f"n{i}",
                        api.LABEL_ZONE: f"z{i}"}))
        for i in range(4):
            p = make_pod(f"sp-{i}", cpu="100m", labels={"grp": "w"})
            p.spec.topology_spread_constraints = [
                _spread(match={"grp": "w"})]
            store.create("pods", p)
        assert sched.schedule_pending() == 4
        per_node = {}
        for p in store.list("pods"):
            if p.spec.node_name:
                per_node[p.spec.node_name] = \
                    per_node.get(p.spec.node_name, 0) + 1
        assert per_node == {"n0": 2, "n1": 2}, per_node

    def test_keyless_nodes_fail_hard_constraint(self):
        """Nodes missing the topology key are infeasible for
        DoNotSchedule pods (modern semantics) but fine for
        ScheduleAnyway pods."""
        store = ObjectStore()
        sched = Scheduler(store, wave_size=8)
        for i in range(3):
            store.create("nodes", make_node(
                f"n{i}", cpu="8",
                labels={"kubernetes.io/hostname": f"n{i}"}))  # no zone
        hard = make_pod("hard", cpu="100m", labels={"grp": "k"})
        hard.spec.topology_spread_constraints = [_spread(match={"grp": "k"})]
        soft = make_pod("soft", cpu="100m", labels={"grp": "k"})
        soft.spec.topology_spread_constraints = [
            _spread(when=api.SCHEDULE_ANYWAY, match={"grp": "k"})]
        store.create("pods", hard)
        store.create("pods", soft)
        assert sched.schedule_pending() == 1
        assert store.get("pods", "default", "soft").spec.node_name
        assert not store.get("pods", "default", "hard").spec.node_name


# ---------------------------------------------------------------------------
# gang compactness + accel-gen steering


class TestGangCompactness:
    def _rack_cluster(self, store):
        # n0-n2 = rack rA gen 1, n3-n5 = rack rB gen 3: the LOW-gen rack
        # comes first in node order, so tie-break order alone would land
        # a gang on rA — only the accel-gen plane pulls it to rB
        for i in range(6):
            rack = i // 3
            store.create("nodes", make_node(
                f"n{i}", cpu="16",
                labels={"kubernetes.io/hostname": f"n{i}",
                        api.LABEL_ZONE: f"z{i % 2}",
                        api.LABEL_RACK: "rA" if rack == 0 else "rB",
                        api.LABEL_SUPERPOD: "spA" if rack == 0 else "spB",
                        api.LABEL_ACCEL_GEN: "1" if rack == 0 else "3"}))

    def _gang(self, store, n=3):
        for i in range(n):
            p = make_pod(f"g-{i}", cpu="1", priority=5)
            p.metadata.annotations = {
                "pod-group.scheduling.k8s.io/name": "tg",
                "pod-group.scheduling.k8s.io/min-available": str(n)}
            store.create("pods", p)

    def test_priority_gang_colocates_on_high_gen_rack(self):
        """A priority gang lands entirely inside one rack — and the
        accel-gen plane steers it to the gen-3 rack even though the
        gen-1 rack's nodes come first in tie-break order."""
        store = ObjectStore()
        sched = Scheduler(store, wave_size=16)
        self._rack_cluster(store)
        self._gang(store)
        assert sched.schedule_pending() == 3
        placed_on = {p.spec.node_name for p in store.list("pods")
                     if p.spec.node_name}
        assert placed_on <= {"n3", "n4", "n5"}, placed_on

    def test_compactness_zeroed_profile_scatters(self):
        """The scattered baseline: zeroing TopologyCompactnessPriority
        compiles the plane out, and without gen steering the same gang
        no longer lands on the high-gen rack."""
        from kubernetes_tpu.plugins.registry import default_profile

        store = ObjectStore()
        prof = default_profile(store)
        prof.score_weights = dict(prof.score_weights)
        prof.score_weights["TopologyCompactnessPriority"] = 0
        sched = Scheduler(store, profile=prof, wave_size=16)
        self._rack_cluster(store)
        self._gang(store)
        assert sched.schedule_pending() == 3
        placed_on = {p.spec.node_name for p in store.list("pods")
                     if p.spec.node_name}
        assert not placed_on <= {"n3", "n4", "n5"}, placed_on


# ---------------------------------------------------------------------------
# the scan's per-domain sums: compare-and-reduce over one-hot planes


def domain_world(seed, racks):
    """Twenty-four nodes, a service selecting `app=a`, running `app=a`
    pods for the selector spread to count, and a round of pending pods
    of both apps and of priority 0 and 5 (5 turns on the accel-gen
    bias). racks=False leaves every zone, rack and superpod id 0, as the
    benchmark's clusters have them; racks=True spreads the nodes over 3
    zones and 20 racks under 3 superpods, so the shared zones vocabulary
    grows past 8."""
    rng = np.random.RandomState(seed)
    store = ObjectStore()
    sched = Scheduler(store, wave_size=8)
    store.create("services", api.Service(
        metadata=api.ObjectMeta(name="svc-a", namespace="default"),
        selector={"app": "a"}))
    for i in range(24):
        labels = {api.LABEL_HOSTNAME: f"n{i}"}
        if racks:
            rack = int(rng.randint(20))
            labels.update({api.LABEL_ZONE: f"z{i % 3}",
                           api.LABEL_RACK: f"r{rack}",
                           api.LABEL_SUPERPOD: f"sp{rack % 3}",
                           api.LABEL_ACCEL_GEN: str(rng.randint(1, 4))})
        store.create("nodes", make_node(f"n{i}", cpu=str(rng.randint(2, 5)),
                                        memory="16Gi", labels=labels))
    for i in range(12):
        store.create("pods", make_pod(
            f"ex-{i}", cpu="500m", labels={"app": "a"},
            node_name=f"n{rng.randint(24)}"))
    pending = [make_pod(f"pend-{i}", cpu=f"{rng.randint(2, 9)}00m",
                        priority=int(rng.choice([0, 5])),
                        labels={"app": str(rng.choice(["a", "b"]))})
               for i in range(16)]
    return store, sched, pending


class TestDomainSums:
    @pytest.mark.parametrize("seed,racks", [
        pytest.param(0, False, id="flat-0"),
        pytest.param(1, False, id="flat-1"),
        pytest.param(0, True, id="racks-0"),
        pytest.param(1, True, id="racks-1")])
    def test_round_matches_twin(self, seed, racks):
        """The device round (two chained waves) against the numpy twin
        chained wave by wave: chosen, fail counts, round-robin, usage and
        the whole score decomposition, bit for bit, with the selector
        spread's zone sums and the compactness plane's rack and superpod
        sums in force."""
        from kubernetes_tpu.ops.kernel import schedule_round
        from kubernetes_tpu.sched.scheduler import assemble_round

        store, sched, pending = domain_world(seed, racks)
        snap, feat = sched.snapshot, sched.featurizer
        waves = [pending[:8], pending[8:]]
        # the first pass interns the pods' labels, so the second's
        # shapes are the round's
        [feat.featurize(wv) for wv in waves]
        pbs = [feat.featurize(wv) for wv in waves]
        assert np.any(np.asarray(pbs[0].sg_valid)), "pods must spread"
        if racks:
            assert snap.caps.Z > 8
            assert len(set(np.asarray(snap.node_tensors().rack_id))) > 8
        else:
            nt0 = snap.node_tensors()
            for ids in (nt0.zone_id, nt0.rack_id, nt0.superpod_id):
                assert not np.any(np.asarray(ids))
        P = pbs[0].req.shape[0]
        pm_rows, term_rows = snap.stage_pending(pending)
        kw = _weights(sched)
        nt, pm, tt = snap.to_device()
        stacked, rows, trows = assemble_round(
            pbs, waves, pm_rows, term_rows, 2, term_rows.shape[1])
        chosen, fails, usage_d, rr_d, deco, _fin = schedule_round(
            nt, pm, tt, stacked, (nt.requested, nt.nonzero, nt.pod_count),
            jnp.asarray(2, jnp.int32), rows, trows, has_ipa=False,
            collect_scores=True, **kw)
        nth, pmh, tth = snap.host_tensors()
        pm_node, pm_valid = pmh.node.copy(), pmh.valid.copy()
        usage = (nth.requested, nth.nonzero, nth.pod_count)
        rr = 2
        for w, pb in enumerate(pbs):
            pm_w = pmh._replace(node=pm_node.copy(), valid=pm_valid.copy())
            res, usage = hostwave.schedule_wave_host(
                nth, pm_w, tth, pb, np.ones((P, nth.valid.shape[0]), bool),
                rr, usage_in=usage, collect_scores=True, **kw)
            rr = int(res.rr_end)
            np.testing.assert_array_equal(np.asarray(chosen[w]), res.chosen)
            np.testing.assert_array_equal(np.asarray(fails[w]),
                                          res.fail_counts)
            for a, b in zip(deco, res.deco):
                np.testing.assert_array_equal(np.asarray(a[w]), b)
            placed = (res.chosen >= 0) & (rows[w] >= 0)
            pm_node[rows[w][placed]] = res.chosen[placed]
            pm_valid[rows[w][placed]] = True
            # later pods of the wave see its earlier placements in the
            # rack and superpod sums
            assert np.sum(res.chosen >= 0) >= 2
        assert int(np.asarray(rr_d)) == rr
        for a, b in zip(usage_d, usage):
            np.testing.assert_array_equal(np.asarray(a), b)
        for p in pending:
            snap.unstage(p)

    @pytest.mark.parametrize("num_domains", [8, 32, 256])
    def test_domain_sums_equal_segment_sum(self, num_domains):
        """_domain_sums and _domain_values are segment_sum and the gather
        of its result, bit for bit, on integer-valued counts (a rack
        vocabulary of hundreds included)."""
        import jax

        from kubernetes_tpu.ops.scores import (
            _domain_onehot, _domain_sums, _domain_values)

        rng = np.random.RandomState(num_domains)
        ids = jnp.asarray(rng.randint(num_domains, size=500), jnp.int32)
        v = jnp.asarray(rng.randint(0, 110, size=500), jnp.float32)
        oh = _domain_onehot(ids, num_domains)
        want = jax.ops.segment_sum(v, ids, num_segments=num_domains)
        got = _domain_sums(v, oh)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(_domain_values(got, oh)),
                                      np.asarray(want[ids]))

    @pytest.mark.parametrize("fn", ["spread_reduce", "_domain_colocation"])
    def test_domain_sums_lower_without_scatter_or_gather(self, fn):
        """The per-domain sums of the scan step lower to compares and
        reductions: a scatter over the node axis runs one update after
        another on the TPU, and a gather reads back what the one-hot
        already holds."""
        import jax

        from kubernetes_tpu.ops import scores

        N, Z = 16, 8
        ids = jax.ShapeDtypeStruct((N,), jnp.int32)
        oh = jax.ShapeDtypeStruct((Z, N), jnp.bool_)
        f32 = jax.ShapeDtypeStruct((N,), jnp.float32)
        if fn == "spread_reduce":
            args = (jax.ShapeDtypeStruct((N,), jnp.int32),
                    jax.ShapeDtypeStruct((N,), jnp.bool_), ids, oh)
        else:
            args = (f32, ids, oh)
        text = jax.jit(getattr(scores, fn)).lower(*args).as_text()
        assert "reduce" in text
        assert "scatter" not in text
        assert "gather" not in text


# ---------------------------------------------------------------------------
# recompile-free weight swaps


class TestRecompileFree:
    def test_topology_weight_swap_reuses_program(self):
        """Swapping the TopologySpread/TopologyCompactness multipliers
        through the traced weight_vec must not add jit cache entries —
        the planes' static gates (Weights fields) are unchanged."""
        from kubernetes_tpu.ops.kernel import _schedule_wave
        from kubernetes_tpu.ops.scores import (SCORE_STACK, W_COMPACT,
                                               W_TOPO_SPREAD, stack_weights)

        store, sched, pending = topo_world(1)
        pb = sched.featurizer.featurize(pending)
        P = pb.req.shape[0]
        extra = np.ones((P, sched.snapshot.caps.N), bool)
        nt, pm, tt = sched.snapshot.to_device()
        kw = _weights(sched)
        vec = np.asarray(stack_weights(kw["weights"]), np.float32)
        rr = jnp.asarray(0, jnp.int32)
        schedule_wave(nt, pm, tt, pb, extra, rr, None, has_ipa=False,
                      weight_vec=jnp.asarray(vec), **kw)
        base = _schedule_wave._cache_size()
        vec2 = vec.copy()
        vec2[W_TOPO_SPREAD] = 7.0
        vec2[W_COMPACT] = 0.25
        res = schedule_wave(nt, pm, tt, pb, extra, rr, None, has_ipa=False,
                            weight_vec=jnp.asarray(vec2), **kw)
        assert _schedule_wave._cache_size() == base
        assert res.chosen.shape == (P,)
        assert len(vec) == len(SCORE_STACK)


# ---------------------------------------------------------------------------
# scrubber: the topo columns are audited + repairable


class TestScrubberTopology:
    def test_corrupt_rack_and_gen_detected_and_repaired(self):
        store = ObjectStore()
        sched = Scheduler(store)
        for i in range(4):
            store.create("nodes", make_node(
                f"n{i}", cpu="4",
                labels={"kubernetes.io/hostname": f"n{i}",
                        api.LABEL_RACK: f"r{i % 2}",
                        api.LABEL_SUPERPOD: "sp0",
                        api.LABEL_ACCEL_GEN: "2"}))
        for i in range(4):
            store.create("pods", make_pod(f"p{i}", cpu="1"))
        assert sched.schedule_pending() == 4
        assert sched.scrubber.scrub().clean
        idx = sched.snapshot.node_index["n1"]
        good_rack = int(sched.snapshot.rack_id[idx])
        sched.snapshot.rack_id[idx] = good_rack + 7   # phantom rack
        sched.snapshot.accel_gen[idx] = 9             # phantom generation
        rep = sched.scrubber.scrub()
        assert len(rep.divergences) == 1, rep.summary()
        d = rep.divergences[0]
        assert d.node == "n1" and d.repaired
        assert set(d.fields) == {"rack_id", "accel_gen"}
        assert int(sched.snapshot.rack_id[idx]) == good_rack
        assert int(sched.snapshot.accel_gen[idx]) == 2
        assert sched.scrubber.scrub().clean

    def test_corrupt_superpod_repaired_via_set_node(self):
        store = ObjectStore()
        sched = Scheduler(store)
        store.create("nodes", make_node(
            "n0", cpu="4", labels={"kubernetes.io/hostname": "n0",
                                   api.LABEL_RACK: "r0",
                                   api.LABEL_SUPERPOD: "spX"}))
        store.create("pods", make_pod("p0", cpu="1"))
        assert sched.schedule_pending() == 1
        idx = sched.snapshot.node_index["n0"]
        good = int(sched.snapshot.superpod_id[idx])
        assert good > 0  # labeled nodes intern a real superpod id
        sched.snapshot.superpod_id[idx] = 0
        rep = sched.scrubber.scrub()
        assert not rep.clean and "superpod_id" in rep.divergences[0].fields
        assert int(sched.snapshot.superpod_id[idx]) == good


# ---------------------------------------------------------------------------
# delta upload: topo label churn scatters, bitwise vs full upload


def _topo_nodes(n=12):
    nodes = []
    for i in range(n):
        rack = i % 4
        nodes.append(make_node(
            f"n{i}", cpu="8",
            labels={"kubernetes.io/hostname": f"n{i}",
                    api.LABEL_ZONE: f"z{i % 3}",
                    api.LABEL_RACK: f"r{rack}",
                    api.LABEL_SUPERPOD: f"sp{rack // 2}",
                    api.LABEL_ACCEL_GEN: str(1 + i % 3)}))
    return nodes


def _relabel(cache, snap, name, rack=None, gen=None):
    """Topology label change through the informer path: mutate the
    cached node object, then set_node re-derives the dense columns."""
    ni = cache.node_infos[name]
    if rack is not None:
        ni.node.metadata.labels[api.LABEL_RACK] = rack
    if gen is not None:
        ni.node.metadata.labels[api.LABEL_ACCEL_GEN] = gen
    snap.set_node(ni)


class TestDeltaUploadTopology:
    def test_rack_gen_label_change_scatter_matches_full(self):
        from test_delta_upload import _assert_matches_fresh
        from test_parity import build

        # 96 nodes -> N bucket 128: the DELTA_MIN_ROWS=16 scatter floor
        # is then 1/8 of the rows, so a genuine row-level delta is
        # distinguishable from a full re-upload (at toy clusters the
        # floor covers every row and the gate below can't hold)
        cache, snap = build(_topo_nodes(96), [])
        snap.to_device()
        full = sum(snap._group_bytes.values())
        idx = snap.node_index["n0"]
        old_rack, old_gen = int(snap.rack_id[idx]), int(snap.accel_gen[idx])
        # swap to a rack value that is ALREADY interned (n1's): a pure
        # row-level delta, no vocab growth / realloc fallback
        before = snap.upload_bytes_total
        _relabel(cache, snap, "n0", rack="r1", gen="3")
        snap.to_device()
        moved = snap.upload_bytes_total - before
        assert 0 < moved < full // 4, (moved, full)
        assert int(snap.rack_id[idx]) == int(snap.rack_id[
            snap.node_index["n1"]]) != old_rack
        assert int(snap.accel_gen[idx]) == 3 != old_gen
        _assert_matches_fresh(snap)

    def test_topo_churn_parity_under_mesh(self):
        from kubernetes_tpu.parallel.mesh import make_mesh

        from test_delta_upload import _assert_matches_fresh
        from test_parity import build

        mesh = make_mesh(8)
        cache, snap = build(_topo_nodes(), [])
        snap.to_device(mesh=mesh)
        for i, (rack, gen) in enumerate([("r2", "1"), ("r0", "2"),
                                         ("r3", "3")]):
            _relabel(cache, snap, f"n{i}", rack=rack, gen=gen)
            _assert_matches_fresh(snap, mesh=mesh)

    def test_topo_delta_after_reform(self):
        """Mesh reform drops delta tracking; topo label churn after the
        reform must scatter against the NEW sharding bitwise."""
        from kubernetes_tpu.parallel.mesh import make_mesh, reform_mesh

        from test_delta_upload import _assert_matches_fresh
        from test_parity import build

        mesh = make_mesh(8)
        cache, snap = build(_topo_nodes(), [])
        snap.to_device(mesh=mesh)
        _relabel(cache, snap, "n2", rack="r0", gen="2")
        small = reform_mesh(list(mesh.devices.flat),
                            exclude={str(mesh.devices.flat[1])})
        assert small.devices.size == 4
        snap.to_device(mesh=small)
        assert not any(snap._dirty_rows.values())
        _relabel(cache, snap, "n3", rack="r1", gen="1")
        _assert_matches_fresh(snap, mesh=small)


# ---------------------------------------------------------------------------
# kubemark: HollowCluster stamps the topology label set


class TestHollowTopology:
    def test_hollow_cluster_stamps_racks_and_generations(self):
        from kubernetes_tpu.kubemark import HollowCluster

        store = ObjectStore()
        cluster = HollowCluster(store, 4, racks=2, generations=2)
        try:
            for node in cluster.nodes:
                node.kubelet.register_node()
            nodes = {n.metadata.name: n.metadata.labels
                     for n in store.list("nodes")}
            assert len(nodes) == 4
            assert nodes["hollow-0"][api.LABEL_RACK] == "rack-0"
            assert nodes["hollow-1"][api.LABEL_RACK] == "rack-1"
            assert nodes["hollow-0"][api.LABEL_SUPERPOD] == "sp-0"
            assert nodes["hollow-0"][api.LABEL_ACCEL_GEN] == "1"
            assert nodes["hollow-1"][api.LABEL_ACCEL_GEN] == "2"
        finally:
            cluster.stop()

    def test_hollow_cluster_default_has_no_topo_labels(self):
        from kubernetes_tpu.kubemark import HollowCluster

        store = ObjectStore()
        cluster = HollowCluster(store, 1)
        try:
            labels = cluster.nodes[0].kubelet.labels
            assert api.LABEL_RACK not in labels
            assert api.LABEL_ACCEL_GEN not in labels
        finally:
            cluster.stop()
