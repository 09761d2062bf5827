"""Per-kernel device==twin bitwise parity for the score (priority)
kernels.

tests/test_hostwave.py proves whole-wave parity; this file pins each
public kernel in ops/scores.py to its numpy twin in ops/hostwave.py
INDIVIDUALLY, so a divergence is attributed to the exact kernel instead
of surfacing as a wave-level placement diff. ktpu-lint's twin-coverage
rule requires every (kernel, twin) pair to be named by a parity test —
this file is that contract for the score family: floor_div,
least_requested, most_requested, balanced_allocation, node_affinity_raw,
taint_intolerable_raw, spread_counts, spread_reduce, image_locality,
prefer_avoid, normalize_reduce.
"""

import numpy as np
import pytest

import kubernetes_tpu.api.types as api
from kubernetes_tpu.api import labels as lbl
from kubernetes_tpu.ops import hostwave, scores
from kubernetes_tpu.runtime.store import ObjectStore
from kubernetes_tpu.sched.scheduler import Scheduler

from helpers import make_node, make_pod

pytestmark = pytest.mark.hostpath


def rich_world(seed, n_nodes=7, n_existing=9, n_pending=8):
    """Cluster whose snapshot exercises every score plane: node labels
    for affinity terms, PreferNoSchedule taints, container images, and
    existing pods with selector-spread-visible labels."""
    rng = np.random.RandomState(seed)
    store = ObjectStore()
    sched = Scheduler(store, wave_size=16)
    images = [("img:app", 64 << 20), ("img:base", 900 << 20),
              ("img:tool", 10 << 20)]
    for i in range(n_nodes):
        labels = {"zone": f"z{rng.randint(3)}",
                  "kubernetes.io/hostname": f"n{i}",
                  "disk": rng.choice(["ssd", "hdd"])}
        taints = []
        if rng.rand() < 0.5:
            taints.append(api.Taint(key="dedicated",
                                    value=rng.choice(["a", "b"]),
                                    effect="PreferNoSchedule"))
        node = make_node(f"n{i}", cpu=str(rng.randint(2, 9)),
                         memory=f"{rng.randint(2, 9)}Gi", labels=labels,
                         taints=taints)
        node.status.images = [
            api.ContainerImage(names=[nm], size_bytes=sz)
            for nm, sz in images if rng.rand() < 0.6]
        store.create("nodes", node)
    for i in range(n_existing):
        store.create("pods", make_pod(
            f"ex-{i}", cpu=str(rng.randint(1, 3)),
            labels={"app": rng.choice(["a", "b", "c"])},
            owner_uid=f"rs-{rng.choice(['a', 'b', 'c'])}"))
    sched.schedule_pending()
    pending = []
    for i in range(n_pending):
        affinity = None
        if rng.rand() < 0.7:
            pref = [api.PreferredSchedulingTerm(
                weight=int(rng.randint(1, 100)),
                preference=api.NodeSelectorTerm(match_expressions=[
                    lbl.Requirement("disk", lbl.IN,
                                    (rng.choice(["ssd", "hdd"]),))]))
                for _ in range(rng.randint(1, 3))]
            affinity = api.Affinity(
                node_affinity=api.NodeAffinity(preferred=pref))
        tols = []
        if rng.rand() < 0.4:
            tols = [api.Toleration(key="dedicated", operator="Exists",
                                   effect="PreferNoSchedule")]
        pod = make_pod(f"pend-{i}", cpu=str(rng.randint(1, 4)),
                       labels={"app": rng.choice(["a", "b", "c"])},
                       affinity=affinity, tolerations=tols,
                       owner_uid=f"rs-{rng.choice(['a', 'b', 'c'])}")
        if rng.rand() < 0.6:
            pod.spec.containers[0].image = images[rng.randint(
                len(images))][0]
        pending.append(pod)
    pb = sched.featurizer.featurize(pending)
    nt_h, pm_h, tt_h = sched.snapshot.host_tensors()
    nt_d, pm_d, tt_d = sched.snapshot.to_device()
    return sched, pb, (nt_h, pm_h, tt_h), (nt_d, pm_d, tt_d)


def _eq(device_out, host_out):
    d = np.asarray(device_out)
    assert d.dtype == np.asarray(host_out).dtype
    assert np.array_equal(d, host_out), (d, host_out)


class TestTensorKernelTwins:
    """Kernels over the featurized NodeTensors/PodBatch/PodMatrix
    planes, device vs twin on the SAME snapshot."""

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_node_affinity_raw_parity(self, seed):
        sched, pb, (nt_h, _pm, _tt), (nt_d, _pmd, _ttd) = rich_world(seed)
        _eq(scores.node_affinity_raw(nt_d, pb),
            hostwave.node_affinity_raw(nt_h, pb))

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_taint_intolerable_raw_parity(self, seed):
        sched, pb, (nt_h, _pm, _tt), (nt_d, _pmd, _ttd) = rich_world(seed)
        _eq(scores.taint_intolerable_raw(nt_d, pb),
            hostwave.taint_intolerable_raw(nt_h, pb))

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_image_locality_parity(self, seed):
        sched, pb, (nt_h, _pm, _tt), (nt_d, _pmd, _ttd) = rich_world(seed)
        _eq(scores.image_locality(nt_d, pb),
            hostwave.image_locality(nt_h, pb))

    @pytest.mark.parametrize("seed", [0, 1])
    def test_prefer_avoid_parity(self, seed):
        sched, pb, (nt_h, _pm, _tt), (nt_d, _pmd, _ttd) = rich_world(seed)
        _eq(scores.prefer_avoid(nt_d, pb),
            hostwave.prefer_avoid(nt_h, pb))

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_spread_counts_parity(self, seed):
        sched, pb, (_nt, pm_h, _tt), (_ntd, pm_d, _ttd) = rich_world(seed)
        n = sched.snapshot.caps.N
        _eq(scores.spread_counts(pm_d, pb, n),
            hostwave.spread_counts(pm_h, pb, n))


class TestGangTwin:
    """ops/gang.py schedule_gang vs ops/hostwave.py schedule_gang_host:
    every GangResult plane bitwise, both the admitting and the
    all-or-nothing-rewind arms."""

    @pytest.mark.parametrize("seed,need", [(0, 2), (1, 4), (2, 99)])
    def test_schedule_gang_parity(self, seed, need):
        import jax.numpy as jnp

        from kubernetes_tpu.ops.gang import schedule_gang

        sched, pb, (nt_h, pm_h, tt_h), (nt_d, pm_d, tt_d) = rich_world(seed)
        P = pb.req.shape[0]
        extra = np.ones((P, sched.snapshot.caps.N), bool)
        kw = dict(weights=sched.profile.weights(),
                  num_zones=sched.snapshot.caps.Z,
                  num_label_values=sched.snapshot.num_label_values)
        res_d = schedule_gang(nt_d, pm_d, tt_d, pb, extra,
                              jnp.asarray(2, jnp.int32), None,
                              jnp.asarray(need, jnp.int32), **kw)
        res_h = hostwave.schedule_gang_host(nt_h, pm_h, tt_h, pb, extra,
                                            2, None, need, **kw)
        assert bool(np.asarray(res_d.ok)) == bool(res_h.ok)
        assert np.array_equal(np.asarray(res_d.chosen), res_h.chosen)
        assert int(np.asarray(res_d.placed)) == int(res_h.placed)
        assert np.array_equal(np.asarray(res_d.fail_counts),
                              res_h.fail_counts)
        assert np.array_equal(np.asarray(res_d.masks), res_h.masks)
        assert int(np.asarray(res_d.rr_end)) == int(res_h.rr_end)


class TestArrayKernelTwins:
    """Kernels over plain planes — randomized f32 inputs, bit compare."""

    def _rng(self, seed):
        return np.random.RandomState(seed)

    @pytest.mark.parametrize("seed", [0, 1])
    def test_floor_div_parity(self, seed):
        x = self._rng(seed).rand(64).astype(np.float32) * 10.0
        _eq(scores.floor_div(x), hostwave.floor_div(x))

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_least_requested_parity(self, seed):
        rng = self._rng(seed)
        alloc2 = (rng.randint(0, 9, (16, 2)) * 1000.0).astype(np.float32)
        nz = (rng.randint(0, 8, (16, 2)) * 500.0).astype(np.float32)
        pod_nz = np.asarray([1500.0, 2000.0], np.float32)
        _eq(scores.least_requested(nz, alloc2, pod_nz),
            hostwave.least_requested(nz, alloc2, pod_nz))

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_most_requested_parity(self, seed):
        rng = self._rng(seed)
        alloc2 = (rng.randint(1, 9, (16, 2)) * 1000.0).astype(np.float32)
        nz = (rng.randint(0, 8, (16, 2)) * 500.0).astype(np.float32)
        pod_nz = np.asarray([500.0, 1000.0], np.float32)
        _eq(scores.most_requested(nz, alloc2, pod_nz),
            hostwave.most_requested(nz, alloc2, pod_nz))

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_balanced_allocation_parity(self, seed):
        rng = self._rng(seed)
        alloc2 = (rng.randint(0, 9, (16, 2)) * 1000.0).astype(np.float32)
        nz = (rng.randint(0, 8, (16, 2)) * 500.0).astype(np.float32)
        pod_nz = np.asarray([1000.0, 500.0], np.float32)
        _eq(scores.balanced_allocation(nz, alloc2, pod_nz),
            hostwave.balanced_allocation(nz, alloc2, pod_nz))

    @pytest.mark.parametrize("seed", [0, 1])
    @pytest.mark.parametrize("reverse", [False, True])
    def test_normalize_reduce_parity(self, seed, reverse):
        rng = self._rng(seed)
        raw = (rng.randint(0, 40, 32)).astype(np.float32)
        feasible = rng.rand(32) < 0.7
        _eq(scores.normalize_reduce(raw, feasible, reverse),
            hostwave.normalize_reduce(raw, feasible, reverse))

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_spread_reduce_parity(self, seed):
        rng = self._rng(seed)
        cnt = rng.randint(0, 6, 24).astype(np.int32)
        feasible = rng.rand(24) < 0.8
        zone_id = rng.randint(0, 4, 24).astype(np.int32)
        zone_oh = scores._domain_onehot(np.asarray(zone_id), 4)
        _eq(scores.spread_reduce(cnt, feasible, zone_id, zone_oh),
            hostwave.spread_reduce(cnt, feasible, zone_id, 4))
