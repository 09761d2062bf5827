"""Delta (per-row) snapshot uploads: randomized bind/evict/heartbeat
churn must leave the delta-updated device mirror bit-for-bit identical
to a from-scratch upload of the same snapshot (the scrubber's
golden-row trick applied to the transport layer: the host arrays ARE
the truth, the device cache must always equal them), including the
grow/realloc path that invalidates every dirty range — and the whole
point, a >=10x cut in steady-state upload bytes per round on a
trickle-style workload, measured via snapshot_upload_bytes_total.
"""

import random

import numpy as np
import pytest

from kubernetes_tpu.api import types as api
from kubernetes_tpu.state.snapshot import Snapshot

from helpers import make_node, make_pod
from test_parity import build, random_world

GROUPS = ("res", "topo", "pods", "terms")


def _device_groups(snap, mesh=None):
    """Upload (delta or full, whatever the dirt dictates) and fetch the
    cached device groups back as host arrays."""
    snap.to_device(mesh=mesh)
    return {g: [np.asarray(a) for a in snap._device_cache[g]]
            for g in GROUPS}


def _assert_matches_fresh(snap, mesh=None):
    """The golden comparison: the delta-maintained device cache vs a
    from-scratch to_device() of the SAME snapshot (cache cleared ->
    whole-group re-upload of the live host arrays)."""
    got = _device_groups(snap, mesh=mesh)
    snap._device_cache.clear()
    want = _device_groups(snap, mesh=mesh)
    for g in GROUPS:
        assert len(got[g]) == len(want[g])
        for i, (a, b) in enumerate(zip(got[g], want[g])):
            np.testing.assert_array_equal(
                a, b, err_msg=f"group {g} array {i} diverged after delta "
                              f"upload")


def _churn(rng, cache, snap, nodes, n_ops=40):
    """One randomized churn burst: binds (new pods, some with
    anti-affinity terms so the term table churns too), evictions, and
    node heartbeats (topology refreshes)."""
    from kubernetes_tpu.api import labels as lbl

    bound = [uid for uid in snap.pod_slot]
    seq = rng.randrange(10**6)
    for _ in range(n_ops):
        op = rng.random()
        if op < 0.5:  # bind
            seq += 1
            node = rng.choice(nodes).metadata.name
            aff = None
            labels = {"app": rng.choice(["web", "db"])}
            if rng.random() < 0.3:
                labels["anti"] = f"g{rng.randrange(3)}"
                aff = api.Affinity(pod_anti_affinity=api.PodAntiAffinity(
                    required=[api.PodAffinityTerm(
                        label_selector=lbl.LabelSelector(
                            match_labels={"anti": labels["anti"]}),
                        topology_key="kubernetes.io/hostname")]))
            p = make_pod(f"churn-{seq}", cpu="100m", memory="64Mi",
                         labels=labels, node_name=node, affinity=aff)
            cache.add_pod(p)
            snap.refresh_node_resources(cache.node_infos[node])
            snap.add_pod(p)
            bound.append(p.uid)
        elif op < 0.8 and bound:  # evict
            uid = bound.pop(rng.randrange(len(bound)))
            slot = snap.pod_slot.get(uid)
            if slot is None:
                continue
            node_idx = int(snap.ep_node[slot])
            snap.remove_pod_by_uid(uid)
            name = snap.node_names[node_idx]
            ni = cache.node_infos.get(name)
            if ni is not None:
                ni.pods = [q for q in ni.pods if q.uid != uid]
                snap.refresh_node_resources(ni)
        else:  # heartbeat / node refresh
            node = rng.choice(nodes)
            snap.set_node(cache.node_infos[node.metadata.name])


@pytest.mark.parametrize("seed", range(4))
def test_randomized_churn_bitwise_parity(seed):
    rng = random.Random(seed)
    nodes, existing, _ = random_world(rng, n_nodes=20, n_existing=24)
    cache, snap = build(nodes, existing)
    snap.to_device()  # warm full upload
    for _ in range(5):
        _churn(rng, cache, snap, nodes)
        _assert_matches_fresh(snap)


def test_delta_path_actually_engages():
    """The parity test is vacuous if every round takes the full-upload
    fallback: a small churn against a warm cache must move FEWER bytes
    than the resident footprint, and must not mark any group bytes as
    re-uploaded wholesale."""
    rng = random.Random(7)
    nodes, existing, _ = random_world(rng, n_nodes=24, n_existing=30)
    cache, snap = build(nodes, existing)
    snap.to_device()
    full = sum(snap._group_bytes.values())
    # one bind: touches one res row + one pods row
    node = nodes[0].metadata.name
    p = make_pod("delta-probe", cpu="100m", node_name=node)
    cache.add_pod(p)
    snap.refresh_node_resources(cache.node_infos[node])
    snap.add_pod(p)
    before = snap.upload_bytes_total
    snap.to_device()
    moved = snap.upload_bytes_total - before
    assert 0 < moved < full // 4, (moved, full)
    _assert_matches_fresh(snap)


@pytest.mark.parametrize("grow_dim", ["node", "label"])
def test_grow_realloc_invalidates_dirty_ranges(grow_dim):
    """Growth reallocates the host arrays: every pending dirty row range
    refers to the OLD shapes and must be discarded for a whole-group
    upload — a stale range applied to reallocated arrays would silently
    corrupt rows."""
    rng = random.Random(11)
    nodes, existing, _ = random_world(rng, n_nodes=12, n_existing=16)
    cache, snap = build(nodes, existing)
    snap.to_device()
    # dirty some rows, then grow BEFORE uploading them
    _churn(rng, cache, snap, nodes, n_ops=10)
    pre = {g: set(s) for g, s in snap._dirty_rows.items()}
    assert any(pre.values())
    if grow_dim == "node":
        extra = [make_node(f"grown-{i}", cpu="8",
                           labels={"kubernetes.io/hostname": f"grown-{i}"})
                 for i in range(snap.caps.N - len(snap.node_names) + 1)]
    else:
        extra = [make_node("fat-label", cpu="8",
                           labels={f"grow-key-{i}": "v"
                                   for i in range(snap.caps.K + 1)})]
    for n in extra:
        cache.add_node(n)
        snap.set_node(cache.node_infos[n.name])
    assert snap.dirty_topology  # growth forces whole-group flags
    # every PRE-grow dirty row was discarded at realloc (only the
    # growth-triggering nodes' own fresh rows may be marked now)
    for g in GROUPS:
        assert not (snap._dirty_rows[g] & pre[g]), (g, snap._dirty_rows[g])
    _assert_matches_fresh(snap)


def test_churn_parity_under_mesh():
    """Delta scatters against a node-sharded device cache (GSPMD
    partitions the row scatter) stay bit-for-bit with the from-scratch
    sharded upload."""
    from kubernetes_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(8)
    rng = random.Random(3)
    nodes, existing, _ = random_world(rng, n_nodes=20, n_existing=24)
    cache, snap = build(nodes, existing)
    snap.to_device(mesh=mesh)
    for _ in range(3):
        _churn(rng, cache, snap, nodes)
        _assert_matches_fresh(snap, mesh=mesh)


def test_mode_switch_invalidates_cache():
    """to_device(mesh=...) after to_device() (and back) must re-commit
    the groups, not serve arrays with the wrong sharding."""
    from kubernetes_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(8)
    rng = random.Random(5)
    nodes, existing, _ = random_world(rng, n_nodes=16, n_existing=8)
    cache, snap = build(nodes, existing)
    nt_single, _, _ = snap.to_device()
    nt_mesh, _, _ = snap.to_device(mesh=mesh)
    assert len(nt_mesh.valid.sharding.device_set) == 8
    np.testing.assert_array_equal(np.asarray(nt_mesh.valid),
                                  np.asarray(nt_single.valid))
    nt_back, _, _ = snap.to_device()
    assert len(nt_back.valid.sharding.device_set) == 1


def test_reform_invalidates_delta_tracking():
    """Mesh reform (parallel/mesh.py reform_mesh) regression: a NEW mesh
    object must drop the whole device cache — pending dirty rows were
    tracked against the OLD sharding and applying them as a delta
    scatter against re-committed arrays would be wrong. The reformed
    upload is FULL (bytes == resident footprint), delta tracking resets,
    and the re-committed groups match a from-scratch sharded upload
    bit-for-bit; subsequent churn deltas engage again."""
    from kubernetes_tpu.parallel.mesh import make_mesh, reform_mesh

    mesh = make_mesh(8)
    rng = random.Random(17)
    nodes, existing, _ = random_world(rng, n_nodes=20, n_existing=24)
    cache, snap = build(nodes, existing)
    snap.to_device(mesh=mesh)
    # dirty some rows against the 8-way sharding, then reform to 4
    _churn(rng, cache, snap, nodes, n_ops=12)
    assert any(snap._dirty_rows.values())
    small = reform_mesh(list(mesh.devices.flat),
                        exclude={str(mesh.devices.flat[3])})
    assert small.devices.size == 4
    before = snap.upload_bytes_total
    nt, _, _ = snap.to_device(mesh=small)
    # full re-upload to the new sharding, delta tracking reset
    assert snap.upload_bytes_total - before >= sum(
        snap._group_bytes.values())
    assert not any(snap._dirty_rows.values())
    assert len(nt.valid.sharding.device_set) == 4
    _assert_matches_fresh(snap, mesh=small)
    # churn against the reformed mesh: deltas engage and stay bitwise
    _churn(rng, cache, snap, nodes, n_ops=12)
    _assert_matches_fresh(snap, mesh=small)
    # healing back upward re-commits again, same contract
    _churn(rng, cache, snap, nodes, n_ops=6)
    _assert_matches_fresh(snap, mesh=mesh)


def test_trickle_upload_bytes_cut_10x():
    """The acceptance gate: steady-state upload bytes per trickle round
    are >=10x below the whole-mirror re-upload the pre-delta scheduler
    paid, measured via the scheduler's snapshot_upload_bytes_total."""
    from kubernetes_tpu.ops.encoding import Caps
    from kubernetes_tpu.runtime.store import ObjectStore
    from kubernetes_tpu.sched.scheduler import Scheduler
    from kubernetes_tpu.state.vocab import bucket_size

    store = ObjectStore()
    caps = Caps(M=bucket_size(1024), P=32, LV=bucket_size(256 + 256, 64))
    sched = Scheduler(store, wave_size=32, caps=caps)
    for i in range(256):
        store.create("nodes", make_node(
            f"node-{i}", cpu="16", memory="32Gi",
            labels={api.LABEL_ZONE: f"zone-{i % 3}",
                    "kubernetes.io/hostname": f"node-{i}"}))
    # fill pass: places one wave, warms the device cache
    for i in range(32):
        store.create("pods", make_pod(f"fill-{i}", cpu="100m",
                                      memory="128Mi", owner_uid="rc-fill"))
    assert sched.schedule_pending() == 32
    full = sum(sched.snapshot._group_bytes.values())
    assert full > 0
    # steady state: 16-pod chunks, each drained before the next lands
    per_round = []
    for r in range(6):
        for i in range(16):
            store.create("pods", make_pod(f"t{r}-{i}", cpu="100m",
                                          memory="128Mi",
                                          owner_uid="rc-trickle"))
        before = sched.metrics.snapshot_upload_bytes.value
        assert sched.schedule_pending() == 16
        per_round.append(sched.metrics.snapshot_upload_bytes.value - before)
    # skip the first steady round (residual dirt from the fill pass)
    steady = per_round[1:]
    assert all(b > 0 for b in steady), steady  # rounds DID upload deltas
    worst = max(steady)
    assert worst * 10 <= full, (per_round, full)
    sched.close()


def test_device_groups_never_alias_host_planes():
    """The host planes are written in place, so every device group must
    be a copy: a host write reaches the device only through an upload.
    On the CPU backend device_put may alias an aligned numpy buffer
    instead of copying it, so several fresh worlds try."""
    for seed in range(8):
        nodes, existing, _ = random_world(random.Random(seed), n_nodes=64,
                                          n_existing=64)
        _cache, snap = build(nodes, existing)
        snap.to_device()
        uploaded = {g: [np.array(a, copy=True) for a in snap._device_cache[g]]
                    for g in GROUPS}
        for g in GROUPS:
            for a in snap._group_host(g):
                # in place, and no dirty row: no upload is due
                a[...] = ~a if a.dtype == bool else a + 1
        snap.to_device()
        for g in GROUPS:
            for i, a in enumerate(snap._device_cache[g]):
                np.testing.assert_array_equal(
                    np.asarray(a), uploaded[g][i],
                    err_msg=f"group {g} array {i} follows the host plane")
