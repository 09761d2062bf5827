"""Predicate/priority parity: tensor kernels vs golden host semantics on
randomized fixtures (analog of the reference's table-driven
predicates_test.go / priorities tests, driven by property-based random
worlds instead of hand-written tables)."""

import random

import jax.numpy as jnp
import numpy as np
import pytest

from kubernetes_tpu.api import labels as lbl
from kubernetes_tpu.api import types as api
from kubernetes_tpu.ops import encoding as enc
from kubernetes_tpu.ops import filters, scores
from kubernetes_tpu.plugins import golden
from kubernetes_tpu.state.cache import SchedulerCache
from kubernetes_tpu.state.featurize import PodFeaturizer
from kubernetes_tpu.state.snapshot import Snapshot

from helpers import make_node, make_pod

KEYS = ["zone", "disk", "arch", "env"]
VALUES = ["a", "b", "c", "1", "2", "17", "42"]
TAINT_KEYS = ["dedicated", "special", "gpu"]
EFFECTS = [api.NO_SCHEDULE, api.PREFER_NO_SCHEDULE, api.NO_EXECUTE]


def random_world(rng, n_nodes=24, n_existing=30, n_pods=16):
    nodes = []
    for i in range(n_nodes):
        labels = {k: rng.choice(VALUES) for k in KEYS if rng.random() < 0.7}
        if rng.random() < 0.5:
            labels[api.LABEL_ZONE] = rng.choice(["z1", "z2", "z3"])
        taints = []
        for _ in range(rng.randint(0, 2)):
            taints.append(api.Taint(rng.choice(TAINT_KEYS), rng.choice(VALUES),
                                    rng.choice(EFFECTS)))
        conds = [api.NodeCondition(api.NODE_READY,
                                   rng.choice([api.COND_TRUE] * 4 + [api.COND_FALSE]))]
        if rng.random() < 0.15:
            conds.append(api.NodeCondition(api.NODE_MEMORY_PRESSURE, api.COND_TRUE))
        if rng.random() < 0.1:
            conds.append(api.NodeCondition(api.NODE_DISK_PRESSURE, api.COND_TRUE))
        nodes.append(make_node(
            f"n{i}", cpu=rng.choice(["2", "4", "8"]),
            memory=rng.choice(["4Gi", "8Gi", "16Gi"]),
            pods=rng.choice([5, 110]), labels=labels, taints=taints,
            unschedulable=rng.random() < 0.1, conditions=conds))

    existing = []
    for i in range(n_existing):
        existing.append(make_pod(
            f"e{i}", cpu=rng.choice([None, "250m", "1"]),
            memory=rng.choice([None, "256Mi", "1Gi"]),
            labels={"app": rng.choice(["web", "db", "cache"])},
            node_name=f"n{rng.randrange(n_nodes)}",
            ports=rng.choice([[], [8080]] if rng.random() < 0.3 else [[]])))

    pods = []
    for i in range(n_pods):
        sel = {}
        if rng.random() < 0.4:
            sel[rng.choice(KEYS)] = rng.choice(VALUES)
        affinity = None
        if rng.random() < 0.5:
            terms = []
            for _ in range(rng.randint(1, 2)):
                exprs = []
                for _ in range(rng.randint(1, 2)):
                    op = rng.choice([lbl.IN, lbl.NOT_IN, lbl.EXISTS,
                                     lbl.DOES_NOT_EXIST, lbl.GT, lbl.LT])
                    vals = ()
                    if op in (lbl.IN, lbl.NOT_IN):
                        vals = tuple(rng.sample(VALUES, rng.randint(1, 3)))
                    elif op in (lbl.GT, lbl.LT):
                        vals = (rng.choice(["5", "20", "x"]),)
                    exprs.append(lbl.Requirement(rng.choice(KEYS), op, vals))
                terms.append(api.NodeSelectorTerm(match_expressions=exprs))
            pref = []
            for _ in range(rng.randint(0, 2)):
                exprs = [lbl.Requirement(rng.choice(KEYS), lbl.IN,
                                         tuple(rng.sample(VALUES, 2)))]
                pref.append(api.PreferredSchedulingTerm(
                    weight=rng.randint(1, 100),
                    preference=api.NodeSelectorTerm(match_expressions=exprs)))
            affinity = api.Affinity(node_affinity=api.NodeAffinity(
                required=api.NodeSelector(terms) if rng.random() < 0.7 else None,
                preferred=pref))
        tols = []
        for _ in range(rng.randint(0, 2)):
            tols.append(api.Toleration(
                key=rng.choice(TAINT_KEYS + [""]),
                operator=rng.choice([api.TOLERATION_OP_EQUAL, api.TOLERATION_OP_EXISTS]),
                value=rng.choice(VALUES + [""]),
                effect=rng.choice(EFFECTS + [""])))
        if any(t.key == "" and t.operator == api.TOLERATION_OP_EQUAL for t in tols):
            tols = [t for t in tols if not (t.key == "" and t.operator == api.TOLERATION_OP_EQUAL)]
        pods.append(make_pod(
            f"p{i}", cpu=rng.choice([None, "100m", "1", "4"]),
            memory=rng.choice([None, "128Mi", "2Gi"]),
            labels={"app": rng.choice(["web", "db"])},
            node_selector=sel, affinity=affinity, tolerations=tols,
            ports=[8080] if rng.random() < 0.2 else [],
            owner_uid=rng.choice(["rs-web", "rs-db", ""])))
    return nodes, existing, pods


def build(nodes, existing):
    cache, snap = SchedulerCache(), Snapshot()
    for n in nodes:
        cache.add_node(n)
        snap.set_node(cache.node_infos[n.name])
    for p in existing:
        cache.add_pod(p)
        snap.refresh_node_resources(cache.node_infos[p.spec.node_name])
        snap.add_pod(p)
    return cache, snap


GOLDEN_BY_NAME = {
    "CheckNodeCondition": None,  # handled specially (split reasons)
    "CheckNodeUnschedulable": None,
    "PodFitsResources": golden.pod_fits_resources,
    "HostName": golden.pod_fits_host,
    "PodFitsHostPorts": golden.pod_fits_host_ports,
    "MatchNodeSelector": golden.pod_matches_node_selector,
    "PodToleratesNodeTaints": golden.pod_tolerates_node_taints,
    "CheckNodeMemoryPressure": golden.check_node_memory_pressure,
    "CheckNodeDiskPressure": golden.check_node_disk_pressure,
    "CheckNodePIDPressure": golden.check_node_pid_pressure,
}


@pytest.mark.parametrize("seed", range(6))
def test_predicate_parity(seed):
    rng = random.Random(seed)
    nodes, existing, pods = random_world(rng)
    cache, snap = build(nodes, existing)
    feat = PodFeaturizer(snap)
    pb = feat.featurize(pods)
    nt, pm, tt = snap.to_device()
    R = nt.alloc.shape[1]
    is_core = jnp.arange(R) < enc.RES_FIXED
    masks = np.asarray(filters.static_predicate_masks(nt, pb, is_core))
    for pi, pod in enumerate(pods):
        for ni_idx, node in enumerate(nodes):
            ninfo = cache.node_infos[node.name]
            for q, name in enumerate(enc.DEVICE_PREDICATES):
                if name == "MatchInterPodAffinity":
                    continue  # parity covered in test_interpod.py
                if name == "PodTopologySpread":
                    continue  # scan-filled plane (ops/topology.py), not
                    # in static_predicate_masks; parity in test_topology.py
                dev = bool(masks[q, pi, ni_idx])
                if name == "CheckNodeCondition":
                    ok, reasons = golden.check_node_condition(pod, ninfo)
                    gold = not any(r != api.NODE_READY and True for r in []) if ok else False
                    # device splits unschedulable out of CheckNodeCondition
                    gold = not [r for r in reasons
                                if r != golden.REASONS["NodeUnschedulable"]]
                elif name == "CheckNodeUnschedulable":
                    gold = not node.spec.unschedulable
                else:
                    gold, _ = GOLDEN_BY_NAME[name](pod, ninfo)
                assert dev == gold, (
                    f"seed={seed} predicate {name}: pod {pod.name} node "
                    f"{node.name} device={dev} golden={gold}")


@pytest.mark.parametrize("seed", range(4))
def test_score_parity(seed):
    rng = random.Random(seed + 100)
    nodes, existing, pods = random_world(rng)
    cache, snap = build(nodes, existing)
    feat = PodFeaturizer(snap)
    pb = feat.featurize(pods)
    nt, pm, tt = snap.to_device()

    aff_raw = np.asarray(scores.node_affinity_raw(nt, pb))
    taint_raw = np.asarray(scores.taint_intolerable_raw(nt, pb))
    lr = np.asarray(scores.least_requested(nt.nonzero, nt.alloc[:, :2], pb.nonzero[0]))
    bal = np.asarray(scores.balanced_allocation(nt.nonzero, nt.alloc[:, :2], pb.nonzero[0]))

    for pi, pod in enumerate(pods):
        for ni_idx, node in enumerate(nodes):
            ninfo = cache.node_infos[node.name]
            assert aff_raw[pi, ni_idx] == golden.node_affinity_map(pod, ninfo), (
                f"seed={seed} aff: {pod.name}/{node.name}")
            assert taint_raw[pi, ni_idx] == golden.taint_toleration_map(pod, ninfo), (
                f"seed={seed} taint: {pod.name}/{node.name}")
    # resource scores: computed for pod 0's nonzero request
    pod0 = pods[0]
    for ni_idx, node in enumerate(nodes):
        ninfo = cache.node_infos[node.name]
        assert int(lr[ni_idx]) == golden.least_requested_map(pod0, ninfo), (
            f"seed={seed} least_requested: {node.name}")
        assert int(bal[ni_idx]) == golden.balanced_allocation_map(pod0, ninfo), (
            f"seed={seed} balanced: {node.name}")


@pytest.mark.parametrize("seed", range(4))
def test_spread_parity(seed):
    rng = random.Random(seed + 200)
    nodes, existing, pods = random_world(rng)
    cache, snap = build(nodes, existing)
    sel_map = {
        "rs-web": [lbl.Selector.from_set({"app": "web"})],
        "rs-db": [lbl.Selector.from_set({"app": "db"})],
    }

    def group_selectors(pod):
        for ref in pod.metadata.owner_references:
            if ref.uid in sel_map:
                return sel_map[ref.uid]
        return []

    feat = PodFeaturizer(snap, group_selectors=group_selectors)
    pb = feat.featurize(pods)
    nt, pm, tt = snap.to_device()
    cnt = np.asarray(scores.spread_counts(pm, pb, snap.caps.N))
    for pi, pod in enumerate(pods):
        sels = group_selectors(pod)
        for ni_idx, node in enumerate(nodes):
            ninfo = cache.node_infos[node.name]
            gold = golden.selector_spread_map(pod, ninfo, sels)
            assert cnt[pi, ni_idx] == gold, (
                f"seed={seed} spread: {pod.name}/{node.name} "
                f"device={cnt[pi, ni_idx]} golden={gold}")

    # zone-weighted reduce parity over a random feasible set
    for pi, pod in enumerate(pods[:4]):
        feas = np.array([rng.random() < 0.8 for _ in nodes] +
                        [False] * (snap.caps.N - len(nodes)))
        if not feas.any():
            continue
        dev = np.asarray(scores.spread_reduce(
            jnp.asarray(cnt[pi]), jnp.asarray(feas), nt.zone_id,
            scores._domain_onehot(nt.zone_id, snap.caps.Z)))
        counts = {n.name: int(cnt[pi, i]) for i, n in enumerate(nodes) if feas[i]}
        zones = {n.name: api.get_zone_key(n) for n in nodes}
        gold = golden.selector_spread_reduce(counts, zones)
        for i, n in enumerate(nodes):
            if feas[i]:
                assert int(dev[i]) == gold[n.name], (
                    f"seed={seed} spread_reduce: {pod.name}/{n.name} "
                    f"device={int(dev[i])} golden={gold[n.name]}")


@pytest.mark.parametrize("seed", range(3))
def test_normalize_reduce_parity(seed):
    rng = random.Random(seed + 300)
    N = 32
    raw = np.array([rng.randint(0, 50) for _ in range(N)], np.float32)
    feas = np.array([rng.random() < 0.7 for _ in range(N)])
    for reverse in (False, True):
        dev = np.asarray(scores.normalize_reduce(
            jnp.asarray(raw), jnp.asarray(feas), reverse))
        scores_dict = {i: int(raw[i]) for i in range(N) if feas[i]}
        gold = golden.normalize_reduce(scores_dict, reverse)
        for i in gold:
            assert int(dev[i]) == gold[i], (
                f"seed={seed} reverse={reverse} node {i}: "
                f"device={int(dev[i])} golden={gold[i]}")
