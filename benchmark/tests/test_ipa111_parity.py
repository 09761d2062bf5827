"""The inter-pod reference (references/ipa111.py) against the program's
golden host implementation of 1.11 (plugins/golden.py), written apart
from it: on small random worlds of template kinds, with hostname and
zone keys, required and preferred terms on both sides and two
namespaces, both place the same pods one at a time, with the oldest
bound pod deleted after every fifth. For every pod they have to agree on
the feasible nodes, the InterPodAffinity score of each, and the node
chosen (1.11's selectHost over LeastRequested, BalancedResourceAllocation
and InterPodAffinity).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_ipa111_parity.py -q
"""

import sys
from collections import deque
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import check  # noqa: E402
import loadgen  # noqa: E402

ZONE = "topology.kubernetes.io/zone"
NAMESPACES = ("sched-0", "sched-1")
LABELS = ({"foo": ""}, {"app": "a"}, {"app": "b"}, {"app": "a", "foo": ""})


def random_config(rng) -> dict:
    """Nodes in two or three zones, four template kinds of random
    namespace, labels, requests and terms."""

    def term(required: bool) -> dict:
        t = {"labelSelector": {"matchLabels": dict(
                 LABELS[rng.integers(len(LABELS))])},
             "topologyKey": (loadgen.HOST_LABEL, ZONE)[rng.integers(2)]}
        if rng.random() < 0.5:
            t["namespaces"] = list(rng.choice(
                NAMESPACES, rng.integers(1, 3), replace=False))
        if required:
            return t
        return {"weight": int(rng.integers(1, 6)), "podAffinityTerm": t}

    kinds = []
    for k in range(4):
        aff = {}
        for block in ("podAffinity", "podAntiAffinity"):
            b = {}
            if rng.random() < 0.4:
                b[loadgen.REQUIRED] = [term(True)]
            pref = [term(False) for _ in range(rng.integers(0, 3))]
            if pref:
                b[loadgen.PREFERRED] = pref
            if b:
                aff[block] = b
        kinds.append({
            "name": f"k{k}", "shape": "template",
            "namespace": NAMESPACES[rng.integers(2)],
            "labels": dict(LABELS[rng.integers(len(LABELS))]),
            "requests": {"cpu": f"{int(rng.integers(1, 8)) * 100}m",
                         "memory": f"{int(rng.integers(1, 8)) * 256}Mi"},
            "affinity": aff})
    nodes = int(rng.integers(6, 13))
    return {"nodes": nodes, "resident": 0, "backlog": 0, "reference": "ipa111",
            "affinity_labels": 0, "anti_groups": 0,
            "node_labels": {ZONE: [f"z{z}" for z in
                                   range(int(rng.integers(2, 4)))]},
            "kinds": kinds, "mix": {f"k{k}": 1 for k in range(4)},
            "node_allocatable": {"cpu": "2", "memory": "4Gi", "pods": 12}}


def golden_world(cfg):
    from kubernetes_tpu.runtime.store import ObjectStore
    from kubernetes_tpu.state.node_info import NodeInfo

    store = ObjectStore()
    loadgen.build_cluster(store, cfg)
    by_name = {n.metadata.name: n for n in store.list("nodes")}
    return {name: NodeInfo(by_name[name]) for name in
            (loadgen.node_name(i) for i in range(cfg["nodes"]))}


def golden_choose(pod, infos, rr):
    """(feasible mask, InterPodAffinity scores, chosen node) by golden."""
    from kubernetes_tpu.plugins import golden

    view = golden.ClusterView(infos)
    nis = list(infos.values())
    ok = np.asarray([golden.pod_fits_resources(pod, ni)[0]
                     and golden.interpod_affinity_predicate(pod, ni, view)[0]
                     for ni in nis])
    ipa = np.zeros(len(nis), np.int64)
    if not ok.any():
        return ok, ipa, -1
    feas = [ni for ni, f in zip(nis, ok) if f]
    by_node = golden.interpod_affinity_priority(pod, feas, view)
    tot = np.full(len(nis), -1, np.int64)
    for c, ni in enumerate(nis):
        if ok[c]:
            ipa[c] = by_node[ni.node.name]
            tot[c] = (golden.least_requested_map(pod, ni)
                      + golden.balanced_allocation_map(pod, ni) + ipa[c])
    ties = np.flatnonzero(ok & (tot == tot[ok].max()))
    return ok, ipa, int(ties[rr % len(ties)])


@pytest.mark.parametrize("seed", range(8))
def test_ipa111_places_as_golden_does(seed):
    rng = np.random.default_rng([seed, 30])
    cfg = random_config(rng)
    ref = check.reference("ipa111")
    cl = ref.Cluster.from_config(cfg)
    st = ref.State(cl)
    plan = loadgen.plan_pods(cfg, 60, seed)
    pods = loadgen.build_pods(cfg, plan)
    infos = golden_world(cfg)
    nis = list(infos.values())
    live = deque()
    rr = placed = filtered = varied = 0
    for i, pod in enumerate(pods):
        k = int(plan.kind[i])
        want, ok, _ = ref.choose(cl, st, k, -1, -1, rr)
        g_ok, g_ipa, g_want = golden_choose(pod, infos, rr)
        assert ok.tolist() == g_ok.tolist(), (seed, i)
        filtered += bool(np.any(ref.k8s111.feasible_nodes(cl, st, k, -1, -1)
                                & ~ok))
        if ok.any():
            ipa = ref.ipa_scores(cl, st, k, ok)
            assert ipa[ok].tolist() == g_ipa[ok].tolist(), (seed, i)
            varied += len(set(ipa[ok].tolist())) > 1
        assert want == g_want, (seed, i)
        if want >= 0:
            rr += 1
            placed += 1
            st.place(want, k, -1)
            pod.spec.node_name = loadgen.node_name(want)
            nis[want].add_pod(pod)
            live.append((i, want))
        if i % 5 == 4 and live:
            q, c = live.popleft()
            st.place(c, int(plan.kind[q]), -1, -1)
            assert nis[c].remove_pod(pods[q])
    # the world did something: pods placed, nodes that fit a pod's
    # requests filtered out by its terms, scores that differ by node
    assert placed >= 10 and filtered and varied
