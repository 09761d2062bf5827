"""Inter-pod affinity/anti-affinity: device-kernel parity vs golden
semantics on randomized worlds, plus behavioral e2e (anti-affinity
spreading, affinity co-location, wave-internal visibility, symmetry).

Reference behaviors under test:
  pkg/scheduler/algorithm/predicates/predicates.go:1115
    InterPodAffinityMatches (metadata path)
  pkg/scheduler/algorithm/priorities/interpod_affinity.go:118
    CalculateInterPodAffinityPriority
"""

import random

import numpy as np
import pytest

from kubernetes_tpu.api import labels as lbl
from kubernetes_tpu.api import types as api
from kubernetes_tpu.ops import encoding as enc
from kubernetes_tpu.ops.kernel import Weights, schedule_wave
from kubernetes_tpu.plugins import golden
from kubernetes_tpu.runtime.store import ObjectStore
from kubernetes_tpu.sched.scheduler import Scheduler
from kubernetes_tpu.state.cache import SchedulerCache
from kubernetes_tpu.state.featurize import PodFeaturizer
from kubernetes_tpu.state.snapshot import Snapshot

from helpers import make_node, make_pod

HOSTNAME = "kubernetes.io/hostname"
ZONE = "failure-domain.beta.kubernetes.io/zone"


def aff_term(match: dict, tk: str, namespaces=()) -> api.PodAffinityTerm:
    return api.PodAffinityTerm(
        label_selector=api.LabelSelector(match_labels=dict(match)),
        namespaces=list(namespaces), topology_key=tk)


def pod_affinity(required=(), preferred=()) -> api.Affinity:
    return api.Affinity(pod_affinity=api.PodAffinity(
        required=list(required),
        preferred=[api.WeightedPodAffinityTerm(weight=w, pod_affinity_term=t)
                   for w, t in preferred]))


def pod_anti_affinity(required=(), preferred=()) -> api.Affinity:
    return api.Affinity(pod_anti_affinity=api.PodAntiAffinity(
        required=list(required),
        preferred=[api.WeightedPodAffinityTerm(weight=w, pod_affinity_term=t)
                   for w, t in preferred]))


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


def run_wave(snap, pods, weights=Weights()):
    feat = PodFeaturizer(snap)
    pb = feat.featurize(pods)
    nt, pm, tt = snap.to_device()
    extra = np.ones((pb.req.shape[0], snap.caps.N), bool)
    return schedule_wave(nt, pm, tt, pb, extra, 0, weights=weights,
                         num_zones=snap.caps.Z,
                         num_label_values=snap.num_label_values, has_ipa=True)


# --- behavioral e2e ----------------------------------------------------------


def test_required_anti_affinity_spreads_one_per_node():
    """The scheduler_perf anti-affinity benchmark shape: each pod requires
    anti-affinity to its own labels on hostname — exactly one per node,
    including wave-internal visibility."""
    nodes = [make_node(f"n{i}", labels={HOSTNAME: f"n{i}"}) for i in range(4)]
    cache, snap = build(nodes, [])
    anti = pod_anti_affinity(required=[aff_term({"app": "w"}, HOSTNAME)])
    pods = [make_pod(f"p{i}", labels={"app": "w"}, affinity=anti)
            for i in range(6)]
    res = run_wave(snap, pods)
    chosen = np.asarray(res.chosen)[:6]
    placed = [c for c in chosen if c >= 0]
    assert len(placed) == 4, f"expected 4 placements, got {chosen}"
    assert len(set(placed)) == 4  # all distinct nodes
    q = enc.PRED_IDX["MatchInterPodAffinity"]
    fail = np.asarray(res.fail_counts)
    for i, c in enumerate(chosen):
        if c < 0:
            assert fail[q, i] == 4  # blocked on every node by wave placements


def test_required_affinity_colocates_by_zone():
    nodes = [make_node(f"n{i}", labels={HOSTNAME: f"n{i}", ZONE: f"z{i // 2}"})
             for i in range(4)]
    existing = [make_pod("db", labels={"app": "db"}, node_name="n3")]
    cache, snap = build(nodes, existing)
    aff = pod_affinity(required=[aff_term({"app": "db"}, ZONE)])
    res = run_wave(snap, [make_pod("web", labels={"app": "web"}, affinity=aff)])
    chosen = int(res.chosen[0])
    # db is on n3 (zone z1) -> web must land on n2 or n3
    assert snap.node_names[chosen] in ("n2", "n3")


def test_affinity_bootstrap_rule_first_pod_of_group():
    """A self-affine pod with no matching pods anywhere may schedule
    (predicates.go:1409); a non-self-matching one may not."""
    nodes = [make_node("n0", labels={HOSTNAME: "n0"})]
    cache, snap = build(nodes, [])
    self_aff = pod_affinity(required=[aff_term({"app": "w"}, HOSTNAME)])
    res = run_wave(snap, [make_pod("first", labels={"app": "w"}, affinity=self_aff)])
    assert int(res.chosen[0]) == 0
    other_aff = pod_affinity(required=[aff_term({"app": "missing"}, HOSTNAME)])
    res2 = run_wave(snap, [make_pod("stuck", labels={"app": "w"}, affinity=other_aff)])
    assert int(res2.chosen[0]) == -1


def test_bootstrap_rule_defeated_by_wave_placement_on_unlabeled_node():
    """The matchingPods existence check is topology-independent
    (predicates.go:1410): once a wave sibling matching the props is placed
    anywhere — even on a node without the topology key — the bootstrap
    exception no longer applies."""
    nodes = [make_node("bare"),  # no zone label
             make_node("zoned", labels={ZONE: "z0"})]
    cache, snap = build(nodes, [])
    plain = make_pod("plain", labels={"app": "w"}, priority=100,
                     node_selector={})  # no affinity; placed first
    aff = pod_affinity(required=[aff_term({"app": "w"}, ZONE)])
    follower = make_pod("follower", labels={"app": "w"}, affinity=aff)
    res = run_wave(snap, [plain, follower])
    first = snap.node_names[int(res.chosen[0])]
    second = int(res.chosen[1])
    if first == "bare":
        # a matching pod exists on a zoneless node: no topology anchor, and
        # bootstrap is off -> follower unschedulable (reference behavior)
        assert second == -1
    else:
        # plain landed on the zoned node: follower must co-locate in z0
        assert snap.node_names[second] == "zoned"


def test_existing_pod_anti_affinity_symmetry():
    """An existing pod's required anti-affinity blocks matching incomers in
    its topology domain (satisfiesExistingPodsAntiAffinity)."""
    nodes = [make_node(f"n{i}", labels={HOSTNAME: f"n{i}", ZONE: "z0" if i < 2 else "z1"})
             for i in range(4)]
    guard = make_pod("guard", labels={"app": "guard"}, node_name="n0",
                     affinity=pod_anti_affinity(
                         required=[aff_term({"app": "noisy"}, ZONE)]))
    cache, snap = build(nodes, [guard])
    res = run_wave(snap, [make_pod("noisy1", labels={"app": "noisy"})])
    # z0 (n0, n1) is blocked by guard's anti-affinity
    assert snap.node_names[int(res.chosen[0])] in ("n2", "n3")


def test_wave_internal_symmetry():
    """A pod placed earlier in the wave carrying anti-affinity blocks a
    later matching pod in the same wave."""
    nodes = [make_node(f"n{i}", labels={HOSTNAME: f"n{i}", ZONE: "z0"})
             for i in range(2)]
    cache, snap = build(nodes, [])
    guard = make_pod("guard", labels={"app": "guard"},
                     affinity=pod_anti_affinity(
                         required=[aff_term({"app": "noisy"}, ZONE)]),
                     priority=100)
    noisy = make_pod("noisy", labels={"app": "noisy"})
    res = run_wave(snap, [guard, noisy])
    assert int(res.chosen[0]) >= 0
    assert int(res.chosen[1]) == -1  # whole zone blocked by in-wave guard


def test_preferred_anti_affinity_steers_away():
    nodes = [make_node(f"n{i}", labels={HOSTNAME: f"n{i}"}) for i in range(3)]
    existing = [make_pod("e0", labels={"app": "w"}, node_name="n1")]
    cache, snap = build(nodes, existing)
    pref = pod_anti_affinity(preferred=[(100, aff_term({"app": "w"}, HOSTNAME))])
    res = run_wave(snap, [make_pod("p", labels={"app": "w"}, affinity=pref)],
                   weights=Weights(least_requested=0.0, balanced=0.0))
    assert snap.node_names[int(res.chosen[0])] != "n1"


def test_namespace_scoping():
    """Affinity terms default to the owner pod's namespace."""
    nodes = [make_node(f"n{i}", labels={HOSTNAME: f"n{i}"}) for i in range(2)]
    existing = [make_pod("other-ns", labels={"app": "db"}, node_name="n0",
                         namespace="prod")]
    cache, snap = build(nodes, existing)
    aff = pod_affinity(required=[aff_term({"app": "db"}, HOSTNAME)])
    # same selector, default ns -> no match (existing pod is in prod)
    res = run_wave(snap, [make_pod("p", namespace="default", affinity=aff,
                                   labels={"app": "x"})])
    assert int(res.chosen[0]) == -1
    # explicit namespaces=['prod'] -> colocated on n0
    aff2 = pod_affinity(required=[aff_term({"app": "db"}, HOSTNAME,
                                           namespaces=["prod"])])
    res2 = run_wave(snap, [make_pod("p2", namespace="default", affinity=aff2,
                                    labels={"app": "x"})])
    assert snap.node_names[int(res2.chosen[0])] == "n0"


# --- randomized parity vs golden ---------------------------------------------

APPS = ["web", "db", "cache", "batch"]


def random_affinity(rng):
    terms_req, terms_pref = [], []
    tk = rng.choice([HOSTNAME, ZONE])
    if rng.random() < 0.7:
        terms_req = [aff_term({"app": rng.choice(APPS)}, tk)]
    if rng.random() < 0.4:
        terms_pref = [(rng.randint(1, 100),
                       aff_term({"app": rng.choice(APPS)},
                                rng.choice([HOSTNAME, ZONE])))]
    kind = rng.random()
    if kind < 0.45:
        return pod_affinity(required=terms_req, preferred=terms_pref)
    if kind < 0.9:
        return pod_anti_affinity(required=terms_req, preferred=terms_pref)
    # both sides
    a = pod_affinity(required=terms_req)
    b = pod_anti_affinity(
        required=[aff_term({"app": rng.choice(APPS)}, rng.choice([HOSTNAME, ZONE]))])
    return api.Affinity(pod_affinity=a.pod_affinity,
                        pod_anti_affinity=b.pod_anti_affinity)


def random_ipa_world(rng, n_nodes=10, n_existing=18, n_pods=10):
    nodes = [make_node(f"n{i}", labels={HOSTNAME: f"n{i}",
                                        ZONE: f"z{i % 3}"})
             for i in range(n_nodes)]
    existing = []
    for i in range(n_existing):
        existing.append(make_pod(
            f"e{i}", labels={"app": rng.choice(APPS)},
            namespace=rng.choice(["default", "prod"]),
            node_name=f"n{rng.randrange(n_nodes)}",
            affinity=random_affinity(rng) if rng.random() < 0.5 else None))
    pods = []
    for i in range(n_pods):
        pods.append(make_pod(
            f"p{i}", labels={"app": rng.choice(APPS)},
            namespace=rng.choice(["default", "prod"]),
            affinity=random_affinity(rng) if rng.random() < 0.8 else None))
    return nodes, existing, pods


@pytest.mark.parametrize("seed", range(8))
def test_interpod_predicate_parity(seed):
    rng = random.Random(seed + 1000)
    nodes, existing, pods = random_ipa_world(rng)
    cache, snap = build(nodes, existing)
    feat = PodFeaturizer(snap)
    pb = feat.featurize(pods)
    nt, pm, tt = snap.to_device()
    from kubernetes_tpu.ops.affinity import incoming_statics

    ipa = incoming_statics(nt, pm, tt, pb, snap.num_label_values, 1.0)
    view = golden.ClusterView(cache.node_infos)
    sym = np.asarray(ipa.sym_blocked)
    ok_aff = np.asarray(ipa.ok_aff)
    any_aff = np.asarray(ipa.any_aff)
    blocked = np.asarray(ipa.blocked_anti)
    for pi, pod in enumerate(pods):
        for ni_idx, node in enumerate(nodes):
            ninfo = cache.node_infos[node.name]
            gold, _ = golden.interpod_affinity_predicate(pod, ninfo, view)
            # reconstruct device verdict from statics (no wave interaction
            # here: statics only)
            ra_terms = golden._affinity_terms(pod)
            dev_ok_aff = True
            if ra_terms:
                dev_ok_aff = bool(ok_aff[pi, ni_idx]) or (
                    not any_aff[pi]
                    and golden._pod_matches_all_term_props(pod, pod, ra_terms))
            rn_terms = golden._anti_affinity_terms(pod)
            dev = (not sym[pi, ni_idx]) and dev_ok_aff and not (
                bool(rn_terms) and blocked[pi, ni_idx])
            assert dev == gold, (
                f"seed={seed}: pod {pod.name} node {node.name} "
                f"device={dev} golden={gold} (sym={sym[pi, ni_idx]} "
                f"okaff={ok_aff[pi, ni_idx]} anyaff={any_aff[pi]} "
                f"blocked={blocked[pi, ni_idx]})")


@pytest.mark.parametrize("seed", range(6))
def test_interpod_priority_parity(seed):
    rng = random.Random(seed + 2000)
    nodes, existing, pods = random_ipa_world(rng)
    cache, snap = build(nodes, existing)
    feat = PodFeaturizer(snap)
    pb = feat.featurize(pods)
    nt, pm, tt = snap.to_device()
    from kubernetes_tpu.ops.affinity import incoming_statics

    hard_w = rng.choice([0, 1, 10])
    ipa = incoming_statics(nt, pm, tt, pb, snap.num_label_values, float(hard_w))
    counts = np.asarray(ipa.counts)
    view = golden.ClusterView(cache.node_infos)
    for pi, pod in enumerate(pods):
        # golden counts (pre-normalization) via the reference algorithm over
        # all nodes as "feasible"
        feasible = [cache.node_infos[n.name] for n in nodes]
        gold_scores = golden.interpod_affinity_priority(pod, feasible, view,
                                                        hard_weight=hard_w)
        # normalize device counts the same way to compare end results
        c = counts[pi, : len(nodes)]
        mx, mn = max(c.max(), 0.0), min(c.min(), 0.0)
        for ni_idx, node in enumerate(nodes):
            dev = int(10.0 * (c[ni_idx] - mn) / (mx - mn)) if mx != mn else 0
            assert dev == gold_scores[node.name], (
                f"seed={seed}: pod {pod.name} node {node.name} "
                f"device={dev} ({c[ni_idx]}) golden={gold_scores[node.name]}")


RACK = api.LABEL_RACK


def anchoring_world(on_node, in_zone, n_nodes=12):
    """Nodes under three topology keys (hostname, zone z0-z2, rack r0-r3)
    with `on_node` pods of group g0 on n0 and `in_zone` pods of group g1
    spread over zone z1's nodes, and pending pods whose required and
    preferred terms use all three keys, so one wave's unique tables hold
    three keys. Odd counts past 256 are what bf16 cannot hold."""
    nodes = [make_node(f"n{i}", cpu="64", memory="256Gi", pods=1000,
                       labels={HOSTNAME: f"n{i}", ZONE: f"z{i % 3}",
                               RACK: f"r{i % 4}"})
             for i in range(n_nodes)]
    z1 = [f"n{i}" for i in range(n_nodes) if i % 3 == 1]
    existing = [make_pod(f"a{i}", labels={"grp": "g0"}, node_name="n0")
                for i in range(on_node)]
    existing += [make_pod(f"b{i}", labels={"grp": "g1"},
                          node_name=z1[i % len(z1)])
                 for i in range(in_zone)]
    existing += [make_pod(f"c{i}", labels={"grp": "g2"},
                          node_name=f"n{i % n_nodes}") for i in range(7)]
    pods = []
    for i, tk in enumerate((HOSTNAME, ZONE, RACK)):
        for j, grp in enumerate(("g0", "g1", "g2")):
            term = aff_term({"grp": grp}, tk)
            aff = (pod_affinity(required=[term],
                                preferred=[(7 + j, aff_term({"grp": grp}, tk))])
                   if (i + j) % 2 else
                   pod_anti_affinity(required=[term],
                                     preferred=[(13 + i, term)]))
            pods.append(make_pod(f"p{i}{j}", labels={"grp": grp},
                                 affinity=aff))
    return nodes, existing, pods


@pytest.mark.parametrize("on_node,in_zone", [
    pytest.param(5, 9, id="three-keys"),
    pytest.param(301, 9, id="node-past-256"),
    pytest.param(5, 333, id="zone-past-256")])
def test_anchoring_matches_twin(on_node, in_zone):
    """The device's anchoring of matches at node domains (exact MXU
    contractions) equals the twin's per-pod bincount in every
    IncomingStatics plane, with three topology keys in the wave's
    tables and counts past bf16's exact integer range."""
    from kubernetes_tpu.ops import hostwave
    from kubernetes_tpu.ops.affinity import incoming_statics

    nodes, existing, pods = anchoring_world(on_node, in_zone)
    cache, snap = build(nodes, existing)
    pb = PodFeaturizer(snap, group_selectors=lambda p: []).featurize(pods)
    keys = {snap.label_key_col(k) for k in (HOSTNAME, ZONE, RACK)}
    assert set(np.asarray(pb.iu_tk)) - {0} == keys
    assert set(np.asarray(pb.pu_tk)) - {0} == keys
    lv = snap.num_label_values
    nt, pm, tt = snap.to_device()
    dev = incoming_statics(nt, pm, tt, pb, lv, 1.0)
    nth, pmh, tth = snap.host_tensors()
    host = hostwave.incoming_statics_host(nth, pmh, tth, pb, lv, 1.0)
    assert np.any(host.ok_aff) and np.any(host.blocked_anti)
    assert np.abs(host.counts).max() > 7 * max(on_node, in_zone)
    for f in dev._fields:
        np.testing.assert_array_equal(np.asarray(getattr(dev, f)),
                                      np.asarray(getattr(host, f)),
                                      err_msg=f)


# --- full scheduler path ------------------------------------------------------


def test_scheduler_e2e_anti_affinity():
    store = ObjectStore()
    sched = Scheduler(store, wave_size=8)
    for i in range(4):
        store.create("nodes", make_node(f"n{i}", labels={HOSTNAME: f"n{i}"}))
    anti = pod_anti_affinity(required=[aff_term({"app": "s"}, HOSTNAME)])
    for i in range(4):
        store.create("pods", make_pod(f"s{i}", labels={"app": "s"}, affinity=anti))
    placed = sched.schedule_pending(max_waves=4)
    assert placed == 4
    hosts = {store.get("pods", "default", f"s{i}").spec.node_name for i in range(4)}
    assert len(hosts) == 4


def test_scheduler_host_path_multi_topology_key():
    """Required terms with two distinct topology keys route through the
    exact golden host path."""
    store = ObjectStore()
    sched = Scheduler(store, wave_size=8)
    for i in range(4):
        store.create("nodes", make_node(
            f"n{i}", labels={HOSTNAME: f"n{i}", ZONE: f"z{i // 2}"}))
    store.create("pods", make_pod("db", labels={"app": "db"}, node_name="n2"))
    aff = api.Affinity(pod_affinity=api.PodAffinity(required=[
        aff_term({"app": "db"}, ZONE),
        aff_term({"app": "db"}, HOSTNAME),
    ]))
    store.create("pods", make_pod("web", labels={"app": "web"}, affinity=aff))
    placed = sched.schedule_pending(max_waves=4)
    assert placed == 1
    assert store.get("pods", "default", "web").spec.node_name == "n2"
