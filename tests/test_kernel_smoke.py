"""End-to-end smoke test of the tensor path: cache -> snapshot ->
featurize -> schedule_wave."""

import numpy as np

from kubernetes_tpu.api import types as api
from kubernetes_tpu.ops import encoding as enc
from kubernetes_tpu.ops.kernel import Weights, schedule_wave
from kubernetes_tpu.state.cache import SchedulerCache
from kubernetes_tpu.state.featurize import PodFeaturizer
from kubernetes_tpu.state.snapshot import Snapshot

from helpers import make_node, make_pod


def build_world(nodes, scheduled_pods=()):
    cache = SchedulerCache()
    snap = Snapshot()
    for n in nodes:
        cache.add_node(n)
        snap.set_node(cache.node_infos[n.name])
    for p in scheduled_pods:
        cache.add_pod(p)
        snap.refresh_node_resources(cache.node_infos[p.spec.node_name])
        snap.add_pod(p)
    return cache, snap


def run_wave(snap, pods, weights=Weights(), feat=None, has_ipa=False):
    feat = feat or PodFeaturizer(snap)
    pb = feat.featurize(pods)
    nt, pm, tt = snap.to_device()
    extra = np.ones((pb.req.shape[0], snap.caps.N), bool)
    res = schedule_wave(nt, pm, tt, pb, extra, 0, weights=weights,
                        num_zones=snap.caps.Z,
                        num_label_values=snap.num_label_values,
                        has_ipa=has_ipa or snap.has_affinity_terms)
    return res


def test_basic_placement():
    nodes = [make_node(f"n{i}", cpu="4", memory="8Gi") for i in range(4)]
    cache, snap = build_world(nodes)
    pods = [make_pod(f"p{i}", cpu="1", memory="1Gi") for i in range(3)]
    res = run_wave(snap, pods)
    chosen = np.asarray(res.chosen)[:3]
    assert (chosen >= 0).all()
    # spreading is off (no owners); least-requested should spread by usage:
    # three pods land on three distinct empty nodes via round-robin ties
    assert len(set(chosen.tolist())) == 3


def test_resource_exhaustion_within_wave():
    nodes = [make_node("n0", cpu="2", memory="4Gi", pods=10)]
    cache, snap = build_world(nodes)
    pods = [make_pod(f"p{i}", cpu="1") for i in range(3)]
    res = run_wave(snap, pods)
    chosen = np.asarray(res.chosen)[:3]
    # only 2 cpus: third pod must fail even though the wave started feasible
    assert chosen[0] == 0 and chosen[1] == 0
    assert chosen[2] == -1
    q = enc.PRED_IDX["PodFitsResources"]
    assert np.asarray(res.fail_counts)[q, 2] == 1


def test_node_selector_and_affinity():
    nodes = [
        make_node("small", labels={"size": "s"}),
        make_node("large", labels={"size": "l"}),
    ]
    cache, snap = build_world(nodes)
    p = make_pod("p", node_selector={"size": "l"})
    res = run_wave(snap, [p])
    assert snap.node_names[int(res.chosen[0])] == "large"
    # unmatched selector -> unschedulable, charged to MatchNodeSelector
    p2 = make_pod("p2", node_selector={"size": "xl"})
    res2 = run_wave(snap, [p2])
    assert int(res2.chosen[0]) == -1
    q = enc.PRED_IDX["MatchNodeSelector"]
    assert np.asarray(res2.fail_counts)[q, 0] == 2


def test_taints_and_tolerations():
    nodes = [
        make_node("tainted", taints=[api.Taint("dedicated", "gpu", api.NO_SCHEDULE)]),
        make_node("open"),
    ]
    cache, snap = build_world(nodes)
    res = run_wave(snap, [make_pod("p")])
    assert snap.node_names[int(res.chosen[0])] == "open"
    tol = api.Toleration(key="dedicated", operator="Equal", value="gpu",
                         effect=api.NO_SCHEDULE)
    res2 = run_wave(snap, [make_pod("p2", tolerations=[tol])])
    assert int(res2.chosen[0]) >= 0  # both feasible now


def test_unschedulable_and_not_ready_nodes():
    nodes = [
        make_node("cordoned", unschedulable=True),
        make_node("down", conditions=[api.NodeCondition(api.NODE_READY, api.COND_FALSE)]),
        make_node("ok"),
    ]
    cache, snap = build_world(nodes)
    res = run_wave(snap, [make_pod("p")])
    assert snap.node_names[int(res.chosen[0])] == "ok"


def test_selector_spreading():
    nodes = [make_node(f"n{i}") for i in range(3)]
    # existing replica of the same group on n0
    existing = make_pod("e0", labels={"app": "web"}, node_name="n0", owner_uid="rs1")
    cache, snap = build_world(nodes, [existing])

    from kubernetes_tpu.api.labels import Selector

    feat = PodFeaturizer(
        snap, group_selectors=lambda pod: [Selector.from_set({"app": "web"})])
    res = run_wave(snap, [make_pod("p", labels={"app": "web"}, owner_uid="rs1")],
                   feat=feat)
    # must avoid n0 (it already holds a replica)
    assert snap.node_names[int(res.chosen[0])] != "n0"


def test_graft_entry_step_jits():
    """__graft_entry__.entry() hands out a step that traces under an
    outer jax.jit (no host-side numpy read of a tracer) and computes
    what the plain call computes."""
    import jax

    import __graft_entry__

    fn, args = __graft_entry__.entry()
    jitted = jax.jit(fn)(*args)
    plain = fn(*args)
    for field in ("chosen", "score", "feasible_count", "fail_counts",
                  "rr_end"):
        np.testing.assert_array_equal(np.asarray(getattr(jitted, field)),
                                      np.asarray(getattr(plain, field)))
    assert (np.asarray(jitted.chosen)[:8] >= 0).all()
