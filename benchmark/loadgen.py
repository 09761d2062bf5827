"""Cluster and pod generators, and the one general traffic generator.

build_cluster and base_pod are copies of bench.py's build_cluster and
_base_pod, and the pod shapes are those of bench.py's make_pods: the
yardstick keeps its own copy so that a later change to bench.py cannot
move it. What is new here is the plan: every draw (the interleaving of
the mix, the node-affinity label and anti-affinity group of each pod,
the nodes of the running pods, the arrival times) comes from --seed, and
every seed draws the same counts in another order, so the work does not
change with the seed.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import List, Optional

import numpy as np

# pod kinds of the mix, in the order config "mix" weights name them
KINDS = ("density", "affinity", "antiaffinity")
HOST_LABEL = "kubernetes.io/hostname"


def node_name(i: int) -> str:
    return f"node-{i}"


def build_cluster(store, cfg):
    """bench.py build_cluster: `nodes` Ready nodes, each with a hostname
    label and, where the configuration has them, one of
    `affinity_labels` aff-<k> labels; no zone label, as upstream's node
    templates have none."""
    from kubernetes_tpu.api import types as api

    alloc = cfg["node_allocatable"]
    for i in range(cfg["nodes"]):
        labels = {HOST_LABEL: node_name(i)}
        if cfg["affinity_labels"]:
            labels[f"aff-{i % cfg['affinity_labels']}"] = "yes"
        store.create("nodes", api.Node(
            metadata=api.ObjectMeta(name=node_name(i), labels=labels),
            status=api.NodeStatus(
                allocatable=api.resource_list(**alloc),
                conditions=[api.NodeCondition(api.NODE_READY,
                                              api.COND_TRUE)])))


def _stratified(rng, n: int, k: int) -> np.ndarray:
    """n draws of 0..k-1: each block of k holds every value once, in an
    order drawn from rng — the same counts for every seed."""
    blocks = -(-n // k)
    out = np.argsort(rng.random((blocks, k)), axis=1).ravel()
    return out[:n].astype(np.int32)


@dataclass
class PodPlan:
    """What each pod of a run is. Index i is the pod's creation order."""

    kind: np.ndarray  # int32 index into KINDS
    aff: np.ndarray  # int32 aff-<k> label an affinity pod requires, else -1
    group: np.ndarray  # int32 anti-affinity group, else -1

    def __len__(self):
        return len(self.kind)


def plan_pods(cfg, n: int, seed: int) -> PodPlan:
    rng = np.random.default_rng([seed, 1])
    weights = [int(cfg["mix"].get(k, 0)) for k in KINDS]
    cycle = np.repeat(np.arange(len(KINDS), dtype=np.int32), weights)
    blocks = -(-n // len(cycle))
    order = np.argsort(rng.random((blocks, len(cycle))), axis=1)
    kind = cycle[order].ravel()[:n]
    aff = np.full(n, -1, np.int32)
    group = np.full(n, -1, np.int32)
    for k, arr, count in ((1, aff, cfg["affinity_labels"]),
                          (2, group, cfg["anti_groups"])):
        sel = np.flatnonzero(kind == k)
        if len(sel):
            arr[sel] = _stratified(rng, len(sel), count)
    return PodPlan(kind=kind, aff=aff, group=group)


def resident_nodes(cfg, plan: PodPlan, n: int, seed: int) -> np.ndarray:
    """Nodes of the first n plan pods, the cluster's running pods at the
    start, drawn from the seed: the pods of an anti-affinity group on
    distinct nodes, a pod requiring aff-<a> on a node carrying that label,
    every other pod on any node. Capacity is not drawn around: at the
    configurations' counts (at most 6 pods a node on average, 40 fit) no
    node comes near it, and one past it would read as a violation."""
    rng = np.random.default_rng([seed, 4])
    nodes = cfg["nodes"]
    labels = max(cfg["affinity_labels"], 1)
    out = np.empty(n, np.int64)
    g, a = plan.group[:n], plan.aff[:n]
    for grp in np.unique(g[g >= 0]):
        sel = np.flatnonzero(g == grp)
        out[sel] = rng.permutation(nodes)[:len(sel)]
    for lab in np.unique(a[a >= 0]):
        sel = np.flatnonzero(a == lab)
        out[sel] = lab + labels * rng.integers(
            0, -(-(nodes - lab) // labels), len(sel))
    sel = np.flatnonzero((g < 0) & (a < 0))
    out[sel] = rng.integers(0, nodes, len(sel))
    return out


def base_pod(api, name, prefix, requests, labels=None, affinity=None):
    """bench.py _base_pod, with the requests given."""
    return api.Pod(
        metadata=api.ObjectMeta(
            name=name, labels=labels or {"type": prefix},
            owner_references=[api.OwnerReference(
                kind="ReplicationController", name=prefix, uid=f"rc-{prefix}",
                controller=True)]),
        spec=api.PodSpec(
            affinity=affinity, tolerations=[],
            containers=[api.Container(
                resources=api.ResourceRequirements(
                    requests=dict(requests)))]))


def make_pod(api, plan: PodPlan, i: int, requests, prefix: str = "pod"):
    """Pod i of the plan, shaped as bench.py make_pods shapes its kind:
    density; required node affinity on aff-<k> (scheduler_test.go);
    required hostname anti-affinity within its group
    (scheduler_bench_test.go)."""
    from kubernetes_tpu.api.labels import LabelSelector, Requirement

    kind = KINDS[plan.kind[i]]
    tag = f"{kind}-pod"
    name = f"{prefix}-{i}"
    if kind == "density":
        return base_pod(api, name, tag, requests)
    if kind == "affinity":
        aff = api.Affinity(node_affinity=api.NodeAffinity(
            required=api.NodeSelector([api.NodeSelectorTerm(
                match_expressions=[Requirement(
                    f"aff-{plan.aff[i]}", "In", ("yes",))])])))
        return base_pod(api, name, tag, requests, affinity=aff)
    g = f"g{plan.group[i]}"
    aff = api.Affinity(pod_anti_affinity=api.PodAntiAffinity(
        required=[api.PodAffinityTerm(
            label_selector=LabelSelector(match_labels={"anti-group": g}),
            topology_key=HOST_LABEL)]))
    return base_pod(api, name, tag, requests,
                    labels={"type": tag, "anti-group": g}, affinity=aff)


def build_pods(cfg, plan: PodPlan, prefix: str = "pod") -> List[object]:
    from kubernetes_tpu.api import types as api

    requests = api.resource_list(**cfg["pod_requests"])
    return [make_pod(api, plan, i, requests, prefix) for i in range(len(plan))]


def poisson_due(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Due times (s from the window start) of an open loop at `rate`:
    exactly round(rate * seconds) arrivals with Poisson gaps, drawn from
    the seed (a Poisson process conditioned on its count), so every seed
    offers the same number of pods in another pattern."""
    rng = np.random.default_rng([seed, 2])
    n = int(round(rate * seconds))
    gaps = rng.exponential(1.0, n + 1)
    return (np.cumsum(gaps)[:n] / gaps.sum() * seconds).astype(np.float64)


class OpenLoop(threading.Thread):
    """The open-loop generator: at each due time it hands the next
    pre-built pod to the inbox, however far behind the scheduler is.
    `accepted[i]` is the host clock at which pod i was handed over."""

    def __init__(self, pods, due, inbox, t0: float):
        super().__init__(name="loadgen", daemon=True)
        self.pods = pods
        self.due = due
        self.inbox = inbox
        self.t0 = t0
        self.accepted = np.full(len(due), np.nan)
        self._halt = threading.Event()

    def stop(self):
        self._halt.set()

    def run(self):
        import jax

        with jax.profiler.TraceAnnotation("loadgen"):
            for i, d in enumerate(self.due):
                wait = self.t0 + d - time.perf_counter()
                if wait > 0 and self._halt.wait(wait):
                    return
                if self._halt.is_set():
                    return
                self.accepted[i] = time.perf_counter()
                self.inbox.put(self.pods[i])


class Inbox:
    """Pods the generator has handed over and the scheduler's informers
    have not yet seen. The serve loop (and, in a closed loop, the round
    boundary) moves them into the store; it waits on the inbox instead
    of sleeping, so no fixed sleep sets a latency floor."""

    def __init__(self):
        self._cv = threading.Condition()
        self._items: List[object] = []
        self._closed = False

    def put(self, pod):
        with self._cv:
            self._items.append(pod)
            self._cv.notify()

    def close(self):
        with self._cv:
            self._closed = True
            self._cv.notify_all()

    def take(self, block: bool, timeout: Optional[float] = None):
        """All waiting pods; with block, wait until there is one (or the
        inbox closes, or `timeout` passes)."""
        with self._cv:
            if block:
                self._cv.wait_for(lambda: self._items or self._closed,
                                  timeout)
            out, self._items = self._items, []
            return out
