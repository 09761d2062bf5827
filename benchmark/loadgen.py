"""Cluster and pod generators, and the one general traffic generator.

build_cluster and base_pod are copies of bench.py's build_cluster and
_base_pod, and the pod shapes are those of bench.py's make_pods: the
yardstick keeps its own copy so that a later change to bench.py cannot
move it. What is new here is the plan: every draw (the interleaving of
the mix, the node-affinity label and anti-affinity group of each pod,
the nodes of the running pods, the arrival times) comes from --seed, and
every seed draws the same counts in another order, so the work does not
change with the seed.

A configuration may declare its pod kinds (`kinds`: name, shape, and
optionally requests and priority), a mix for its running pods apart from
the backlog's (`resident_mix`), where its running pods are put
(`resident_placement`) and labels its nodes carry beyond the hostname
(`node_labels`). Without them it has one kind of each shape, named after
the shape, at `pod_requests`, and one mix for every pod.

A kind of shape `template` is a pod template of upstream's
scheduler_perf: its `labels`, its `namespace` (else "default") and its
`affinity`, a JSON transcription of the template's affinity block
(podAffinity and podAntiAffinity, required and preferred, each term with
labelSelector.matchLabels, topologyKey, namespaces and, where preferred,
weight).
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import List, Optional

import numpy as np

# pod shapes: plain, required node affinity on aff-<k>, required
# hostname anti-affinity within a group; also the kinds, in this order,
# of a configuration that declares none
KINDS = ("density", "affinity", "antiaffinity")
# ... and a pod template of upstream's scheduler_perf
SHAPES = KINDS + ("template",)
HOST_LABEL = "kubernetes.io/hostname"
# the template affinity block's keys, as the Kubernetes API spells them
REQUIRED = "requiredDuringSchedulingIgnoredDuringExecution"
PREFERRED = "preferredDuringSchedulingIgnoredDuringExecution"
TERM_KEYS = {"labelSelector", "topologyKey", "namespaces"}


def kinds(cfg) -> List[dict]:
    """The configuration's pod kinds, in the order PodPlan.kind indexes
    them: each a dict with `name` and `shape`, and where declared
    `requests` (else `pod_requests`) and `priority` (else unset)."""
    out = cfg.get("kinds")
    if out is None:
        return [{"name": k, "shape": k} for k in KINDS]
    names = [k["name"] for k in out]
    if len(set(names)) != len(names):
        raise ValueError(f"kinds named twice: {names}")
    for k in out:
        if k["shape"] not in SHAPES:
            raise ValueError(f"kind {k['name']!r}: shape {k['shape']!r} "
                             f"is none of {SHAPES}")
        if k["shape"] != "template" and {"labels", "namespace",
                                         "affinity"} & set(k):
            raise ValueError(f"kind {k['name']!r}: labels, namespace and "
                             "affinity belong to shape 'template'")
    return out


def template_terms(kind: dict) -> List[tuple]:
    """The inter-pod terms of a template kind's affinity block, each
    (block, which, weight, term): block podAffinity or podAntiAffinity,
    which REQUIRED or PREFERRED, weight None where required, term the
    JSON term. Refuses what the block may not hold."""
    aff = kind.get("affinity") or {}
    if set(aff) - {"podAffinity", "podAntiAffinity"}:
        raise ValueError(f"kind {kind['name']!r}: affinity holds "
                         f"{sorted(aff)}; only podAffinity and "
                         "podAntiAffinity are carried")
    out = []
    for block in ("podAffinity", "podAntiAffinity"):
        b = aff.get(block) or {}
        if set(b) - {REQUIRED, PREFERRED}:
            raise ValueError(f"kind {kind['name']!r}: {block} holds "
                             f"{sorted(b)}")
        for t in b.get(REQUIRED, []):
            out.append((block, REQUIRED, None, t))
        for wt in b.get(PREFERRED, []):
            out.append((block, PREFERRED, int(wt["weight"]),
                        wt["podAffinityTerm"]))
    for _, _, _, t in out:
        sel = t.get("labelSelector")
        if set(t) - TERM_KEYS or (sel is not None
                                  and set(sel) - {"matchLabels"}):
            raise ValueError(f"kind {kind['name']!r}: term {t} holds more "
                             f"than {sorted(TERM_KEYS)} and matchLabels")
    return out


def term_count(kind: dict) -> int:
    """Inter-pod (anti-)affinity terms a pod of `kind` carries."""
    if kind["shape"] == "template":
        return len(template_terms(kind))
    return int(kind["shape"] == "antiaffinity")


def node_name(i: int) -> str:
    return f"node-{i}"


def build_cluster(store, cfg):
    """bench.py build_cluster: `nodes` Ready nodes, each with a hostname
    label and, where the configuration has them, one of
    `affinity_labels` aff-<k> labels; no zone label, as upstream's node
    templates have none. `node_labels` {key: [values]} gives node i
    values[i % len] under each key, as scheduler_perf's
    labelNodePrepareStrategy labels its nodes."""
    from kubernetes_tpu.api import types as api

    alloc = cfg["node_allocatable"]
    node_labels = cfg.get("node_labels", {})
    for i in range(cfg["nodes"]):
        labels = {HOST_LABEL: node_name(i)}
        if cfg["affinity_labels"]:
            labels[f"aff-{i % cfg['affinity_labels']}"] = "yes"
        for key, values in node_labels.items():
            labels[key] = values[i % len(values)]
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

    kind: np.ndarray  # int32 index into kinds(cfg)
    aff: np.ndarray  # int32 aff-<k> label an affinity pod requires, else -1
    group: np.ndarray  # int32 anti-affinity group, else -1

    def __len__(self):
        return len(self.kind)

    def like(self, i: int) -> tuple:
        """What pod i is: kind, label and group."""
        return int(self.kind[i]), int(self.aff[i]), int(self.group[i])

    def __getitem__(self, sel) -> "PodPlan":
        return PodPlan(self.kind[sel], self.aff[sel], self.group[sel])

    @staticmethod
    def concat(parts) -> "PodPlan":
        return PodPlan(*(np.concatenate([getattr(p, f) for p in parts])
                         for f in ("kind", "aff", "group")))


def _draw(cfg, mix: dict, n: int, rng) -> PodPlan:
    """n pods of `mix` (kind name: weight), interleaved in blocks of one
    cycle of the weights; then the aff-<k> label of each node-affinity
    pod and the group of each anti-affinity pod, stratified."""
    ks = kinds(cfg)
    names = [k["name"] for k in ks]
    if set(mix) - set(names):
        raise ValueError(f"mix {mix} names kinds not among {names}")
    weights = [int(mix.get(k, 0)) for k in names]
    cycle = np.repeat(np.arange(len(ks), dtype=np.int32), weights)
    blocks = -(-n // len(cycle))
    order = np.argsort(rng.random((blocks, len(cycle))), axis=1)
    kind = cycle[order].ravel()[:n]
    aff = np.full(n, -1, np.int32)
    group = np.full(n, -1, np.int32)
    for shape, arr, count in (("affinity", aff, cfg["affinity_labels"]),
                              ("antiaffinity", group, cfg["anti_groups"])):
        of = [j for j, k in enumerate(ks) if k["shape"] == shape]
        sel = np.flatnonzero(np.isin(kind, of))
        if len(sel):
            arr[sel] = _stratified(rng, len(sel), count)
    return PodPlan(kind=kind, aff=aff, group=group)


def plan_pods(cfg, n: int, seed: int, n_res: int = 0) -> PodPlan:
    """The plan of n pods, of which the first n_res are the running ones.
    With `resident_mix` those are drawn from it and the rest from `mix`,
    each from a stream of its own; without it all n are one draw from
    `mix`."""
    if "resident_mix" not in cfg:
        return _draw(cfg, cfg["mix"], n, np.random.default_rng([seed, 1]))
    return PodPlan.concat([
        _draw(cfg, cfg["resident_mix"], n_res,
              np.random.default_rng([seed, 1])),
        _draw(cfg, cfg["mix"], n - n_res, np.random.default_rng([seed, 5]))])


def refill_plan(plan: PodPlan, n_res: int, n: int) -> PodPlan:
    """n pods that repeat the running pods' plan entries (kind, label,
    group) in order: the pool from which an evicted pod is replaced by
    one like it."""
    if n and not n_res:
        raise ValueError("a refill pool needs running pods to repeat")
    return plan[np.arange(n) % max(n_res, 1)]


def resident_nodes(cfg, plan: PodPlan, n: int, seed: int) -> np.ndarray:
    """Nodes of the first n plan pods, the cluster's running pods at the
    start. `resident_placement` "drawn" (the default) draws them from the
    seed: the pods of an anti-affinity group on distinct nodes, a pod
    requiring aff-<a> on a node carrying that label, every other pod on
    any node. Capacity is not drawn around: at the configurations' counts
    (at most 6 pods a node on average, 40 fit) no node comes near it, and
    one past it would read as a violation. "even" puts pod i on node
    i mod nodes, where 1.11's LeastRequested puts identical pods on an
    empty uniform cluster; the run checks it at set-up. "reference"
    places them one at a time, in plan order, from the empty cluster,
    where the configuration's reference (1.11's filters, scores and
    selectHost) puts them: where upstream's scheduler puts its
    initPods."""
    placement = cfg.get("resident_placement", "drawn")
    nodes = cfg["nodes"]
    if placement == "even":
        return np.arange(n, dtype=np.int64) % nodes
    if placement == "reference":
        import check

        ref = check.reference(cfg["reference"])
        _, pod, node, _ = ref.greedy(ref.Cluster.from_config(cfg), plan,
                                     np.arange(n))
        if len(pod) != n:
            raise ValueError(f"the reference places {len(pod)} of the "
                             f"{n} running pods")
        out = np.empty(n, np.int64)
        out[pod] = node
        return out
    if placement != "drawn":
        raise ValueError(f"resident_placement {placement!r}")
    rng = np.random.default_rng([seed, 4])
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


def template_pod(api, name, kind: dict, requests):
    """A pod of a template kind: its labels, namespace and inter-pod
    terms, no owner (scheduler_perf creates its pods directly)."""
    from kubernetes_tpu.api.labels import LabelSelector

    def term(t):
        sel = t.get("labelSelector")
        return api.PodAffinityTerm(
            label_selector=(None if sel is None else LabelSelector(
                match_labels=dict(sel.get("matchLabels", {})))),
            namespaces=list(t.get("namespaces", [])),
            topology_key=t.get("topologyKey", ""))

    blocks = {"podAffinity": api.PodAffinity(),
              "podAntiAffinity": api.PodAntiAffinity()}
    for block, which, weight, t in template_terms(kind):
        b = blocks[block]
        if which == REQUIRED:
            b.required.append(term(t))
        else:
            b.preferred.append(api.WeightedPodAffinityTerm(
                weight=weight, pod_affinity_term=term(t)))
    aff = kind.get("affinity") or {}
    affinity = None
    if aff:
        affinity = api.Affinity(
            pod_affinity=blocks["podAffinity"] if "podAffinity" in aff
            else None,
            pod_anti_affinity=(blocks["podAntiAffinity"]
                               if "podAntiAffinity" in aff else None))
    return api.Pod(
        metadata=api.ObjectMeta(
            name=name, namespace=kind.get("namespace", "default"),
            labels=dict(kind.get("labels", {}))),
        spec=api.PodSpec(
            affinity=affinity, tolerations=[],
            containers=[api.Container(
                resources=api.ResourceRequirements(
                    requests=dict(requests)))]))


def make_pod(api, plan: PodPlan, i: int, kind: dict, requests,
             prefix: str = "pod"):
    """Pod i of the plan, of `kind` (one of kinds(cfg)), shaped as
    bench.py make_pods shapes its kind: plain (density); required node
    affinity on aff-<k> (scheduler_test.go); required hostname
    anti-affinity within its group (scheduler_bench_test.go); or as its
    template declares it (template). A kind with a priority sets
    spec.priority."""
    from kubernetes_tpu.api.labels import LabelSelector, Requirement

    shape = kind["shape"]
    tag = f"{kind['name']}-pod"
    name = f"{prefix}-{i}"
    if shape == "template":
        pod = template_pod(api, name, kind, requests)
    elif shape == "density":
        pod = base_pod(api, name, tag, requests)
    elif shape == "affinity":
        aff = api.Affinity(node_affinity=api.NodeAffinity(
            required=api.NodeSelector([api.NodeSelectorTerm(
                match_expressions=[Requirement(
                    f"aff-{plan.aff[i]}", "In", ("yes",))])])))
        pod = base_pod(api, name, tag, requests, affinity=aff)
    else:
        g = f"g{plan.group[i]}"
        aff = api.Affinity(pod_anti_affinity=api.PodAntiAffinity(
            required=[api.PodAffinityTerm(
                label_selector=LabelSelector(match_labels={"anti-group": g}),
                topology_key=HOST_LABEL)]))
        pod = base_pod(api, name, tag, requests,
                       labels={"type": tag, "anti-group": g}, affinity=aff)
    if kind.get("priority") is not None:
        pod.spec.priority = int(kind["priority"])
    return pod


def build_pods(cfg, plan: PodPlan, prefix: str = "pod") -> List[object]:
    from kubernetes_tpu.api import types as api

    ks = kinds(cfg)
    reqs = [api.resource_list(**(k["requests"] if "requests" in k
                                 else cfg["pod_requests"])) for k in ks]
    return [make_pod(api, plan, i, ks[k], reqs[k], prefix)
            for i, k in enumerate(plan.kind.tolist())]


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
