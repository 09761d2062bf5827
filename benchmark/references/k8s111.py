"""Plain reference for the kube-scheduler 1.11 default provider, as the
benchmark's configurations exercise it. Imports nothing of the program.

Semantics (algorithmprovider/defaults/defaults.go, 1.11):

- Filters: PodFitsResources (requests + the pod <= allocatable for cpu
  and memory, pod count + 1 <= allowed pods), required node affinity
  (In on an aff-<k> label), required pod anti-affinity on the hostname
  (a pod of the group on the node blocks the node, both ways).
- Scores, weight 1 each, in Go's own arithmetic: LeastRequested
  (least_requested.go: int64 ((capacity - requested) * 10) / capacity,
  cpu and memory averaged by int64 division) and
  BalancedResourceAllocation (balanced_resource_allocation.go: float64
  fractions, int64((1 - |cpu - memory|) * 10), 0 where either fraction
  reaches 1). The other default priorities (SelectorSpread,
  InterPodAffinity, NodeAffinity preferred, TaintToleration,
  ImageLocality, NodePreferAvoidPods) are constant over the nodes for
  these pods: no service or controller object selects them, and they
  carry no preferred terms, tolerations or images; so they cannot move
  an argmax or a gap and are left out.
- Order: pods are placed one at a time in the order the scheduler bound
  them, each against every earlier bind and completion. Nothing here
  depends on how the program batches its work.
- Choice: among the feasible nodes of the highest score, in node order,
  the one at lastNodeIndex modulo their number, lastNodeIndex counting
  the pods scheduled before (1.11 selectHost, generic_scheduler.go). The
  program breaks ties the same way (its round-robin counter), so a
  comparison can be exact.

- Kinds: a pod requests what its kind requests (the configuration's
  `kinds`, else `pod_requests` for every pod). Priority is read by kind
  too (`Cluster.kind_prio`, 0 where unset, as 1.11's GetPodPriority);
  nothing here depends on it: the filters and scores above do not read
  it, and a replay of a run that preempts is a later reference's.

`replay` walks the event log of a run (binds, the deletions of pods that
completed or were evicted, nominations), checks every bind against the
filters in the state it lands in, and for a sample of binds computes,
from the state the pod was placed into, the node the reference chooses,
the best score of any feasible node and the score of the node it got.
`greedy` is the same reference put in the scheduler's place; with
first_tie it is the control.
"""

from __future__ import annotations

import re
from dataclasses import dataclass

import numpy as np

MAX = 10  # schedulerapi.MaxPriority
# the event log's op codes (benchmark/run.py EventLog)
BIND, COMPLETE, EVICT, NOMINATE = 1, -1, -2, 2
# kinds of a configuration that declares none: one per shape
DEFAULT_KINDS = 3
_UNITS = {"": 1, "m": 1e-3, "k": 1e3, "M": 1e6, "G": 1e9,
          "Ki": 2 ** 10, "Mi": 2 ** 20, "Gi": 2 ** 30, "Ti": 2 ** 40}


def quantity(q) -> float:
    """A Kubernetes quantity ("100m", "32Gi", 110) as a number."""
    if isinstance(q, (int, float)):
        return float(q)
    m = re.fullmatch(r"([0-9.]+)([A-Za-z]*)", str(q))
    if m is None or m.group(2) not in _UNITS:
        raise ValueError(f"quantity {q!r}")
    return float(m.group(1)) * _UNITS[m.group(2)]


@dataclass
class Cluster:
    """Node and pod facts of a configuration, from the benchmark's own
    generation: cpu in millicores, memory in bytes, both integers as Go
    holds them; pod requests and priority by kind (the plan's index)."""

    alloc_cpu: int
    alloc_mem: int
    alloc_pods: int
    aff_label: np.ndarray  # [N] the aff-<k> label a node carries, -1 none
    kind_cpu: np.ndarray  # [K] int64 cpu request of each kind
    kind_mem: np.ndarray  # [K] int64 memory request of each kind
    kind_prio: np.ndarray  # [K] int64 priority of each kind
    groups: int

    @classmethod
    def from_config(cls, cfg):
        n = cfg["nodes"]
        a = cfg["node_allocatable"]
        kinds = cfg.get("kinds") or [{}] * DEFAULT_KINDS
        reqs = [k["requests"] if "requests" in k else cfg["pod_requests"]
                for k in kinds]
        return cls(
            alloc_cpu=round(quantity(a["cpu"]) * 1000),
            alloc_mem=round(quantity(a["memory"])),
            alloc_pods=round(quantity(a["pods"])),
            aff_label=(np.arange(n) % cfg["affinity_labels"]
                       if cfg["affinity_labels"] else np.full(n, -1)),
            kind_cpu=np.asarray([round(quantity(r["cpu"]) * 1000)
                                 for r in reqs], np.int64),
            kind_mem=np.asarray([round(quantity(r["memory"])) for r in reqs],
                                np.int64),
            kind_prio=np.asarray([k.get("priority") or 0 for k in kinds],
                                 np.int64),
            groups=max(cfg["anti_groups"], 1))


def scores(cl: Cluster, st: "State", kind: int) -> np.ndarray:
    """LeastRequested + BalancedResourceAllocation of a pod of `kind`
    onto every node in state `st`, as 1.11 computes them."""
    cpu = st.cpu + cl.kind_cpu[kind]  # int64: requested, the pod included
    mem = st.mem + cl.kind_mem[kind]

    def least(req, cap):
        return np.where(req > cap, 0, (cap - req) * MAX // cap)

    lr = (least(cpu, cl.alloc_cpu) + least(mem, cl.alloc_mem)) // 2
    fc = cpu.astype(np.float64) / float(cl.alloc_cpu)
    fm = mem.astype(np.float64) / float(cl.alloc_mem)
    ba = np.where((fc >= 1) | (fm >= 1), 0,
                  np.trunc((1.0 - np.abs(fc - fm)) * MAX)).astype(np.int64)
    return lr + ba


def feasible_nodes(cl: Cluster, st: "State", kind: int, aff: int,
                   group: int):
    ok = (st.cpu + cl.kind_cpu[kind] <= cl.alloc_cpu) \
        & (st.mem + cl.kind_mem[kind] <= cl.alloc_mem) \
        & (st.cnt + 1 <= cl.alloc_pods)
    if aff >= 0:
        ok &= cl.aff_label == aff
    if group >= 0:
        ok &= st.anti[group] == 0
    return ok


class State:
    """Per node: pods, cpu and memory requested, pods of each group."""

    def __init__(self, cl: Cluster):
        n = len(cl.aff_label)
        self.cl = cl
        self.cnt = np.zeros(n, np.int64)
        self.cpu = np.zeros(n, np.int64)
        self.mem = np.zeros(n, np.int64)
        self.anti = np.zeros((cl.groups, n), np.int64)

    def place(self, node, kind, group, d=1):
        self.cnt[node] += d
        self.cpu[node] += d * self.cl.kind_cpu[kind]
        self.mem[node] += d * self.cl.kind_mem[kind]
        if group >= 0:
            self.anti[group, node] += d

    def over(self, node) -> bool:
        """The node holds more than it allows."""
        cl = self.cl
        return bool(self.cnt[node] > cl.alloc_pods
                    or self.cpu[node] > cl.alloc_cpu
                    or self.mem[node] > cl.alloc_mem)

    def copy(self) -> "State":
        out = State.__new__(State)
        out.cl = self.cl
        out.cnt, out.cpu, out.mem = (self.cnt.copy(), self.cpu.copy(),
                                     self.mem.copy())
        out.anti = self.anti.copy()
        return out


def choose(cl: Cluster, st: State, kind: int, aff: int, group: int, rr: int,
           first_tie: bool = False):
    """The node 1.11 chooses for a pod in state `st` (-1: none fits),
    the feasible mask and the scores."""
    ok = feasible_nodes(cl, st, kind, aff, group)
    tot = scores(cl, st, kind)
    if not ok.any():
        return -1, ok, tot
    ties = np.flatnonzero(ok & (tot == tot[ok].max()))
    return int(ties[0 if first_tie else rr % len(ties)]), ok, tot


def replay(cl: Cluster, plan, op, pod, node, made, sample) -> dict:
    """The event log of a run, in the order the store applied it: op
    BIND a bind (pod, node), COMPLETE or EVICT the deletion of a bound
    pod, NOMINATE a nomination (skipped here: 1.11's binds and scores in
    these configurations do not read it); pod is the plan index; made
    marks the binds the scheduler made (not the running pods bound at
    set-up). sample: bool per event, the binds compared with the
    reference's own choice, where lastNodeIndex counts the binds the
    scheduler made before. Returns violations (binds that break a
    filter: over capacity, node affinity or anti-affinity; a pod bound
    twice), mismatches (sampled binds not on the reference's node),
    not_best (sampled binds below the best feasible score, or onto an
    infeasible node), checked (sampled binds) and gap_max (the widest
    score gap among them)."""
    st = State(cl)
    violations = mismatches = not_best = checked = 0
    gap_max = 0
    bound = set()
    last_node_index = 0
    for j in range(len(pod)):
        if op[j] == NOMINATE:
            continue
        p, c = pod[j], node[j]
        k, aff, grp = plan.kind[p], plan.aff[p], plan.group[p]
        if op[j] < 0:
            st.place(c, k, grp, -1)
            continue
        if sample[j]:
            checked += 1
            want, ok, tot = choose(cl, st, k, aff, grp, last_node_index)
            mismatches += c != want
            if not ok[c]:
                not_best += 1
            else:
                gap = int(tot[ok].max() - tot[c])
                gap_max = max(gap_max, gap)
                not_best += gap > 0
        if made[j]:
            last_node_index += 1
        st.place(c, k, grp)
        violations += (st.over(c) + (aff >= 0 and cl.aff_label[c] != aff)
                       + (grp >= 0 and st.anti[grp, c] > 1) + (p in bound))
        bound.add(p)
    return {"violations": int(violations), "mismatches": int(mismatches),
            "not_best": int(not_best), "checked": checked,
            "gap_max": gap_max}


def greedy(cl: Cluster, plan, order, resident=None, keep=None,
           batch: int = 1, batched=False, first_tie=False):
    """The reference put in the scheduler's place: place plan pods in
    `order`, ties broken round-robin as 1.11 breaks them. `resident`
    (pods, nodes) are bound at the start; after every `batch` pods, the
    oldest bound pods complete until `keep` remain. batched: filter and
    score every pod of a batch against the state at the batch's start
    (no commit between its pods). first_tie: take the first max-score
    node (a plain argmax) instead of the round-robin choice. Returns the
    event log (op, pod, node, made)."""
    from collections import deque

    st = State(cl)
    out = []
    live = deque()
    for p, c in zip(*(resident or ((), ()))):
        st.place(c, plan.kind[p], plan.group[p])
        out.append((BIND, p, c, 0))
        live.append((p, c))
    rr = 0
    seen = st
    for j, p in enumerate(order):
        if batched and j % batch == 0:
            seen = st.copy()
        c, _, _ = choose(cl, seen, plan.kind[p], plan.aff[p], plan.group[p],
                         rr, first_tie)
        if c >= 0:
            rr += 1
            st.place(c, plan.kind[p], plan.group[p])
            out.append((BIND, p, c, 1))
            live.append((p, c))
        if (j + 1) % batch == 0:
            while keep is not None and len(live) > keep:
                q, d = live.popleft()
                st.place(d, plan.kind[q], plan.group[q], -1)
                out.append((COMPLETE, q, d, 0))
    arr = np.asarray(out, np.int64).reshape(-1, 4)
    return arr[:, 0], arr[:, 1], arr[:, 2], arr[:, 3].astype(bool)
