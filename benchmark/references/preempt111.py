"""Plain reference for kube-scheduler 1.11 with pod priority and
preemption, as the benchmark's preemption configurations exercise it.
Imports nothing of the program. The filters, the scores and selectHost
are k8s111.py's (loaded by path); this adds what 1.11 does with
priorities (generic_scheduler.go, scheduler.go).

- Nominated pods. A pod nominated to a node (status.nominatedNodeName)
  holds room there until it binds, is deleted or is nominated again (the
  scheduler deletes the nomination when it assumes the pod).
  podFitsOnNode adds to a node every pod nominated there with priority
  >= the pod's own, other than the pod itself (addNominatedPods), and
  runs the filters on that; where it added any, the pod must also fit
  without them, which the resource and anti-affinity filters here always
  do once they fit with them. Scores read no nomination.
- Binds. A sampled bind is compared with the node 1.11 chooses: of the
  feasible nodes, nominations counted, those of the highest score, in
  node order, the one at lastNodeIndex modulo their number (selectHost).
- Nominations. Every NOMINATE of a node is judged, none sampled, in the
  state it was made in with the nominations then in force (Preempt).
  selectVictimsOnNode, on every node: take off the pods of lower
  priority; the node is a candidate where the preemptor then fits,
  nominations counted; the pods taken off come back one at a time,
  highest priority first, each where the preemptor still fits, and the
  rest are the node's victims. pickOneNodeForPreemption: a node with no
  victims where there is one; else the fewest PodDisruptionBudget
  violations (no configuration has a budget: 0 everywhere), the lowest
  highest victim priority, the lowest sum of victim priorities (each
  plus 2^31, as 1.11 sums them), the fewest victims. Go's map order
  makes a full tie arbitrary, so a nomination is right where its node is
  among the nodes tied best, and the EVICTs that follow it are that
  node's victims in number, each of lower priority than the preemptor.
- Evictions. An EVICT is justified by a nomination in force on its node
  of a pod of higher priority than the evicted one.

Pods of one kind are alike, so the reprieve is counted per kind: of a
kind's pods taken off a node, as many come back as leave the preemptor
room, less those of the preemptor's anti-affinity group, which never
can. Kinds of one priority with different requests would leave the
victim count to the reprieve order (1.11 sorts with sort.Slice, which
is not stable): such a configuration is refused.

`replay` walks the event log as k8s111.replay does and returns the same
numbers; `mismatches` counts sampled binds off 1.11's node and
nominations that fail the judgement above, `violations` also counts
evictions no nomination justifies.
"""

from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

import numpy as np


def _load(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_reference_{name}", Path(__file__).with_name(f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


k8s111 = _load("k8s111")
Cluster, quantity, scores = k8s111.Cluster, k8s111.quantity, k8s111.scores
BIND, COMPLETE, EVICT, NOMINATE = (k8s111.BIND, k8s111.COMPLETE,
                                   k8s111.EVICT, k8s111.NOMINATE)
PRIO_OFFSET = 2 ** 31  # added to each victim's priority in the sum


class State(k8s111.State):
    """k8s111's per-node sums, and per node the pods of each kind, and of
    each kind in each anti-affinity group."""

    def __init__(self, cl: Cluster):
        super().__init__(cl)
        shape = (len(cl.kind_cpu), len(cl.aff_label))
        self.kind = np.zeros(shape, np.int64)
        self.kind_anti = np.zeros((shape[0], cl.groups, shape[1]), np.int64)

    def place(self, node, kind, group, d=1):
        super().place(node, kind, group, d)
        self.kind[kind, node] += d
        if group >= 0:
            self.kind_anti[kind, group, node] += d


class Nominations:
    """The nominations in force: each pod's (node, kind, group), and per
    node the nominated pods of each kind, and of each kind in each
    anti-affinity group."""

    def __init__(self, cl: Cluster):
        self.cl = cl
        self.of = {}
        shape = (len(cl.kind_cpu), len(cl.aff_label))
        self.kind = np.zeros(shape, np.int64)
        self.kind_anti = np.zeros((shape[0], cl.groups, shape[1]), np.int64)

    def _add(self, x, d):
        node, kind, group = x
        self.kind[kind, node] += d
        if group >= 0:
            self.kind_anti[kind, group, node] += d

    def set(self, pod, node, kind, group):
        self.drop(pod)
        self.of[pod] = (node, kind, group)
        self._add(self.of[pod], 1)

    def drop(self, pod):
        x = self.of.pop(pod, None)
        if x is not None:
            self._add(x, -1)

    def held(self, pod, prio):
        """What the pods nominated to each node with priority >= prio,
        other than `pod`, add there: pods, cpu, memory [N] and pods of
        each anti-affinity group [G, N]."""
        cl = self.cl
        sel = (cl.kind_prio >= prio).astype(np.int64)
        k = self.kind * sel[:, None]
        anti = self.kind_anti * sel[:, None, None]
        own = self.of.get(pod)
        if own is not None and sel[own[1]]:
            node, kind, group = own
            k[kind, node] -= 1
            if group >= 0:
                anti[kind, group, node] -= 1
        return k.sum(0), cl.kind_cpu @ k, cl.kind_mem @ k, anti.sum(0)

    def justifies(self, node, prio) -> bool:
        """A pod of priority above `prio` is nominated to `node`."""
        above = self.cl.kind_prio > prio
        return bool(self.kind[above, node].sum() > 0)


def reprieve_order(cl: Cluster) -> np.ndarray:
    """Kinds in the order their pods come back: priority descending.
    Refuses kinds of one priority with different requests."""
    order = np.lexsort((np.arange(len(cl.kind_prio)), -cl.kind_prio))
    for a, b in zip(order[:-1], order[1:]):
        if cl.kind_prio[a] == cl.kind_prio[b] and (
                cl.kind_cpu[a] != cl.kind_cpu[b]
                or cl.kind_mem[a] != cl.kind_mem[b]):
            raise ValueError(f"kinds {a} and {b} share priority "
                             f"{cl.kind_prio[a]} with different requests: "
                             "the victim count would depend on the order")
    return order


def feasible(cl: Cluster, st: State, nom: Nominations, pod: int, kind: int,
             aff: int, group: int) -> np.ndarray:
    """podFitsOnNode with the nominated pods added, on every node."""
    cnt, cpu, mem, anti = nom.held(pod, cl.kind_prio[kind])
    ok = (st.cpu + cpu + cl.kind_cpu[kind] <= cl.alloc_cpu) \
        & (st.mem + mem + cl.kind_mem[kind] <= cl.alloc_mem) \
        & (st.cnt + cnt + 1 <= cl.alloc_pods)
    if aff >= 0:
        ok &= cl.aff_label == aff
    if group >= 0:
        ok &= st.anti[group] + anti[group] == 0
    return ok


def choose(cl: Cluster, st: State, nom: Nominations, pod: int, kind: int,
           aff: int, group: int, rr: int):
    """The node 1.11 chooses (-1: none fits), the feasible mask and the
    scores."""
    ok = feasible(cl, st, nom, pod, kind, aff, group)
    tot = scores(cl, st, kind)
    if not ok.any():
        return -1, ok, tot
    ties = np.flatnonzero(ok & (tot == tot[ok].max()))
    return int(ties[rr % len(ties)]), ok, tot


def select_victims(cl: Cluster, st: State, nom: Nominations, order,
                   pod: int, kind: int, aff: int, group: int):
    """selectVictimsOnNode on every node for pod `pod` of `kind`. Returns
    the candidate mask and, per node, the victims' number, highest
    priority (-1 where none) and priority sum (each plus PRIO_OFFSET)."""
    prio = cl.kind_prio[kind]
    n_cnt, n_cpu, n_mem, n_anti = nom.held(pod, prio)
    lower = (cl.kind_prio < prio).astype(np.int64)
    off = st.kind * lower[:, None]  # [K, N] pods taken off
    free_cpu = cl.alloc_cpu - (st.cpu - cl.kind_cpu @ off + n_cpu) \
        - cl.kind_cpu[kind]
    free_mem = cl.alloc_mem - (st.mem - cl.kind_mem @ off + n_mem) \
        - cl.kind_mem[kind]
    free_pods = cl.alloc_pods - (st.cnt - off.sum(0) + n_cnt) - 1
    ok = (free_cpu >= 0) & (free_mem >= 0) & (free_pods >= 0)
    if aff >= 0:
        ok &= cl.aff_label == aff
    blocked = np.zeros_like(off)  # pods that would block the preemptor
    if group >= 0:
        mine = st.kind_anti[:, group]
        ok &= st.anti[group] - (mine * lower[:, None]).sum(0) \
            + n_anti[group] == 0
        blocked = mine
    n = np.zeros(len(ok), np.int64)
    vmax = np.full(len(ok), -1, np.int64)
    vsum = np.zeros(len(ok), np.int64)
    big = np.iinfo(np.int64).max
    for k in order:
        if not lower[k]:
            continue
        room = np.minimum(
            free_pods,
            np.minimum(free_cpu // cl.kind_cpu[k] if cl.kind_cpu[k] else big,
                       free_mem // cl.kind_mem[k] if cl.kind_mem[k] else big))
        back = np.clip(np.minimum(off[k] - blocked[k], room), 0, None)
        back = np.where(ok, back, 0)
        free_cpu = free_cpu - back * cl.kind_cpu[k]
        free_mem = free_mem - back * cl.kind_mem[k]
        free_pods = free_pods - back
        v = off[k] - back
        n += v
        vsum += v * (cl.kind_prio[k] + PRIO_OFFSET)
        vmax = np.where(v > 0, np.maximum(vmax, cl.kind_prio[k]), vmax)
    return ok, n, vmax, vsum


def best_nodes(ok, n, vmax, vsum) -> np.ndarray:
    """pickOneNodeForPreemption's nodes, every full tie kept."""
    if not ok.any():
        return ok
    none = ok & (n == 0)
    if none.any():
        return none
    best = ok
    for key in (vmax, vsum, n):
        best = best & (key == key[best].min())
    return best


def replay(cl: Cluster, plan, op, pod, node, made, sample) -> dict:
    """The event log of a run, in the order the store applied it (op BIND
    a bind, COMPLETE or EVICT the deletion of a bound pod, NOMINATE a
    nomination, node -1 where one is cleared; pod the plan index; made
    the binds the scheduler made; sample the binds compared with the
    reference's own choice). Returns violations, mismatches, not_best,
    checked and gap_max as k8s111.replay does, and nominations: the
    NOMINATEs judged."""
    order = reprieve_order(cl)
    st = State(cl)
    nom = Nominations(cl)
    prio_of = cl.kind_prio
    violations = mismatches = not_best = checked = judged = 0
    gap_max = 0
    bound = set()
    last_node_index = 0
    for j in range(len(pod)):
        p, c = pod[j], node[j]
        k, aff, grp = plan.kind[p], plan.aff[p], plan.group[p]
        if op[j] == NOMINATE:
            if c < 0:
                nom.drop(p)
                continue
            judged += 1
            ok, n, vmax, vsum = select_victims(cl, st, nom, order, p, k,
                                               aff, grp)
            best = best_nodes(ok, n, vmax, vsum)
            e = j + 1
            while e < len(op) and op[e] == EVICT:
                e += 1
            evicted = pod[j + 1:e]
            right = (bool(best[c]) and len(evicted) == n[c]
                     and bool(np.all(node[j + 1:e] == c))
                     and bool(np.all(prio_of[plan.kind[evicted]]
                                     < prio_of[k])))
            mismatches += not right
            nom.set(p, c, k, grp)
            continue
        if op[j] < 0:
            if op[j] == EVICT and not nom.justifies(c, prio_of[k]):
                violations += 1
            st.place(c, k, grp, -1)
            nom.drop(p)
            continue
        if sample[j]:
            checked += 1
            want, ok, tot = choose(cl, st, nom, p, k, aff, grp,
                                   last_node_index)
            mismatches += c != want
            if not ok[c]:
                not_best += 1
            else:
                gap = int(tot[ok].max() - tot[c])
                gap_max = max(gap_max, gap)
                not_best += gap > 0
        if made[j]:
            last_node_index += 1
        nom.drop(p)
        st.place(c, k, grp)
        violations += (st.over(c) + (aff >= 0 and cl.aff_label[c] != aff)
                       + (grp >= 0 and st.anti[grp, c] > 1) + (p in bound))
        bound.add(p)
    return {"violations": int(violations), "mismatches": int(mismatches),
            "not_best": int(not_best), "checked": checked,
            "gap_max": gap_max, "nominations": judged}
