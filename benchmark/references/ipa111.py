"""Plain reference for kube-scheduler 1.11 with inter-pod affinity, as
the benchmark's configurations of template kinds exercise it. Imports
nothing of the program. The resource filters, LeastRequested,
BalancedResourceAllocation, node affinity on aff-<k>, the anti-affinity
groups and selectHost are k8s111.py's (loaded by path); this adds the
two inter-pod parts of the default provider (predicates.go
InterPodAffinityMatches, metadata path; priorities/interpod_affinity.go
CalculateInterPodAffinityPriority).

A term is a template kind's (loadgen.py): namespaces (empty: its owner's
own), matchLabels (a missing labelSelector matches nothing, an empty one
everything) and a topologyKey. Two nodes share a term's topology where
both carry its key with one value (NodesHaveSameTopologyKey). Node
labels are the generator's: the hostname, `node_labels` (node i takes
values[i % len]) and aff-<k>.

- InterPodAffinityMatches, on node n for a pod p:
  - no existing pod carries a required anti-affinity term that p
    matches while sharing the term's topology with n
    (satisfiesExistingPodsAntiAffinity);
  - p's required affinity term: some existing pod that matches it shares
    its topology with n; or no existing pod anywhere matches it and p
    matches it itself (the first pod of a self-affine series);
  - p's required anti-affinity term: no existing pod that matches it
    shares its topology with n.
  A kind carries at most one required term of each: 1.11's metadata
  path (each term met by some pod) and a walk over the pods (one pod
  meeting every term) read two differently, and no scheduler_perf
  template carries two.
- CalculateInterPodAffinityPriority, over the nodes that pass the
  filters: each node's count is the sum, over existing pods q that share
  a term's topology with it, of +weight for p's preferred affinity terms
  q matches, -weight for p's preferred anti-affinity terms q matches,
  +1 (hardPodAffinitySymmetricWeight, 1.11's default) for q's required
  affinity terms p matches, +weight and -weight for q's preferred
  affinity and anti-affinity terms p matches. With min and max over the
  filtered nodes' counts, starting at 0, the score is
  int(10 * ((count - min) / (max - min))) in float64, 0 where max = min.
  It is added at weight 1 to LeastRequested and BalancedResourceAllocation.

Pods of one kind share labels, namespace and terms, so the state is pods
of each kind per topology domain of each key: a pod costs O(K * N).
The anti-affinity shape's pods carry labels that differ by group; a
template term may not select on `anti-group`, and their group term is
k8s111's.

`replay` and `greedy` have k8s111's signatures and return its numbers;
a bind that breaks an inter-pod filter in the state it lands in counts
in `violations`. greedy's `frozen_counts` is a fault for the tests: the
priority's counts read the state at the start of each batch of that many
pods, the filters and the other scores the state as it is.
"""

from __future__ import annotations

import importlib.util
import sys
from dataclasses import dataclass
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
quantity, MAX = k8s111.quantity, k8s111.MAX
BIND, COMPLETE, EVICT, NOMINATE = (k8s111.BIND, k8s111.COMPLETE,
                                   k8s111.EVICT, k8s111.NOMINATE)
HOST = "kubernetes.io/hostname"
HARD_WEIGHT = 1  # hardPodAffinitySymmetricWeight, 1.11's default
GROUP_KEY = "anti-group"  # the anti-affinity shape's per-group label
SHAPES = ("density", "affinity", "antiaffinity")
AFF, ANTI = "podAffinity", "podAntiAffinity"
REQUIRED = "requiredDuringSchedulingIgnoredDuringExecution"
PREFERRED = "preferredDuringSchedulingIgnoredDuringExecution"


def _kinds(cfg) -> list:
    out = [dict(k) for k in cfg.get("kinds")
           or [{"name": s, "shape": s} for s in SHAPES]]
    for k in out:
        labels = k.get("labels", {}) if k["shape"] == "template" else {
            "type": f"{k['name']}-pod"}
        k["_labels"] = labels
        k["_ns"] = k.get("namespace", "default")
        k["_terms"] = []
        aff = k.get("affinity") or {}
        if set(aff) - {AFF, ANTI}:
            raise ValueError(f"kind {k['name']!r}: affinity {sorted(aff)}")
        for block in (AFF, ANTI):
            b = aff.get(block) or {}
            for t in b.get(REQUIRED, []):
                k["_terms"].append((block, True, 1, t))
            for wt in b.get(PREFERRED, []):
                k["_terms"].append((block, False, int(wt["weight"]),
                                    wt["podAffinityTerm"]))
        for block in (AFF, ANTI):
            if sum(b == block and req for b, req, _, _ in k["_terms"]) > 1:
                raise ValueError(f"kind {k['name']!r}: more than one "
                                 f"required {block} term")
        if GROUP_KEY in labels or any(
                GROUP_KEY in ((t.get("labelSelector") or {})
                              .get("matchLabels", {}))
                for _, _, _, t in k["_terms"]):
            raise ValueError(f"kind {k['name']!r}: {GROUP_KEY!r} is the "
                             "anti-affinity shape's label")
    return out


def _matches(owner: dict, term: dict, target: dict) -> bool:
    """PodMatchesTermsNamespaceAndSelector: target's namespace among the
    term's (else the owner's own), its labels meet matchLabels."""
    if target["_ns"] not in (term.get("namespaces") or [owner["_ns"]]):
        return False
    sel = term.get("labelSelector")
    if sel is None:
        return False
    return all(target["_labels"].get(key) == v
               for key, v in sel.get("matchLabels", {}).items())


def _node_values(cfg, key: str):
    """Each node's value under a label key, None where it has none."""
    n = cfg["nodes"]
    if key == HOST:
        return [f"node-{i}" for i in range(n)]
    values = cfg.get("node_labels", {}).get(key)
    if values is not None:
        return [values[i % len(values)] for i in range(n)]
    labels = cfg["affinity_labels"]
    if labels and key.startswith("aff-") and key[4:].isdigit():
        return ["yes" if i % labels == int(key[4:]) else None
                for i in range(n)]
    return [None] * n


@dataclass
class Cluster(k8s111.Cluster):
    """k8s111's node and pod facts, and the inter-pod terms as tables over
    (incoming kind, topology key, existing kind) [K, T, K]: each entry
    applies to the existing pods of that kind in the node's domain of
    that key."""

    dom: np.ndarray  # [T, N] domain of each node under each key, -1 none
    n_dom: np.ndarray  # [T] domains of each key
    block: np.ndarray  # [K, T, K] bool: such a pod blocks the node
    need: np.ndarray  # [K, T, K] bool: such a pod meets the required affinity
    need_any: np.ndarray  # [K] bool: the kind carries a required affinity term
    self_ok: np.ndarray  # [K] bool: ... and matches it itself
    pref_own: np.ndarray  # [K, T, K] int64: the incoming pod's preferred terms
    pref_sym: np.ndarray  # [K, T, K] int64: the existing pods' terms

    @classmethod
    def from_config(cls, cfg):
        base = k8s111.Cluster.from_config(cfg)
        ks = _kinds(cfg)
        keys = [HOST]
        for k in ks:
            for _, _, _, t in k["_terms"]:
                if t.get("topologyKey", "") not in keys:
                    keys.append(t.get("topologyKey", ""))
        dom = np.full((len(keys), cfg["nodes"]), -1, np.int64)
        for i, key in enumerate(keys):
            ids = {}
            for c, v in enumerate(_node_values(cfg, key) if key else []):
                if v is not None:
                    dom[i, c] = ids.setdefault(v, len(ids))
        shape = (len(ks), len(keys), len(ks))
        block = np.zeros(shape, bool)
        need = np.zeros(shape, bool)
        pref_own = np.zeros(shape, np.int64)
        pref_sym = np.zeros(shape, np.int64)
        need_any = np.zeros(len(ks), bool)
        self_ok = np.zeros(len(ks), bool)
        for a, p in enumerate(ks):  # the incoming pod's kind
            for block_of, req, w, t in p["_terms"]:
                tk = keys.index(t.get("topologyKey", ""))
                if req and block_of == AFF:
                    need_any[a] = True
                    self_ok[a] = _matches(p, t, p)
                for b, q in enumerate(ks):
                    if not _matches(p, t, q):
                        continue
                    if req:
                        (need if block_of == AFF else block)[a, tk, b] = True
                    else:
                        pref_own[a, tk, b] += w if block_of == AFF else -w
            for b, q in enumerate(ks):  # an existing pod's kind
                for block_of, req, w, t in q["_terms"]:
                    if not _matches(q, t, p):
                        continue
                    tk = keys.index(t.get("topologyKey", ""))
                    if req and block_of == ANTI:
                        block[a, tk, b] = True
                    elif req:
                        pref_sym[a, tk, b] += HARD_WEIGHT
                    else:
                        pref_sym[a, tk, b] += w if block_of == AFF else -w
        return cls(**vars(base), dom=dom,
                   n_dom=np.asarray([max(d.max() + 1, 0) for d in dom]),
                   block=block, need=need, need_any=need_any,
                   self_ok=self_ok, pref_own=pref_own, pref_sym=pref_sym)


class State(k8s111.State):
    """k8s111's per-node sums, and the pods of each kind per domain of
    each topology key, and in all."""

    def __init__(self, cl: Cluster):
        super().__init__(cl)
        n_kinds = len(cl.kind_cpu)
        self.near = [np.zeros((n_kinds, d), np.int64) for d in cl.n_dom]
        self.total = np.zeros(n_kinds, np.int64)

    def place(self, node, kind, group, d=1):
        super().place(node, kind, group, d)
        self.total[kind] += d
        for t, x in enumerate(self.cl.dom[:, node]):
            if x >= 0:
                self.near[t][kind, x] += d

    def copy(self) -> "State":
        out = super().copy()
        out.__class__ = State
        out.near = [x.copy() for x in self.near]
        out.total = self.total.copy()
        return out


def near(cl: Cluster, st: State, table: np.ndarray, at=slice(None)):
    """sum over keys t and kinds j of table[t, j] times the pods of kind j
    in each node's domain of t, for the nodes `at`."""
    at_dom = cl.dom[:, at]
    out = np.zeros(at_dom.shape[1:], table.dtype)
    for t in np.flatnonzero(table.any(axis=1) & (cl.n_dom > 0)):
        d = at_dom[t]
        per = table[t].astype(np.int64) @ st.near[t]
        out = out + np.where(d >= 0, per[np.maximum(d, 0)], 0)
    return out


def ipa_fits(cl: Cluster, st: State, kind: int, at=slice(None)):
    """InterPodAffinityMatches for a pod of `kind` on the nodes `at`."""
    ok = near(cl, st, cl.block[kind], at) == 0
    if cl.need_any[kind]:
        met = near(cl, st, cl.need[kind], at) > 0
        anywhere = int(cl.need[kind].any(axis=0) @ st.total)
        if not (anywhere == 0 and cl.self_ok[kind]):
            ok &= met
    return ok


def ipa_scores(cl: Cluster, st: State, kind: int, ok: np.ndarray):
    """CalculateInterPodAffinityPriority for a pod of `kind` on every
    node, normalised over the feasible ones (ok)."""
    table = cl.pref_own[kind] + cl.pref_sym[kind]
    count = near(cl, st, table)
    lo = min(int(count[ok].min()), 0)
    hi = max(int(count[ok].max()), 0)
    if hi == lo:
        return np.zeros(len(count), np.int64)
    return (float(MAX) * ((count - lo) / float(hi - lo))).astype(np.int64)


def choose(cl: Cluster, st: State, kind: int, aff: int, group: int, rr: int,
           first_tie: bool = False, seen: State = None):
    """The node 1.11 chooses for a pod in state `st` (-1: none fits), the
    feasible mask and the scores. seen: the state the priority's counts
    read (the fault of greedy's frozen_counts), else st."""
    ok = k8s111.feasible_nodes(cl, st, kind, aff, group) \
        & ipa_fits(cl, st, kind)
    if not ok.any():
        return -1, ok, k8s111.scores(cl, st, kind)
    tot = k8s111.scores(cl, st, kind) + ipa_scores(
        cl, st if seen is None else seen, kind, ok)
    ties = np.flatnonzero(ok & (tot == tot[ok].max()))
    return int(ties[0 if first_tie else rr % len(ties)]), ok, tot


def replay(cl: Cluster, plan, op, pod, node, made, sample) -> dict:
    """k8s111.replay with the inter-pod filters and priority: violations
    also counts binds that break InterPodAffinityMatches in the state
    they land in."""
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
        fits = bool(ipa_fits(cl, st, k, [c])[0])
        st.place(c, k, grp)
        violations += (st.over(c) + (aff >= 0 and cl.aff_label[c] != aff)
                       + (grp >= 0 and st.anti[grp, c] > 1) + (p in bound)
                       + (not fits))
        bound.add(p)
    return {"violations": int(violations), "mismatches": int(mismatches),
            "not_best": int(not_best), "checked": checked,
            "gap_max": gap_max}


def greedy(cl: Cluster, plan, order, resident=None, keep=None,
           batch: int = 1, first_tie=False, frozen_counts: int = 0):
    """The reference put in the scheduler's place, as k8s111.greedy:
    place plan pods in `order`, ties broken round-robin as 1.11 breaks
    them (first_tie: the first max-score node). `resident` (pods, nodes)
    are bound at the start; after every `batch` pods, the oldest bound
    pods complete until `keep` remain. frozen_counts: the fault above.
    Returns the event log (op, pod, node, made)."""
    from collections import deque

    st = State(cl)
    out = []
    live = deque()
    for p, c in zip(*(resident or ((), ()))):
        st.place(c, plan.kind[p], plan.group[p])
        out.append((BIND, p, c, 0))
        live.append((p, c))
    rr = 0
    seen = None
    for j, p in enumerate(order):
        if frozen_counts and j % frozen_counts == 0:
            seen = st.copy()
        c, _, _ = choose(cl, st, plan.kind[p], plan.aff[p], plan.group[p],
                         rr, first_tie, seen)
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

