"""Batched inter-pod affinity/anti-affinity kernels.

Reproduces the reference's InterPodAffinityMatches predicate
(pkg/scheduler/algorithm/predicates/predicates.go:1115, metadata path)
and CalculateInterPodAffinityPriority
(pkg/scheduler/algorithm/priorities/interpod_affinity.go:118) as dense
computations — SURVEY.md §7 hard part (a), and the quadratic pod×pod
term the reference parallelizes across 16 goroutines
(metadata.go getMatchingAntiAffinityTerms).

Dense shape of the problem:

  * Existing pods' terms live in a TermTable (one row per term, E rows).
    An [P, E] "entry matches incoming pod" matrix times an [E, N]
    "entry's topology domain contains node" matrix — an MXU matmul —
    yields both the anti-affinity symmetry mask and the existing-pod
    side of the priority in one contraction.
  * The incoming pod's required terms collapse to one combined AND
    program (metadata semantics match ALL term properties at once) with
    a single shared topology key; satisfaction is anchored through the
    node axis, then the label-value vocabulary. A pod's domain is a
    function of its node, so matching pods are first counted per node
    by one segment-sum keyed by each pod's node, shared by every program
    ([U, M] -> [U, N]). The nodes then reach their domains through MXU
    contractions (`anchor`): per topology key in the tables, a
    node-to-domain one-hot O [N, LV] takes the node counts to per-domain
    sums ([U, N] @ O -> [U, LV]) and those back to each node's own
    domain ([U, LV] @ O.T -> [U, N]). Operands are bf16 0/1 or base-256
    digits of the counts, accumulated in f32, so every sum is exact.
    Pods whose required terms span >1 topology key take the exact host
    path (plugins/golden.py) instead.
  * Wave-internal visibility (a pod must see placements made earlier in
    the same wave, like the reference's one-at-a-time assume) is handled
    in the commit scan in ops/kernel.py using [P, P] cross-match
    matrices computed here.

This plane is twinned in numpy (ops/hostwave.py incoming_statics_host +
schedule_wave_host's has_ipa step logic, bitwise parity asserted in
tests/test_hostwave.py TestInterPodAffinityTwin), so breaker-open and
mesh-reform-salvage rounds place affinity pods batched instead of
draining them through the per-pod golden path.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from . import encoding as enc
from .encoding import NodeTensors, PodBatch, PodMatrix, TermTable
from .selectors import eval_and_program


def ns_match(ns_sets, ns_ids):
    """bool [..., X]: is ns_ids[x] in ns_sets[...]?
    ns_sets: i32 [..., TNS] (0 pad — an all-pad set matches nothing);
    ns_ids:  i32 [X]."""
    expanded = ns_sets[..., :, None]  # [..., TNS, 1]
    ids = ns_ids.reshape((1,) * (ns_sets.ndim - 1) + (1, -1))  # [...1, 1, X]
    return jnp.any((expanded == ids) & (expanded > 0), axis=-2)


def _eval_programs(label_matrix, key, op, vals):
    """Evaluate AND programs (no numeric ops) against a label matrix.
    key/op: [..., E]; vals: [..., E, V]; label_matrix [X, K] -> bool [..., X]."""
    num = jnp.full(key.shape, jnp.nan, jnp.float32)
    ids = jnp.arange(label_matrix.shape[0], dtype=jnp.int32)
    return eval_and_program(label_matrix, None, key, op, vals, num, ids)


def term_entry_match(tt: TermTable, pb: PodBatch) -> jnp.ndarray:
    """bool [P, E] — does TermTable entry e's (namespaces, selector) match
    incoming pod p? (predicates.go PodMatchesTermsNamespaceAndSelector,
    with the term owner's default namespace already baked into tt.ns)."""
    sel = _eval_programs(pb.pl_val, tt.key, tt.op, tt.vals)  # [E, P]
    nsm = ns_match(tt.ns, pb.ns_id)  # [E, P]
    return (sel & nsm & tt.valid[:, None]).T


def same_domain(tt: TermTable, nt: NodeTensors) -> jnp.ndarray:
    """bool [E, N] — is node n in the same topology domain as entry e's
    owner node under e's topology key? (NodesHaveSameTopologyKey:
    both labels present and equal.)"""
    K = nt.labels.shape[1]
    tk = jnp.clip(tt.tk, 0, K - 1)
    own = jnp.take_along_axis(nt.labels[tt.node], tk[:, None], axis=1)[:, 0]  # [E]
    node_dom = jnp.take(nt.labels, tk, axis=1).T  # [E, N]
    return ((node_dom == own[:, None]) & (own > 0)[:, None] & (node_dom > 0)
            & (tt.tk > 0)[:, None] & tt.valid[:, None] & nt.valid[None, :])


def _bool_matmul(a, b):
    """bool [P, E] @ bool [E, N] -> bool [P, N] via f32 MXU contraction."""
    return (a.astype(jnp.float32) @ b.astype(jnp.float32)) > 0.5


def node_domains(nt: NodeTensors, tk) -> jnp.ndarray:
    """i32 [..., N] — each node's domain (label value id) under per-row
    topology keys tk [...]. 0 = key absent."""
    K = nt.labels.shape[1]
    safe = jnp.clip(tk, 0, K - 1)
    dom = jnp.take(nt.labels, safe.reshape(-1), axis=1).T  # [B, N]
    dom = jnp.where((tk.reshape(-1) > 0)[:, None], dom, 0)
    return dom.reshape(tk.shape + (nt.labels.shape[0],))


class IncomingStatics(NamedTuple):
    """Per-wave static (pre-scan) inter-pod affinity state."""

    sym_blocked: jnp.ndarray  # bool [P, N] existing pods' req-anti symmetry
    ok_aff: jnp.ndarray  # bool [P, N]  incoming req-affinity satisfied (static)
    any_aff: jnp.ndarray  # bool [P]    any matching pod exists (bootstrap rule)
    blocked_anti: jnp.ndarray  # bool [P, N] incoming req-anti violated (static)
    counts: jnp.ndarray  # f32 [P, N]   priority raw counts
    node_dom_ra: jnp.ndarray  # i32 [P, N] node domain under pod's aff tk
    node_dom_rn: jnp.ndarray  # i32 [P, N] node domain under pod's anti tk
    wm_aff: jnp.ndarray  # bool [P, P]  wave pod j matches pod i's aff props
    wm_anti: jnp.ndarray  # bool [P, P] wave pod j matches pod i's anti props


def node_counts(match, node, num_nodes):
    """f32 [B, N] — matching pods per node. match: bool [B, M]; node:
    i32 [M] each pod's node. Every row b shares the index, so this is
    one segment-sum of [M, B] rows: M row updates B lanes wide, not B·M
    scalar ones. A freed pod-matrix row keeps its stale node, so `match`
    must carry the row's validity."""
    rows = match.T.astype(jnp.float32)
    return jax.ops.segment_sum(rows, node, num_segments=num_nodes).T


def _digits(x):
    """bf16 [3, ...] — base-256 digits, lowest first, of the integer-valued
    f32 x (0 <= x < 2**24). Each digit is exact in bf16, so a contraction
    of the digits on the MXU (f32 accumulation) rounds nothing."""
    hi = jnp.floor(x / 65536.0)
    rest = x - hi * 65536.0
    mid = jnp.floor(rest / 256.0)
    return jnp.stack([rest - mid * 256.0, mid, hi]).astype(jnp.bfloat16)


def _undigit(d):
    """f32 [...] from f32 [3, ...] digit sums (inverse of _digits)."""
    return d[0] + 256.0 * d[1] + 65536.0 * d[2]


def anchor(x, tk, labels, num_label_values, count=False, to_nodes=False):
    """Anchor per-node values at topology domains.

    x: [B, N] matching pods per node (or bool presence), integer-valued
    and below 2**24; tk: i32 [B] row b's topology key column, 0 = none
    (read as node_domains reads it); labels: i32 [N, K] node label value
    ids. Returns, per label value v > 0, the sum (count) or any (else)
    of row b over the nodes whose value under b's key is v, [B, LV]; or,
    to_nodes, that result at each node's own domain, 0 where the node
    lacks the key, [B, N].

    One pass per distinct key among the rows (a while loop, so the count
    adapts to the tables): the key's node-to-domain one-hot O [N, LV]
    contracts the key's rows on the MXU, x @ O, then back, @ O.T."""
    N, K = labels.shape
    B = x.shape[0]
    key = jnp.where(tk > 0, jnp.clip(tk, 0, K - 1), -1)
    lv = jnp.arange(num_label_values, dtype=jnp.int32)
    if count:
        planes = _digits(x.astype(jnp.float32))  # [3, B, N]
    else:
        planes = (x > 0).astype(jnp.bfloat16)[None]  # [1, B, N]

    def contract(a, onehot, back):
        dims = (((2,), (1 if back else 0,)), ((), ()))
        out = lax.dot_general(a, onehot, dims,
                              preferred_element_type=jnp.float32)
        return _undigit(out) if count else out[0] > 0.5

    def next_key(k):
        return jnp.min(jnp.where(key > k, key, K))

    def body(c):
        k, acc = c
        col = lax.dynamic_index_in_dim(labels, k, axis=1, keepdims=False)
        onehot = ((col[:, None] == lv[None, :])
                  & (lv > 0)[None, :]).astype(jnp.bfloat16)  # [N, LV]
        rows = jnp.where((key == k)[None, :, None], planes, 0)
        hit = contract(rows, onehot, False)  # [B, LV]
        if to_nodes:
            up = (_digits(hit) if count
                  else hit.astype(jnp.bfloat16)[None])  # [3 or 1, B, LV]
            hit = contract(up, onehot, True)  # [B, N]
        return next_key(k), (acc + hit if count else acc | hit)

    width = N if to_nodes else num_label_values
    acc0 = jnp.zeros((B, width), jnp.float32 if count else bool)
    _, out = lax.while_loop(lambda c: c[0] < K, body, (next_key(-1), acc0))
    return out


def incoming_statics(nt: NodeTensors, pm: PodMatrix, tt: TermTable,
                     pb: PodBatch, num_label_values: int,
                     hard_weight: float) -> IncomingStatics:
    em = term_entry_match(tt, pb)  # [P, E]
    sd = same_domain(tt, nt)  # [E, N]
    kind = tt.kind
    sym_blocked = _bool_matmul(em & (kind == enc.TERM_REQ_ANTI)[None, :], sd)

    # --- incoming required (anti)affinity, deduplicated ------------------
    # The wave's unique required programs (pb.iu_*, row 0 = never-matches)
    # are evaluated ONCE against the existing-pod matrix — [U, M] instead
    # of [P, M]; per-pod views are gathers through ra_uid/rn_uid. Pods
    # stamped from one controller share programs, so U << P in practice.
    u_sel = _eval_programs(pm.labels, pb.iu_key, pb.iu_op, pb.iu_vals)  # [U, M]
    u_m = u_sel & ns_match(pb.iu_ns, pm.ns) & pm.valid[None, :]
    node_dom_u = node_domains(nt, pb.iu_tk)  # [U, N]
    # incoming pods' preferred terms, the same way (unique table pb.pu_*)
    pu_sel = _eval_programs(pm.labels, pb.pu_key, pb.pu_op, pb.pu_vals)
    pu_m = pu_sel & ns_match(pb.pu_ns, pm.ns) & pm.valid[None, :]  # [UP, M]
    U = u_m.shape[0]
    with jax.named_scope("affinity_anchor"):
        # both tables' matching pods per node in one shared-index scatter
        cnt_n = node_counts(jnp.concatenate([u_m, pu_m]), pm.node,
                            nt.valid.shape[0])  # [U + UP, N]
        # "a matching pod exists in node n's domain" per unique program
        ok_u = anchor(cnt_n[:U], pb.iu_tk, nt.labels, num_label_values,
                      to_nodes=True)  # [U, N]
        # matching pods in node n's domain per unique preferred term
        cnt_node_u = anchor(cnt_n[U:], pb.pu_tk, nt.labels,
                            num_label_values, count=True,
                            to_nodes=True)  # [UP, N]
    any_u = jnp.any(u_m, axis=1)  # [U]

    ok_aff = ok_u[pb.ra_uid]  # [P, N]
    any_aff = any_u[pb.ra_uid]
    node_dom_ra = node_dom_u[pb.ra_uid]
    blocked_anti = ok_u[pb.rn_uid]
    node_dom_rn = node_dom_u[pb.rn_uid]

    # --- priority counts -------------------------------------------------
    # existing-pod side: hard symmetric weight for required affinity terms,
    # signed weights for preferred terms (interpod_affinity.go:149-188)
    we = jnp.select(
        [kind == enc.TERM_REQ_AFF, kind == enc.TERM_PREF_AFF,
         kind == enc.TERM_PREF_ANTI],
        [jnp.full_like(tt.weight, hard_weight), tt.weight, -tt.weight],
        default=jnp.zeros_like(tt.weight))
    counts = (em.astype(jnp.float32) * we[None, :]) @ sd.astype(jnp.float32)
    # incoming pods' preferred terms: per-slot gather of the unique
    # table's counts + weight (weights stay per-pod in pa_w)
    PA = pb.pa_w.shape[1]
    for t in range(PA):
        counts = counts + pb.pa_w[:, t, None] * cnt_node_u[pb.pa_uid[:, t]]
    counts = counts * nt.valid[None, :]

    # --- wave-internal cross matrices ------------------------------------
    wave_aff_sel = _eval_programs(pb.pl_val, pb.ra_key, pb.ra_op, pb.ra_vals)
    wm_aff = (wave_aff_sel & ns_match(pb.ra_ns, pb.ns_id)
              & pb.ra_has[:, None] & pb.valid[None, :])
    wave_anti_sel = _eval_programs(pb.pl_val, pb.rn_key, pb.rn_op, pb.rn_vals)
    wm_anti = (wave_anti_sel & ns_match(pb.rn_ns, pb.ns_id)
               & pb.rn_has[:, None] & pb.valid[None, :])

    return IncomingStatics(
        sym_blocked=sym_blocked, ok_aff=ok_aff, any_aff=any_aff,
        blocked_anti=blocked_anti, counts=counts,
        node_dom_ra=node_dom_ra, node_dom_rn=node_dom_rn,
        wm_aff=wm_aff, wm_anti=wm_anti)
