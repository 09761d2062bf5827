"""Batched score (priority) kernels.

Each function reproduces one reference priority
(pkg/scheduler/algorithm/priorities/) as a dense computation. Scores are
integers 0..10 per the reference's Map/Reduce model
(generic_scheduler.go:544 PrioritizeNodes, :636 weighted sum); integer
divisions are emulated as float32 floor with a +1e-5 guard (all
quotients live in [0, 10], far above f32 resolution).

Normalizing reduces (NormalizeReduce, priorities/reduce.go:29) run over
the *feasible* node set of each pod — in the reference, Reduce sees only
nodes that passed filtering — so they execute inside the commit scan in
ops/kernel.py where per-pod feasibility is known.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import encoding as enc
from .encoding import NodeTensors, PodBatch, PodMatrix
from .selectors import eval_and_program

MAX_PRIORITY = 10.0
EPS = 1e-5

# --- score decomposition (the decision observatory) --------------------------
#
# The wave scan computes every per-priority score plane and then sums
# them away before argmax; with collect_scores on (tracing), the scan
# additionally keeps the stack alive long enough to gather — per pod —
# the per-priority contributions of the chosen node and the top-k
# candidates by total score, so "why did node-42 win" is answerable
# after the fact without recomputing anything. Row order here is the
# contract for every consumer (ledger, /debug/score, tests).
SCORE_STACK = (
    "LeastRequested",
    "BalancedAllocation",
    "MostRequested",
    "NodeAffinity",
    "TaintToleration",
    "SelectorSpread",
    "PreferAvoid",
    "ImageLocality",
    "InterPodAffinity",
    "TopologySpread",  # PodTopologySpread skew score (ops/topology.py)
    "TopologyCompactness",  # gang rack/superpod co-location + accel-gen steering
    "HostExtra",  # pre-weighted host/extender scores (weight renders as 1)
)
# candidates gathered per pod (the chosen node is gathered separately:
# round-robin tie-breaks can pick a node top_k would rank past K)
SCORE_TOPK = 4

# SCORE_STACK row -> ops/kernel.py Weights field. HostExtra rows arrive
# pre-weighted (weight renders as 1), so it maps to no field. The live
# WeightProfile machinery (sched/weights.py) uses this to gate plane
# compilation and to build SCORE_STACK-aligned vectors from
# plugin-name-keyed weight tables.
WEIGHT_FIELDS = {
    "LeastRequested": "least_requested",
    "BalancedAllocation": "balanced",
    "MostRequested": "most_requested",
    "NodeAffinity": "node_affinity",
    "TaintToleration": "taint_toleration",
    "SelectorSpread": "selector_spread",
    "PreferAvoid": "prefer_avoid",
    "ImageLocality": "image_locality",
    "InterPodAffinity": "interpod",
    "TopologySpread": "topology_spread",
    "TopologyCompactness": "topology_compactness",
    "HostExtra": None,
}

# SCORE_STACK row indices, named — the kernel and its numpy twin index
# the traced weight vector with these so the contract stays greppable
(W_LEAST, W_BALANCED, W_MOST, W_AFFINITY, W_TAINT, W_SPREAD, W_AVOID,
 W_IMAGE, W_INTERPOD, W_TOPO_SPREAD, W_COMPACT,
 W_EXTRA) = range(len(SCORE_STACK))


class ScoreDeco(NamedTuple):
    """Per-pod score decomposition planes fetched alongside a wave's
    placements (only when tracing): raw 0-10 per-priority scores — NOT
    weighted — for the chosen node and the top-k nodes by weighted
    total. Leading axes match the producing program ([P] per wave,
    [W, P] per round)."""

    chosen_parts: jnp.ndarray  # f32 [..., S]     chosen node's raw scores
    top_idx: jnp.ndarray  # i32 [..., K]     top-k node indices by total
    top_vals: jnp.ndarray  # f32 [..., K]     their weighted totals (-1 infeasible)
    top_parts: jnp.ndarray  # f32 [..., S, K]  their raw per-priority scores


def stack_weights(w) -> np.ndarray:
    """f32 [S] weight vector aligned with SCORE_STACK (HostExtra rows
    arrive pre-weighted, so weight 1)."""
    return np.asarray(
        [w.least_requested, w.balanced, w.most_requested, w.node_affinity,
         w.taint_toleration, w.selector_spread, w.prefer_avoid,
         w.image_locality, w.interpod, w.topology_spread,
         w.topology_compactness, 1.0], np.float32)


def floor_div(x):
    """Go integer-division / truncation emulation for non-negative values."""
    return jnp.floor(x + EPS)


# --- resource allocation family (in-scan dynamic) ---------------------------


def least_requested(nz, alloc2, pod_nz):
    """[N] — reference least_requested.go:36 leastResourceScorer:
    (cpuScore + memScore) / 2, score_r = (cap - req) * 10 / cap.
    nz: f32 [N, 2] current nonzero-defaulted usage; alloc2: f32 [N, 2];
    pod_nz: f32 [2]."""
    r = nz + pod_nz[None, :]
    per = floor_div((alloc2 - r) * MAX_PRIORITY / jnp.maximum(alloc2, 1.0))
    per = jnp.where((alloc2 == 0) | (r > alloc2), 0.0, per)
    return floor_div((per[:, 0] + per[:, 1]) / 2.0)


def most_requested(nz, alloc2, pod_nz):
    """[N] — reference most_requested.go mostResourceScorer."""
    r = nz + pod_nz[None, :]
    per = floor_div(r * MAX_PRIORITY / jnp.maximum(alloc2, 1.0))
    per = jnp.where((alloc2 == 0) | (r > alloc2), 0.0, per)
    return floor_div((per[:, 0] + per[:, 1]) / 2.0)


def balanced_allocation(nz, alloc2, pod_nz):
    """[N] — reference balanced_resource_allocation.go:41
    balancedResourceScorer: 10 - |cpuFrac - memFrac| * 10 (truncated)."""
    r = nz + pod_nz[None, :]
    frac = jnp.where(alloc2 == 0, 1.0, r / jnp.maximum(alloc2, 1.0))
    diff = jnp.abs(frac[:, 0] - frac[:, 1])
    score = floor_div((1.0 - diff) * MAX_PRIORITY)
    return jnp.where(jnp.any(frac >= 1.0, axis=1), 0.0, score)


# --- static [P, N] raw scores ------------------------------------------------


def node_affinity_raw(nt: NodeTensors, pb: PodBatch) -> jnp.ndarray:
    """f32 [P, N] — sum of matched preferred-term weights (reference:
    priorities/node_affinity.go:34 CalculateNodeAffinityPriorityMap).
    Normalized per-pod in the scan (NormalizeReduce(10, false))."""
    N = nt.labels.shape[0]
    node_ids = jnp.arange(N, dtype=jnp.int32)
    term_match = eval_and_program(nt.labels, nt.label_nums, pb.pt_key, pb.pt_op,
                                  pb.pt_vals, pb.pt_num, node_ids)  # [P, PT, N]
    w = pb.pt_weight[:, :, None]
    # Term-axis sum (replicated under GSPMD — the node axis is the
    # sharded one) of integer-valued weights <= 100*PT: exact in f32 in
    # any association, and the twin mirrors the op order bit-for-bit.
    # ktpu: allow[f32-reduction] integer-valued, term axis, twin-mirrored
    return jnp.sum(jnp.where(term_match, w, 0.0), axis=1)


def taint_intolerable_raw(nt: NodeTensors, pb: PodBatch) -> jnp.ndarray:
    """f32 [P, N] — count of PreferNoSchedule taints not tolerated by the
    pod's PreferNoSchedule-eligible tolerations (reference:
    priorities/taint_toleration.go:55; tolerations with empty effect or
    PreferNoSchedule are eligible, :43). Normalized reversed in the scan."""
    P = pb.req.shape[0]
    N = nt.taint_key.shape[0]
    eligible = (pb.tol_effect == 0) | (pb.tol_effect == enc.EFFECT_PREFER_NO_SCHEDULE)
    eligible &= pb.tol_op != enc.TOL_PAD
    count = jnp.zeros((P, N), jnp.float32)
    for t in range(nt.taint_key.shape[1]):
        tk = nt.taint_key[:, t]
        tv = nt.taint_val[:, t]
        te = nt.taint_effect[:, t]
        relevant = te == enc.EFFECT_PREFER_NO_SCHEDULE  # [N]
        key_ok = (pb.tol_key == 0)[:, :, None] | (pb.tol_key[:, :, None] == tk[None, None, :])
        val_ok = (pb.tol_op == enc.TOL_EXISTS)[:, :, None] | (
            pb.tol_val[:, :, None] == tv[None, None, :])
        eff_ok = (pb.tol_effect == 0)[:, :, None] | (
            pb.tol_effect[:, :, None] == te[None, None, :])
        tol = jnp.any((eligible[:, :, None]) & key_ok & val_ok & eff_ok, axis=1)
        count += (relevant[None, :] & ~tol).astype(jnp.float32)
    return count


def spread_counts(pm: PodMatrix, pb: PodBatch, num_nodes: int) -> jnp.ndarray:
    """i32 [P, N] — per-node count of existing same-namespace, live pods
    matching any of the pod's group selectors (reference:
    priorities/selector_spreading.go:66 CalculateSpreadPriorityMap).
    The zone-weighted reduce happens in the scan."""
    M = pm.labels.shape[0]
    ep_ids = jnp.arange(M, dtype=jnp.int32)
    m = eval_and_program(pm.labels, None, pb.sg_key, pb.sg_op, pb.sg_vals,
                         pb.sg_num, ep_ids)  # [P, SG, M]
    any_sel = jnp.any(m & pb.sg_valid[:, :, None], axis=1)  # [P, M]
    has_sel = jnp.any(pb.sg_valid, axis=1)  # [P] — no selectors -> count 0
    eligible = pm.valid & pm.alive
    same_ns = pm.ns[None, :] == pb.ns_id[:, None]
    matched = any_sel & eligible[None, :] & same_ns & has_sel[:, None]

    def seg(row):
        return jax.ops.segment_sum(row.astype(jnp.int32), pm.node,
                                   num_segments=num_nodes)

    return jax.vmap(seg)(matched)


def _domain_onehot(ids, num_domains: int):
    """bool [Z, N] — node n lies in domain z (ids < Z: the snapshot's
    vocabulary bounds them). Built once per wave, ahead of the scan, so
    the scan's per-domain sums need no scatter and no gather."""
    return ids[None, :] == jnp.arange(num_domains, dtype=ids.dtype)[:, None]


def _domain_sums(v, onehot):
    """[Z] — per-domain sums of the node values v [N] (segment_sum by
    compare-and-reduce: a scatter over the node axis runs one update at
    a time on the TPU)."""
    # Node-axis sum of integer-valued f32 far below 2^24: exact in any
    # association, so bit-equal to the twin's bincount.
    # ktpu: allow[f32-reduction] integer-valued, exact in any order, twin-mirrored
    return jnp.sum(jnp.where(onehot, v[None, :], 0.0), axis=1)


def _domain_values(s, onehot):
    """[N] — each node's entry of the per-domain values s [Z] (s[ids]
    without a gather)."""
    # ktpu: allow[f32-reduction] one term per node, exact, twin-mirrored
    return jnp.sum(jnp.where(onehot, s[:, None], 0.0), axis=0)


def _domain_colocation(v, ids, onehot):
    """[N] — the sum of v [N] over each node's domain, 0 on nodes with
    no domain (id 0)."""
    return _domain_values(_domain_sums(v, onehot), onehot) * (ids > 0)


def spread_reduce(cnt, feasible, zone_id, zone_onehot):
    """[N] — reference selector_spreading.go:122 CalculateSpreadPriorityReduce
    with zoneWeighting = 2/3. zone_onehot: _domain_onehot(zone_id, Z)."""
    cntf = jnp.where(feasible, cnt, 0).astype(jnp.float32)
    max_node = jnp.max(cntf)
    zc = _domain_sums(jnp.where(zone_id > 0, cntf, 0.0), zone_onehot)
    max_zone = jnp.max(jnp.where(jnp.arange(zc.shape[0]) > 0, zc, 0.0))
    have_zones = jnp.any(feasible & (zone_id > 0))
    f = jnp.where(max_node > 0, MAX_PRIORITY * (max_node - cntf) / jnp.maximum(max_node, 1.0),
                  MAX_PRIORITY)
    node_zc = _domain_values(zc, zone_onehot)
    zscore = jnp.where(max_zone > 0, MAX_PRIORITY * (max_zone - node_zc) / jnp.maximum(max_zone, 1.0),
                       MAX_PRIORITY)
    f = jnp.where(have_zones & (zone_id > 0), f / 3.0 + (2.0 / 3.0) * zscore, f)
    return floor_div(f)


def image_locality(nt: NodeTensors, pb: PodBatch) -> jnp.ndarray:
    """i32-valued f32 [P, N] — reference priorities/image_locality.go:39:
    bucketed sum of present image sizes, 23MB..1000MB -> 0..10."""
    P, PI = pb.img_id.shape
    N = nt.img_id.shape[0]
    total = jnp.zeros((P, N), jnp.float32)
    for i in range(PI):
        pid = pb.img_id[:, i]  # [P]
        hit = pid[:, None, None] == nt.img_id[None, :, :]  # [P, N, NI]
        # Image-slot axis (short, replicated under GSPMD — the node axis
        # is the sharded one); device and twin share the identical
        # expression, parity gated in tests/test_hostwave.py.
        # ktpu: allow[f32-reduction] image-slot axis, twin-mirrored
        sz = jnp.sum(jnp.where(hit, nt.img_size[None, :, :], 0.0), axis=-1)
        total += jnp.where((pid > 0)[:, None], sz, 0.0)
    mb = 1024.0 * 1024.0
    min_img, max_img = 23.0 * mb, 1000.0 * mb
    mid = floor_div(MAX_PRIORITY * (total - min_img) / (max_img - min_img)) + 1.0
    return jnp.where(total < min_img, 0.0,
                     jnp.where(total >= max_img, MAX_PRIORITY, mid))


def prefer_avoid(nt: NodeTensors, pb: PodBatch) -> jnp.ndarray:
    """f32 [P, N] — reference priorities/node_prefer_avoid_pods.go:32.
    Simplified: any preferAvoidPods annotation on the node zeroes the
    score for RC/RS-controlled pods (the reference matches the exact
    controller ref; host-side plugin refines this in later rounds)."""
    avoid = nt.avoid[None, :] & pb.owned[:, None]
    return jnp.where(avoid, 0.0, MAX_PRIORITY)


def normalize_reduce(raw, feasible, reverse: bool):
    """[N] — reference priorities/reduce.go:29 NormalizeReduce(10, reverse)
    over the feasible set."""
    m = jnp.max(jnp.where(feasible, raw, 0.0))
    score = floor_div(MAX_PRIORITY * raw / jnp.maximum(m, 1.0))
    if reverse:
        score = MAX_PRIORITY - score
        return jnp.where(m > 0, score, MAX_PRIORITY)
    return jnp.where(m > 0, score, 0.0)
