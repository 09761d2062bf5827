"""Dense tensor encoding of cluster state.

This is the HBM mirror of the scheduler cache (SURVEY.md §7 step 1): the
reference's NodeInfo (pkg/scheduler/schedulercache/node_info.go:40) is
already denormalized to int64 scalars per node, so the jump to dense
arrays is natural. Strings (label keys/values, taints, ports, image
names, namespaces) are interned to integer ids by state/vocab.py; match
expressions compile to fixed-shape "selector programs" evaluated by
ops/selectors.py.

All shapes are static and bucketed (powers of two) so XLA compiles once
per bucket configuration, not per cluster mutation.

dtype policy:
  float32  resources. CPU milli / memory bytes / storage bytes fit f32's
           24-bit mantissa for all practical node sizes at the precision
           the *scores* need; exact feasibility of the final pick is
           re-verified host-side in int64 (state/node_info.py
           fits_exactly), so f32 rounding can never produce an invalid
           binding.
  int32    every id / count / score (reference scores are ints 0-10).
  bool     masks.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import NamedTuple

import numpy as np

# --- resource dims (column layout of alloc/requested/req) -------------------
RES_CPU = 0  # milli-cores
RES_MEM = 1  # bytes
RES_EPH = 2  # bytes
RES_FIXED = 3  # first extended-resource column

# --- node condition flag columns (cond[:, c]) -------------------------------
# CheckNodeCondition blockers (reference: predicates.go:1583).
COND_NOT_READY = 0  # Ready != True
COND_OUT_OF_DISK = 1  # OutOfDisk != False
COND_NET_UNAVAIL = 2  # NetworkUnavailable != False
COND_UNSCHEDULABLE = 3  # node.Spec.Unschedulable
COND_MEM_PRESSURE = 4  # MemoryPressure == True
COND_DISK_PRESSURE = 5  # DiskPressure == True
COND_PID_PRESSURE = 6  # PIDPressure == True
N_COND = 7

# --- taint effects ----------------------------------------------------------
EFFECT_NONE = 0  # pad
EFFECT_NO_SCHEDULE = 1
EFFECT_PREFER_NO_SCHEDULE = 2
EFFECT_NO_EXECUTE = 3

EFFECT_IDS = {
    "NoSchedule": EFFECT_NO_SCHEDULE,
    "PreferNoSchedule": EFFECT_PREFER_NO_SCHEDULE,
    "NoExecute": EFFECT_NO_EXECUTE,
    "": EFFECT_NONE,
}

# --- toleration operators ---------------------------------------------------
TOL_PAD = -1
TOL_EQUAL = 0
TOL_EXISTS = 1

# --- selector-program op codes ----------------------------------------------
OP_PAD = -1  # padding expression: always true
OP_IN = 0
OP_NOT_IN = 1
OP_EXISTS = 2
OP_DOES_NOT_EXIST = 3
OP_GT = 4
OP_LT = 5
OP_NODE_NAME_IN = 6  # matchFields metadata.name; vals are node indices
OP_FALSE = 7  # compiled "matches nothing" (e.g. unknown label value... NotIn still true)

_OP_IDS = {
    "In": OP_IN,
    "NotIn": OP_NOT_IN,
    "Exists": OP_EXISTS,
    "DoesNotExist": OP_DOES_NOT_EXIST,
    "Gt": OP_GT,
    "Lt": OP_LT,
}


def op_id(op: str) -> int:
    return _OP_IDS[op]


# --- inter-pod affinity term kinds (TermTable.kind) -------------------------
# One TermTable row per affinity term carried by an *existing* pod
# (reference: metadata.go getMatchingAntiAffinityTerms walks required
# anti-affinity terms; interpod_affinity.go:149-188 walks required +
# preferred terms of existing pods for the priority).
TERM_PAD = 0
TERM_REQ_ANTI = 1  # requiredDuringScheduling anti-affinity (predicate symmetry)
TERM_REQ_AFF = 2  # required affinity (hardPodAffinitySymmetricWeight in priority)
TERM_PREF_AFF = 3  # preferred affinity (priority +w)
TERM_PREF_ANTI = 4  # preferred anti-affinity (priority -w)


# --- capacity buckets -------------------------------------------------------


@dataclass
class Caps:
    """Static padded dimensions. Growing any of these triggers a retrace;
    all start small and grow by powers of two."""

    N: int = 8  # nodes
    Z: int = 8  # zone vocabulary
    K: int = 8  # node label keys
    KP: int = 8  # pod label keys (separate vocab; see state/snapshot.py)
    R: int = RES_FIXED  # resource columns (3 + extended)
    T: int = 4  # taint slots per node
    PP: int = 8  # used host-port slots per node
    NI: int = 8  # image slots per node
    M: int = 64  # existing-pod matrix rows
    # pod-batch dims
    P: int = 8  # wavefront width
    NS: int = 8  # nodeSelector equality pairs
    AT: int = 4  # required node-affinity terms
    AE: int = 4  # expressions per term
    AV: int = 4  # values per expression
    PT: int = 4  # preferred node-affinity terms
    TL: int = 4  # tolerations
    PQ: int = 4  # host ports requested per pod
    SG: int = 4  # spreading group selectors
    SE: int = 8  # expressions per spreading selector
    SV: int = 2  # values per spreading expression
    PI: int = 4  # images per pod
    # inter-pod affinity dims
    E: int = 8  # TermTable rows (existing-pod affinity terms)
    TE: int = 4  # expressions per term selector program
    TV: int = 2  # values per term expression
    TNS: int = 2  # namespace-set slots per term / per combined program
    IE: int = 8  # expressions in a pod's combined required (anti)affinity program
    IV: int = 2  # values per combined-program expression
    PA: int = 2  # preferred pod-(anti)affinity terms per pending pod
    LV: int = 64  # label-value vocab bucket (segment count for domain anchoring)
    UI: int = 8  # unique required (anti)affinity programs per wave (dedup table)
    UP: int = 4  # unique preferred pod-affinity terms per wave (dedup table)
    TS: int = 2  # topologySpreadConstraints per pod


class NodeTensors(NamedTuple):
    """Per-node cluster state, mirrored into HBM."""

    alloc: np.ndarray  # f32 [N, R]  allocatable
    requested: np.ndarray  # f32 [N, R]  sum of pod requests
    nonzero: np.ndarray  # f32 [N, 2]  nonzero-defaulted (cpu, mem)
    pod_count: np.ndarray  # i32 [N]
    allowed_pods: np.ndarray  # i32 [N]
    labels: np.ndarray  # i32 [N, K]   value id per key col (0 absent)
    label_nums: np.ndarray  # f32 [N, K] parsed ints (NaN if unparseable)
    taint_key: np.ndarray  # i32 [N, T]
    taint_val: np.ndarray  # i32 [N, T]
    taint_effect: np.ndarray  # i32 [N, T]
    cond: np.ndarray  # bool [N, N_COND]
    ports: np.ndarray  # i32 [N, PP]  interned proto/port ids (0 pad)
    zone_id: np.ndarray  # i32 [N]  (0 = no zone key)
    # interconnect topology + heterogeneity columns (ops/topology.py):
    # rack/superpod ids are interned into the shared zone vocabulary with
    # hierarchical keys ("sp:<v>" / "sp:<v>/rk:<r>"), so link distance is
    # derivable from id prefixes and every rack/superpod segment-sum
    # reuses the num_zones segment count
    rack_id: np.ndarray  # i32 [N]  (0 = no rack label)
    superpod_id: np.ndarray  # i32 [N]  (0 = no superpod label)
    accel_gen: np.ndarray  # i32 [N]  accelerator generation rank (0 = unlabeled)
    img_id: np.ndarray  # i32 [N, NI]
    img_size: np.ndarray  # f32 [N, NI]
    avoid: np.ndarray  # bool [N]  preferAvoidPods annotation present
    valid: np.ndarray  # bool [N]


class PodMatrix(NamedTuple):
    """Existing (scheduled) pods — input to spreading and inter-pod
    affinity. Incrementally maintained slots."""

    labels: np.ndarray  # i32 [M, KP]
    ns: np.ndarray  # i32 [M]
    node: np.ndarray  # i32 [M]   node index
    valid: np.ndarray  # bool [M]
    alive: np.ndarray  # bool [M]  deletionTimestamp unset
    req: np.ndarray  # f32 [M, R]  resource requests (preemption what-if)
    prio: np.ndarray  # i32 [M]   pod priority


class TermTable(NamedTuple):
    """Dense table of affinity terms carried by existing (scheduled) pods —
    the device analog of predicateMetadata.matchingAntiAffinityTerms
    (metadata.go:58) plus the existing-pod term walk of
    interpod_affinity.go:149. One row per term; selector programs run
    against the *incoming* pod's labels (pod-label key space)."""

    kind: np.ndarray  # i32 [E]  TERM_* (0 pad)
    owner: np.ndarray  # i32 [E]  pod slot in PodMatrix
    node: np.ndarray  # i32 [E]  owner's node index
    tk: np.ndarray  # i32 [E]  topology key as node-label key id (0 invalid)
    weight: np.ndarray  # f32 [E]  preferred weight (REQ_* rows: 1.0)
    ns: np.ndarray  # i32 [E, TNS]  allowed incoming-pod namespace ids (0 pad)
    key: np.ndarray  # i32 [E, TE]  selector program over pod-label keys
    op: np.ndarray  # i32 [E, TE]
    vals: np.ndarray  # i32 [E, TE, TV]
    valid: np.ndarray  # bool [E]


class PodBatch(NamedTuple):
    """A featurized wavefront of pending pods."""

    req: np.ndarray  # f32 [P, R]
    nonzero: np.ndarray  # f32 [P, 2]
    best_effort: np.ndarray  # bool [P]
    host_idx: np.ndarray  # i32 [P]  (-1: no spec.nodeName)
    # spec.nodeSelector equality pairs (key id 0 = pad; val -1 = unknown value)
    ns_key: np.ndarray  # i32 [P, NS]
    ns_val: np.ndarray  # i32 [P, NS]
    # required node affinity
    has_aff: np.ndarray  # bool [P]
    at_valid: np.ndarray  # bool [P, AT]
    at_key: np.ndarray  # i32 [P, AT, AE]
    at_op: np.ndarray  # i32 [P, AT, AE]
    at_vals: np.ndarray  # i32 [P, AT, AE, AV]
    at_num: np.ndarray  # f32 [P, AT, AE]
    # preferred node affinity (weight 0 = pad term)
    pt_weight: np.ndarray  # f32 [P, PT]
    pt_key: np.ndarray  # i32 [P, PT, AE]
    pt_op: np.ndarray  # i32 [P, PT, AE]
    pt_vals: np.ndarray  # i32 [P, PT, AE, AV]
    pt_num: np.ndarray  # f32 [P, PT, AE]
    # tolerations
    tol_key: np.ndarray  # i32 [P, TL]  (0 = match all keys)
    tol_val: np.ndarray  # i32 [P, TL]
    tol_op: np.ndarray  # i32 [P, TL]  (-1 pad / 0 equal / 1 exists)
    tol_effect: np.ndarray  # i32 [P, TL] (0 = all effects)
    # host ports
    ports: np.ndarray  # i32 [P, PQ] (0 pad)
    # spreading selectors over pod-label space
    ns_id: np.ndarray  # i32 [P]  pod namespace id
    sg_valid: np.ndarray  # bool [P, SG]
    sg_key: np.ndarray  # i32 [P, SG, SE]
    sg_op: np.ndarray  # i32 [P, SG, SE]
    sg_vals: np.ndarray  # i32 [P, SG, SE, SV]
    sg_num: np.ndarray  # f32 [P, SG, SE]
    # inter-pod affinity (incoming side). Required terms collapse to ONE
    # combined AND program + one namespace-set intersection per pod —
    # legal because the metadata path matches existing pods against ALL
    # term properties at once (predicates.go podMatchesAffinityTermProperties
    # "matches all the given properties"). The shared topology key
    # (ra_tk/rn_tk) encodes the single-topology-key fast path; pods whose
    # required terms use >1 distinct key are routed host-side.
    pl_val: np.ndarray  # i32 [P, KP]  the pod's own labels (pod-label key space)
    ra_has: np.ndarray  # bool [P]  has required pod-affinity terms
    ra_key: np.ndarray  # i32 [P, IE]
    ra_op: np.ndarray  # i32 [P, IE]
    ra_vals: np.ndarray  # i32 [P, IE, IV]
    ra_ns: np.ndarray  # i32 [P, TNS]  ns-set intersection (0 pad)
    ra_tk: np.ndarray  # i32 [P]  shared topology key (node-label key id)
    ra_self: np.ndarray  # bool [P]  pod matches its own affinity properties
    rn_has: np.ndarray  # bool [P]  has required anti-affinity terms
    rn_key: np.ndarray  # i32 [P, IE]
    rn_op: np.ndarray  # i32 [P, IE]
    rn_vals: np.ndarray  # i32 [P, IE, IV]
    rn_ns: np.ndarray  # i32 [P, TNS]
    rn_tk: np.ndarray  # i32 [P]
    # preferred pod-(anti)affinity terms of the incoming pod (priority)
    pa_w: np.ndarray  # f32 [P, PA]  signed weight (+aff / -anti; 0 pad)
    pa_tk: np.ndarray  # i32 [P, PA]
    pa_ns: np.ndarray  # i32 [P, PA, TNS]
    pa_key: np.ndarray  # i32 [P, PA, TE]
    pa_op: np.ndarray  # i32 [P, PA, TE]
    pa_vals: np.ndarray  # i32 [P, PA, TE, TV]
    # misc
    owned: np.ndarray  # bool [P]  has RC/RS controller ref (prefer-avoid)
    img_id: np.ndarray  # i32 [P, PI]
    prio: np.ndarray  # i32 [P]  pod priority
    valid: np.ndarray  # bool [P]
    # topologySpreadConstraints (forward-port; ops/topology.py). One row
    # per constraint: the topology key (node-label key id), maxSkew, a
    # hard/soft flag (DoNotSchedule vs ScheduleAnyway), and a selector
    # program over the existing-pod label space (TermTable conventions:
    # key 0 + OP_PAD rows are padding, so an empty selector matches all).
    ts_valid: np.ndarray  # bool [P, TS]
    ts_hard: np.ndarray  # bool [P, TS]  whenUnsatisfiable == DoNotSchedule
    ts_skew: np.ndarray  # f32 [P, TS]  maxSkew
    ts_tk: np.ndarray  # i32 [P, TS]  topology key (node-label key id; 0 invalid)
    ts_key: np.ndarray  # i32 [P, TS, TE]  selector program over pod-label keys
    ts_op: np.ndarray  # i32 [P, TS, TE]
    ts_vals: np.ndarray  # i32 [P, TS, TE, TV]
    # Dedup tables for the O(P x M) hot paths in ops/affinity.py: pods
    # from the same controller share identical (anti)affinity programs,
    # so the wave's REQUIRED programs are interned into one [UI, ...]
    # table (row 0 = reserved never-matches row) evaluated once against
    # the existing-pod matrix, and per-pod results are gathered via
    # ra_uid/rn_uid. Preferred terms intern likewise into [UP, ...] /
    # pa_uid. Replicated (not wave-sharded) under a device mesh.
    ra_uid: np.ndarray  # i32 [P]  index into iu_* (0 = no program)
    rn_uid: np.ndarray  # i32 [P]
    pa_uid: np.ndarray  # i32 [P, PA]  index into pu_* (0 = no term)
    iu_key: np.ndarray  # i32 [UI, IE]
    iu_op: np.ndarray  # i32 [UI, IE]
    iu_vals: np.ndarray  # i32 [UI, IE, IV]
    iu_ns: np.ndarray  # i32 [UI, TNS]
    iu_tk: np.ndarray  # i32 [UI]
    pu_key: np.ndarray  # i32 [UP, TE]
    pu_op: np.ndarray  # i32 [UP, TE]
    pu_vals: np.ndarray  # i32 [UP, TE, TV]
    pu_ns: np.ndarray  # i32 [UP, TNS]
    pu_tk: np.ndarray  # i32 [UP]


# priority of a pad row of Nominations.prio: above every pod priority,
# so no pod's fit reads the row and no placement drops from it
NOM_PAD_PRIO = np.iinfo(np.int32).max


class Nominations(NamedTuple):
    """Pods nominated to nodes (status.nominatedNodeName), as a pod's
    resource fit counts them: 1.11's podFitsOnNode adds to a node every
    pod nominated there with priority >= the pod's own, other than the
    pod itself (generic_scheduler.go addNominatedPods). Scores read none.

    Row l of req/count sums the pods nominated to each node whose
    priority is >= prio[l]; prio holds the nominated pods' distinct
    priorities, ascending, then NOM_PAD_PRIO pad rows of zeros. A pod
    that places drops its own nomination from every row it is in."""

    req: np.ndarray  # f32 [L, N, R]
    count: np.ndarray  # i32 [L, N]
    prio: np.ndarray  # i32 [L]
    own: np.ndarray  # i32 [..., P]  node of the pod's own nomination, -1 none


# Names + order of the device-evaluated predicates; the stacked mask output
# of the kernel indexes into this list. Order mirrors the reference's
# predicatesOrdering (predicates.go:133) restricted to tensorized ones.
DEVICE_PREDICATES = (
    "CheckNodeCondition",
    "CheckNodeUnschedulable",
    "PodFitsResources",
    "HostName",
    "PodFitsHostPorts",
    "MatchNodeSelector",
    "PodToleratesNodeTaints",
    "CheckNodeMemoryPressure",
    "CheckNodeDiskPressure",
    "CheckNodePIDPressure",
    # forward-ported (no 1.11 analog): hard topologySpreadConstraints
    # (whenUnsatisfiable=DoNotSchedule) evaluated wave-internally by
    # ops/topology.py — counts include same-wave placements
    "PodTopologySpread",
    "MatchInterPodAffinity",  # last, as in predicatesOrdering (predicates.go:139)
)
PRED_IDX = {name: i for i, name in enumerate(DEVICE_PREDICATES)}

# Full mask-stack row names as emitted by ops/kernel.py (device predicates
# plus the host-plugin pseudo-row appended at the end).
MASK_STACK_NAMES = DEVICE_PREDICATES + ("HostPlugins",)
