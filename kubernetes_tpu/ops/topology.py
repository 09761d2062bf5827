"""Batched topology-spread and heterogeneity kernels.

Forward-ports the PodTopologySpread plugin (introduced upstream after
this codebase's reference cut as pkg/scheduler/framework/plugins/
podtopologyspread/) into the dense wave formulation, and adds the
topology/heterogeneity raw scores the gang path uses for compact
placement on rack/superpod hierarchies of mixed accelerator
generations.

Dense shape of the problem:

  * Each pod carries up to Caps.TS spread constraints, featurized into
    per-constraint rows (state/featurize.py): a topology-key column id,
    maxSkew, a hard/soft flag and an AND selector program over POD
    labels. Resident matching-pod counts per topology-domain VALUE are
    anchored as in ops/affinity.py: matching pods counted per node by
    one segment-sum keyed by each pod's node (`node_counts`), then the
    nodes summed per domain value by an exact MXU contraction with the
    key's node-to-domain one-hot (`anchor`, one pass per distinct key
    among the constraints).
  * Per-node skew is then a gather at each node's domain value; global
    min/max match counts reduce over the domain values PRESENT among
    valid nodes (upstream's "global minimum matchNum"; domains are
    enumerated from the node set, so an empty domain still pulls the
    minimum down).
  * Wave-internal visibility (a pod must see same-wave placements,
    upstream's assume semantics) rides the commit scan's `placed`
    carry in ops/kernel.py via the [P, TS, P] cross-match matrix
    computed here — the same pattern as affinity's wm_aff/wm_anti.

Simplifications vs upstream, documented for PARITY.md: the min/max
match counts reduce over domains of ALL valid nodes rather than the
per-pod filtered node set, and the incoming pod always counts itself
(+1) only when it matches its own constraint's selector (upstream's
selfMatchNum). Both are deterministic and twinned bitwise.

The compactness raw score (gang co-location + accelerator-generation
steering) is computed inside the scan in ops/kernel.py from the
rack/superpod id columns (state/snapshot.py interns them into the
shared zones vocab with hierarchical keys, so `num_zones` bounds the
segment-sums and no new static kernel argument exists).

Twinned in numpy (ops/hostwave.py topo_statics_host + the has_ts step
logic of schedule_wave_host), bitwise parity asserted in
tests/test_topology.py.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .affinity import _eval_programs, anchor, node_counts, node_domains
from .encoding import NodeTensors, PodBatch, PodMatrix


class TopoStatics(NamedTuple):
    """Per-wave static (pre-scan) topology-spread state. Leading axes:
    P wave pods x TS spread-constraint slots."""

    node_dom: jnp.ndarray  # i32 [P, TS, N] node's domain value id (0 = key absent)
    counts: jnp.ndarray  # f32 [P, TS, LV] resident matching pods per domain value
    present: jnp.ndarray  # bool [P, TS, LV] domain value exists among valid nodes
    wm: jnp.ndarray  # bool [P, TS, P] wave pod j matches constraint (i, t)
    selfm: jnp.ndarray  # bool [P, TS]   pod i matches its own constraint (i, t)


def topo_statics(nt: NodeTensors, pm: PodMatrix, pb: PodBatch,
                 num_label_values: int) -> TopoStatics:
    """All scan-invariant PodTopologySpread state for one wave.

    match = selector(existing pod labels) & same-namespace & live, per
    constraint row (upstream counts only the constraint owner's
    namespace; a nil selector was featurized as OP_FALSE and matches
    nothing). Counts reduce the matches per node, then the nodes by
    their domain value; `present` anchors the valid nodes themselves
    so empty domains still participate in the min (upstream enumerates
    domains from the node list, not the pod list)."""
    P, TS = pb.ts_tk.shape
    N = nt.labels.shape[0]
    dom = node_domains(nt, pb.ts_tk)  # [P, TS, N]
    dom = dom * nt.valid[None, None, :]
    tk_f = pb.ts_tk.reshape(P * TS)

    live = pb.ts_valid[:, :, None]  # [P, TS, 1]
    sel = _eval_programs(pm.labels, pb.ts_key, pb.ts_op, pb.ts_vals)  # [P, TS, M]
    same_ns = (pm.ns[None, None, :] == pb.ns_id[:, None, None])
    match = sel & same_ns & (pm.valid & pm.alive)[None, None, :] & live
    M = pm.labels.shape[0]
    with jax.named_scope("affinity_anchor"):
        cnt_n = node_counts(match.reshape(P * TS, M), pm.node, N)
        # only valid nodes belong to a domain
        counts = anchor(cnt_n * nt.valid[None, :], tk_f, nt.labels,
                        num_label_values, count=True)
        present = anchor(
            jnp.broadcast_to(nt.valid[None, :], (P * TS, N)), tk_f,
            nt.labels, num_label_values)

    wsel = _eval_programs(pb.pl_val, pb.ts_key, pb.ts_op, pb.ts_vals)  # [P, TS, P]
    wave_ns = (pb.ns_id[None, None, :] == pb.ns_id[:, None, None])
    wm = wsel & wave_ns & pb.valid[None, None, :] & live
    selfm = wm[jnp.arange(P), :, jnp.arange(P)]  # [P, TS]
    return TopoStatics(node_dom=dom.astype(jnp.int32),
                       counts=counts.reshape(P, TS, num_label_values),
                       present=present.reshape(P, TS, num_label_values),
                       wm=wm, selfm=selfm)
