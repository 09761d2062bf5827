"""Batched device-side preemption what-if.

Reference: genericScheduler.Preempt fans 16 goroutines over candidate
nodes and simulates victim removal pod-by-pod on cloned NodeInfos
(generic_scheduler.go:840 selectNodesForPreemption -> :898
selectVictimsOnNode). Here the whole what-if for a BATCH of failed pods
runs as one XLA program over the existing-pod matrix:

  * victims are modeled as priority-threshold classes: removing "all
    alive pods with priority < t" subtracts a segment-sum of their
    request rows from the node's usage. The reference's reprieve loop
    re-adds victims highest-priority-first, so its victim set is exactly
    a threshold class boundary (plus intra-class refinement the host
    performs exactly on the one chosen node).
  * per (failed pod, node, threshold): feasibility = resource fit with
    the class removed AND every static non-resource predicate passing
    (nodesWherePreemptionMightHelp's unresolvable-reason filter,
    generic_scheduler.go:972 — a node failing NodeSelector/taints can't
    be helped by eviction).
  * the LOWEST feasible threshold per (pod, node) yields the stats the
    host needs for pickOneNodeForPreemption's tie-breaks
    (generic_scheduler.go:702): victim count, priority sum, priority
    max. Exact victim selection (reprieve + PDBs + affinity) then runs
    host-side on the chosen node only (sched/preemption.py
    select_victims_on_node).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import encoding as enc
from .filters import static_predicate_masks

NEG = jnp.int32(-(2**31) + 1)


class PreemptStats:
    """Host view over ONE fetched [5, P, N] i32 plane stack. Packing the
    stat planes into a single array makes the chunk's result one
    device->host transfer instead of five, each of which is a
    synchronization point. Planes 0-2 (ok, victim count, priority max)
    are native i32 — exact for the full int32 priority range (Kubernetes
    permits ~2e9); planes 3 (priority SUM) and 4 (gang-disruption
    weight: how much the class's eviction breaks victim gangs below
    minMember, see preemption_stats' gang_w) are f32 bitcast to i32 for
    the ride and viewed back here."""

    __slots__ = ("ok", "victims", "prio_sum", "prio_max", "gang_viol")

    def __init__(self, packed):
        self.ok = packed[0] != 0            # [P, N] bool
        self.victims = packed[1]            # [P, N] i32
        self.prio_max = packed[2]           # [P, N] i32 (NEG sentinel)
        self.prio_sum = np.ascontiguousarray(packed[3]).view(np.float32)
        self.gang_viol = np.ascontiguousarray(packed[4]).view(np.float32)


def preemption_stats(nt: enc.NodeTensors, pm: enc.PodMatrix,
                     pb: enc.PodBatch, levels, *, num_levels: int,
                     gang_w=None):
    """Entry point for the what-if program — routed through the
    record_dispatch seam (ops/kernel.py) like every other device
    dispatch, so the watchdog deadline, the `device.lost` chaos point,
    jit-cache telemetry, and per-device failure attribution all cover
    the preemption path too (a mid-preempt-chunk device loss must reform
    the mesh exactly like a mid-wave one)."""
    from .kernel import _device_count, record_dispatch

    bucket = (pb.req.shape[0], nt.valid.shape[0], pm.node.shape[0],
              int(num_levels), _device_count(nt.valid),
              int(gang_w is not None))
    return record_dispatch(
        "preempt", bucket,
        lambda: _preemption_stats(nt, pm, pb, levels,
                                  num_levels=num_levels, gang_w=gang_w))


@functools.partial(jax.jit, static_argnames=("num_levels",))
def _preemption_stats(nt: enc.NodeTensors, pm: enc.PodMatrix,
                      pb: enc.PodBatch, levels, *, num_levels: int,
                      gang_w=None):
    """levels: i32 [num_levels] ascending candidate priority thresholds
    (pad with INT32_MAX). Victim class at level l for failed pod p =
    alive existing pods with priority < min(levels[l], prio_p).

    gang_w: optional f32 [M] per-existing-pod gang-disruption weight
    (host-computed: 1.0 for pods whose gang has no slack above
    minMember, 0 elsewhere; None compiles the gang-free variant). The
    per-class segment sum ranks candidate nodes by how badly the
    eviction breaks victim gangs — the device analog of the host
    GangGuard, consumed as the FIRST ranking criterion so exact
    validation slots go to gang-sparing nodes first.

    Returns ONE packed i32 [5, P, N] array (see PreemptStats): plane 0
    ok, 1 victim count, 2 priority max, 3 f32 priority sum bitcast to
    i32, 4 f32 gang-disruption sum bitcast to i32 — stats of the lowest
    feasible level; prio_max is NEG where victims == 0 (a no-victim
    placement is ranked best by the host, matching
    pickOneNodeForPreemption's early return)."""
    P = pb.req.shape[0]
    N = nt.valid.shape[0]
    R = nt.alloc.shape[1]
    is_core = jnp.arange(R) < enc.RES_FIXED

    # non-resource eligibility: every static predicate except the
    # RESOLVABLE ones — resources (the thing eviction frees) and host
    # ports (a victim may hold the conflicting port; the reference's
    # unresolvable-reason list excludes PodFitsHostPorts,
    # generic_scheduler.go:972). The host's exact validation re-runs
    # the full predicate set against the post-eviction state.
    masks = static_predicate_masks(nt, pb, is_core, False, False)
    masks = masks.at[enc.PRED_IDX["PodFitsResources"]].set(True)
    masks = masks.at[enc.PRED_IDX["PodFitsHostPorts"]].set(True)
    static_ok = jnp.all(masks, axis=0)  # [P, N]
    static_ok = static_ok & nt.valid[None, :] & pb.valid[:, None]

    live = pm.valid & pm.alive  # [M]
    node_ids = jnp.clip(pm.node, 0)

    def seg_sum(weights):  # [M] or [M, R] -> per-node sums
        return jax.ops.segment_sum(weights, node_ids, num_segments=N)

    ok = jnp.zeros((P, N), bool)
    victims = jnp.zeros((P, N), jnp.int32)
    prio_sum = jnp.zeros((P, N), jnp.float32)
    prio_max = jnp.full((P, N), NEG)
    gang_viol = jnp.zeros((P, N), jnp.float32)

    for l in range(num_levels):
        thresh = jnp.minimum(levels[l], pb.prio)  # [P]
        cls = live[None, :] & (pm.prio[None, :] < thresh[:, None])  # [P, M]
        w = cls.astype(jnp.float32)

        def per_pod(w_row):
            rem_req = seg_sum(w_row[:, None] * pm.req)  # [N, R]
            rem_cnt = seg_sum(w_row)  # [N]
            rem_psum = seg_sum(w_row * pm.prio.astype(jnp.float32))
            rem_pmax = jax.ops.segment_max(
                jnp.where(w_row > 0, pm.prio, NEG), node_ids,
                num_segments=N)
            rem_gang = (seg_sum(w_row * gang_w) if gang_w is not None
                        else jnp.zeros((N,), jnp.float32))
            return rem_req, rem_cnt, rem_psum, rem_pmax, rem_gang

        rem_req, rem_cnt, rem_psum, rem_pmax, rem_gang = jax.vmap(per_pod)(w)
        # resource fit with the class removed (exact recheck is host-side
        # int64; f32 here only ranks candidates). Column semantics follow
        # filters.resource_fit: core columns always checked, extended
        # columns only when requested (predicates.go:688).
        used = nt.requested[None] - rem_req + pb.req[:, None, :]
        col_ok = used <= nt.alloc[None]  # [P, N, R]
        check = is_core[None, None, :] | (pb.req[:, None, :] > 0)
        fits = jnp.all(col_ok | ~check, axis=-1)
        fits &= (nt.pod_count[None] - rem_cnt.astype(jnp.int32) + 1
                 <= nt.allowed_pods[None])
        feasible = fits & static_ok
        take = feasible & ~ok  # lowest feasible level wins
        ok |= feasible
        victims = jnp.where(take, rem_cnt.astype(jnp.int32), victims)
        prio_sum = jnp.where(take, rem_psum, prio_sum)
        prio_max = jnp.where(take, rem_pmax, prio_max)
        gang_viol = jnp.where(take, rem_gang, gang_viol)
    # a node where the pod fits with ZERO victims is not a preemption
    # candidate at all (it would have been placed) — unless usage raced;
    # keep it, the host recheck resolves
    return jnp.stack([ok.astype(jnp.int32),
                      victims,
                      prio_max,
                      jax.lax.bitcast_convert_type(prio_sum, jnp.int32),
                      jax.lax.bitcast_convert_type(gang_viol, jnp.int32)])
