"""The fused scheduling wave kernel.

One jitted program schedules an entire wavefront of pending pods:

  1. static predicate masks + raw priority scores, batched [P, N]
     (replaces hot loops generic_scheduler.go:378 findNodesThatFit and
     :609 PrioritizeNodes across BOTH axes at once);
  2. a lax.scan over the wave that, per pod: re-applies resource fit
     against live usage, runs the normalizing reduces over the pod's
     feasible set, weighted-sums, and commits the argmax into the
     carried usage tensors — so later pods in the wave see earlier
     placements exactly like the reference's assume step
     (scheduler.go:486) makes assumed pods visible to the next cycle;
  3. host-name round-robin tie-break emulating selectHost
     (generic_scheduler.go:178) with a carried counter.

Failure attribution follows the reference's short-circuit predicate
ordering (generic_scheduler.go:503 breaks at the first failed predicate;
predicates.go:133 predicatesOrdering): a node is charged only to its
first failing predicate, which is what FitError aggregation and
preemption's unresolvable-reason filter (generic_scheduler.go:972)
consume.
"""

from __future__ import annotations

import functools
import time
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

import numpy as np

from . import encoding as enc
from ..utils import faultpoints
from .affinity import incoming_statics
from .filters import resource_fit, static_predicate_masks
from .topology import topo_statics
from .scores import (
    SCORE_STACK,
    SCORE_TOPK,
    W_AFFINITY,
    W_AVOID,
    W_BALANCED,
    W_COMPACT,
    W_IMAGE,
    W_INTERPOD,
    W_LEAST,
    W_MOST,
    W_SPREAD,
    W_TAINT,
    W_TOPO_SPREAD,
    ScoreDeco,
    floor_div,
    stack_weights,
    _domain_colocation,
    _domain_onehot,
    balanced_allocation,
    image_locality,
    least_requested,
    most_requested,
    node_affinity_raw,
    normalize_reduce,
    prefer_avoid,
    spread_counts,
    spread_reduce,
    taint_intolerable_raw,
)


class Weights(NamedTuple):
    """Priority weights (reference defaults:
    algorithmprovider/defaults/defaults.go:219 — weight 1 each, except
    NodePreferAvoidPods at 10000; ImageLocality/MostRequested optional)."""

    least_requested: float = 1.0
    balanced: float = 1.0
    most_requested: float = 0.0
    node_affinity: float = 1.0
    taint_toleration: float = 1.0
    selector_spread: float = 1.0
    prefer_avoid: float = 10000.0
    image_locality: float = 0.0
    interpod: float = 1.0
    # forward-ported topology planes (ops/topology.py): PodTopologySpread
    # skew score + gang rack/superpod compactness & accel-gen steering
    topology_spread: float = 1.0
    topology_compactness: float = 1.0
    # HardPodAffinitySymmetricWeight (componentconfig default 1,
    # pkg/apis/componentconfig/types.go)
    hard_pod_affinity: float = 1.0


class WaveResult(NamedTuple):
    chosen: jnp.ndarray  # i32 [P]  node index or -1
    score: jnp.ndarray  # f32 [P]  winning weighted score (-1 if none)
    feasible_count: jnp.ndarray  # i32 [P]
    fail_counts: jnp.ndarray  # i32 [Q, P]  first-fail per predicate
    masks: jnp.ndarray  # bool [Q, P, N]  per-predicate pass masks
    rr_end: jnp.ndarray  # i32  round-robin counter after the wave
    # per-priority decomposition of the decision (collect_scores only;
    # None otherwise — the compiled program is then byte-identical to
    # the pre-observatory kernel)
    deco: Optional[ScoreDeco] = None
    # numeric-integrity sentinel: bool [P] — False where the pod's own
    # inputs (req/nonzero) or its winning score are non-finite. A NaN
    # req poisons the scan's usage carry through `preq * 0.0` even for
    # an unplaced pod, silently shifting every LATER pod's placement —
    # the host must discard the whole round and quarantine the flagged
    # pods (sched/scheduler.py poison-work isolation). Computed inside
    # the same program and fetched alongside `chosen`: zero extra
    # dispatch. The hostwave twin mirrors it bitwise.
    finite: Optional[jnp.ndarray] = None


# -- device telemetry --------------------------------------------------------
#
# The scheduler registers its Metrics here (set_telemetry) so every
# kernel dispatch can account jit program-cache hits/misses per shape
# bucket and the compile seconds a miss costs — the "why did this round
# take 8s" answer is usually "it recompiled". Process-global because the
# jit compile cache itself is process-global; the last scheduler built
# owns the series (one scheduler per process everywhere real).
_TELEMETRY = None
_COMPILED: set = set()
# Device-dispatch watchdog (utils/watchdog.py), registered by the
# scheduler exactly like the telemetry hook (set_watchdog; last
# scheduler built owns it, None disables). Every dispatch through
# record_dispatch then runs under a deadline budget: a dispatch that
# exceeds cfg.wave_deadline_s is abandoned with DispatchTimeout so a
# wedged XLA runtime can never wedge the scheduling loop.
_WATCHDOG = None
# Active mesh device names (set_devices; () = single device / no mesh).
# Two consumers: the `device.lost` fault point receives the tuple as its
# payload so per-device chaos (sched/breaker.py lost_device_fault) fires
# only while its victim is actually in the dispatch set, and failed
# dispatches are attributed to a culprit device for the
# scheduling_errors_total{stage=dispatch, device=...} series.
_DEVICES: tuple = ()


def set_telemetry(metrics) -> None:
    global _TELEMETRY
    _TELEMETRY = metrics


def set_watchdog(watchdog) -> None:
    global _WATCHDOG
    _WATCHDOG = watchdog


def set_devices(devices) -> None:
    """Register the device names the scheduler currently dispatches
    across (the active mesh's flattened device list; ()/None clears).
    Refreshed on every mesh reform."""
    global _DEVICES
    _DEVICES = tuple(str(d) for d in (devices or ()))


def _attribute_device(exc: BaseException) -> str:
    """Culprit device name for a failed dispatch: the exception carries
    one (DeviceLost.device), or its text names exactly one active
    device as an exact token (a name followed by another digit is a
    different device's id — 'TPU_1' inside 'TPU_10' is not a hit);
    'unknown' otherwise. Token logic mirrors sched/breaker.py
    device_name_hits (kept local: ops must not import sched)."""
    dev = getattr(exc, "device", None)
    if isinstance(dev, str) and dev in _DEVICES:
        return dev
    text = str(exc)
    hits = []
    for d in _DEVICES:
        if not d:
            continue
        idx = text.find(d)
        while idx != -1:
            end = idx + len(d)
            if end == len(text) or not text[end].isdigit():
                hits.append(d)
                break
            idx = text.find(d, idx + 1)
    return hits[0] if len(hits) == 1 else "unknown"


def _count_dispatch_error(tel, exc: BaseException) -> None:
    """Label one failed dispatch on scheduling_errors_total with a
    bounded device value (the active device set + 'unknown' — never
    free text, so the family stays metrics-hygiene clean)."""
    if tel is None:
        return
    from ..utils.metrics import bounded_label

    tel.scheduling_errors.labels(
        stage="dispatch",
        device=bounded_label(_attribute_device(exc), _DEVICES,
                             other="unknown")).inc()


def _device_count(x) -> int:
    """How many devices the input is committed across (1 for numpy /
    single-device arrays): shardings participate in the jit cache key,
    so a mesh-sharded dispatch must not be misclassified as a cache hit
    of the single-device program (or vice versa)."""
    sharding = getattr(x, "sharding", None)
    if sharding is None:
        return 1
    try:
        return len(sharding.device_set)
    except Exception:
        return 1


def dispatch_bucket(nt, pm, tt, kw, lead=()) -> tuple:
    """The shape bucket a dispatch compiles under: every dimension that
    participates in the jit cache key in practice — the caller's wave/pod
    rows (`lead`), node rows, pod-matrix and term-table caps (vocab
    growth retraces!), the static num_label_values/num_zones, the mesh
    device count (sharded and unsharded dispatches compile separately),
    and the formulation statics. Weight VALUES are deliberately excluded:
    the traced weight_vec swaps freely inside one program, and the static
    gating Weights is profile-constant — an activation-set change would
    mint one mislabelled 'hit', not a recurring lie. The weight_vec
    PRESENCE is in the key (None vs array is a different pytree, hence a
    different compiled program)."""
    return tuple(lead) + (
        nt.valid.shape[0], pm.node.shape[0], tt.node.shape[0],
        _device_count(nt.valid),
        int(kw.get("num_label_values", 64)), int(kw.get("num_zones", 0)),
        int(bool(kw.get("has_ipa", False))),
        int(bool(kw.get("has_ts", False))),
        int(bool(kw.get("use_pallas", False))),
        int(bool(kw.get("collect_scores", False))),
        int(kw.get("weight_vec") is not None),
        0 if kw.get("nom") is None else int(kw["nom"].prio.shape[0]))


def record_dispatch(program: str, bucket_key: tuple, fn):
    """Run one kernel dispatch, classifying it as a program-cache hit or
    miss by shape bucket and timing the miss (trace+lower+compile happen
    synchronously inside the first call at a new shape). With neither
    telemetry nor a watchdog registered this costs one kernel.hang
    fault-point check (a single dict read when inactive) and nothing
    else.

    This is also the watchdog seam (set_watchdog): with a watchdog
    registered the dispatch runs on a deadline-budgeted worker thread
    and raises DispatchTimeout on abandonment — unwarmed buckets get
    the compile-scaled budget, since a first-shape compile is not a
    hang. The `kernel.hang` fault point fires INSIDE the guarded
    dispatch (a `latency` fault there models a wedged XLA dispatch that
    silently never returns — the failure mode the breaker's
    exception-only accounting can't see)."""
    tel = _TELEMETRY
    wd = _WATCHDOG
    if tel is None and (wd is None or not wd.armed()):
        # fully unarmed hot path: the chaos seams still fire, nothing
        # else is paid. (_COMPILED is not fed here; a watchdog armed
        # later merely grants warm programs the larger compile-scaled
        # budget once — benign in the safe direction.)
        faultpoints.fire("kernel.hang")
        faultpoints.fire("device.lost", payload=_DEVICES or None)
        faultpoints.fire("device.oom", payload=_DEVICES or None)
        return fn()
    key = (program,) + bucket_key
    miss = key not in _COMPILED
    inner = fn

    def dispatch():
        faultpoints.fire("kernel.hang")
        # per-device chaos: the payload names the devices this dispatch
        # runs across, so a corrupt-mode lost_device_fault fires only
        # while its victim is still in the active mesh
        faultpoints.fire("device.lost", payload=_DEVICES or None)
        # capacity chaos: an HBM RESOURCE_EXHAUSTED at the dispatch —
        # classified as a capacity fault upstream, never a device fault
        faultpoints.fire("device.oom", payload=_DEVICES or None)
        return inner()

    if wd is not None and wd.armed():
        fn = lambda: wd.run(dispatch, program=program, warm=not miss)
    else:
        fn = dispatch
    if tel is None:
        out = fn()
        _COMPILED.add(key)  # warm-tracking feeds the watchdog's scaling
        return out
    t0 = time.monotonic()
    try:
        out = fn()
    except Exception as e:
        # device-attributed error accounting (the mesh fault plane's
        # dashboard signal): stage=dispatch, device bounded to the
        # active set + "unknown"
        _count_dispatch_error(tel, e)
        raise
    _COMPILED.add(key)
    bucket = "x".join(str(d) for d in bucket_key)
    tel.device_jit_events.labels(
        program=program, bucket=bucket,
        event="miss" if miss else "hit").inc()
    if miss:
        dt = time.monotonic() - t0
        tel.device_jit_compile_seconds.observe(dt)
        from ..utils import tracing

        tracing.event("jit_compile", program=program, bucket=bucket,
                      seconds=round(dt, 3))
    return out


def _nominated_use(nreq, ncnt, nprio, own, pprio, preq):
    """What the pods nominated to each node add to one pod's fit: the
    row of the nominated pods of priority >= the pod's, less the pod's
    own nomination. Returns (f32 [N, R], i32 [N])."""
    L = nprio.shape[0]
    lvl = jnp.sum((nprio < pprio).astype(jnp.int32))
    on = lvl < L
    row = jnp.minimum(lvl, L - 1)
    mine = jnp.arange(nreq.shape[1], dtype=jnp.int32) == own
    add_req = (jnp.where(on, nreq[row], 0.0)
               - jnp.where(mine[:, None], preq[None, :], 0.0))
    add_cnt = jnp.where(on, ncnt[row], 0) - mine.astype(jnp.int32)
    return add_req, add_cnt


def _drop_nominated(nreq, ncnt, nprio, own, pprio, preq, placed):
    """A pod that placed leaves every nomination row it was in (1.11
    deletes a nominated pod's nomination when it is assumed)."""
    mine = jnp.arange(nreq.shape[1], dtype=jnp.int32) == own
    drop = (nprio <= pprio)[:, None] & mine[None, :] & placed
    nreq = nreq - jnp.where(drop[:, :, None], preq[None, None, :], 0.0)
    ncnt = ncnt - drop.astype(jnp.int32)
    return nreq, ncnt


def pallas_default() -> bool:
    """Use the fused Pallas filter kernel? On the TPU backend only."""
    return jax.default_backend() == "tpu"


def _wave_body(nt: enc.NodeTensors, pm: enc.PodMatrix, tt: enc.TermTable,
               pb: enc.PodBatch, extra_mask, rr_start, extra_scores,
               weights: Weights, num_zones: int, num_label_values: int,
               has_ipa: bool, use_pallas: bool, pallas_interpret: bool,
               usage_in=None, taint_ports=None, collect_scores: bool = False,
               weight_vec=None, has_ts: bool = False, nom=None):
    """Shared wave computation. usage_in: optional (requested, nonzero,
    pod_count) overriding nt's usage columns — the device-resident carry
    that lets consecutive waves chain without a host roundtrip.
    taint_ports: precomputed (taints_ok, ports_ok) [P, N] from the
    round path's hoisted Pallas pass. Returns (WaveResult, usage_out).

    nom: optional enc.Nominations (own [P]): each pod's resource fit
    counts the pods nominated to each node, and a nominated pod that
    places drops out of the rows for the pods after it. usage_out then
    ends with the updated (req, count) rows. None compiles nothing in.

    collect_scores (static): keep the per-priority score stack alive
    through the scan and emit, per pod, the SCORE_STACK contributions of
    the chosen node plus the top-SCORE_TOPK candidates by weighted total
    (WaveResult.deco). The weighted-sum feeding argmax is the SAME
    accumulation expression either way, so placements are bit-identical;
    off, the program is byte-identical to the pre-observatory kernel.

    weight_vec: optional TRACED f32 [S] SCORE_STACK-aligned weight
    vector. When given, it supplies the multipliers of the weighted sum
    — the live WeightProfile hot-swap path (sched/weights.py): a new
    vector is a new array value inside the SAME compiled program, so a
    swap or rollback between rounds never recompiles. The static
    `weights` still gates which score planes are compiled in (a plane
    the profile activates past a 0 static weight needs a gating bump —
    gate_weights — and that one activation-set change does retrace).
    None (direct kernel callers, what-ifs) folds stack_weights(weights)
    in as a trace-time constant — numerically identical f32 ops."""
    N = nt.valid.shape[0]
    P = pb.req.shape[0]
    R = nt.alloc.shape[1]
    is_core = jnp.arange(R) < enc.RES_FIXED
    # the per-wave dense work ahead of the serial scan, named for the
    # profiler (benchmark/program_trace.py reads the scopes)
    with jax.named_scope("wave_dense"):
        masks = static_predicate_masks(nt, pb, is_core, use_pallas,
                                       pallas_interpret,
                                       taint_ports)  # [Q-1, P, N]
        # placeholder rows for the scan-filled predicates (PodTopologySpread,
        # MatchInterPodAffinity), in DEVICE_PREDICATES order
        ts_placeholder = jnp.ones((1, P, N), bool)
        ipa_placeholder = jnp.ones((1, P, N), bool)
        masks = jnp.concatenate([masks, ts_placeholder, ipa_placeholder,
                                 extra_mask[None]], axis=0)
        res_i = enc.PRED_IDX["PodFitsResources"]
        ipa_i = enc.PRED_IDX["MatchInterPodAffinity"]
        ts_i = enc.PRED_IDX["PodTopologySpread"]
        static_nonres = jnp.all(masks.at[res_i].set(True), axis=0)  # [P, N]
        alloc2 = nt.alloc[:, :2]
        ipa = (incoming_statics(nt, pm, tt, pb, num_label_values,
                                weights.hard_pod_affinity)
               if has_ipa else None)
        topo = (topo_statics(nt, pm, pb, num_label_values) if has_ts else None)
        lv_ids = jnp.arange(num_label_values, dtype=jnp.int32)
        # node-in-domain planes for the scan's per-domain sums (rack and
        # superpod ids intern into the zones vocabulary, so num_zones
        # bounds all three)
        zone_oh = _domain_onehot(nt.zone_id, num_zones)
        rack_oh = _domain_onehot(nt.rack_id, num_zones)
        superpod_oh = _domain_onehot(nt.superpod_id, num_zones)

        w = weights
        # the weighted-sum multipliers: the traced weight_vec when the live
        # profile machinery supplies one, the static weights folded to a
        # trace-time constant otherwise — wv[s] is an f32 scalar either way,
        # so the arithmetic (and the twin's mirror of it) is identical
        wv = (weight_vec if weight_vec is not None
              else jnp.asarray(stack_weights(w)))
        # raw planes also feed the decomposition: under collect_scores they
        # are computed even at weight 0 (a 0-weight priority still explains
        # the decision it did not influence — zeroed planes would fabricate
        # flat 0 / MAX_PRIORITY rows in /debug/score and the ledger)
        aff_raw = (node_affinity_raw(nt, pb)
                   if w.node_affinity or collect_scores else None)
        taint_raw = (taint_intolerable_raw(nt, pb)
                     if w.taint_toleration or collect_scores else None)
        spread_cnt = (spread_counts(pm, pb, N)
                      if w.selector_spread or collect_scores
                      else jnp.zeros(static_nonres.shape, jnp.int32))
        static_score = jnp.zeros(static_nonres.shape, jnp.float32)
        if w.image_locality:
            static_score = static_score + wv[W_IMAGE] * image_locality(nt, pb)
        if w.prefer_avoid:
            static_score = static_score + wv[W_AVOID] * prefer_avoid(nt, pb)
        if extra_scores is not None:
            static_score += extra_scores
        P = pb.req.shape[0]
        if aff_raw is None:
            aff_raw = jnp.zeros((P, N), jnp.float32)
        if taint_raw is None:
            taint_raw = jnp.zeros((P, N), jnp.float32)
        if collect_scores:
            # RAW per-priority planes for the decomposition, computed
            # regardless of weights (a 0-weight priority still explains the
            # decision it did not influence); never folded into the total
            avoid_full = prefer_avoid(nt, pb)
            img_full = image_locality(nt, pb)
            extra_full = (extra_scores if extra_scores is not None
                          else jnp.zeros((P, N), jnp.float32))

    usage0 = usage_in if usage_in is not None else (
        nt.requested, nt.nonzero, nt.pod_count)
    # wave-start pod counts: the compactness plane measures co-location
    # against placements made THIS wave (the gang's members), not the
    # cluster's standing population
    pod_count0 = usage0[2]

    def step(carry, x):
        if nom is not None:
            carry, (nreq_c, ncnt_c) = carry[:-2], carry[-2:]
            x, nown = x[:-1], x[-1]
        req_c, nz_c, cnt_c, rr, placed = carry
        if collect_scores:
            x, (avoid_row, img_row, extra_row) = x[:-3], x[-3:]
        if has_ts:
            x, (tsv, tsh, tss, tdom, tcnt, tpres, twm, tself) = x[:-8], x[-8:]
        x, pprio = x[:-1], x[-1]
        if has_ipa:
            (i, preq, pnz, mask_sn, araw, traw, scnt, sscore, pvalid,
             sym_row, okaff_row, anyaff_s, banti_row, counts_row,
             dra_row, drn_row, wmaff_row, wmanti_row, wmT_row,
             ra_has_i, rn_has_i, ra_self_i) = x
        else:
            (i, preq, pnz, mask_sn, araw, traw, scnt, sscore, pvalid) = x
        if nom is None:
            fits = resource_fit(nt.alloc, nt.allowed_pods, req_c, cnt_c,
                                preq[None, :], is_core)[0]  # [N]
        else:
            add_req, add_cnt = _nominated_use(nreq_c, ncnt_c, nom.prio,
                                              nown, pprio, preq)
            fits = resource_fit(nt.alloc, nt.allowed_pods, req_c + add_req,
                                cnt_c + add_cnt, preq[None, :], is_core)[0]
        feasible = mask_sn & fits & nt.valid & pvalid
        if has_ipa:
            active = placed >= 0
            safe_pl = jnp.clip(placed, 0)
            # incoming required affinity vs pods placed earlier this wave
            pl_dom = dra_row[safe_pl]  # [P] placement domain under MY aff tk
            src = wmaff_row & active & (pl_dom > 0)
            wave_aff = jnp.any(
                src[:, None] & (pl_dom[:, None] == dra_row[None, :]), axis=0
            ) & (dra_row > 0)
            # bootstrap existence check is topology-independent
            # (predicates.go:1410: matchingPods counts props matches on ANY
            # node, labeled or not)
            any_aff = anyaff_s | jnp.any(wmaff_row & active)
            ok_aff = okaff_row | wave_aff | (~any_aff & ra_self_i)
            ok_aff = jnp.where(ra_has_i, ok_aff, True)
            # incoming required anti-affinity vs wave placements
            pl_dom_n = drn_row[safe_pl]
            srcn = wmanti_row & active & (pl_dom_n > 0)
            wave_anti = jnp.any(
                srcn[:, None] & (pl_dom_n[:, None] == drn_row[None, :]), axis=0
            ) & (drn_row > 0)
            ok_anti = ~(rn_has_i & (banti_row | wave_anti))
            # symmetry: wave pod j's required anti terms vs me, under j's tk
            pd_sym = jnp.take_along_axis(
                node_dom_rn_full, safe_pl[:, None], axis=1)[:, 0]  # [P]
            srcs = wmT_row & active & (pd_sym > 0)
            sym_wave = jnp.any(
                srcs[:, None] & (pd_sym[:, None] == node_dom_rn_full)
                & (node_dom_rn_full > 0), axis=0)
            ipa_ok = ~(sym_row | sym_wave) & ok_aff & ok_anti
            feasible &= ipa_ok
        else:
            ipa_ok = jnp.ones_like(feasible)
        if has_ts:
            # PodTopologySpread vs resident pods + same-wave placements
            # (upstream's assume semantics, like the ipa block above)
            active_t = placed >= 0
            safe_pl_t = jnp.clip(placed, 0)
            pl_dom_ts = tdom[:, safe_pl_t]  # [TS, P] placement domains
            addm = twm & active_t[None, :] & (pl_dom_ts > 0)
            onehot = ((pl_dom_ts[:, :, None] == lv_ids[None, None, :])
                      & addm[:, :, None])
            # ktpu: allow[f32-reduction] integer-valued one-hot sum, exact in f32 in any association, twin-mirrored
            cnt_dyn = tcnt + jnp.sum(onehot.astype(jnp.float32), axis=1)
            cnt_at = jnp.take_along_axis(cnt_dyn, tdom, axis=1)  # [TS, N]
            key_ok = tdom > 0  # node has the constraint's topology key
            anyp = jnp.any(tpres, axis=1)  # [TS]
            minm = jnp.where(
                anyp,
                jnp.min(jnp.where(tpres, cnt_dyn, jnp.inf), axis=1), 0.0)
            # skew = count-if-placed-here minus global min; self counts
            # only when the pod matches its own selector (selfMatchNum)
            cand = cnt_at + tself[:, None].astype(jnp.float32)
            hard = (tsv & tsh)[:, None]
            ok_rows = jnp.where(
                hard,
                key_ok & ((cand - minm[:, None]) <= tss[:, None]), True)
            ts_ok = jnp.all(ok_rows, axis=0)  # [N]
            feasible &= ts_ok
        else:
            ts_ok = None
        total = sscore
        fscore = None
        if has_ipa and (w.interpod or collect_scores):
            cmasked = jnp.where(feasible, counts_row, 0.0)
            cmin = jnp.minimum(jnp.min(cmasked), 0.0)
            cmax = jnp.maximum(jnp.max(cmasked), 0.0)
            crange = cmax - cmin
            fscore = jnp.where(crange > 0,
                               floor_div(10.0 * (counts_row - cmin) / crange),
                               0.0)
        if has_ipa and w.interpod:
            total = total + wv[W_INTERPOD] * fscore
        aff_n = (normalize_reduce(araw, feasible, False)
                 if w.node_affinity or collect_scores else None)
        if w.node_affinity:
            total = total + wv[W_AFFINITY] * aff_n
        taint_n = (normalize_reduce(traw, feasible, True)
                   if w.taint_toleration or collect_scores else None)
        if w.taint_toleration:
            total = total + wv[W_TAINT] * taint_n
        spread_n = (spread_reduce(scnt, feasible, nt.zone_id, zone_oh)
                    if w.selector_spread or collect_scores else None)
        if w.selector_spread:
            total = total + wv[W_SPREAD] * spread_n
        lr = (least_requested(nz_c, alloc2, pnz)
              if w.least_requested or collect_scores else None)
        if w.least_requested:
            total = total + wv[W_LEAST] * lr
        ba = (balanced_allocation(nz_c, alloc2, pnz)
              if w.balanced or collect_scores else None)
        if w.balanced:
            total = total + wv[W_BALANCED] * ba
        mr = (most_requested(nz_c, alloc2, pnz)
              if w.most_requested or collect_scores else None)
        if w.most_requested:
            total = total + wv[W_MOST] * mr
        ts_n = None
        if has_ts and (w.topology_spread or collect_scores):
            # raw spread score: headroom below the fullest domain — a
            # node in a less-crowded domain scores higher; key-less
            # nodes score 0 (upstream scores them lowest)
            maxm = jnp.where(
                anyp,
                jnp.max(jnp.where(tpres, cnt_dyn, -jnp.inf), axis=1), 0.0)
            # ktpu: allow[f32-reduction] TS-axis (2 rows) of integer-valued f32, twin-mirrored
            ts_raw = jnp.sum(
                jnp.where(key_ok & tsv[:, None],
                          jnp.maximum(maxm[:, None] - cnt_at, 0.0), 0.0),
                axis=0)
            ts_n = normalize_reduce(ts_raw, feasible, False)
        if has_ts and w.topology_spread:
            total = total + wv[W_TOPO_SPREAD] * ts_n
        compact_n = None
        if w.topology_compactness or collect_scores:
            # gang compactness + heterogeneity steering: count this
            # wave's placements per rack/superpod, prefer co-located
            # nodes with a rack-over-superpod gradient, and bias
            # priority-bearing (throughput-sensitive) pods toward newer
            # accelerator generations. All-zero columns make this plane
            # exactly 0.
            wave_placed = (cnt_c - pod_count0).astype(jnp.float32)
            rackc = _domain_colocation(wave_placed, nt.rack_id, rack_oh)
            spc = _domain_colocation(wave_placed, nt.superpod_id,
                                    superpod_oh)
            gen = nt.accel_gen.astype(jnp.float32) * (pprio > 0)
            compact_raw = 3.0 * rackc + spc + gen
            compact_n = normalize_reduce(compact_raw, feasible, False)
        if w.topology_compactness:
            total = total + wv[W_COMPACT] * compact_n
        sm = jnp.where(feasible, total, -1.0)
        best = jnp.max(sm)
        has = best >= 0
        ties = feasible & (sm == best)
        k = jnp.maximum(jnp.sum(ties), 1)
        rank = jnp.cumsum(ties.astype(jnp.int32)) - 1
        chosen = jnp.argmax(ties & (rank == rr % k)).astype(jnp.int32)
        chosen = jnp.where(has, chosen, -1)
        safe = jnp.maximum(chosen, 0)
        gain = jnp.where(has, 1.0, 0.0)
        req_c = req_c.at[safe].add(preq * gain)
        nz_c = nz_c.at[safe].add(pnz * gain)
        cnt_c = cnt_c.at[safe].add(jnp.where(has, 1, 0))
        rr = rr + jnp.where(has, 1, 0)
        placed = placed.at[i].set(chosen)
        out = (chosen, best, fits, jnp.sum(feasible.astype(jnp.int32)), ipa_ok)
        if has_ts:
            out = out + (ts_ok,)
        if collect_scores:
            # SCORE_STACK-ordered raw planes [S, N]; the chosen node's
            # column and the top-k candidates' columns ride out of the
            # scan — everything else about the decision is discarded
            # exactly as before
            zr = jnp.zeros_like(total)
            parts = jnp.stack([
                lr, ba, mr, aff_n, taint_n, spread_n,
                avoid_row, img_row,
                fscore if fscore is not None else zr,
                ts_n if ts_n is not None else zr,
                compact_n if compact_n is not None else zr,
                extra_row,
            ])
            kk = min(SCORE_TOPK, N)
            top_vals, top_idx = lax.top_k(sm, kk)
            out = out + (parts[:, safe], top_idx.astype(jnp.int32),
                         top_vals, jnp.take(parts, top_idx, axis=1))
        if nom is not None:
            return (req_c, nz_c, cnt_c, rr, placed) + _drop_nominated(
                nreq_c, ncnt_c, nom.prio, nown, pprio, preq, has), out
        return (req_c, nz_c, cnt_c, rr, placed), out

    carry0 = (usage0[0], usage0[1], usage0[2],
              jnp.asarray(rr_start, jnp.int32), jnp.full((P,), -1, jnp.int32))
    if nom is not None:
        carry0 = carry0 + (nom.req, nom.count)
    ii = jnp.arange(P, dtype=jnp.int32)
    if has_ipa:
        node_dom_rn_full = ipa.node_dom_rn
        xs = (ii, pb.req, pb.nonzero, static_nonres, aff_raw, taint_raw,
              spread_cnt, static_score, pb.valid,
              ipa.sym_blocked, ipa.ok_aff, ipa.any_aff, ipa.blocked_anti,
              ipa.counts, ipa.node_dom_ra, ipa.node_dom_rn,
              ipa.wm_aff, ipa.wm_anti, ipa.wm_anti.T,
              pb.ra_has, pb.rn_has, pb.ra_self)
    else:
        xs = (ii, pb.req, pb.nonzero, static_nonres, aff_raw, taint_raw,
              spread_cnt, static_score, pb.valid)
    xs = xs + (pb.prio,)
    if has_ts:
        xs = xs + (pb.ts_valid, pb.ts_hard, pb.ts_skew, topo.node_dom,
                   topo.counts, topo.present, topo.wm, topo.selfm)
    if collect_scores:
        xs = xs + (avoid_full, img_full, extra_full)
    if nom is not None:
        xs = xs + (nom.own,)
    with jax.named_scope("pod_scan"):
        carry_end, outs = lax.scan(step, carry0, xs)
    req_end, nz_end, cnt_end, rr_end = carry_end[:4]
    chosen, best, dyn_fits, feas_cnt, ipa_masks = outs[:5]
    rest = outs[5:]
    ts_masks = None
    if has_ts:
        ts_masks, rest = rest[0], rest[1:]
    deco = None
    if collect_scores:
        cparts, tidx, tvals, tparts = rest
        deco = ScoreDeco(chosen_parts=cparts, top_idx=tidx,
                         top_vals=tvals, top_parts=tparts)

    masks = masks.at[res_i].set(dyn_fits)
    if has_ts:
        masks = masks.at[ts_i].set(ts_masks)
    if has_ipa:
        masks = masks.at[ipa_i].set(ipa_masks)
    # short-circuit first-fail attribution in predicate order
    prefix_ok = jnp.cumprod(masks.astype(jnp.int8), axis=0).astype(bool)
    first = jnp.concatenate(
        [jnp.ones((1,) + masks.shape[1:], bool), prefix_ok[:-1]], axis=0)
    first_fail = ~masks & first & nt.valid[None, None, :]
    fail_counts = jnp.sum(first_fail.astype(jnp.int32), axis=-1)  # [Q, P]
    # numeric-integrity sentinel (see WaveResult.finite): per-pod, over
    # the pod's OWN inputs plus its winning score — a NaN injected via
    # extra_scores surfaces through jnp.max's NaN propagation in `best`,
    # while input NaN names the culprit directly even when the pod never
    # placed. Pad rows carry zeroed inputs and best == -1: always finite.
    finite = (jnp.all(jnp.isfinite(pb.req), axis=1)
              & jnp.all(jnp.isfinite(pb.nonzero), axis=1)
              & jnp.isfinite(best))
    res = WaveResult(chosen=chosen, score=best, feasible_count=feas_cnt,
                     fail_counts=fail_counts, masks=masks, rr_end=rr_end,
                     deco=deco, finite=finite)
    return res, (req_end, nz_end, cnt_end) + tuple(carry_end[5:])


def _drop_no_nominations(kw: dict) -> None:
    """jit keys a call by its keyword names, so nom=None and no nom would
    be two cache entries of one program: a warm-up that passes no nom
    would leave the first round that passes None to compile again."""
    if "nom" in kw and kw["nom"] is None:
        del kw["nom"]


def schedule_wave(*args, **kw):
    """Entry point for the per-wave program. The fault point fires HERE,
    outside the jit boundary — inside `_schedule_wave` it would only run
    at trace time, so once the compile cache warms an injected fault
    would silently stop firing."""
    faultpoints.fire("kernel.wave")
    nt, pm, tt, pb = args[0], args[1], args[2], args[3]
    # has_ts is static like has_ipa: derived host-side from the wave's
    # featurized batch (numpy in every real call path) so spread-free
    # waves keep the exact pre-topology program
    kw.setdefault("has_ts", bool(np.any(np.asarray(pb.ts_valid))))
    _drop_no_nominations(kw)
    bucket = dispatch_bucket(nt, pm, tt, kw, lead=(pb.req.shape[0],))
    return record_dispatch("wave", bucket,
                           lambda: _schedule_wave(*args, **kw))


@functools.partial(jax.jit, static_argnames=(
    "weights", "num_zones", "num_label_values", "has_ipa", "has_ts",
    "use_pallas", "pallas_interpret", "collect_scores"))
def _schedule_wave(nt: enc.NodeTensors, pm: enc.PodMatrix, tt: enc.TermTable,
                   pb: enc.PodBatch, extra_mask, rr_start, extra_scores=None,
                   *, weights: Weights,
                   num_zones: int, num_label_values: int = 64,
                   has_ipa: bool = False, has_ts: bool = False,
                   use_pallas: bool = False,
                   pallas_interpret: bool = False,
                   collect_scores: bool = False,
                   weight_vec=None, nom=None) -> WaveResult:
    """extra_mask: bool [P, N] — host-evaluated predicates (NoDiskConflict,
    volume predicates) for the rare pods that need them; all-True rows for
    everyone else. Appended to the mask stack as a final "HostPlugins"
    pseudo-predicate for failure attribution.

    extra_scores: optional f32 [P, N] — host-evaluated Score contributions
    (policy host priorities, HTTP extender Prioritize), pre-multiplied by
    their weights; added to the device weighted sum before argmax
    (reference: generic_scheduler.go:650 folds extender priorities into
    the same result list).

    has_ipa (static): compiles the inter-pod affinity path in. When no
    affinity terms exist anywhere (the common case), the False variant
    keeps the program identical to the affinity-free kernel.

    weight_vec: optional traced f32 [S] live weight vector (see
    _wave_body) — the hot-swap path never recompiles on a value change.

    nom: optional enc.Nominations (own [P]) the pods' fit counts (see
    _wave_body); None keeps the program without them."""
    res, _ = _wave_body(nt, pm, tt, pb, extra_mask, rr_start, extra_scores,
                        weights, num_zones, num_label_values, has_ipa,
                        use_pallas, pallas_interpret,
                        collect_scores=collect_scores,
                        weight_vec=weight_vec, has_ts=has_ts, nom=nom)
    return res


def _stage_placements(pm: enc.PodMatrix, tt: enc.TermTable, chosen,
                      pm_rows, term_rows):
    """Flip this wave's placements into the pod matrix / term table ON
    DEVICE so the next chained wave sees them (spreading counts read pm;
    required (anti)affinity reads tt)."""
    ok = (chosen >= 0) & (pm_rows >= 0)
    safe_choice = jnp.clip(chosen, 0)
    # pad/unplaced entries scatter to an out-of-bounds row and are
    # DROPPED (mode="drop") — clipping them to row 0 would race real
    # updates to row 0 under duplicate-index scatter ordering
    M = pm.node.shape[0]
    target = jnp.where(ok, pm_rows, M)
    pm2 = pm._replace(
        node=pm.node.at[target].set(safe_choice, mode="drop"),
        valid=pm.valid.at[target].set(True, mode="drop"))
    TPP = term_rows.shape[1]
    E = tt.node.shape[0]
    tok = ok[:, None] & (term_rows >= 0)
    ttarget = jnp.where(tok, term_rows, E).ravel()
    tchoice = jnp.repeat(safe_choice, TPP)
    tt2 = tt._replace(
        node=tt.node.at[ttarget].set(tchoice, mode="drop"),
        valid=tt.valid.at[ttarget].set(True, mode="drop"))
    return pm2, tt2


# The round is the device-resident pipeline driver (scan over resident
# waves); degraded mode deliberately chunks schedule_wave_host instead —
# whole-round residency is a device-only optimization, not semantics
# (tests/test_hostwave.py asserts breaker-open placements match the
# clean device scheduler's).
# ktpu: allow[twin-coverage] round residency is device-only by design
def schedule_round(*args, **kw):
    """Entry point for the device-resident round. The fault point fires
    HERE, outside the jit boundary — inside `_schedule_round` it would
    only run on a trace-cache miss, making injected faults vanish after
    the first compile."""
    faultpoints.fire("kernel.round")
    nt, pm, tt, pbs = args[0], args[1], args[2], args[3]
    kw.setdefault("has_ts", bool(np.any(np.asarray(pbs.ts_valid))))
    _drop_no_nominations(kw)
    bucket = dispatch_bucket(nt, pm, tt, kw,
                             lead=(pbs.req.shape[0], pbs.req.shape[1]))
    return record_dispatch("round", bucket,
                           lambda: _schedule_round(*args, **kw))


@functools.partial(jax.jit, static_argnames=(
    "weights", "num_zones", "num_label_values", "has_ipa", "has_ts",
    "use_pallas", "pallas_interpret", "collect_scores"))
def _schedule_round(nt: enc.NodeTensors, pm: enc.PodMatrix,
                    tt: enc.TermTable, pbs: enc.PodBatch,
                    usage, rr_start, pm_rows, term_rows, *,
                   weights: Weights, num_zones: int,
                   num_label_values: int = 64, has_ipa: bool = False,
                   has_ts: bool = False,
                   use_pallas: bool = False, pallas_interpret: bool = False,
                   collect_scores: bool = False, weight_vec=None, nom=None):
    """An ENTIRE scheduling round as one program: lax.scan over W waves,
    each wave a full _wave_body pass whose placements are staged into the
    pod matrix / term table carries before the next wave runs.

    One program per round instead of one per wave: each dispatch and
    each device->host fetch is a host/device synchronization (on the
    v5e about 0.6 ms dispatch-to-ready for a trivial program, 1 ms with
    a scalar fetch: CHANGES.md PR 21), and the host's per-wave work
    would sit between them with the device idle. The round pays one
    dispatch and one fetch for all W waves.

    pbs: a PodBatch whose fields are stacked [W, ...] (padded waves have
    valid=False rows and schedule nothing). pm_rows [W, P] / term_rows
    [W, P, TPP]: pre-staged row ids (-1 pads). Host-plugin masks and
    extender scores are deliberately absent: waves needing them take the
    per-wave path (scheduler falls back when any mask row is non-trivial).

    use_pallas: the taint/port masks for EVERY wave are computed by one
    hoisted Pallas pass before the scan (the fused kernel faults under
    lax.scan on Mosaic; hoisting sidesteps that and amortizes the
    launch), then threaded through the scan as per-wave xs slices.
    Returns (chosen [W, P], fail_counts [W, Q, P], usage', rr_end,
    deco, finite) — deco a ScoreDeco of [W, P, ...] planes when
    collect_scores, None otherwise (the compiled program is then
    unchanged); finite the [W, P] numeric-integrity sentinel
    (WaveResult.finite semantics, pad waves all-True).

    nom: optional enc.Nominations with own [W, P]: every wave's fit
    counts the pods nominated to each node, and the rows carry from
    wave to wave, so a nominated pod placed in one wave no longer counts
    for the later ones. None compiles nothing in."""
    W = pbs.req.shape[0]
    P = pbs.req.shape[1]
    N = nt.valid.shape[0]
    ones = jnp.ones((P, N), bool)

    Q = len(enc.MASK_STACK_NAMES)
    S = len(SCORE_STACK)
    KK = min(SCORE_TOPK, N)

    def live_wave(carry, x):
        wnom = None
        if nom is not None:
            carry, (nreq_c, ncnt_c) = carry[:-2], carry[-2:]
            x, own = x[:-1], x[-1]
            wnom = enc.Nominations(nreq_c, ncnt_c, nom.prio, own)
        pm_c, tt_c, usage_c, rr_c = carry
        pb, rows, trows, tp = x
        res, usage_o = _wave_body(nt, pm_c, tt_c, pb, ones, rr_c, None,
                                  weights, num_zones, num_label_values,
                                  has_ipa, False, pallas_interpret,
                                  usage_in=usage_c, taint_ports=tp,
                                  collect_scores=collect_scores,
                                  weight_vec=weight_vec, has_ts=has_ts,
                                  nom=wnom)
        with jax.named_scope("stage_placements"):
            pm_o, tt_o = _stage_placements(pm_c, tt_c, res.chosen, rows,
                                           trows)
        out = (res.chosen, res.fail_counts)
        if collect_scores:
            out = out + tuple(res.deco)
        out = out + (res.finite,)
        return (pm_o, tt_o, usage_o[:3], res.rr_end) + usage_o[3:], out

    def padded_wave(carry, x):
        # bucket-padding waves skip the whole body at RUNTIME (lax.cond
        # executes one branch): without this, a padded ipa wave still
        # pays the full O(P*M) precompute — 31 pad waves in a 1-wave
        # warm round cost ~25s of device time for nothing
        with jax.named_scope("pad_wave"):
            out = (jnp.full((P,), -1, jnp.int32),
                   jnp.zeros((Q, P), jnp.int32))
            if collect_scores:
                # pad-wave deco: top_vals at -1 read as "infeasible" so the
                # host consumer skips them without a special case
                out = out + (jnp.zeros((P, S), jnp.float32),
                             jnp.zeros((P, KK), jnp.int32),
                             jnp.full((P, KK), -1.0, jnp.float32),
                             jnp.zeros((P, S, KK), jnp.float32))
            # pad waves schedule nothing: their sentinel rows are clean
            out = out + (jnp.ones((P,), bool),)
        return carry, out

    active = jnp.any(pbs.valid, axis=1)  # [W]
    if use_pallas:
        from .pallas_kernels import taint_ports_masks

        # one flattened [W*P] pod batch per chunk. The chunk is bounded
        # to 256 pod rows — the per-wave kernel's hardware-proven
        # configuration: its VMEM working set is ~6 live [Pp, n_block]
        # i32 tiles (guide: ~16MB VMEM/core; 256x512x4B = 512KB/tile),
        # so larger flat batches risk VMEM exhaustion for zero gain
        # (the launches all live inside this one compiled program)
        with jax.named_scope("taint_ports"):
            waves_per_chunk = max(1, 256 // P)
            t_parts, p_parts = [], []
            for s in range(0, W, waves_per_chunk):
                e = min(W, s + waves_per_chunk)
                flat = pbs._replace(
                    req=pbs.req[s:e].reshape((e - s) * P, -1),
                    tol_key=pbs.tol_key[s:e].reshape((e - s) * P, -1),
                    tol_val=pbs.tol_val[s:e].reshape((e - s) * P, -1),
                    tol_op=pbs.tol_op[s:e].reshape((e - s) * P, -1),
                    tol_effect=pbs.tol_effect[s:e].reshape((e - s) * P, -1),
                    ports=pbs.ports[s:e].reshape((e - s) * P, -1))
                t, po = taint_ports_masks(nt, flat,
                                          interpret=pallas_interpret)
                t_parts.append(t.reshape(e - s, P, N))
                p_parts.append(po.reshape(e - s, P, N))
            taints_all = jnp.concatenate(t_parts, axis=0)
            ports_all = jnp.concatenate(p_parts, axis=0)

        def wave(carry, x):
            pb, rows, trows, act, ta, po = x[:6]
            return lax.cond(act, live_wave, padded_wave, carry,
                            (pb, rows, trows, (ta, po)) + x[6:])

        xs = (pbs, pm_rows, term_rows, active, taints_all, ports_all)
    else:
        def wave(carry, x):
            pb, rows, trows, act = x[:4]
            return lax.cond(act, live_wave, padded_wave, carry,
                            (pb, rows, trows, None) + x[4:])

        xs = (pbs, pm_rows, term_rows, active)

    carry0 = (pm, tt, usage, jnp.asarray(rr_start, jnp.int32))
    if nom is not None:
        xs = xs + (nom.own,)
        carry0 = carry0 + (nom.req, nom.count)
    carry_end, outs = lax.scan(wave, carry0, xs)
    usage_end, rr_end = carry_end[2], carry_end[3]
    if collect_scores:
        chosen, fail_counts, cparts, tidx, tvals, tparts, finite = outs
        deco = ScoreDeco(chosen_parts=cparts, top_idx=tidx,
                         top_vals=tvals, top_parts=tparts)
    else:
        chosen, fail_counts, finite = outs
        deco = None
    # finite [W, P]: the per-wave numeric-integrity sentinel planes ride
    # out with the chosen planes — the host checks them in the SAME
    # fetch and discards any round a poison pod contaminated
    return chosen, fail_counts, usage_end, rr_end, deco, finite


