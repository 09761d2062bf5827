"""Chip smoke: drive the scheduling plane's device path once on a TPU.

    python chip_smoke.py            # one chip: mixed5k, gang, preempt
    python chip_smoke.py --mesh 4   # four chips: sharded vs unsharded

One process holds the chip for the whole run and starts no child that
touches JAX. Every phase goes through the entry point a user calls,
ObjectStore -> Scheduler.schedule_pending(), built and warmed by
bench.py's own config code, and then holds the run to the device path:
everything placed, a strict cluster-invariant check (twice, for its
hysteresis), the formulation pallas_default() chose, and no Pallas
demotion, host wave, breaker trip, capacity fault or scheduling error.
The main phase also replays its first (ipa-free) round through the
numpy twin (ops/hostwave.py) and requires bit-equal placements.

Each phase prints one JSON line (wall time, compile seconds and count,
wave path, HBM in use, whether the compile cache was warm). The last
line is {"ok": true, "device": {...}} and is printed only when every
check passed; any failure exits non-zero before it. Without a TPU the
script exits non-zero at once.
"""

import argparse
import json
import sys
import time

import numpy as np

# bench.py SUITE shapes; mixed5k is the north-star config, and its
# caps.N=8192 node slots divide a 4-chip mesh
MIXED = dict(nodes=5000, pods=30000, wave=256)
GANG = dict(nodes=500, pods=2016, wave=256)
PREEMPT = dict(nodes=50, pods=100, wave=256)


class SmokeFailure(Exception):
    pass


def check(cond, what):
    if not cond:
        raise SmokeFailure(what)


def emit(rec):
    print(json.dumps(rec), flush=True)


class CompileStats:
    """Backend compile seconds/count and persistent-cache hits/misses,
    from JAX's own monitoring events, cumulative over the process."""

    def __init__(self):
        from jax import monitoring

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.cache_writes = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration_secs
            self.compiles += 1

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_writes += 1

    def snapshot(self):
        return (self.seconds, self.compiles, self.cache_hits,
                self.cache_writes)


def hbm_in_use(devices):
    """bytes_in_use / peak per device, where the backend reports them."""
    out = {}
    for d in devices:
        st = d.memory_stats() or {}
        if "bytes_in_use" in st:
            out[str(d)] = {"bytes_in_use": int(st["bytes_in_use"]),
                           "peak_bytes_in_use":
                               int(st.get("peak_bytes_in_use", 0))}
    return out


def device_path_checks(sched, placed, want, expect_path):
    """The checks every phase shares: full placement, strict invariants
    (checked twice: conservation and gang atomicity only fire when a
    violation persists across two checks), and no sign of any fallback
    off the device path."""
    from kubernetes_tpu.chaos.invariants import InvariantChecker
    from kubernetes_tpu.sched.breaker import CLOSED

    check(placed == want, f"placed {placed}/{want}")
    chk = InvariantChecker(metrics=sched.metrics, strict=True)
    with sched._mu:
        chk.check(sched)
        chk.check(sched)
    m = sched.metrics
    check(sched.wave_path() == expect_path,
          f"wave_path {sched.wave_path()!r}, expected {expect_path!r}")
    check(m.scheduling_errors.value(stage="pallas") == 0,
          "a Pallas program was demoted to XLA")
    check(m.scheduling_errors.total() == 0,
          f"scheduling errors: {[c.name for c in m.scheduling_errors.children() if c.value]}")
    check(m.waves_total.value(path="host") == 0,
          f"{m.waves_total.value(path='host'):.0f} waves ran on the host")
    check(m.waves_total.value(path="device") > 0, "no device wave ran")
    check(m.degraded_golden_pods.total() == 0,
          "pods took the degraded golden path")
    check(sched.breaker.state == CLOSED and sched.breaker.trips == 0,
          f"breaker {sched.breaker.state} after {sched.breaker.trips} trips")
    check(m.capacity_faults.value == 0, "capacity faults")


class RoundCapture:
    """Records the first ipa-free schedule_round dispatch of a drain:
    its arguments and a copy of the host snapshot it was uploaded from
    (commits mutate the snapshot right after the round)."""

    def __init__(self, sched):
        self.sched = sched
        self.first = None

    def __enter__(self):
        from kubernetes_tpu.ops import kernel

        self._kernel = kernel
        self._orig = orig = kernel.schedule_round

        def wrapped(*args, **kw):
            if self.first is None and not kw.get("has_ipa"):
                host = tuple(type(t)(*[np.array(a) for a in t])
                             for t in self.sched.snapshot.host_tensors())
                out = orig(*args, **kw)
                self.first = (host, args, kw, out)
                return out
            return orig(*args, **kw)

        kernel.schedule_round = wrapped
        return self

    def __exit__(self, *exc):
        self._kernel.schedule_round = self._orig


def twin_round(first, num_zones, num_label_values):
    """Replay a captured ipa-free round through the numpy twin, wave by
    wave, staging each wave's placements into the pod matrix and term
    table and carrying usage and rr exactly as the device scan does.
    Returns (device chosen [W, P], twin chosen [W, P])."""
    from kubernetes_tpu.ops import encoding as enc
    from kubernetes_tpu.ops.hostwave import schedule_wave_host

    (nt, pm, tt), args, kw, out = first
    pbs = enc.PodBatch(*[np.asarray(a) for a in args[3]])
    rr = int(np.asarray(args[5]))
    pm_rows, term_rows = np.asarray(args[6]), np.asarray(args[7])
    has_ts = bool(np.any(pbs.ts_valid))
    wvec = np.asarray(kw["weight_vec"], np.float32)
    W, P = pbs.req.shape[:2]
    N = nt.valid.shape[0]
    usage = (nt.requested.copy(), nt.nonzero.copy(), nt.pod_count.copy())
    ones = np.ones((P, N), bool)
    want = np.full((W, P), -1, np.int32)
    for w in range(W):
        pb = enc.PodBatch(*[a[w] for a in pbs])
        if not pb.valid.any():
            continue
        res, usage = schedule_wave_host(
            nt, pm, tt, pb, ones, rr, None, weights=kw["weights"],
            num_zones=num_zones, num_label_values=num_label_values,
            has_ipa=False, has_ts=has_ts, usage_in=usage, weight_vec=wvec)
        rr = int(res.rr_end)
        c = np.asarray(res.chosen, np.int32)
        want[w] = c
        ok = (c >= 0) & (pm_rows[w] >= 0)
        pm.node[pm_rows[w][ok]] = c[ok]
        pm.valid[pm_rows[w][ok]] = True
        tok = ok[:, None] & (term_rows[w] >= 0)
        tt.node[term_rows[w][tok]] = np.broadcast_to(
            c[:, None], term_rows[w].shape)[tok]
        tt.valid[term_rows[w][tok]] = True
    return np.asarray(out[0]), want


def ran_program(sched, program):
    """Did the scheduler dispatch `program` (a record_dispatch name)?"""
    return any(f'program="{program}"' in c.name and c.value > 0
               for c in sched.metrics.device_jit_events.children())


def run_phase(name, prepare, drain, want, expect_path, stats, devices,
              twin=False):
    """prepare() -> (store, sched) built and warmed; drain(store, sched)
    -> placed. Prints the phase's JSON line; raises SmokeFailure."""
    c0 = stats.snapshot()
    t0 = time.perf_counter()
    store, sched = prepare()
    t_prep = time.perf_counter() - t0
    capture = RoundCapture(sched) if twin else None
    t1 = time.perf_counter()
    if capture is not None:
        with capture:
            placed = drain(store, sched)
    else:
        placed = drain(store, sched)
    t_drain = time.perf_counter() - t1
    sched.close()
    c1 = stats.snapshot()
    tel = sched.metrics
    rec = {
        "phase": name, "placed": placed, "want": want,
        "prepare_s": t_prep, "drain_s": t_drain,
        "wave_path": sched.wave_path(),
        "compile_s": c1[0] - c0[0], "compiles": c1[1] - c0[1],
        "cache_hits": c1[2] - c0[2], "cache_writes": c1[3] - c0[3],
        "jit_misses": tel.device_jit_compile_seconds.total,
        "jit_miss_s": tel.device_jit_compile_seconds.sum,
        "device_waves": tel.waves_total.value(path="device"),
        "hbm": hbm_in_use(devices),
    }
    emit(rec)
    device_path_checks(sched, placed, want, expect_path)
    if capture is not None:
        check(capture.first is not None, "no ipa-free round was dispatched")
        t2 = time.perf_counter()
        got, ref = twin_round(capture.first, sched.snapshot.caps.Z,
                              sched.snapshot.num_label_values)
        live = int(np.sum(got >= 0))
        emit({"phase": name, "check": "round1_vs_twin",
              "waves": int(got.shape[0]), "placed": live,
              "mismatches": int(np.sum(got != ref)),
              "twin_s": time.perf_counter() - t2})
        check(live > 0, "round 1 placed nothing")
        check(np.array_equal(got, ref),
              "round-1 placements differ from the numpy twin")
    return store, sched


def single_chip(stats, devices):
    import bench
    from kubernetes_tpu.ops.kernel import pallas_default

    path = "pallas" if pallas_default() else "xla"

    def mixed_prepare():
        return bench.prepare_config(MIXED["nodes"], MIXED["pods"],
                                    MIXED["wave"], "mixed")

    def mixed_drain(store, sched):
        bench.make_pods(store, MIXED["pods"], "mixed")
        return sched.schedule_pending()

    run_phase("mixed5k", mixed_prepare, mixed_drain, MIXED["pods"], path,
              stats, devices, twin=True)

    def gang_prepare():
        return bench.prepare_config(GANG["nodes"], GANG["pods"],
                                    GANG["wave"], "gang")

    def gang_drain(store, sched):
        bench.make_pods(store, GANG["pods"], "gang")
        return sched.schedule_pending()

    _store, sched = run_phase("gang", gang_prepare, gang_drain, GANG["pods"],
                              path, stats, devices)
    check(ran_program(sched, "gang"), "the gang program never ran")

    def preempt_prepare():
        return bench.prepare_preempt_config(PREEMPT["nodes"],
                                            PREEMPT["pods"], PREEMPT["wave"])

    def preempt_drain(store, sched):
        bench.make_vip_pods(store, PREEMPT["pods"])
        return bench.drain_preempt(sched, PREEMPT["pods"])

    _store, sched = run_phase("preempt", preempt_prepare, preempt_drain,
                              PREEMPT["pods"], path, stats, devices)
    check(sched.pipeline_preemptions > 0
          and sched.metrics.pod_preemption_victims.value > 0,
          "the preempt phase evicted nothing")
    check(ran_program(sched, "preempt"),
          "the device preemption what-if never ran")


def mesh_phase(n, stats, devices):
    import bench
    from kubernetes_tpu.ops.kernel import pallas_default
    from kubernetes_tpu.parallel.mesh import make_mesh

    check(len(devices) >= n, f"--mesh {n} needs {n} devices, "
                             f"found {len(devices)}")
    mesh = make_mesh(n)
    results = {}
    for name, m, path in (
            ("sharded", mesh, "xla"),  # pallas_call does not partition
            ("unsharded", None, "pallas" if pallas_default() else "xla")):

        def prepare(m=m):
            return bench.prepare_config(MIXED["nodes"], MIXED["pods"],
                                        MIXED["wave"], "mixed", mesh=m)

        def drain(store, sched):
            bench.make_pods(store, MIXED["pods"], "mixed")
            return sched.schedule_pending()

        store, sched = run_phase(f"mesh{n}_{name}", prepare, drain,
                                 MIXED["pods"], path, stats, devices)
        if m is not None:
            check(sched._active_mesh is not None,
                  "the sharded run fell back to one device")
            per_dev = sched.snapshot.hbm_bytes_per_device()
            nt, _pm, _tt = sched.snapshot.to_device(mesh=m)
            held = sorted(str(s.device) for s in nt.alloc.addressable_shards)
            emit({"phase": f"mesh{n}_{name}", "hbm_per_device": per_dev,
                  "alloc_shards": held})
            check(len(per_dev) == n and all(v > 0 for v in per_dev.values()),
                  f"hbm_bytes_per_device {per_dev}")
            check(len(set(held)) == n,
                  f"node tensors sit on {len(set(held))} devices, not {n}")
            used = hbm_in_use(devices[:n])
            check(len(used) < n or all(v["bytes_in_use"] > 0
                                       for v in used.values()),
                  f"a mesh device holds no memory: {used}")
        rr = (sched._host_rr if sched._rr is None
              else int(np.asarray(sched._rr)))
        results[name] = (sorted((p.metadata.name, p.spec.node_name)
                                for p in store.list("pods")), rr)
        del store, sched
    same = results["sharded"][0] == results["unsharded"][0]
    emit({"phase": f"mesh{n}", "placements_equal": same,
          "rr": {k: v[1] for k, v in results.items()}})
    check(same, "sharded placements differ from unsharded")
    check(results["sharded"][1] == results["unsharded"][1],
          "sharded rr counter differs from unsharded")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--mesh", type=int, default=0, metavar="N",
                    help="run only the N-chip mesh phase (sharded vs "
                         "unsharded placements)")
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"FAIL: no TPU: JAX's first device is {devices[0]!r}",
              file=sys.stderr)
        return 1

    from kubernetes_tpu.utils import compile_cache

    emit({"compile_cache_dir": compile_cache.enable(),
          "device_kind": devices[0].device_kind, "devices": len(devices)})
    stats = CompileStats()
    t0 = time.perf_counter()
    try:
        if args.mesh:
            mesh_phase(args.mesh, stats, devices)
        else:
            single_chip(stats, devices)
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    s, n, hits, writes = stats.snapshot()
    emit({"total_s": time.perf_counter() - t0, "compile_s": s,
          "compiles": n, "cache_hits": hits, "cache_writes": writes,
          "compile_cache_warm": hits > 0})
    emit({"ok": True, "device": {"platform": devices[0].platform,
                                 "kind": devices[0].device_kind,
                                 "count": len(devices)}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
