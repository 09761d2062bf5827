"""Benchmark entry point: one cell, one seed, one measured window.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of BENCHMARK.json's "workloads"; its file
workloads/<cell>.json names the configuration (configs/<name>.json),
the traffic mix (traffic/<name>.json, read by loadgen.py) and the mix's
parameters. Metrics are readers in metrics/<name>.py. A later cell,
configuration, traffic mix or metric is new files and new entries.

A run: fail unless JAX finds a TPU (and as many chips as the cell asks
for); build the cluster, warm the cell's round programs and pre-build
every pod object the window can use (set-up); drive
Scheduler.schedule_pending() over an in-process ObjectStore for
--seconds; check what the timed path produced against the plain
reference (check.py); print the checks on stderr and one JSON line on
stdout. With --trace 1 the run also records a profiler trace of part of
the window and reports the per-layer metrics instead of the end-to-end
ones.

Traffic loops:
- closed (drain): the queue holds the configuration's backlog when
  the window opens, and at the end of every round the pods that round
  bound are replaced, so each round starts with the backlog full. The
  window is whole rounds: it opens when schedule_pending() is called
  and closes at the end of the first round to end after --seconds.
  Where schedule_pending() returns with pods still queued (every one
  parked in backoff) the loop waits for the first to be due and calls
  it again; that wait counts in the window.
- open (paced): a generator thread hands pods over at due times drawn
  from the seed, whether or not the scheduler keeps up; the serve loop
  waits on them, moves them into the store and calls schedule_pending().
  Latency counts from the due time.

At each round's end running pods complete: the oldest until the
cluster holds `resident` again, or with the traffic's `complete_kinds`
every bound pod of those kinds. With `refill_evicted` each pod the
scheduler evicted is then replaced by a pod like it from a pool built at
set-up, created bound to the node it left once that node holds no
nomination.

Pods enter the store in the scheduler's own thread: the in-process
store delivers informer events under its lock, and the scheduler holds
its lock for a whole round, so a second thread creating pods during a
round would deadlock against the round's binds.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
from collections import OrderedDict, deque  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import List, Optional  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for _p in (str(BENCH), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import check  # noqa: E402
import loadgen  # noqa: E402
import probes  # noqa: E402
import trace_reduce  # noqa: E402
from check import BIND, COMPLETE, EVICT, NOMINATE  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"


class BenchError(Exception):
    pass


def _json(path: Path):
    return json.loads(path.read_text())


def load_cell(name: str, base: Path = BENCH, spec: Optional[dict] = None):
    """The cell `name`, merged from BENCHMARK.json (or `spec`, a dict of
    the same shape) and its workload, configuration and traffic files
    under `base`."""
    spec = spec if spec is not None else _json(ROOT / "BENCHMARK.json")
    entry = next((w for w in spec["workloads"] if w["name"] == name), None)
    if entry is None:
        raise BenchError(f"no cell {name!r} in BENCHMARK.json")
    work = _json(base / "workloads" / f"{name}.json")
    cfg = _json(base / "configs" / f"{entry['config']}.json")
    traffic = _json(base / "traffic" / f"{entry['traffic']}.json")
    traffic.update(work.get("traffic_params", {}))

    def mine(m):
        return "workloads" not in m or name in m["workloads"]

    return {"name": name, "chips": entry["chips"], "config": cfg,
            "traffic": traffic, "work": work,
            "end_to_end": [m for m in spec["end_to_end"] if mine(m)],
            "per_layer": [m for m in spec["per_layer"] if mine(m)]}


def reader(name: str, base: Path = BENCH):
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"),
        base / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class EventLog:
    """Store watcher: in the order the store applies them, every bind as
    it lands (BIND), every bound pod deleted (COMPLETE where the harness
    deleted it, EVICT where the scheduler did) and every nomination of
    an unbound pod (NOMINATE, node -1 where one is cleared) — op, the
    pod's plan index, the node index, the host clock, and for a bind the
    scheduler made, its round and position in it (the pods bound at
    set-up or created bound have position -1). A nomination holds until
    the pod binds, is deleted or is nominated again."""

    def __init__(self):
        self.rows: List[tuple] = []  # (op, t, pod, node, round, pos)
        self.n_binds = 0  # binds the scheduler made
        self.live = OrderedDict()  # names of the bound pods, oldest first
        self.nominated = {}  # plan index -> nominated node name
        self.evicted = []  # (plan index, node index) not yet refilled
        self.completing = False  # the harness is deleting pods
        self.cur = 0
        self.k = 0

    def new_round(self):
        self.cur += 1
        self.k = 0

    def __call__(self, ev):
        obj = ev.obj
        name = obj.metadata.name
        if not name.startswith("pod-"):
            return
        if not obj.spec.node_name:
            if ev.type == "MODIFIED":
                self._nomination(int(name[4:]),
                                 obj.status.nominated_node_name)
            return
        if ev.type == "MODIFIED" and ev.old is not None \
                and not ev.old.spec.node_name:
            op, rnd, pos = BIND, self.cur, self.k
            self.k += 1
            self.n_binds += 1
        elif ev.type == "ADDED":
            op, rnd, pos = BIND, 0, -1
        elif ev.type == "DELETED":
            op, rnd, pos = COMPLETE if self.completing else EVICT, 0, -1
        else:
            return
        i = int(name[4:])
        node = int(obj.spec.node_name[5:])
        self.rows.append((op, time.perf_counter(), i, node, rnd, pos))
        if op == BIND:
            self.live[name] = None
        else:
            self.live.pop(name, None)
            if op == EVICT:
                self.evicted.append((i, node))
        if self.nominated:
            self.nominated.pop(i, None)

    def _nomination(self, i: int, nom: str):
        # the scheduler may set the name on the pod it holds before the
        # store's event, so the log's own record is the one compared
        if nom == self.nominated.get(i, ""):
            return
        if nom:
            self.nominated[i] = nom
        else:
            del self.nominated[i]
        self.rows.append((NOMINATE, time.perf_counter(), i,
                          int(nom[5:]) if nom else -1, self.cur, -1))

    def arrays(self):
        a = np.asarray(self.rows, np.float64).reshape(-1, 6)
        out = {k: a[:, j].astype(np.int64)
               for j, k in enumerate(("op", "t", "pod", "node", "round",
                                      "pos")) if k != "t"}
        out["t"] = a[:, 1]
        return out


@dataclass
class Round:
    start: float
    binds_start: int
    end: Optional[float] = None
    binds_end: int = 0


def round_hook(binds: EventLog):
    """A step profiler (utils/profiling.py, fed by the Trace each pipeline
    round keeps) that also notes where every round starts and ends and
    runs `on_end(round)` in the scheduler's thread when one ends. A
    round starts when its featurize step began and ends at its commit
    step."""
    from kubernetes_tpu.utils import profiling

    class RoundHook(profiling.Profiler):
        def __init__(self):
            super().__init__()
            self.rounds: List[Round] = []
            self.steps: List[tuple] = []  # (step, start, end) host clock
            self.on_end = None
            self.error: Optional[BaseException] = None

        def record_step(self, trace_name, step, dt):
            super().record_step(trace_name, step, dt)
            if not trace_name.startswith("pipeline"):
                return
            now = time.perf_counter()
            self.steps.append((step, now - dt, now))
            if step == "featurized+staged":
                binds.new_round()
                self.rounds.append(Round(now - dt, binds.n_binds))
            elif step == "committed" and self.rounds:
                r = self.rounds[-1]
                r.end, r.binds_end = now, binds.n_binds
                if self.on_end is not None and self.error is None:
                    try:
                        self.on_end(r)
                    except BaseException as e:  # noqa: BLE001
                        # the program must not see the benchmark's fault
                        self.error = e

    hook = RoundHook()
    # profiling.enable() would install a plain Profiler; the benchmark
    # installs this subclass in its place
    profiling._ACTIVE = hook
    return hook


class Tracer:
    """One profiler trace around part of the window, bracketed by a host
    annotation named `bench_window` that trace_reduce takes as the
    window. start() and stop() run on one thread."""

    def __init__(self):
        self.dir = TRACE_DIR
        self.ann = None
        self.t0 = self.t1 = None

    def start(self):
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self.ann = jax.profiler.TraceAnnotation("bench_window")
        self.t0 = time.perf_counter()
        self.ann.__enter__()

    def stop(self):
        import jax

        self.ann.__exit__(None, None, None)
        self.t1 = time.perf_counter()
        jax.profiler.stop_trace()

    def reduce(self, steps):
        files = sorted(self.dir.glob("plugins/profile/*/*.xplane.pb"))
        if self.t1 is None or not files:
            return None
        out = trace_reduce.read(files[-1])
        if out is None:
            return None
        # program steps onto the trace clock: bench_window opened at t0
        lo = out["window"][0]
        spans = [(n, lo + (s - self.t0) * 1e9, lo + (e - self.t0) * 1e9)
                 for n, s, e in steps if e > self.t0 and s < self.t1]
        from jax.profiler import ProfileData

        pd = ProfileData.from_file(str(files[-1]))
        spans += trace_reduce.host_spans(pd, HOST_SPANS)
        out["idle_by_host"] = trace_reduce.label_idle(out["idle"], spans)
        return out


HOST_SPANS = {"schedule_pending", "serve_wait", "arrivals", "topup",
              "completions", "refills", "backoff_wait"}


WARM_PODS = 512  # throwaway pods of the warm-up, more than a wave


def build_scheduler(cfg, work, log, residents):
    """Store + scheduler (the program's own wave size and settings) with
    caps sized for the running pods and the backlog (completions keep
    the cluster at `resident`, so nothing grows, and nothing compiles,
    inside the window); the cluster and the running pods, bound; and the
    cell's round programs warmed on throwaway pods of the run's own
    mix."""
    import jax

    from kubernetes_tpu.ops.encoding import Caps
    from kubernetes_tpu.runtime.store import ObjectStore
    from kubernetes_tpu.sched.scheduler import Scheduler
    from kubernetes_tpu.state.vocab import bucket_size

    held = cfg["resident"] + cfg["backlog"] + WARM_PODS
    anti = anti_share(cfg, cfg["mix"])
    terms = anti * held
    if "resident_mix" in cfg:
        terms += (anti_share(cfg, cfg["resident_mix"]) - anti) \
            * cfg["resident"]
    n_terms = int(math.ceil(terms))
    caps = Caps(M=bucket_size(held),
                E=bucket_size(n_terms + 64) if n_terms else 8,
                LV=bucket_size(cfg["nodes"] + 256, 64))
    store = ObjectStore()
    store.watch("pods", log)
    sched = Scheduler(store, caps=caps)
    loadgen.build_cluster(store, cfg)
    for p in residents:
        store.create("pods", p)
    warm_plan = loadgen.plan_pods(cfg, WARM_PODS, 0)
    warm = loadgen.build_pods(cfg, warm_plan, prefix="warm")
    for p in warm:
        store.create("pods", p)
    with jax.profiler.TraceAnnotation("warm"):
        for w in work["warm_waves"]:
            sched.warm_pipeline(warm, n_waves=w)
    for p in warm:
        store.delete("pods", "default", p.metadata.name)
    return store, sched


def anti_share(cfg, mix: dict) -> float:
    """Share of a mix's pods that carry an anti-affinity term."""
    shape = {k["name"]: k["shape"] for k in loadgen.kinds(cfg)}
    return sum(w for k, w in mix.items()
               if shape[k] == "antiaffinity") / sum(mix.values())


def complete(ctx):
    """Running pods complete: every bound pod of the traffic's
    `complete_kinds`, or without them the oldest bound pods until the
    cluster holds `resident` again. Runs in the scheduler's thread,
    between rounds."""
    import jax

    log = ctx.log
    live = log.live
    if ctx.complete_kinds is not None:
        kind = ctx.plan.kind
        done = [n for n in live if kind[int(n[4:])] in ctx.complete_kinds]
    else:
        done = None
        keep = ctx.cfg["resident"]
        if len(live) <= keep:
            return
    with jax.profiler.TraceAnnotation("completions"):
        log.completing = True
        try:
            if done is None:
                while len(live) > keep:
                    ctx.store.delete("pods", "default",
                                     live.popitem(last=False)[0])
            else:
                for name in done:
                    ctx.store.delete("pods", "default", name)
        finally:
            log.completing = False


def refill(ctx):
    """Each pod the scheduler evicted is replaced by the next pool pod
    like it (its kind, label and group), created bound to the node it
    left, once that node holds no nomination. It runs after complete(),
    so with `complete_kinds` no pod of those kinds is bound then either:
    a refill never undoes a preemption still pending."""
    import jax

    log = ctx.log
    if not log.evicted:
        return
    held = set(log.nominated.values())
    wait = []
    with jax.profiler.TraceAnnotation("refills"):
        for i, c in log.evicted:
            name = loadgen.node_name(c)
            if name in held:
                wait.append((i, c))
                continue
            free = ctx.refill_free.get(ctx.plan.like(i))
            if not free:
                raise BenchError(f"refill pool has no pod like pod-{i}: "
                                 "raise refill_per_s in the cell file")
            p = free.popleft()
            p.spec.node_name = name
            ctx.store.create("pods", p)
            ctx.refilled += 1
    log.evicted = wait


def round_end(ctx):
    complete(ctx)
    if ctx.refill_free is not None:
        refill(ctx)


def wait_ready(queue) -> bool:
    """Wait on the scheduler's queue until a pod it holds is due: the
    earliest backoff deadline passes, or a pod turns active. False where
    no pod is due at any time (the queue is empty, or holds only pods
    parked unschedulable until an event). Reads the queue's backoff
    deadlines under its lock: SchedulingQueue keeps them private."""
    import jax

    with jax.profiler.TraceAnnotation("backoff_wait"), queue._lock:
        while True:
            queue._flush_backoff_locked()
            if queue._items:
                return True
            due = [queue._backoff_until.get(u, 0.0) for u in queue._backoff]
            if not due:
                return False
            queue._lock.wait(max(min(due) - queue.clock(), 0.0))


def window_open(win, stats, hook, t0=None):
    import jax

    # any program traced inside the window is named on stderr, before
    # the result, by JAX's own compile log
    jax.config.update("jax_log_compiles", True)
    win["compiles0"] = stats.snapshot()[1]
    win["steps0"] = hook.step_totals()
    win["t0"] = time.perf_counter() if t0 is None else t0


def window_close(win, stats, hook, t1):
    import jax

    win["t1"] = t1
    win["compiles1"] = stats.snapshot()[1]
    win["steps1"] = hook.step_totals()
    jax.config.update("jax_log_compiles", False)


def drain(ctx, seconds):
    """Closed loop. The first `warm_rounds` rounds run before the window
    opens: they compile what the round's own shapes need beyond the warm
    programs (the delta-upload scatters, bucketed by the rows a round
    dirties) and leave the cluster as the window finds it. The window
    opens at the end of the last of them, once the backlog is full again,
    and closes at the end of the first round to end after `seconds`.
    A traced run traces the window's first round."""
    import jax

    sched, store, hook = ctx.sched, ctx.store, ctx.hook
    pods, win, tracer = ctx.pods, ctx.win, ctx.tracer
    backlog = ctx.cfg["backlog"]
    warm_rounds = ctx.work["warm_rounds"]

    def top_up():
        # the queue's own count: a pod the round dropped is replaced too
        need = backlog - sched.queue.pending_count()
        if ctx.created + need > len(pods):
            raise BenchError(f"pod pool of {len(pods)} ran out: raise "
                             "pool_per_s in the cell file")
        with jax.profiler.TraceAnnotation("topup"):
            for p in pods[ctx.created:ctx.created + need]:
                store.create("pods", p)
        ctx.created += need

    def on_end(r: Round):
        k = len(hook.rounds) - warm_rounds  # rounds of the window so far
        if tracer is not None and k == 1:
            tracer.stop()
            ctx.traced_binds = r.binds_end - r.binds_start
        if k >= 1 and r.end - win["t0"] >= seconds:
            window_close(win, ctx.stats, hook, r.end)
            sched.enter_dormant()
            return
        round_end(ctx)
        top_up()
        if k == 0:
            window_open(win, ctx.stats, hook)
            if tracer is not None:
                tracer.start()

    top_up()
    hook.on_end = on_end
    while True:
        with jax.profiler.TraceAnnotation("schedule_pending"):
            sched.schedule_pending()
        if hook.error is not None:
            raise hook.error
        if "t1" in win or not wait_ready(sched.queue):
            break
    if "t1" not in win:
        raise BenchError("the backlog drained before the window closed: "
                         f"{sched.queue.pending_count()} pods queued, "
                         "none due")


def paced(ctx, seconds, traffic):
    """Open loop. Before the window, batches of the sizes in
    `warm_batches` go through schedule_pending() one after another, so
    the round and upload programs of every round size up to the largest
    are compiled. Then the generator hands pods over at their due times,
    and the first `warm_s` seconds of them run before the window opens,
    so the window starts in the traffic's own steady state; the loop
    serves until every pod is bound or `grace_s` past the window. A
    traced run traces the window's last `trace_s` seconds."""
    import jax

    sched, store, hook, log = ctx.sched, ctx.store, ctx.hook, ctx.log
    pods, win, tracer = ctx.pods, ctx.win, ctx.tracer
    for size in ctx.work["warm_batches"]:
        for p in pods[ctx.created:ctx.created + size]:
            store.create("pods", p)
        ctx.created += size
        sched.schedule_pending()
        round_end(ctx)
    n_warm = ctx.created
    inbox = loadgen.Inbox()
    gen = loadgen.OpenLoop(pods[n_warm:], ctx.due, inbox,
                           time.perf_counter())
    t0 = gen.t0 + traffic["warm_s"]
    ctx.gen = gen
    gen.start()
    tthread = None
    if tracer is not None:
        def _trace():
            lead = max(seconds - traffic["trace_s"], 0)
            time.sleep(max(t0 + lead - time.perf_counter(), 0))
            tracer.start()
            time.sleep(max(t0 + seconds - time.perf_counter(), 0))
            tracer.stop()

        tthread = threading.Thread(target=_trace, name="tracer", daemon=True)
        tthread.start()
    deadline = t0 + seconds + traffic["grace_s"]
    try:
        while True:
            if "t0" not in win and time.perf_counter() >= t0:
                window_open(win, ctx.stats, hook, t0)
            with jax.profiler.TraceAnnotation("serve_wait"):
                items = inbox.take(block=True, timeout=0.25)
            if items:
                with jax.profiler.TraceAnnotation("arrivals"):
                    for p in items:
                        store.create("pods", p)
                ctx.created += len(items)
            with jax.profiler.TraceAnnotation("schedule_pending"):
                sched.schedule_pending()
            round_end(ctx)
            now = time.perf_counter()
            if "t1" not in win and now >= t0 + seconds:
                window_close(win, ctx.stats, hook, t0 + seconds)
            if (not gen.is_alive() and ctx.created == len(pods)
                    and (log.n_binds >= ctx.created or now > deadline)):
                break
    finally:
        gen.stop()
        inbox.close()
        gen.join()
        if tthread is not None:
            tthread.join()
    if "t1" not in win:
        window_close(win, ctx.stats, hook, t0 + seconds)


class Ctx:
    """What one run holds: the scheduler under test and the benchmark's
    own bookkeeping around it."""

    def __init__(self, **kw):
        self.created = 0  # backlog pods created
        self.refilled = 0  # evicted pods replaced
        self.traced_binds = None
        self.gen = None
        self.win = {}
        self.__dict__.update(kw)


@dataclass
class RunPlan:
    """Every pod a run may create, by plan index: the running pods
    [0, n_res), then the backlog's pool (closed loop) or the warm-up and
    due pods (open loop) up to n_pods, then the refill pool."""

    plan: loadgen.PodPlan
    n_res: int
    n_pods: int
    res_nodes: np.ndarray  # node of each running pod
    due: Optional[np.ndarray] = None  # open loop: due times, s
    n_warm: int = 0  # open loop: pods of the warm-up batches


def plan_run(cell: dict, seed: int, seconds: float) -> RunPlan:
    cfg, work, traffic = cell["config"], cell["work"], cell["traffic"]
    n_res = cfg["resident"]
    due, n_warm = None, 0
    if traffic["loop"] == "closed":
        n_pods = n_res + (cfg["backlog"] * (1 + work["warm_rounds"])
                          + int(work["pool_per_s"] * seconds))
    else:
        # one Poisson stream; its first warm_s seconds run before the
        # window opens
        due = loadgen.poisson_due(traffic["rate"],
                                  traffic["warm_s"] + seconds, seed)
        n_warm = sum(work["warm_batches"])
        n_pods = n_res + n_warm + len(due)
    plan = loadgen.plan_pods(cfg, n_pods, seed, n_res)
    if traffic.get("refill_evicted"):
        # as many as every running pod evicted in each warm-up round,
        # and refill_per_s for each second of the window
        n_refill = (n_res * work.get("warm_rounds", 1)
                    + int(work["refill_per_s"] * seconds))
        plan = loadgen.PodPlan.concat(
            [plan, loadgen.refill_plan(plan, n_res, n_refill)])
    return RunPlan(plan, n_res, n_pods,
                   loadgen.resident_nodes(cfg, plan, n_res, seed), due,
                   n_warm)


def run(cell: dict, seed: int, seconds: float, trace: bool,
        require_tpu: bool = True) -> dict:
    import jax

    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < cell["chips"]):
        raise BenchError(f"no TPU with {cell['chips']} chip(s): JAX "
                         f"finds {len(devices)} x {devices[0]!r}")
    if require_tpu:
        peaks = _json(BENCH / "peaks.json")
        if devices[0].device_kind not in peaks["devices"]:
            raise BenchError(f"no peaks for {devices[0].device_kind!r}")
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    stats = probes.CompileStats()

    cfg, work, traffic = cell["config"], cell["work"], cell["traffic"]
    closed = traffic["loop"] == "closed"
    rp = plan_run(cell, seed, seconds)
    plan, n_res, n_pods, due = rp.plan, rp.n_res, rp.n_pods, rp.due
    complete_kinds = traffic.get("complete_kinds")
    if complete_kinds is not None:
        names = [k["name"] for k in loadgen.kinds(cfg)]
        if set(complete_kinds) - set(names):
            raise BenchError(f"complete_kinds {complete_kinds} names kinds "
                             f"not among {names}")
        complete_kinds = {names.index(k) for k in complete_kinds}
    pods = loadgen.build_pods(cfg, plan)
    refill_free = None  # the refill pool's pods by what they are like
    if traffic.get("refill_evicted"):
        refill_free = {}
        for q in range(n_pods, len(plan)):
            refill_free.setdefault(plan.like(q), deque()).append(pods[q])
    if cfg.get("resident_placement", "drawn") != "drawn":
        bad = check.resident_violations(cfg, plan, rp.res_nodes)
        if bad:
            raise BenchError(f"running pods placed "
                             f"{cfg['resident_placement']!r} break {bad} "
                             "filters")
    for p, n in zip(pods, rp.res_nodes):
        p.spec.node_name = loadgen.node_name(n)
    log = EventLog()
    store, sched = build_scheduler(cfg, work, log, pods[:n_res])
    ctx = Ctx(cfg=cfg, work=work, store=store, sched=sched, log=log,
              hook=round_hook(log), pods=pods[n_res:n_pods], plan=plan,
              complete_kinds=complete_kinds, refill_free=refill_free,
              tracer=Tracer() if trace else None, stats=stats, due=due)
    hook, win = ctx.hook, ctx.win
    if closed:
        drain(ctx, seconds)
    else:
        paced(ctx, seconds, traffic)
    setup_s = win["t0"] - T0
    t_end = time.perf_counter()

    # after the window: the device's peak, then the program's state goes
    memory_peak = probes.memory_peak_bytes(devices[:max(cell["chips"], 1)])
    expect = "pallas" if devices[0].platform == "tpu" else "xla"
    fallbacks = probes.device_path_fallbacks(sched, expect)
    pending = sched.queue.pending_count()
    sched.close()
    del sched
    ctx.sched = None
    from kubernetes_tpu.utils import profiling

    profiling._ACTIVE = None
    trace_out = (ctx.tracer.reduce(hook.steps) if ctx.tracer is not None
                 else None)

    log = log.arrays()
    store_node = check.store_nodes(store, len(plan))
    # every pod the run created is bound, pending, evicted or completed
    n_completed = int(np.sum(log["op"] == COMPLETE))
    n_evicted = int(np.sum(log["op"] == EVICT))
    n_bound = int(np.sum(log["op"] == BIND)) - n_completed - n_evicted
    lost = (n_res + ctx.created + ctx.refilled - n_bound - pending
            - n_evicted - n_completed)
    counts = {"lost": abs(lost), "fallbacks": len(fallbacks)}
    made = (log["op"] == BIND) & (log["pos"] >= 0)  # the scheduler's binds
    if closed:
        eligible = made & (log["t"] > win["t0"]) & (log["t"] <= win["t1"])
        attempted = int(np.sum(eligible)) + counts["lost"]
        failed = counts["lost"]
    else:
        n_pre = int(np.sum(due < traffic["warm_s"]))
        first = n_res + rp.n_warm + n_pre  # plan index of the first due pod
        accepted = ctx.gen.accepted[n_pre:]
        due = due[n_pre:] - traffic["warm_s"]
        eligible = made & (log["pod"] >= first)
        unbound = len(due) - int(np.sum(eligible))
        counts["unbound"] = unbound
        attempted, failed = len(due), unbound
    t_check = time.perf_counter()
    correct, checks, info = check.compare(
        cfg, plan, log, store_node, eligible, work["sample"], seed, counts,
        work["limits"])
    info["check_s"] = time.perf_counter() - t_check
    info["fallbacks"] = fallbacks
    info["window_compiles"] = win["compiles1"] - win["compiles0"]

    r = Observations(
        cell=cell, setup_s=setup_s,
        t0=win["t0"], t1=win["t1"], t_end=t_end, log=log, rounds=[
            x for x in hook.rounds if x.end is not None],
        step_delta={k: v - win["steps0"].get(k, 0.0)
                    for k, v in win["steps1"].items()},
        trace=trace_out, traced_binds=ctx.traced_binds, due=due,
        due_index0=0 if closed else first,
        accepted=None if closed else accepted)
    metric_defs = cell["per_layer"] if trace else cell["end_to_end"]
    metrics = {}
    for m in metric_defs:
        v = reader(m["name"])(r)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "device_kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if trace_out is not None:
        device["busy_s"] = trace_out["busy_s"]
        device["window_s"] = trace_out["window_s"]
        out["breakdown"] = {
            "device_ops": trace_reduce.top(trace_out["ops"]),
            "idle_gaps": trace_reduce.top(trace_out["idle_by_host"])}
    out["info"] = info
    out["checks"] = checks
    return out


@dataclass
class Observations:
    """What a run saw, for the metric readers (metrics/<name>.py). Host
    clock in seconds (time.perf_counter)."""

    cell: dict
    setup_s: float
    t0: float  # window open
    t1: float  # window close
    t_end: float  # serve loop done (open loop: after the grace wait)
    log: dict  # event log arrays: op, t, pod, node, round, pos (EventLog)
    rounds: list  # Round, every round that ended
    step_delta: dict  # step-profiler seconds accrued inside the window
    trace: Optional[dict] = None  # trace_reduce.read + idle_by_host
    traced_binds: Optional[int] = None  # binds of the traced round
    due: Optional[np.ndarray] = None  # open loop: due times from t0
    accepted: Optional[np.ndarray] = None  # open loop: handed over at
    due_index0: int = 0  # open loop: plan index of the first due pod

    def window_binds(self) -> int:
        """Binds the scheduler made inside the window."""
        t = self.log["t"]
        made = (self.log["op"] == BIND) & (self.log["pos"] >= 0)
        return int(np.sum(made & (t > self.t0) & (t <= self.t1)))

    def bind_latency(self) -> np.ndarray:
        """Open loop: bind landed minus due, per pod due in the window;
        a pod that never bound counts from its due time to the end of
        the run."""
        lat = np.full(len(self.due), self.t_end) - (self.t0 + self.due)
        j = self.log["pod"] - self.due_index0
        ok = (j >= 0) & (self.log["op"] == BIND)
        lat[j[ok]] = self.log["t"][ok] - (self.t0 + self.due[j[ok]])
        return lat


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = load_cell(args.workload)
        out = run(cell, args.seed % (1 << 63), args.seconds, bool(args.trace))
    except BenchError as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    print_result(out)
    return 0


def print_result(out: dict) -> None:
    info = out["info"]
    print(f"window compiles: {info['window_compiles']}", file=sys.stderr)
    if info["fallbacks"]:
        print(f"fallbacks: {', '.join(info['fallbacks'])}", file=sys.stderr)
    print(f"checked {info['checked']} placements against the reference in "
          f"{info['check_s']:.2f} s; widest score gap {info['gap_max']}",
          file=sys.stderr)
    for k, c in out["checks"].items():
        print(f"check {k} {c['value']} limit {c['limit']}", file=sys.stderr)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    sys.exit(main())
