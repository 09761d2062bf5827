"""The harness at a tiny test-only cell on the CPU (tests/data): the last
line's keys, a drain window made of whole rounds, an open loop that
offers a slowed scheduler the same due times, and no result without a
TPU or without the program. A preemption cell (tiny-preempt: declared
kinds and priorities, even residency, completions by kind, refills of
evicted pods) keeps its invariants, and the plans of the benchmark's own
cells stay what they were before configurations could declare kinds. A
cell of template kinds (tiny-ipa: namespaces, node labels, running pods
placed by the reference) runs through the harness with pods in their own
namespaces and nothing compiled in the window.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import hashlib
import json
import os
import re
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
DATA = BENCH / "tests" / "data"
sys.path.insert(0, str(BENCH))

import check  # noqa: E402
import loadgen  # noqa: E402
import run  # noqa: E402
from check import BIND, COMPLETE, EVICT, NOMINATE  # noqa: E402


def tiny(name):
    spec = json.loads((DATA / "BENCHMARK.json").read_text())
    return run.load_cell(name, base=DATA, spec=spec)


def observe(monkeypatch):
    """Run with the metric readers' inputs kept for the test."""
    seen = {}
    real = run.Observations

    def keep(**kw):
        seen["r"] = real(**kw)
        return seen["r"]

    monkeypatch.setattr(run, "Observations", keep)
    return seen


def test_drain_line_and_whole_rounds(monkeypatch):
    seen = observe(monkeypatch)
    out = run.run(tiny("tiny-drain"), 7, 1.0, False, require_tpu=False)
    assert set(out) >= {"correct", "attempted", "failed", "metrics",
                        "device"}
    assert list(out)[-1] == "checks"
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", "pods_per_s"}
    assert set(out["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert out["info"]["window_compiles"] == 0
    r = seen["r"]
    ends = [x.end for x in r.rounds]
    # the window opens and closes at round ends, and holds only whole
    # rounds' binds
    assert r.t1 in ends and r.t1 - r.t0 >= 1.0
    assert min(abs(e - r.t0) for e in ends) < 0.05
    inside = [x for x in r.rounds if r.t0 < x.end <= r.t1]
    assert len(inside) >= 1
    assert sum(x.binds_end - x.binds_start for x in inside) \
        == r.window_binds() == out["attempted"]
    # every round of the window starts with the backlog full
    cfg = r.cell["config"]
    assert all(x.binds_end - x.binds_start <= cfg["backlog"] for x in inside)


def test_paced_offers_due_times_to_a_slow_scheduler(monkeypatch):
    cell = tiny("tiny-paced")
    # slow enough that its rounds stay within the warmed sizes
    cell["traffic"]["rate"] = 60
    seen = observe(monkeypatch)
    out = run.run(cell, 11, 2.0, False, require_tpu=False)
    assert out["correct"] is True
    fast = seen["r"]
    from kubernetes_tpu.sched.scheduler import Scheduler

    real = Scheduler.schedule_pending

    def slow(self, *a, **kw):
        time.sleep(0.2)
        return real(self, *a, **kw)

    monkeypatch.setattr(Scheduler, "schedule_pending", slow)
    out = run.run(cell, 11, 2.0, False, require_tpu=False)
    slowed = seen["r"]
    assert out["correct"] is True
    # the same due times, handed over on time although the scheduler
    # lags: an open loop
    assert np.array_equal(fast.due, slowed.due)
    late = slowed.accepted - (slowed.t0 + slowed.due)
    assert np.nanmax(late) < 0.1
    # and the lag shows in the latency, counted from the due time
    assert np.median(slowed.bind_latency()) > 0.1 > np.median(
        fast.bind_latency())


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "mixed5k-drain",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "mixed5k-drain",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("seed", [0, 2 ** 31 + 5, 12345678901])
def test_plan_same_counts_for_every_seed(seed):
    cfg = tiny("tiny-drain")["config"]
    plan = loadgen.plan_pods(cfg, 1000, seed)
    assert np.bincount(plan.kind, minlength=3).tolist() == [500, 250, 250]
    due = loadgen.poisson_due(100.0, 3.0, seed)
    assert len(due) == 300 and 0 <= due.min() and due.max() < 3.0


# (cell, seed, plan length, digest): what run.run builds at a 40-s window
# before configurations could declare kinds; sha256 over the plan's
# kind, aff and group (int32), the running pods' nodes (int64) and the
# repr of the first PINNED_PODS pod objects without their uids (those
# count up across the process)
PINNED_PODS = 3000
PINNED = [
    ("mixed5k-drain", 7, 220000,
     "a4fc4ed5eb7c5e742cabadfb6a642e0193c8f5a34452d6e57c56d5afacdcbb1c"),
    ("mixed5k-drain", 4200002401, 220000,
     "66d657566efc4b96eb18ee4c2b7794bbd5238e00b1330d8ab6860fe3092360fb"),
    ("mixed5k-drain", 12345678901, 220000,
     "50da456691bcd5f039911503b0b0c94ff70cc991c7540c8a2e5d2a86b0045bb2"),
    ("basic5k-drain", 7, 261000,
     "763b29904f5fc8655eb94f7877042d1c10e5a5aec3db20b9af56d8bb98b61d64"),
    ("basic5k-drain", 4200002401, 261000,
     "88705197c914a27dabc21f1bf378c424d9e15d21444aac135b976a09b714dcb5"),
    ("basic5k-drain", 12345678901, 261000,
     "55f534b47e1467059070f5bfb95f1adb44857261748df8d0a36aaefd26580cbe"),
]


@pytest.mark.parametrize("cell,seed,n,digest", PINNED)
def test_plan_pods_and_residents_pinned(cell, seed, n, digest):
    c = run.load_cell(cell)
    rp = run.plan_run(c, seed, 40)
    pods = loadgen.build_pods(c["config"], rp.plan[:PINNED_PODS])
    h = hashlib.sha256()
    for a in (rp.plan.kind, rp.plan.aff, rp.plan.group):
        h.update(np.ascontiguousarray(a, np.int32).tobytes())
    h.update(np.ascontiguousarray(rp.res_nodes, np.int64).tobytes())
    for p in pods:
        h.update(re.sub(r"uid='uid-\d+'", "", repr(p)).encode())
    assert (len(rp.plan), h.hexdigest()) == (n, digest)


# (seed, plan length, digest) of tiny-ipa's plan at a 2-s window, as
# PINNED is taken
PINNED_TEMPLATE = [
    (7, 8480,
     "b4368e656160ddd729e4c0ce80cf598668e64ae5087609e9f2b28534a796a0df"),
    (4200002401, 8480,
     "1eb88310b54c2896a1f3a4109d68d37fe2c67d73136f8041fa822aa334d9b9cd"),
]


@pytest.mark.parametrize("seed,n,digest", PINNED_TEMPLATE)
def test_template_plan_and_residents_pinned(seed, n, digest):
    c = tiny("tiny-ipa")
    rp = run.plan_run(c, seed, 2.0)
    pods = loadgen.build_pods(c["config"], rp.plan[:PINNED_PODS])
    h = hashlib.sha256()
    for a in (rp.plan.kind, rp.plan.aff, rp.plan.group):
        h.update(np.ascontiguousarray(a, np.int32).tobytes())
    h.update(np.ascontiguousarray(rp.res_nodes, np.int64).tobytes())
    for p in pods:
        h.update(re.sub(r"uid='uid-\d+'", "", repr(p)).encode())
    assert (len(rp.plan), h.hexdigest()) == (n, digest)


def test_template_cell_runs_in_its_namespaces(monkeypatch):
    from kubernetes_tpu.runtime.store import ObjectStore

    made, gone = {}, []
    real_create, real_delete = ObjectStore.create, ObjectStore.delete

    def create(self, kind, obj):
        if kind == "pods":
            made[obj.metadata.name] = obj.metadata.namespace
        return real_create(self, kind, obj)

    def delete(self, kind, namespace, name):
        if kind == "pods":
            gone.append((namespace, name))
        return real_delete(self, kind, namespace, name)

    monkeypatch.setattr(ObjectStore, "create", create)
    monkeypatch.setattr(ObjectStore, "delete", delete)
    seen = observe(monkeypatch)
    cell = tiny("tiny-ipa")
    out = run.run(cell, 4200003021, 2.0, False, require_tpu=False)
    assert out["info"]["window_compiles"] == 0
    checks = {k: c["value"] for k, c in out["checks"].items()}
    assert (checks["violations"], checks["lost"], checks["fallbacks"]) \
        == (0, 0, 0)
    assert out["info"]["checked"] > 0
    # every pod in its kind's namespace, both of them used, and every
    # pod the harness deleted (warm-up pods, completions) deleted there
    ks = loadgen.kinds(cell["config"])
    plan = run.plan_run(cell, 4200003021, 2.0).plan
    for name, ns in made.items():
        if name.startswith("pod-"):
            assert ns == ks[plan.kind[int(name[4:])]]["namespace"], name
    assert {"sched-0", "sched-1"} <= set(made.values())
    assert gone and all(made[name] == ns for ns, name in gone)
    completed = int(np.sum(seen["r"].log["op"] == COMPLETE))
    assert completed > 0
    assert len([n for _, n in gone if n.startswith("pod-")]) == completed


def test_replay_skips_nominations():
    cell = tiny("tiny-drain")
    cfg = dict(cell["config"], nodes=200, resident=1200, backlog=1000)
    ref = check.reference(cfg["reference"])
    cl = ref.Cluster.from_config(cfg)
    plan = loadgen.plan_pods(cfg, 3200, 9)
    res = loadgen.resident_nodes(cfg, plan, 1200, 9)
    op, pod, node, made = ref.greedy(
        cl, plan, np.arange(1200, 3200), resident=(np.arange(1200), res),
        keep=1200, batch=1000)
    plain = ref.replay(cl, plan, op, pod, node, made, made)
    # a nomination (or its clearing) ahead of every fifth event, of a pod
    # and onto a node drawn at random
    rng = np.random.default_rng(9)
    at = np.arange(0, len(op), 5)

    def ins(a, v):
        return np.insert(a, at, v)

    op2 = ins(op, NOMINATE)
    pod2 = ins(pod, rng.integers(0, 3200, len(at)))
    node2 = ins(node, rng.integers(-1, 200, len(at)))
    made2 = ins(made, False)
    assert ref.replay(cl, plan, op2, pod2, node2, made2, made2) == plain
    log = {"op": op2, "pod": pod2, "node": node2,
           "pos": np.where(made2, np.cumsum(made2) - 1, -1)}
    store_node = np.full(3200, -1, np.int64)
    for o, q, c in zip(op, pod, node):
        store_node[q] = c if o == BIND else -1
    correct, checks, info = check.compare(
        cfg, plan, log, store_node, made2, 4096, 9,
        {"lost": 0, "fallbacks": 0}, cell["work"]["limits"])
    assert correct and info["store_mismatch"] == 0
    assert info["checked"] == plain["checked"]


def preempt_run(monkeypatch, refill: bool, seed: int):
    cell = tiny("tiny-preempt")
    cell["traffic"]["refill_evicted"] = refill
    if not refill:
        # the high pods then fit in every round, with no preemption
        # round between: many more binds
        cell["work"]["pool_per_s"] *= 10
    seen = observe(monkeypatch)
    out = run.run(cell, seed, 2.0, False, require_tpu=False)
    return cell, out, seen["r"], run.plan_run(cell, seed, 2.0)


def round_openings(cell, r, rp):
    """Walk the run's log through every state it passes: no node over
    capacity in any, every eviction made for a nominated pod of higher
    priority. Returns, at each round's opening, whether the running low
    pods are all bound or have their room held (evicted and not yet
    replaced only from nodes with a nomination or a bound high pod), and
    the low pods bound at the end."""
    cfg = cell["config"]
    ks = loadgen.kinds(cfg)
    low, high = (next(i for i, k in enumerate(ks) if k["name"] == n)
                 for n in ("low", "high"))
    quantity = check.reference("k8s111").quantity
    cpu = [round(quantity(k["requests"]["cpu"]) * 1000) for k in ks]
    prio = [k.get("priority", 0) for k in ks]
    alloc = round(quantity(cfg["node_allocatable"]["cpu"]) * 1000)
    kind = rp.plan.kind
    used = np.zeros(cfg["nodes"], np.int64)
    high_bound = np.zeros(cfg["nodes"], np.int64)
    room = np.zeros(cfg["nodes"], np.int64)  # evicted low, not replaced
    low_bound = 0
    nominated = {}  # pod -> node
    starts = sorted(x.start for x in r.rounds)
    opened = []
    log = r.log

    def opening():
        held = set(nominated.values()) | set(np.flatnonzero(high_bound))
        kept = sum(int(room[c]) for c in held)
        opened.append(low_bound + kept == cfg["resident"])

    for j in range(len(log["op"])):
        while starts and starts[0] < log["t"][j]:
            starts.pop(0)
            opening()
        o, p, c = log["op"][j], log["pod"][j], log["node"][j]
        k = kind[p]
        if o == NOMINATE:
            if c >= 0:
                nominated[p] = c
            else:
                nominated.pop(p, None)
            continue
        assert o in (BIND, COMPLETE, EVICT)
        if o == EVICT:
            assert any(n == c and prio[kind[q]] > prio[k]
                       for q, n in nominated.items()), (j, p, c)
        nominated.pop(p, None)
        d = 1 if o == BIND else -1
        used[c] += d * cpu[k]
        assert used[c] <= alloc, (j, c, used[c])
        if k == low:
            low_bound += d
            if o == EVICT:
                room[c] += 1
            elif o == BIND and log["pos"][j] < 0 and p >= rp.n_pods:
                room[c] -= 1  # a refill
        elif k == high:
            high_bound[c] += d
    for _ in starts:
        opening()
    return opened, low_bound


def test_tiny_preempt_keeps_its_invariants(monkeypatch):
    cell, out, r, rp = preempt_run(monkeypatch, True, 4200002401)
    assert out["correct"] is True, out["checks"]
    assert out["checks"]["lost"]["value"] == 0
    assert out["info"]["window_compiles"] == 0
    inside = [x for x in r.rounds if r.t0 < x.end <= r.t1]
    assert len(inside) >= 3
    assert {EVICT, NOMINATE, COMPLETE, BIND} <= set(r.log["op"].tolist())
    opened, _ = round_openings(cell, r, rp)
    assert len(opened) == len(r.rounds) and all(opened)


def test_tiny_preempt_without_refills_drains_low_pods(monkeypatch):
    cell, out, r, rp = preempt_run(monkeypatch, False, 4200002402)
    assert out["correct"] is True, out["checks"]
    opened, low_bound = round_openings(cell, r, rp)
    # the first preemption takes three low pods a node, and nothing
    # brings them back
    assert opened[0] and not all(opened)
    assert low_bound < cell["config"]["resident"]


def test_wait_ready_waits_for_the_first_backoff_deadline():
    from kubernetes_tpu.api import types as api
    from kubernetes_tpu.sched.queue import SchedulingQueue

    q = SchedulingQueue()
    assert run.wait_ready(q) is False  # empty
    parked = api.Pod(metadata=api.ObjectMeta(name="parked"))
    q.set_backoff(parked.uid, q.clock() + 60)
    q.add_unschedulable_if_not_present(parked)
    t = time.perf_counter()
    assert run.wait_ready(q) is False  # parked until an event
    assert time.perf_counter() - t < 0.1
    due = api.Pod(metadata=api.ObjectMeta(name="due"))
    q.set_backoff(due.uid, q.clock() + 0.3)
    q.add_unschedulable_if_not_present(due)
    q.move_all_to_active()  # an event: both into backoff until due
    assert q.active_count() == 0 and q.backoff_count() == 2
    t = time.perf_counter()
    assert run.wait_ready(q) is True
    assert 0.25 < time.perf_counter() - t < 5
    assert q.active_count() == 1
