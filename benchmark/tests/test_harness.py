"""The harness at a tiny test-only cell on the CPU (tests/data): the last
line's keys, a drain window made of whole rounds, an open loop that
offers a slowed scheduler the same due times, and no result without a
TPU or without the program.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import json
import os
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

import run  # noqa: E402


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
    import loadgen

    cfg = tiny("tiny-drain")["config"]
    plan = loadgen.plan_pods(cfg, 1000, seed)
    assert np.bincount(plan.kind, minlength=3).tolist() == [500, 250, 250]
    due = loadgen.poisson_due(100.0, 3.0, seed)
    assert len(due) == 300 and 0 <= due.min() and due.max() < 3.0
