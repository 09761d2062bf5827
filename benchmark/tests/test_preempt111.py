"""The preemption reference (references/preempt111.py) on hand-built event
logs, each fault read where it belongs, and tiny-preempt judged by it on
the CPU: correct as the program runs, not correct where the binding
round ignores nominations.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_preempt111.py -q
"""

import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
DATA = BENCH / "tests" / "data"
sys.path.insert(0, str(BENCH))

import check  # noqa: E402
import loadgen  # noqa: E402
import run  # noqa: E402
from check import BIND, EVICT, NOMINATE  # noqa: E402

LOW, HIGH = 0, 1


def preempt_cfg(nodes: int, resident: int) -> dict:
    cfg = json.loads((DATA / "configs" / "preempt16.json").read_text())
    return dict(cfg, nodes=nodes, resident=resident, reference="preempt111")


class Log:
    """A hand-built event log: low pods 0..n_low-1 bound at set-up on
    the nodes given, high pods after them."""

    def __init__(self, low_nodes, n_high):
        self.n_low = len(low_nodes)
        self.rows = [(BIND, i, c, False) for i, c in enumerate(low_nodes)]
        self.on = {}  # node -> low pods still bound there
        for i, c in enumerate(low_nodes):
            self.on.setdefault(c, []).append(i)
        kind = [LOW] * self.n_low + [HIGH] * n_high
        self.plan = loadgen.PodPlan(np.asarray(kind, np.int32),
                                    np.full(len(kind), -1, np.int32),
                                    np.full(len(kind), -1, np.int32))

    def high(self, h):
        return self.n_low + h

    def nominate(self, h, c, evict=3):
        self.rows.append((NOMINATE, self.high(h), c, False))
        for _ in range(evict):
            self.evict(c)

    def evict(self, c):
        self.rows.append((EVICT, self.on[c].pop(), c, False))

    def bind(self, h, c):
        self.rows.append((BIND, self.high(h), c, True))

    def replay(self, cfg):
        ref = check.reference("preempt111")
        op, pod, node, made = (np.asarray(a) for a in zip(*self.rows))
        made = made.astype(bool)
        return ref.replay(ref.Cluster.from_config(cfg), self.plan, op, pod,
                          node, made, made)


def full(nodes=4, per=4):
    return [c for c in range(nodes) for _ in range(per)]


def test_preemption_as_1_11_makes_it_reads_clean():
    log = Log(full(), 2)
    log.nominate(0, 0)
    log.nominate(1, 1)
    log.bind(0, 0)
    log.bind(1, 1)
    res = log.replay(preempt_cfg(4, 16))
    assert res["nominations"] == 2 and res["checked"] == 2
    assert (res["violations"], res["mismatches"]) == (0, 0)


def test_bind_onto_a_node_held_for_an_equal_priority_pod_mismatches():
    # high-0's only feasible node is n0: n1 holds 0.9 CPU and high-1's
    # nomination, 3 CPU of priority 10, as high as its own
    log = Log(full(), 2)
    log.nominate(0, 0)
    log.nominate(1, 1)
    log.bind(0, 1)
    res = log.replay(preempt_cfg(4, 16))
    assert res["mismatches"] == 1 and res["not_best"] == 1
    assert res["violations"] == 0


def test_nomination_off_the_best_nodes_mismatches():
    # n3 holds three low pods: two victims there, three anywhere else,
    # so pickOneNodeForPreemption takes n3 (lowest sum of priorities)
    low = full()[:-1]
    log = Log(low, 2)
    log.nominate(0, 0)
    res = log.replay(preempt_cfg(4, 15))
    assert (res["mismatches"], res["violations"]) == (1, 0)
    right = Log(low, 2)
    right.nominate(0, 3, evict=2)
    assert right.replay(preempt_cfg(4, 15))["mismatches"] == 0


def test_nomination_onto_a_node_already_held_mismatches():
    log = Log(full(), 2)
    log.nominate(0, 0)
    log.nominate(1, 0, evict=1)
    res = log.replay(preempt_cfg(4, 16))
    assert res["mismatches"] == 1


def test_a_fourth_victim_mismatches():
    # 0.9 + 3.0 <= 4: the fourth low pod is reprieved
    log = Log(full(), 1)
    log.nominate(0, 0, evict=4)
    res = log.replay(preempt_cfg(4, 16))
    assert (res["mismatches"], res["violations"]) == (1, 0)


def test_eviction_with_no_nomination_is_a_violation():
    log = Log(full(), 1)
    log.evict(2)
    res = log.replay(preempt_cfg(4, 16))
    assert (res["violations"], res["mismatches"]) == (1, 0)
    # nor does a nomination of a pod no higher than the evicted one
    log = Log(full(), 0)
    log.rows.append((NOMINATE, 0, 2, False))
    log.evict(2)
    assert log.replay(preempt_cfg(4, 16))["violations"] == 1


def test_kinds_of_one_priority_with_different_requests_are_refused():
    cfg = preempt_cfg(4, 16)
    cfg["kinds"] = [dict(k, priority=0) for k in cfg["kinds"]]
    ref = check.reference("preempt111")
    with pytest.raises(ValueError):
        ref.reprieve_order(ref.Cluster.from_config(cfg))


@pytest.fixture
def tiny_preempt(tmp_path):
    """tiny-preempt's files in a directory of this test's own, with the
    configuration judged by preempt111 and mismatches limited to 0."""
    spec = json.loads((DATA / "BENCHMARK.json").read_text())
    for sub in ("configs", "workloads", "traffic"):
        (tmp_path / sub).mkdir()
    shutil.copy(DATA / "traffic" / "drain.json", tmp_path / "traffic")
    (tmp_path / "configs" / "preempt16.json").write_text(
        json.dumps(preempt_cfg(16, 64)))
    work = json.loads((DATA / "workloads" / "tiny-preempt.json").read_text())
    work["limits"]["mismatches"] = 0
    work["warm_rounds"] = 3
    (tmp_path / "workloads" / "tiny-preempt.json").write_text(
        json.dumps(work))
    return run.load_cell("tiny-preempt", base=tmp_path, spec=spec)


def test_tiny_preempt_is_correct_by_preempt111(tiny_preempt):
    out = run.run(tiny_preempt, 4200002601, 2.0, False, require_tpu=False)
    assert out["correct"] is True, out["checks"]
    assert out["info"]["checked"] > 0
    assert out["info"]["window_compiles"] == 0
    assert all(c["value"] == 0 for c in out["checks"].values())


def test_tiny_preempt_ignoring_nominations_mismatches(tiny_preempt,
                                                      monkeypatch):
    """The binding round without the nomination plane, as the program
    ran before it counted nominations: preemptors take one another's
    freed nodes, and preempt111 reads it."""
    from kubernetes_tpu.sched.scheduler import Scheduler

    monkeypatch.setattr(Scheduler, "_nominations",
                        lambda self, *a, **kw: None)
    out = run.run(tiny_preempt, 4200002601, 2.0, False, require_tpu=False)
    assert out["correct"] is False
    assert out["checks"]["mismatches"]["value"] > 0
    assert out["checks"]["violations"]["value"] == 0
