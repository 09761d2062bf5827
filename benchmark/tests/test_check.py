"""The comparison that decides `correct` has to fail: the control (the
reference in the scheduler's place, ties broken at the first max-score
node), and a run with the timed path broken underneath, once for each
fault a cell can have. The exchange between chips has no fault here:
every cell runs on one chip.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

On the chip, the same faults at a cell's own size (a short window):

    BENCH_FAULT_CELL=<cell> BENCH_FAULT_SEEDS="1 2" BENCH_FAULT_SECONDS=10 \
        python -m pytest benchmark/tests/test_check.py -k cell_size -s
"""

import json
import os
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
DATA = BENCH / "tests" / "data"
sys.path.insert(0, str(BENCH))

import check  # noqa: E402
import control  # noqa: E402
import loadgen  # noqa: E402
import run  # noqa: E402


def tiny(name):
    spec = json.loads((DATA / "BENCHMARK.json").read_text())
    return run.load_cell(name, base=DATA, spec=spec)


# the readings of the reference's code before configurations could
# declare kinds, at 200 nodes: the control's mismatches, and a replay of
# the sound log with every seventh scheduled pod moved one node over
PINNED_FIRST_TIE = {1: 3697, 2: 3683, 3: 3702}
PINNED_MOVED = {
    seed: dict(zip(("violations", "mismatches", "not_best", "checked",
                    "gap_max"), v))
    for seed, v in ((1, (160, 3875, 2999, 4000, 3)),
                    (2, (171, 3858, 3193, 4000, 4)),
                    (3, (157, 3837, 3044, 4000, 3)))}


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_is_not_correct_and_sound_reference_is(seed):
    cell = tiny("tiny-drain")
    cfg = dict(cell["config"], nodes=200, resident=1200, backlog=1000)
    work = cell["work"]
    low = control.reading(cfg, work, 4000, seed, "first_tie", 0)
    assert low["correct"] is False
    assert low["checks"]["violations"]["value"] == 0
    assert low["checks"]["mismatches"]["value"] == PINNED_FIRST_TIE[seed]
    sound = control.reading(cfg, work, 4000, seed, "sound", 0)
    assert sound["correct"] is True
    assert sound["checks"]["mismatches"]["value"] == 0


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_replay_of_a_moved_log_is_pinned(seed):
    cfg = dict(tiny("tiny-drain")["config"], nodes=200, resident=1200,
               backlog=1000)
    ref = check.reference(cfg["reference"])
    cl = ref.Cluster.from_config(cfg)
    plan = loadgen.plan_pods(cfg, 5200, seed)
    res = loadgen.resident_nodes(cfg, plan, 1200, seed)
    op, pod, node, made = ref.greedy(
        cl, plan, np.arange(1200, 5200), resident=(np.arange(1200), res),
        keep=1200, batch=1000)
    moved = np.isin(pod, pod[made][::7])
    node = np.where(moved, (node + 1) % 200, node)
    assert ref.replay(cl, plan, op, pod, node, made, made) \
        == PINNED_MOVED[seed]


def state_unchanged(monkeypatch):
    # the device's copy of the cluster never changes after its first
    # upload: every round scores the state the warm-up left
    from kubernetes_tpu.state.snapshot import Snapshot

    real = Snapshot._sync_group

    def frozen(self, jax, key, target, full_dirty):
        if key in self._device_cache:
            self._dirty_rows[key].clear()
            return
        real(self, jax, key, target, full_dirty)

    monkeypatch.setattr(Snapshot, "_sync_group", frozen)


def half_left_out(monkeypatch):
    # every other pod of a round is reported placed and never bound
    from kubernetes_tpu.sched.scheduler import Scheduler

    real = Scheduler._commit
    n = [0]

    def commit(self, pod, node_name):
        n[0] += 1
        return True if n[0] % 2 else real(self, pod, node_name)

    monkeypatch.setattr(Scheduler, "_commit", commit)


def answer_altered(monkeypatch):
    # one pod in seven is bound to the next node over
    from kubernetes_tpu.runtime.store import ObjectStore

    real = ObjectStore.bind
    n = [0]

    def bind(self, pod, node_name):
        n[0] += 1
        if n[0] % 7 == 0:
            node_name = f"node-{(int(node_name[5:]) + 1) % 64}"
        return real(self, pod, node_name)

    monkeypatch.setattr(ObjectStore, "bind", bind)


@pytest.mark.parametrize("cell", ["tiny-drain", "tiny-paced"])
@pytest.mark.parametrize("fault", [state_unchanged, half_left_out,
                                   answer_altered])
def test_broken_timed_path_is_not_correct(monkeypatch, cell, fault):
    fault(monkeypatch)
    out = run.run(tiny(cell), 5, 1.5, False, require_tpu=False)
    assert out["correct"] is False, out["checks"]


@pytest.mark.parametrize("cell", ["tiny-drain", "tiny-paced"])
def test_sound_run_is_correct(cell):
    out = run.run(tiny(cell), 5, 1.5, False, require_tpu=False)
    assert out["correct"] is True, out["checks"]


FAULT_CELL = os.environ.get("BENCH_FAULT_CELL")


@pytest.mark.skipif(not FAULT_CELL, reason="BENCH_FAULT_CELL names a cell "
                    "of BENCHMARK.json to break at its own size, on a TPU")
@pytest.mark.parametrize("seed", [int(x) for x in os.environ.get(
    "BENCH_FAULT_SEEDS", "1").split()])
@pytest.mark.parametrize("fault", [state_unchanged, half_left_out,
                                   answer_altered])
def test_broken_timed_path_at_cell_size(monkeypatch, fault, seed):
    fault(monkeypatch)
    out = run.run(run.load_cell(FAULT_CELL), seed,
                  float(os.environ.get("BENCH_FAULT_SECONDS", "10")), False)
    print(json.dumps({"cell": FAULT_CELL, "fault": fault.__name__,
                      "seed": seed, "correct": out["correct"],
                      "checks": out["checks"]}), flush=True)
    assert out["correct"] is False, out["checks"]
