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

import pytest

BENCH = Path(__file__).resolve().parents[1]
DATA = BENCH / "tests" / "data"
sys.path.insert(0, str(BENCH))

import control  # noqa: E402
import run  # noqa: E402


def tiny(name):
    spec = json.loads((DATA / "BENCHMARK.json").read_text())
    return run.load_cell(name, base=DATA, spec=spec)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_is_not_correct_and_sound_reference_is(seed):
    cell = tiny("tiny-drain")
    cfg = dict(cell["config"], nodes=200, resident=1200, backlog=1000)
    work = cell["work"]
    low = control.reading(cfg, work, 4000, seed, "first_tie", 0)
    assert low["correct"] is False
    assert low["checks"]["violations"]["value"] == 0
    assert low["checks"]["mismatches"]["value"] > 0
    sound = control.reading(cfg, work, 4000, seed, "sound", 0)
    assert sound["correct"] is True
    assert sound["checks"]["mismatches"]["value"] == 0


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
