"""The inter-pod reference (references/ipa111.py) judges tiny-ipa's logs:
the log the sound reference places is correct, and each planted fault
reads where it belongs. tiny-ipa (tests/data/configs/ipa48.json) holds
SchedulingPreferredPodAffinity's pods beside kinds with required
anti-affinity on the hostname, required self-affinity on the zone and
preferred terms that no other kind returns, on 48 nodes in three zones,
its running pods placed by the reference; a round of its backlog is one
wave of 128 pods.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_ipa111.py -q
"""

import json
import sys
from dataclasses import replace
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
from check import BIND  # noqa: E402

SEEDS = [4200003011, 4200003012, 4200003013]
PODS = 1536  # twelve rounds of the backlog


def tiny_ipa():
    spec = json.loads((DATA / "BENCHMARK.json").read_text())
    return run.load_cell("tiny-ipa", base=DATA, spec=spec)


def judged(seed, cl=None, edit=None, **greedy_kw):
    """Place PODS pods after the running ones as control.reading does,
    with the cluster `cl` and greedy's `greedy_kw`, let `edit` change the
    log, and judge it by check.compare with the sound reference."""
    cell = tiny_ipa()
    cfg, work = cell["config"], cell["work"]
    ref = check.reference(cfg["reference"])
    n_res = cfg["resident"]
    plan = loadgen.plan_pods(cfg, n_res + PODS, seed, n_res)
    res_nodes = loadgen.resident_nodes(cfg, plan, n_res, seed)
    op, pod, node, made = ref.greedy(
        cl if cl is not None else ref.Cluster.from_config(cfg), plan,
        np.arange(n_res, n_res + PODS), resident=(np.arange(n_res),
                                                  res_nodes),
        keep=n_res, batch=cfg["backlog"], **greedy_kw)
    if edit is not None:
        node = edit(cfg, plan, op, pod, node.copy(), made)
    log = {"op": op, "pod": pod, "node": node,
           "pos": np.where(made, np.cumsum(made) - 1, -1)}
    store_node = np.full(n_res + PODS, -1, np.int64)
    for o, q, c in zip(op, pod, node):
        store_node[q] = c if o > 0 else -1
    eligible = made & (pod >= n_res + control.warm_pods(cfg, work))
    counts = {"lost": 0, "fallbacks": 0}
    correct, checks, info = check.compare(
        cfg, plan, log, store_node, eligible, work["sample"], seed, counts,
        work["limits"])
    return correct, {k: c["value"] for k, c in checks.items()}, info


def second_anti_pod(cfg, plan, op, pod, node, made):
    """Move one bind of the anti kind onto a node that holds another pod
    of that kind: a second pod of a required anti-affinity term there."""
    anti = [k["name"] for k in loadgen.kinds(cfg)].index("anti")
    on = {}  # anti pods bound: pod -> node
    for j in range(len(op)):
        p = pod[j]
        if plan.kind[p] != anti:
            continue
        if op[j] != BIND:
            on.pop(p, None)
        elif made[j] and on and node[j] not in on.values():
            node[j] = next(iter(on.values()))
            return node
        else:
            on[p] = node[j]
    raise AssertionError("no anti pod to move")


@pytest.mark.parametrize("seed", SEEDS)
def test_sound_reference_is_correct(seed):
    correct, checks, info = judged(seed)
    assert correct, checks
    assert info["checked"] > 1000


@pytest.mark.parametrize("seed", SEEDS)
def test_priority_counts_fixed_per_wave_mismatch(seed):
    # the program's wave reads the priority's counts as they stood when
    # the wave began; 1.11 counts every pod placed before
    correct, checks, _ = judged(seed, frozen_counts=128)
    assert not correct and checks["mismatches"] > 0, checks
    assert checks["violations"] == 0


@pytest.mark.parametrize("seed", SEEDS)
def test_incoming_preferred_terms_dropped_mismatch(seed):
    cfg = tiny_ipa()["config"]
    ref = check.reference(cfg["reference"])
    cl = ref.Cluster.from_config(cfg)
    correct, checks, _ = judged(
        seed, cl=replace(cl, pref_own=np.zeros_like(cl.pref_own)))
    assert not correct and checks["mismatches"] > 0, checks
    assert checks["violations"] == 0


@pytest.mark.parametrize("seed", SEEDS)
def test_first_tie_control_is_not_correct(seed):
    correct, checks, _ = judged(seed, first_tie=True)
    assert not correct and checks["mismatches"] > 0, checks


@pytest.mark.parametrize("seed", SEEDS)
def test_second_anti_affinity_pod_on_a_node_is_a_violation(seed):
    correct, checks, _ = judged(seed, edit=second_anti_pod)
    assert not correct and checks["violations"] > 0, checks


def test_control_reads_tiny_ipa_through_control_py():
    cell = tiny_ipa()
    cfg, work = cell["config"], cell["work"]
    low = control.reading(cfg, work, PODS, SEEDS[0], "first_tie",
                          control.warm_pods(cfg, work))
    sound = control.reading(cfg, work, PODS, SEEDS[0], "sound",
                            control.warm_pods(cfg, work))
    assert low["correct"] is False and sound["correct"] is True
