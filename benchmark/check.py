"""The comparison that decides `correct`.

What the timed path produced is read back once the window has closed:
the store's own record of every bind, of every bound pod deleted (a
completion the harness made, or an eviction the scheduler made) and of
every nomination, in the order the store applied them, and at the end
the node the store holds for each pod, which has to agree with the
binds and deletions of that record.
The configuration's plain reference (references/<name>.py, which
imports nothing of the program) replays the record from the empty
cluster. Numbers compared, each against the
limit the cell file gives:

- violations: binds that break a filter in the state they land in
  (resources and pod count, required node affinity, hostname
  anti-affinity), a pod bound twice, or a pod the store does not hold
  where its record puts it. Exact: limit 0.
- lost: pods created that are neither bound, nor pending in the
  scheduler's queue, nor evicted, nor completed. Exact: limit 0.
- unbound: pods due in an open-loop window that never bound, after a
  grace period past the window. Exact: limit 0.
- fallbacks: ways the run left the device path (probes.py). Limit 0.
- mismatches: of a sample of binds drawn from the seed, those not on
  the node the reference chooses in the state the pod was placed into
  (the max-score feasible nodes in node order, the one at lastNodeIndex
  modulo their number). Exact: limit 0. The control (control.py) goes
  through this same comparison and comes out not correct; PERF.md gives
  the readings.
"""

from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
# the event log's op codes (run.py EventLog)
BIND, COMPLETE, EVICT, NOMINATE = 1, -1, -2, 2


def reference(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_reference_{name}", BENCH / "references" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def store_nodes(store, n_pods: int) -> np.ndarray:
    """Node index of every plan pod as the store holds it (-1 unbound)."""
    out = np.full(n_pods, -1, np.int64)
    for p in store.list("pods"):
        name = p.metadata.name
        if p.spec.node_name and name.startswith("pod-"):
            out[int(name[4:])] = int(p.spec.node_name[5:])
    return out


def compare(cfg, plan, log, store_node, eligible, sample_n: int,
            seed: int, counts: dict, limits: dict):
    """log: the run's event log (arrays op/pod/node/round/pos in the
    order the store applied them, op one of run.py's BIND, COMPLETE,
    EVICT, NOMINATE; pos -1 for the pods bound at set-up or created
    bound). store_node: what the store holds at the end. eligible: bool
    per event, the binds the sample is drawn from. counts: the exact
    counts the run measured (lost, unbound, fallbacks). The reference
    gets every event, evictions and nominations too. Returns (correct,
    checks, info)."""
    ref = reference(cfg["reference"])
    cl = ref.Cluster.from_config(cfg)
    op, pod, node = log["op"], log["pod"], log["node"]
    # the store at the end against the log: each pod on the node of its
    # last bind, or nowhere once deleted; a nomination places nothing
    final = np.full(len(store_node), -1, np.int64)
    placing = np.flatnonzero(op != NOMINATE)
    if len(placing):
        u, first = np.unique(pod[placing][::-1], return_index=True)
        last = placing[len(placing) - 1 - first]  # each pod's last event
        final[u] = np.where(op[last] == BIND, node[last], -1)
    mismatch = int(np.sum(final != store_node))
    made = (op == BIND) & (log["pos"] >= 0)
    rng = np.random.default_rng([seed, 3])
    idx = np.flatnonzero(eligible)
    pick = rng.choice(idx, size=min(sample_n, len(idx)), replace=False)
    sample = np.zeros(len(pod), bool)
    sample[pick] = True
    res = ref.replay(cl, plan, op, pod, node, made, sample)
    numbers = dict(counts)
    numbers["violations"] = res["violations"] + mismatch
    numbers["mismatches"] = res["mismatches"]
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    correct = (all(c["value"] <= c["limit"] for c in checks.values())
               and res["checked"] > 0)
    info = {"checked": res["checked"], "not_best": res["not_best"],
            "gap_max": res["gap_max"], "store_mismatch": mismatch}
    return correct, checks, info


def resident_violations(cfg, plan, nodes: np.ndarray) -> int:
    """Filters the running pods break where they are put at set-up, by
    the configuration's reference: each bound in turn, none sampled."""
    ref = reference(cfg["reference"])
    n = len(nodes)
    op = np.full(n, BIND, np.int64)
    none = np.zeros(n, bool)
    return ref.replay(ref.Cluster.from_config(cfg), plan, op,
                      np.arange(n), np.asarray(nodes, np.int64), none,
                      none)["violations"]
