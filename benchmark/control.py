"""The control of `correct`: the plain reference put in the scheduler's
place with one stated guarantee broken. The configurations state integer
scores (1.11's Go ints), so no lower precision is stated; the guarantee
broken is the choice among the max-score nodes: the control takes the
first of them (a plain argmax) instead of 1.11 selectHost's round-robin,
the shortcut that would save the scan its rank computation. It places a
cell's pods as a run would (the same plan and running pods from the
seed, completions back to `resident` after every `backlog` pods), and
its event log then goes through check.compare, the comparison a run
goes through, with the cell's limits: it has to come out not correct.
Beside it, the sound reference in the same place has to come out
correct.

    python benchmark/control.py --workload <cell> --pods <n> --seeds 1 2 3

--pods is how many pods to place after the running ones; the sample is
drawn from the part after the cell's warm-up (at most the first half),
as in a run. The benchmark's own runs do not run this.
"""

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import check  # noqa: E402
import loadgen  # noqa: E402


VARIANTS = {"first_tie": {"first_tie": True}, "sound": {}}


def reading(cfg, work, n_pods: int, seed: int, variant: str,
            warm: int) -> dict:
    ref = check.reference(cfg["reference"])
    cl = ref.Cluster.from_config(cfg)
    n_res = cfg["resident"]
    plan = loadgen.plan_pods(cfg, n_res + n_pods, seed, n_res)
    res_nodes = loadgen.resident_nodes(cfg, plan, n_res, seed)
    t = time.perf_counter()
    op, pod, node, made = ref.greedy(
        cl, plan, np.arange(n_res, n_res + n_pods),
        resident=(np.arange(n_res), res_nodes), keep=n_res,
        batch=cfg["backlog"], **VARIANTS[variant])
    placed_s = time.perf_counter() - t
    # the log as a run's EventLog gives it, and the state it ends in as
    # the store would hold it
    log = {"op": op, "pod": pod, "node": node,
           "pos": np.where(made, np.cumsum(made) - 1, -1)}
    store_node = np.full(n_res + n_pods, -1, np.int64)
    for o, q, c in zip(op, pod, node):
        store_node[q] = c if o > 0 else -1
    eligible = made & (pod >= n_res + warm)
    counts = {k: 0 for k in work["limits"]
              if k not in ("violations", "mismatches")}
    correct, checks, info = check.compare(
        cfg, plan, log, store_node, eligible, work["sample"], seed, counts,
        work["limits"])
    return {"variant": variant, "seed": seed, "correct": correct,
            "placed": int(np.sum(made)), "placed_s": placed_s,
            "checks": checks, **info}


def warm_pods(cfg, work) -> int:
    """Pods a run places before its window opens (a drain round takes
    up to its backlog)."""
    if "warm_rounds" in work:
        return cfg["backlog"] * work["warm_rounds"]
    return sum(work["warm_batches"])


def main(argv=None):
    import run

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--pods", type=int, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    cfg, work = cell["config"], cell["work"]
    for seed in args.seeds:
        for variant in VARIANTS:
            out = reading(cfg, work, args.pods, seed, variant,
                          min(warm_pods(cfg, work), args.pods // 2))
            print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
