"""Find the knee of an open-loop cell once, by a sweep on the chip: the
highest offered rate at which, over one window, the backlog does not
grow and bind_p99_ms stays under the pod-startup SLO (5 s,
test/e2e/scalability/density.go:55). The cell then offers about four
fifths of it, as a number in its workload file. The benchmark's own
runs do not run this.

    python benchmark/sweep.py --workload basic5k-paced --seconds 15 \
        --rates 1000 2000 3000 4000

All rates run in this one process, which holds the chip. One line per
rate: bind_p50/p99, the median latency of the first and the last fifth
of the pods due (a backlog that grows makes the last fifth wait longer),
and how many pods due were still unbound when the window closed.
"""

import argparse
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402

SLO_MS = 5000.0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    seen = {}
    real = run.Observations

    def keep(**kw):
        seen["r"] = real(**kw)
        return seen["r"]

    run.Observations = keep
    for rate in args.rates:
        cell = run.load_cell(args.workload)
        cell["traffic"]["rate"] = rate
        out = run.run(cell, args.seed, args.seconds, False)
        r = seen["r"]
        lat = r.bind_latency() * 1000.0
        fifth = max(len(lat) // 5, 1)
        t = r.log["t"]
        bound_by_close = np.sum((r.log["pod"] >= r.due_index0)
                                & (r.log["op"] == run.BIND) & (t <= r.t1))
        p99 = float(np.sort(lat)[int(np.ceil(0.99 * len(lat))) - 1])
        print(json.dumps({
            "rate": rate, "correct": out["correct"],
            "bind_p50_ms": float(np.median(lat)), "bind_p99_ms": p99,
            "first_fifth_p50_ms": float(np.median(lat[:fifth])),
            "last_fifth_p50_ms": float(np.median(lat[-fifth:])),
            "unbound_at_close": int(len(lat) - bound_by_close),
            "window_compiles": out["info"]["window_compiles"],
            "under_slo": p99 < SLO_MS}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
