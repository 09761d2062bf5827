"""round_ms_p50.paced: median wall time of the scheduling rounds that
started inside the window, each from the start of its featurize step to
the end of its commit step (the span the program's
e2e_scheduling_latency observes). Host clock."""

import numpy as np


def read(r):
    if r.cell["traffic"]["loop"] != "open":
        return None
    d = [x.end - x.start for x in r.rounds if r.t0 <= x.start <= r.t1]
    return 1000.0 * float(np.median(d)) if d else None
