"""bind_p99_ms: 99th percentile, over every pod due in the window, of
the bind landing in the store minus the pod's due time. A pod that never
bound counts to the end of the run. Host clock."""

import numpy as np


def read(r):
    if r.due is None or not len(r.due):
        return None
    lat = np.sort(r.bind_latency())
    return 1000.0 * float(lat[int(np.ceil(0.99 * len(lat))) - 1])
