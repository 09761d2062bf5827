"""gen_late_p99_ms.paced: 99th percentile of how late the open-loop
generator handed each pod over, after its due time. Host clock."""

import numpy as np


def read(r):
    if r.accepted is None:
        return None
    late = r.accepted - (r.t0 + r.due)
    late = np.sort(late[~np.isnan(late)])
    if not len(late):
        return None
    return 1000.0 * float(late[int(np.ceil(0.99 * len(late))) - 1])
