"""How late the load generator sent: 99th percentile of send time minus
due time, so that a starved generator is not read as a fast server."""

import math


def read(run):
    late = sorted(r.sent - r.due for r in run.requests)
    if not late:
        return None
    return 1e3 * late[math.ceil(0.99 * len(late)) - 1]
