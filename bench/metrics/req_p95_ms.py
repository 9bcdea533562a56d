"""95th percentile of request latency over every request due in the
window, from when it was due (open loop) or sent (closed loop) to its
answer.  A request that failed or never answered counts as the longest
wait the run allowed."""

import math


def read(run):
    if not run.requests:
        return None
    end = max([r.done for r in run.requests if r.done is not None]
              + [run.t1])
    lat = sorted((r.done if r.ok else end) - r.due for r in run.requests)
    return 1e3 * lat[math.ceil(0.95 * len(lat)) - 1]
