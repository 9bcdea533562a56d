"""Median time a request spends in the lane, the workflow pool and its AFT
reads: client latency (lane submit to answer) minus the engine's own time
(engine submit to finish), from the proxy round each replica."""


def read(run):
    own = sorted((r.done - r.sent) - r.engine_s for r in run.requests
                 if r.ok and r.engine_s is not None)
    return 1e3 * own[len(own) // 2] if own else None
