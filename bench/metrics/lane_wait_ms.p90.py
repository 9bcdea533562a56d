"""90th percentile of the time a request takes from the client's send to
the engine's submit: the lane's workflow, its pool batch and its AFT
reads.  A generate step batched behind another waits here for that whole
generation."""

import stamps


def read(run):
    return stamps.p90_ms([s["submitted_at"] - r.sent
                          for r, s in stamps.answered(run)])
