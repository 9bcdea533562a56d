"""90th percentile of the time a request waits in the engine's queue for
a free slot: the engine's admission stamp minus its submit stamp."""

import stamps


def read(run):
    return stamps.wait_ms(run, "submitted_at", "admitted_at")
