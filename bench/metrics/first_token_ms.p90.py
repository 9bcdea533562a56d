"""90th percentile of the time from the engine's submit to the request's
first token: the queue wait, then every prompt chunk, each waiting its
turn behind the other slots' chunks and the decode steps between them."""

import stamps


def read(run):
    return stamps.wait_ms(run, "submitted_at", "first_token_at")
