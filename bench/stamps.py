"""The stamps the program returns with each answer: its engine ticket's
``perf_counter`` readings (``submitted_at``, ``admitted_at``,
``first_token_at``, ``finished_at``), on the same clock as the harness's
``Sent.sent``.  A program that returns none gives the readers nothing."""

import math


def answered(run):
    """``(request, stamps)`` for every answered request whose answer holds
    the engine's stamps."""
    from repro.serve.lane import InferenceLane

    out = []
    for r in run.requests:
        if r.ok:
            got = InferenceLane.payload(r.ticket.result(timeout=0)).get(
                "stamps")
            if got is not None:
                out.append((r, got))
    return out


def p90_ms(values):
    """90th percentile in ms (the highest with about ten of a cell's ~100
    requests beyond it), or None without values."""
    if not values:
        return None
    values = sorted(values)
    return 1e3 * values[math.ceil(0.9 * len(values)) - 1]


def wait_ms(run, start: str, end: str):
    """p90 of the engine's ``end`` stamp minus its ``start`` stamp."""
    return p90_ms([s[end] - s[start] for _, s in answered(run)
                   if s[start] is not None and s[end] is not None])
