"""Device time per program from the reduced trace, and roofline shares,
for the metric readers."""

from __future__ import annotations

from typing import Optional

import work


def mean_ms(run, program: str) -> Optional[float]:
    if run.trace is None:
        return None
    runs = run.trace["programs"].get(program)
    return 1e3 * sum(runs) / len(runs) if runs else None


def roofline(run, kind: str, program: str) -> Optional[float]:
    """Mean least time of the ``kind`` calls dispatched in the traced
    window over the mean device time of ``program``'s runs in it, in %."""
    if run.trace is None or run.trace_t is None or run.peak is None:
        return None
    runs = run.trace["programs"].get(program)
    a, b = run.trace_t
    calls = [c for c in run.calls if c[1] == kind and a <= c[0] < b]
    if not runs or not calls:
        return None
    need = sum(work.least_seconds(work.call_work(run.cell.config, c),
                                   run.peak)
               for c in calls) / len(calls)
    return 100.0 * need / (sum(runs) / len(runs))
