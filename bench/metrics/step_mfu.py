"""The whole step's share of the chip's peak: the operations the algorithm
needs for every prefill and decode call of the traced window, over the
window times the peak bf16 rate.  Bounds every program's roofline share
from above in what it can claim end to end."""

import work


def read(run):
    if run.trace is None or run.trace_t is None or run.peak is None:
        return None
    a, b = run.trace_t
    flops = sum(work.call_work(run.dims, c)["flops"] for c in run.calls
                if a <= c[0] < b)
    return 100.0 * flops / (run.trace["window_s"]
                            * run.peak["bf16_flops_per_s"])
