"""Mean device time of one run of the engine's prefill program
(``jit_prefill``: one prompt chunk of one slot), from the profiler trace."""

import devtime


def read(run):
    return devtime.mean_ms(run, "jit_prefill")
