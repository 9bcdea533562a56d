"""Mean device time of one run of the engine's decode program
(``jit_decode``: one token for every slot), from the profiler trace."""

import devtime


def read(run):
    return devtime.mean_ms(run, "jit_decode")
