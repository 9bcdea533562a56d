"""Share of the decode program's roofline: the least time the chip needs
for the decode work of the traced window (``work.py``: the cache rows of
each live slot's context), over its device time."""

import devtime


def read(run):
    return devtime.roofline(run, "decode", "jit_decode")
