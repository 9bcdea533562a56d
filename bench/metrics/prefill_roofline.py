"""Share of the prefill program's roofline: the least time the chip needs
for the prefill work of the traced window (``work.py``: real prompt
tokens, one logits row per finished prompt), over its device time."""

import devtime


def read(run):
    return devtime.roofline(run, "prefill", "jit_prefill")
