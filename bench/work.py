"""Operations and bytes that the algorithm needs, per call of the served
model's two programs, and the least time the chip needs for them.

They count the work the algorithm needs, not the shapes the program pads
to: real prompt tokens (not the padding of the last chunk), one logits row
per finished prompt, and for decode the cache rows of each live slot's
context, not every ``max_len`` row.  A program that reads fewer rows or
computes fewer logits therefore raises its roofline share, and a share
above 100% means these counts or the measured time are wrong.  The counts
of one call are the configuration's family's
(``families/<model_type>.py``, ``call_work``).
"""

from __future__ import annotations

from typing import Dict

import spec


def call_work(cj: dict, call: tuple) -> Dict[str, float]:
    """``{"flops", "bytes"}`` of one recorded call: ``(t, "prefill",
    tokens, offset, final)`` or ``(t, "decode", live, context_rows)``."""
    fam = spec.family(cj)
    return fam.call_work(fam.dims(cj), call)


def least_seconds(work: Dict[str, float], peak: dict) -> float:
    return max(work["flops"] / peak["bf16_flops_per_s"],
               work["bytes"] / peak["hbm_bytes_per_s"])
