"""Operations and bytes that the algorithm needs, per call of the served
model's two programs, and the least time the chip needs for them.

They count the work the algorithm needs, not the shapes the program pads
to: real prompt tokens (not the padding of the last chunk), one logits row
per finished prompt, and for decode the cache rows of each live slot's
context, not every ``max_len`` row.  A program that reads fewer rows or
computes fewer logits therefore raises its roofline share, and a share
above 100% means these counts or the measured time are wrong.

Weights and cache entries are counted at 2 bytes (bfloat16).  A matrix
product of m x k by k x n counts 2mkn operations; norms, rope, softmax and
biases are left out as small.
"""

from __future__ import annotations

from typing import Dict

import weights

BYTES = 2


def layer_matmul_params(n: Dict[str, int]) -> int:
    """Weights a token multiplies by in one layer: q, k, v, o and the gated
    MLP's three matrices."""
    d, h, kv, hd, f = n["d"], n["h"], n["kv"], n["hd"], n["f"]
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f


def prefill(n: Dict[str, int], tokens: int, offset: int,
            final: bool) -> Dict[str, float]:
    """One prefill chunk of ``tokens`` real prompt tokens at positions
    ``offset .. offset+tokens-1``; ``final`` chunks produce one logits row."""
    L, d, h, kv, hd, v = (n["layers"], n["d"], n["h"], n["kv"], n["hd"],
                          n["v"])
    p = layer_matmul_params(n)
    keys = tokens * offset + tokens * (tokens + 1) // 2  # causal pairs
    flops = L * (2 * tokens * p + 4 * h * hd * keys)
    head = v * d if final else 0
    flops += 2 * head
    kv_rows = offset + tokens  # prefix read, chunk written
    nbytes = BYTES * (L * p + head + tokens * d
                      + L * 2 * kv * hd * kv_rows)
    return {"flops": float(flops), "bytes": float(nbytes)}


def decode(n: Dict[str, int], live: int, context_rows: int
           ) -> Dict[str, float]:
    """One decode step over ``live`` slots whose contexts, the new token
    included, hold ``context_rows`` cache rows in all."""
    L, d, h, kv, hd, v = (n["layers"], n["d"], n["h"], n["kv"], n["hd"],
                          n["v"])
    p = layer_matmul_params(n)
    flops = L * (2 * live * p + 4 * h * hd * context_rows) + 2 * live * v * d
    nbytes = BYTES * (L * p + v * d + live * d
                      + L * 2 * kv * hd * context_rows)
    return {"flops": float(flops), "bytes": float(nbytes)}


def call_work(n: Dict[str, int], call: tuple) -> Dict[str, float]:
    """Work of one recorded call: ``(t, "prefill", tokens, offset, final)``
    or ``(t, "decode", live, context_rows)``."""
    if call[1] == "prefill":
        return prefill(n, call[2], call[3], call[4])
    return decode(n, call[2], call[3])


def least_seconds(work: Dict[str, float], peak: dict) -> float:
    return max(work["flops"] / peak["bf16_flops_per_s"],
               work["bytes"] / peak["hbm_bytes_per_s"])


def dims(cj: dict) -> Dict[str, int]:
    return weights.dims(cj)
