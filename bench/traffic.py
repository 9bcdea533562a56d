"""The one traffic generator: a mix's parameters in, requests and arrival
times out.

Every seed gets the same multiset of requests (session, prompt length,
output length) and the same arrival times, drawn once from a fixed master
seed; the run's seed orders the requests over those times and draws the
token ids.  So two seeds offer the same work, with the same bursts, in
another order, and the spread between runs is the system's, not the
draw's.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List

import numpy as np

MASTER_SEED = 20031506  # fixed: sizes and gaps do not depend on --seed


@dataclass
class Request:
    session: str
    prompt: List[int]
    max_new: int


def _lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "uniform":
        return rng.integers(lo, hi + 1, size=n)
    if spec["dist"] == "lognormal":
        x = rng.lognormal(np.log(spec["median"]), spec["sigma"], size=n)
        return np.clip(np.rint(x), lo, hi).astype(np.int64)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def _sessions(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    count, s = int(spec["count"]), float(spec.get("zipf", 0.0))
    w = 1.0 / np.arange(1, count + 1) ** s
    return rng.choice(count, size=n, p=w / w.sum())


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2**64 - 1), stream])


def make_requests(traffic: dict, n: int, vocab: int, seed: int
                  ) -> List[Request]:
    """``n`` requests: the master multiset in the seed's order, with
    prompt ids drawn from ``[1, vocab)`` by the seed."""
    master = np.random.default_rng(MASTER_SEED)
    plens = _lengths(traffic["prompt"], n, master)
    outs = _lengths(traffic["output"], n, master)
    sess = _sessions(traffic["sessions"], n, master)
    rng = seed_rng(seed, 1)
    order = rng.permutation(n)
    out = []
    for i in order:
        prompt = rng.integers(1, vocab, size=int(plens[i])).tolist()
        out.append(Request(f"s{int(sess[i])}", prompt, int(outs[i])))
    return out


def arrival_gaps(traffic: dict, n: int, rate: float) -> np.ndarray:
    """``n`` gaps between arrivals that sum to ``n / rate`` seconds, from
    the master seed: Poisson, or Gamma with the mix's coefficient of
    variation for bursts."""
    master = np.random.default_rng(MASTER_SEED + 1)
    arr = traffic["arrivals"]
    if arr["kind"] == "poisson":
        gaps = master.exponential(1.0, size=n)
    elif arr["kind"] == "gamma":
        shape = 1.0 / float(arr["cv"]) ** 2
        gaps = master.gamma(shape, 1.0, size=n)
    else:
        raise ValueError(f"unknown arrival process {arr['kind']!r}")
    return gaps * (n / rate) / gaps.sum()


def due_times(gaps: np.ndarray) -> np.ndarray:
    """Offsets from the window's start: the first request is due at 0."""
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
