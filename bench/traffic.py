"""The one traffic generator: a mix's parameters in, requests and arrival
times out.

Every seed gets the same multiset of requests (session, prompt length,
output length) and the same arrival times, drawn once from a fixed master
seed; the run's seed orders the requests over those times and draws the
token ids.  So two seeds offer the same work, with the same bursts, in
another order, and the spread between runs is the system's, not the
draw's.

A mix's optional ``"order"`` says how far the seed may reorder:
``{"kind": "any"}`` (the default) deals every request to any arrival;
``{"kind": "size_groups", "group": k}`` deals the prompt lengths of each
group of ``k`` adjacent lengths over the arrivals that group holds in the
master draw, and every arrival keeps its master output length and
session.  So every burst carries the same prompt tokens, to a group's
width, and the same answers and sessions, whatever the seed.
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


def _order(spec: dict, plens: np.ndarray, rng: np.random.Generator
           ) -> tuple:
    """For each arrival slot, drawn by the seed: the master request whose
    prompt length it gets, and the one whose output length and session."""
    n = len(plens)
    if spec["kind"] == "any":
        order = rng.permutation(n)
        return order, order
    if spec["kind"] == "size_groups":
        k = int(spec["group"])
        by_size = np.argsort(plens, kind="stable")
        order = np.arange(n)
        for j in range(0, n, k):
            held = by_size[j:j + k]
            order[held] = rng.permutation(held)
        return order, np.arange(n)
    raise ValueError(f"unknown request order {spec['kind']!r}")


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2**64 - 1), stream])


def make_requests(traffic: dict, n: int, vocab: int, seed: int
                  ) -> List[Request]:
    """``n`` requests: the master multiset in the seed's order (the mix's
    ``"order"``), with prompt ids drawn from ``[1, vocab)`` by the seed."""
    master = np.random.default_rng(MASTER_SEED)
    plens = _lengths(traffic["prompt"], n, master)
    outs = _lengths(traffic["output"], n, master)
    sess = _sessions(traffic["sessions"], n, master)
    rng = seed_rng(seed, 1)
    prompts, rest = _order(traffic.get("order", {"kind": "any"}), plens,
                           rng)
    out = []
    for i, j in zip(prompts, rest):
        prompt = rng.integers(1, vocab, size=int(plens[i])).tolist()
        out.append(Request(f"s{int(sess[j])}", prompt, int(outs[j])))
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
