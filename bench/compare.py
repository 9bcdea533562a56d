"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the requests the window finished, drawn from the seed and with the longest
in it, is teacher-forced through the float32 reference (``reference.py``)
on the benchmark's own weights.  The number compared is the widest gap by
which a served token's reference logit lies below the reference's best
logit at that position (greedy decoding serves the best up to rounding).
A request in flight across a weight swap is judged by its best split into
a prefix served on the old step and a suffix on the new one.

Exact counts sit beside it with the limit 0: requests that never answered,
requests that answered fewer tokens than asked, and where the traffic
publishes weights, torn weight reads and the AFT trace checker's
violations.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import reference
from traffic import seed_rng

TARGET_TOKENS = 384  # served tokens the sample aims for
MAX_PACKS = 4        # reference passes of ``reference_rows`` rows at most


def pick(done: List, seed: int, rows: int) -> List[List]:
    """The longest request (prompt and answer), any that crossed a weight
    swap, then others in the seed's order, first-fit into packs of
    ``rows`` rows, until the served tokens reach ``TARGET_TOKENS``."""
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.prompt) + len(r.tokens))
    rest = [r for r in done if r is not longest]
    order = seed_rng(seed, 3).permutation(len(rest))
    crossed = [rest[i] for i in order if rest[i].served_by is None][:2]
    others = [rest[i] for i in order if rest[i] not in crossed]
    packs: List[List] = []
    fill: List[int] = []
    served = 0
    for r in [longest] + crossed + others:
        need = len(r.prompt) + len(r.tokens) - 1
        room = [i for i, f in enumerate(fill) if f + need <= rows]
        if room:
            i = room[0]
        elif len(packs) < MAX_PACKS and need <= rows:
            packs.append([])
            fill.append(0)
            i = len(packs) - 1
        else:
            continue
        packs[i].append(r)
        fill[i] += need
        served += len(r.tokens)
        if served >= TARGET_TOKENS:
            break
    return packs


def gaps(params_by_step: Dict[int, object], cj: dict, packs: List[List],
         rows: int, max_len: int, control: bool = False) -> dict:
    """Widest gap over the sample, and with ``control`` the widest gap of
    the tokens the float8 reference puts first (this consumes
    ``params_by_step``: its weights are quantized in place)."""
    window = reference.window_rows(max_len)
    packed = [reference.pack([(r.prompt, r.tokens) for r in p], rows)
              for p in packs]
    sample = [r for p in packs for r in p]
    per_step, exact, ctrl = {}, {}, {}
    for step, params in params_by_step.items():
        per_step[step], exact[step], logits = [], [], []
        for p, pk in zip(packs, packed):
            lg = reference.forward(params, cj, pk, window)
            g, ex = reference.token_gaps(lg, pk[4])
            lengths = [len(r.tokens) for r in p]
            per_step[step] += reference.split_rows(g[:pk[5]], lengths)
            exact[step] += reference.split_rows(ex[:pk[5]], lengths)
            logits.append(lg)
        if control:
            qparams, scales = reference.quantize(params, cj)
            ctrl[step] = max(
                float(reference.control_gaps(
                    lg, reference.forward(qparams, cj, pk, window, scales)
                )[:pk[5]].max(initial=0.0))
                for lg, pk in zip(logits, packed))
        del logits
    steps = sorted(per_step)
    widest, n_exact = 0.0, 0
    for i, r in enumerate(sample):
        g1 = per_step[steps[0]][i]
        g2 = per_step[steps[1]][i] if len(steps) > 1 else None
        by = r.served_by if len(steps) > 1 else steps[0]
        widest = max(widest, reference.request_gap(g1, g2, by))
        step = by if by in exact else steps[-1]
        n_exact += int(exact[step][i].sum())
    out = {"max_gap": widest,
           "served_tokens": sum(len(r.tokens) for r in sample),
           "exact": n_exact, "requests": len(sample), "passes": len(packs)}
    if control:
        out["control_gap"] = min(ctrl.values())
    return out


def counts(run, lane_stats: Optional[dict] = None,
           violations: Optional[int] = None) -> Dict[str, int]:
    done = [r for r in run.requests if r.ok]
    out = {"failed": len(run.requests) - len(done),
           "short": sum(len(r.tokens) != r.max_new for r in done)}
    if lane_stats is not None:
        out["torn_reads"] = int(lane_stats["torn_reads"])
        out["refresh_errors"] = int(lane_stats["refresh_errors"])
    if violations is not None:
        out["checker_violations"] = int(violations)
    return out


def judge(gap: Optional[float], limit: float, exact: Dict[str, int]):
    """``compared`` maps each name to [number, limit]; correct when every
    number is within its limit.  No sample (nothing answered) is not
    correct."""
    compared = {"max_gap": [gap, limit]}
    compared.update({k: [v, 0] for k, v in exact.items()})
    ok = gap is not None and gap <= limit and all(
        v <= 0 for v in exact.values())
    return bool(ok), compared


def describe(compared: dict) -> List[str]:
    return [f"{k}: {v[0]!r} (limit {v[1]!r})" for k, v in compared.items()]
