#!/usr/bin/env python3
"""Host spans of a profile by name, and the device's idle time split over
the program's own phases.

``xtrace.reduce`` labels idle gaps with the benchmark's ``bench.`` spans
only.  The program marks its own phases on the profiler's clock under
``aft.``: ``aft.engine.step`` with its ``admit``, ``prefill``,
``prefill_sync``, ``decode``, ``decode_sync`` and ``emit`` children,
``aft.engine.wait_work``, and ``aft.lane.tokenize``/``read``/``submit``.
``reduce`` reads both kinds over the traced window:

- ``spans``: for each name, its count, total seconds and self seconds (its
  duration less what its child spans on the same thread cover);
- ``idle_gaps``: idle seconds by the span that covered most of each gap,
  the innermost when nested (the rule of ``xtrace.label_gaps``);
- ``idle_split``: each idle instant given to the innermost span open at
  that instant, so that a gap running across several phases is shared
  among them;
- ``engine_host_ms``: for each ``aft.engine.step``, its duration less its
  ``*_sync`` children, the host's own work in one iteration (median and
  count).

    python3 bench/spans.py --workload <cell> --seed <n> [--seconds 40]

sets the cell up as ``run.py`` does, profiles ``harness.TRACE_S`` seconds
from the middle of one window and prints one JSON line with the above.  It
compares nothing with the reference and prints no metric of the contract.
"""

from __future__ import annotations

import argparse
import bisect
import json
import shutil
import statistics
import sys
import tempfile
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Tuple

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import run  # noqa: E402
import spec  # noqa: E402
import xtrace  # noqa: E402

PREFIXES = ("bench.", "aft.")
NO_SPAN = "no host span"

Span = Tuple[str, float, float, tuple]  # name, start_ns, end_ns, thread
Nested = Tuple[Span, int, List[Span]]  # span, depth, direct children


def host_spans(pd, host_prefix: str = "/host:CPU") -> List[Span]:
    """Every ``bench.`` and ``aft.`` span on the host planes, with the
    thread it ran on: a plane and a line by position, since Python's
    threads all give their lines one name."""
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, (plane.name, i))
            for plane in pd.planes if plane.name.startswith(host_prefix)
            for i, line in enumerate(plane.lines)
            for e in line.events if e.name.startswith(PREFIXES)]


def nest(spans: List[Span]) -> List[Nested]:
    """Each span with its depth and its direct children on its thread."""
    by_thread: Dict[tuple, List[Span]] = defaultdict(list)
    for sp in spans:
        by_thread[sp[3]].append(sp)
    out: List[Nested] = []
    for evs in by_thread.values():
        stack: List[Nested] = []
        for sp in sorted(evs, key=lambda sp: (sp[1], -sp[2])):
            while stack and stack[-1][0][2] <= sp[1]:
                stack.pop()
            node = (sp, len(stack), [])
            if stack:
                stack[-1][2].append(sp)
            stack.append(node)
            out.append(node)
    return out


def _covered(kids: List[Span]) -> float:
    return sum(b - a for _, a, b, _ in kids)


def table(nested: List[Nested]) -> Dict[str, dict]:
    """Count, total seconds and self seconds of each span name."""
    out: Dict[str, dict] = {}
    for (name, a, b, _), _, kids in nested:
        t = out.setdefault(name, {"count": 0, "total_s": 0.0, "self_s": 0.0})
        t["count"] += 1
        t["total_s"] += (b - a) / 1e9
        t["self_s"] += (b - a - _covered(kids)) / 1e9
    return out


def engine_host_ms(nested: List[Nested]) -> List[float]:
    """Per engine iteration, its duration less its ``*_sync`` children."""
    return [(b - a - _covered([k for k in kids if k[0].endswith("_sync")]))
            / 1e6 for (name, a, b, _), _, kids in nested
            if name == "aft.engine.step"]


def split_gaps(idle: List[xtrace.Interval], nested: List[Nested]
               ) -> Dict[str, float]:
    """Idle seconds by the innermost span open at each instant: the
    deepest, and of equal depths the latest started."""
    spans = sorted(((a, b, depth, name) for (name, a, b, _), depth, _
                    in nested), key=lambda sp: sp[0])
    starts = [sp[0] for sp in spans]
    longest = max((b - a for a, b, _, _ in spans), default=0.0)
    out: Dict[str, float] = defaultdict(float)
    for s, e in idle:
        cover = [(max(a, s), min(b, e), depth, a, name)
                 for a, b, depth, name in spans[
                     bisect.bisect_left(starts, s - longest):
                     bisect.bisect_left(starts, e)]
                 if b > s and a < e]
        points = sorted({s, e} | {x for c in cover for x in c[:2]})
        for lo, hi in zip(points, points[1:]):
            open_ = [c for c in cover if c[0] <= lo and hi <= c[1]]
            name = max(open_, key=lambda c: (c[2], c[3]))[4] if open_ \
                else NO_SPAN
            out[name] += (hi - lo) / 1e9
    return dict(out)


def _within(evs, lo: float, hi: float) -> List[xtrace.Interval]:
    return [(s, e) for _, s, e in evs if e > lo and s < hi]


def reduce(pd, device_prefix: str = "/device:TPU",
           host_prefix: str = "/host:CPU",
           modules_line: str = xtrace.MODULES,
           ops_line: str = xtrace.OPS):
    """The span table, the idle gaps by ``bench.`` and ``aft.`` spans, and
    the engine's host time per iteration, over the traced window.  None
    when the trace holds no window span or no device events."""
    spans = host_spans(pd, host_prefix)
    win = [sp for sp in spans if sp[0] == xtrace.WINDOW_SPAN]
    modules = xtrace.events(pd, device_prefix, modules_line)
    if not win or not modules:
        return None
    lo, hi = win[0][1], win[0][2]
    spans = [sp for sp in spans
             if sp[0] != xtrace.WINDOW_SPAN and sp[2] > lo and sp[1] < hi]
    ops = xtrace.events(pd, device_prefix, ops_line)
    idle: List[xtrace.Interval] = []
    for plane, mods in modules.items():
        busy = _within(ops.get(plane, []), lo, hi) or _within(mods, lo, hi)
        idle.extend(xtrace.gaps(xtrace.union(xtrace.clip(busy, lo, hi)),
                                lo, hi))
    nested = nest(spans)
    host = engine_host_ms(nested)
    return {
        "window_s": (hi - lo) / 1e9,
        "idle_s": sum(e - s for s, e in idle) / 1e9 / len(modules),
        "idle_gaps": xtrace.top(xtrace.label_gaps(
            idle, [sp[:3] for sp in spans])),
        "idle_split": xtrace.top(split_gaps(idle, nested), 20),
        "spans": table(nested),
        "engine_host_ms": {"p50": statistics.median(host) if host else None,
                           "count": len(host)},
    }


def main(argv=None, root: Path = spec.ROOT, require_chip: bool = True
         ) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=40.0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload, root)
    sys.path.insert(0, str(Path(root) / "src"))
    import jax

    import harness
    from repro.models import Model

    run.use_cache(root)
    lines = {}
    if require_chip:
        try:
            run.check_device(jax.devices(), cell.chips, spec.load_peaks())
        except run.NoChip as exc:
            print(f"spans: {exc}; refusing to run", file=sys.stderr)
            return 2
    else:  # a rehearsal on the CPU reads the CPU client's thread
        lines = {"device_prefix": "/host:CPU",
                 "modules_line": "tf_XLAPjRtCpuClient",
                 "ops_line": "tf_XLAPjRtCpuClient"}
    setup = run.Setup(cell, args.seed, Model(harness.arch_config(cell.config)))
    trace_dir = tempfile.mkdtemp(prefix="bench-spans-")
    try:
        harness.run_window(setup.system, cell, args.seed, args.seconds,
                           vocab=int(cell.config["vocab_size"]),
                           params2=setup.params2, trace_dir=trace_dir)
        setup.finish()
        out = reduce(xtrace.load(trace_dir), **lines)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    if out is None:
        raise RuntimeError("the trace holds no window span or no device "
                           "events")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
