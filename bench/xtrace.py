"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read: device busy time, the device time of each named program, the
device operations that took most time, and the idle gaps labelled by the
host span that was open in them.

Device events come from the device planes (``/device:TPU:0`` and so on):
the ``XLA Modules`` line holds one event per program run (``jit_decode``,
``jit_prefill``), the ``XLA Ops`` line one per operation.  Host spans are
the benchmark's own ``TraceAnnotation``s (``bench.*``) on the host plane.
The window is the host span ``bench.trace_window``, which the harness opens
right after the profiler starts and closes right before it stops.
"""

from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

WINDOW_SPAN = "bench.trace_window"
MODULES, OPS = "XLA Modules", "XLA Ops"

Interval = Tuple[float, float]  # start, end in ns


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def events(pd, plane_prefix: str, line_name: Optional[str] = None
           ) -> Dict[str, List[Tuple[str, float, float]]]:
    """(name, start_ns, end_ns) of every event on the lines whose name
    starts with ``line_name``, by plane name (by plane and line name when
    ``line_name`` is None)."""
    out: Dict[str, List[Tuple[str, float, float]]] = defaultdict(list)
    for plane in pd.planes:
        if not plane.name.startswith(plane_prefix):
            continue
        for line in plane.lines:
            if line_name is not None and not line.name.startswith(line_name):
                continue
            key = plane.name if line_name is not None else \
                f"{plane.name}|{line.name}"
            for e in line.events:
                out[key].append((e.name, e.start_ns,
                                 e.start_ns + e.duration_ns))
    return out


def union(intervals: Iterable[Interval]) -> List[Interval]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def op_name(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` is ``%fusion.12``."""
    return name.split(" = ")[0].strip()


def program_of(name: str) -> str:
    """``jit_decode(12)`` and ``jit_decode.3`` are both ``jit_decode``."""
    return name.split("(")[0].split(".")[0].strip()


def label_gaps(idle: List[Interval], spans: List[Tuple[str, float, float]]
               ) -> Dict[str, float]:
    """Idle seconds by the host span that covered most of each gap (the
    innermost when nested); ``no host span`` when none did."""
    spans = sorted(spans, key=lambda sp: sp[1])
    starts = [sp[1] for sp in spans]
    longest = max((b - a for _, a, b in spans), default=0.0)
    out: Dict[str, float] = defaultdict(float)
    for s, e in idle:
        best, cover = "no host span", 0.0
        for i in range(bisect.bisect_left(starts, s - longest),
                       bisect.bisect_left(starts, e)):
            name, a, b = spans[i]
            c = min(b, e) - max(a, s)
            if c > 0 and c >= cover:
                best, cover = name, c
        out[best] += (e - s) / 1e9
    return dict(out)


def top(d: Dict[str, float], n: int = 10) -> List[List]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def reduce(pd, device_prefix: str = "/device:TPU",
           host_prefix: str = "/host:CPU", modules_line: str = MODULES,
           ops_line: str = OPS) -> Optional[dict]:
    """Busy and idle time over the traced window, per-program device
    durations, the top device operations, and idle gaps by host span.
    None when the trace holds no window span or no device events."""
    host = events(pd, host_prefix)
    spans = [ev for evs in host.values() for ev in evs
             if ev[0].startswith("bench.")]
    win = [ev for ev in spans if ev[0] == WINDOW_SPAN]
    if not win:
        return None
    lo, hi = win[0][1], win[0][2]
    spans = [ev for ev in spans if ev[0] != WINDOW_SPAN]

    modules = events(pd, device_prefix, modules_line)
    ops = events(pd, device_prefix, ops_line)
    if not modules:
        return None
    programs: Dict[str, List[float]] = defaultdict(list)
    busy_s: List[float] = []
    idle: List[Interval] = []
    op_time: Dict[str, float] = defaultdict(float)
    chips = 0
    for plane, mods in modules.items():
        chips += 1
        mods = [(n, s, e) for n, s, e in mods if e > lo and s < hi]
        for n, s, e in mods:
            programs[program_of(n)].append((e - s) / 1e9)
        plane_ops = [(n, s, e) for n, s, e in ops.get(plane, [])
                     if e > lo and s < hi] or mods
        busy = union(clip([(s, e) for _, s, e in plane_ops], lo, hi))
        busy_s.append(sum(e - s for s, e in busy) / 1e9)
        idle.extend(gaps(busy, lo, hi))
        # attribute each op to the program whose run contains it
        mods_sorted = sorted(mods, key=lambda m: m[1])
        j = 0
        for n, s, e in sorted(plane_ops, key=lambda o: o[1]):
            while j + 1 < len(mods_sorted) and mods_sorted[j][2] < s:
                j += 1
            owner = ""
            if mods_sorted and mods_sorted[j][1] <= s < mods_sorted[j][2]:
                owner = program_of(mods_sorted[j][0]) + "/"
            op_time[owner + op_name(n)] += (min(e, hi) - max(s, lo)) / 1e9
    window_s = (hi - lo) / 1e9
    return {
        "window_s": window_s,
        "busy_s": sum(busy_s) / max(chips, 1),
        "chips": chips,
        "programs": dict(programs),
        "device_ops": top(op_time),
        "idle_gaps": top(label_gaps(idle, spans)),
    }


def load(log_dir: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(find_xplane(log_dir))
