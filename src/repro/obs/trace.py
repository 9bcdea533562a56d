"""Structured span tracing with deterministic, UUID-derived trace IDs.

The shim never mints random trace IDs: a workflow's trace ID is a stable
hash of its workflow UUID (``trace_id``), and every transaction UUID the
workflow machinery derives from it (``<uuid>.step.<name>``,
``<uuid>.memo.<step>``, ``<entry>.claim``) maps back to the same trace via
``txn_trace_id`` — so the trace context propagates client →
``WorkflowSession``/``StepTxnSession`` → ``AftNode.commit_transaction_async``
→ pipeline flush → ``ChainConsumer`` child claim *structurally*, with no
context object threaded through call signatures.  Kill-and-retry keeps the
same trace ID (same UUID) while each attempt gets a distinct span ID
(``span_id`` folds the attempt number in), and a chain child
(``<parent>.chain.<edge>``) starts a trace of its own, linked to the parent
trace on the claim/submit events.

Events are JSON-lines records, ring-buffered in memory and optionally
appended to a file sink (``REPRO_TRACE_FILE``).  The file is flushed on
every emit — spans are closed (and therefore durable) one by one, so a
kill-injected crash loses at most the spans still open, never the history
the offline checker (``repro.obs.checker``) replays.

Tracing is **globally off by default**: the module-level tracer is a
disabled instance whose ``emit`` returns immediately, and every
instrumentation site guards on ``tracer.enabled``, keeping the disabled
overhead to one attribute check.

Apart from that event log, ``region(name)`` marks a stretch of host work
on the JAX profiler's clock (a ``jax.profiler.TraceAnnotation``), so a
profile shows the serving path's own phases beside the device's programs.
Region names are static strings under the ``aft.`` prefix.
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional

__all__ = [
    "TRACE_FILE_ENV",
    "Tracer",
    "trace_id",
    "base_uuid",
    "txn_trace_id",
    "span_id",
    "get_tracer",
    "set_tracer",
    "enable",
    "disable",
    "configure_from_env",
    "region",
]

TRACE_FILE_ENV = "REPRO_TRACE_FILE"

# Mirrors the derived-UUID grammar in core/records.py; duplicated literally
# so the obs layer (and the offline checker built on it) stays importable
# without repro.core.
_STEP_INFIXES = (".step.", ".memo.")
_CLAIM_SUFFIXES = (".claim", ".enq")


def trace_id(workflow_uuid: str) -> str:
    """Deterministic 16-hex-digit trace ID for a workflow UUID."""
    return hashlib.blake2b(str(workflow_uuid).encode("utf-8"),
                           digest_size=8).hexdigest()


def base_uuid(txn_uuid: str) -> str:
    """Strip the derived-transaction decorations off a UUID, recovering the
    workflow UUID that owns the trace.  ``.chain.`` infixes are kept: a
    chain child is its own workflow (and its own trace)."""
    u = str(txn_uuid)
    for suffix in _CLAIM_SUFFIXES:
        if u.endswith(suffix):
            u = u[: -len(suffix)]
    for infix in _STEP_INFIXES:
        idx = u.find(infix)
        if idx >= 0:
            u = u[:idx]
    return u


def txn_trace_id(txn_uuid: str) -> str:
    """Trace ID for any transaction UUID the workflow layer derives."""
    return trace_id(base_uuid(txn_uuid))


def span_id(trace: str, name: str, attempt: object = 0) -> str:
    """Span IDs fold an attempt qualifier in, so kill-and-retry replays
    (and same-UUID re-drives, which qualify with a run seed too) emit
    fresh spans instead of duplicate IDs."""
    return f"{trace}/{name}#{attempt}"


class _SpanCtx:
    __slots__ = ("_tracer", "name", "trace", "span", "parent",
                 "attrs", "_t0", "status")

    def __init__(self, tracer: "Tracer", name: str, trace: str,
                 span: str, parent: Optional[str], attrs: Dict):
        self._tracer = tracer
        self.name = name
        self.trace = trace
        self.span = span
        self.parent = parent
        self.attrs = attrs
        self.status = "ok"
        self._t0 = time.perf_counter()

    def __enter__(self) -> "_SpanCtx":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close(status="error" if exc_type is not None else self.status)

    def close(self, status: Optional[str] = None) -> None:
        self._tracer.emit(
            "span",
            name=self.name,
            trace=self.trace,
            span=self.span,
            parent=self.parent,
            dur_ms=round((time.perf_counter() - self._t0) * 1e3, 4),
            status=status or self.status,
            **self.attrs,
        )


class Tracer:
    """Ring-buffered JSON-lines event log with an optional file sink."""

    def __init__(self, path: Optional[str] = None, capacity: int = 16384,
                 enabled: bool = True):
        self.enabled = enabled
        self.path = path
        self._ring: deque = deque(maxlen=capacity)
        self._lock = threading.Lock()
        self._seq = 0
        self._fh = None

    def emit(self, ev: str, **fields) -> None:
        if not self.enabled:
            return
        with self._lock:
            self._seq += 1
            rec: Dict[str, object] = {"seq": self._seq,
                                      "ts": round(time.time(), 6),
                                      "ev": ev}
            rec.update(fields)
            self._ring.append(rec)
            if self.path is not None:
                if self._fh is None:
                    self._fh = open(self.path, "a", encoding="utf-8")
                # flush per event: the log must survive kill-injection
                self._fh.write(json.dumps(rec, default=str) + "\n")
                self._fh.flush()

    def span(self, name: str, trace: str, *, parent: Optional[str] = None,
             attempt: int = 0, **attrs) -> _SpanCtx:
        return _SpanCtx(self, name, trace,
                        span_id(trace, name, attempt), parent, attrs)

    def events(self) -> List[Dict]:
        with self._lock:
            return list(self._ring)

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()

    def close(self) -> None:
        with self._lock:
            if self._fh is not None:
                self._fh.close()
                self._fh = None


_NULL = Tracer(enabled=False)
_tracer: Tracer = _NULL


def get_tracer() -> Tracer:
    return _tracer


def set_tracer(tracer: Optional[Tracer]) -> Tracer:
    """Install a tracer (or None to disable); returns the previous one."""
    global _tracer
    prev = _tracer
    _tracer = tracer if tracer is not None else _NULL
    return prev


def enable(path: Optional[str] = None, capacity: int = 16384) -> Tracer:
    t = Tracer(path=path, capacity=capacity, enabled=True)
    set_tracer(t)
    return t


def disable() -> None:
    set_tracer(None)


def configure_from_env() -> Tracer:
    """Enable tracing with a file sink when ``REPRO_TRACE_FILE`` is set
    (the CI obs-check hook); otherwise leave the disabled tracer alone."""
    path = os.environ.get(TRACE_FILE_ENV)
    if path:
        return enable(path=path)
    return get_tracer()


_region_type = None


def region(name: str):
    """A context manager that spans ``name`` on the JAX profiler's clock.

    It is a ``jax.profiler.TraceAnnotation``, which records nothing and
    costs well under a microsecond while no profile is being taken.  jax is
    imported on the first call, not with this module; where jax is absent
    the region is a no-op, so ``repro.obs`` stays importable without it."""
    global _region_type
    if _region_type is None:
        try:
            from jax.profiler import TraceAnnotation as _region_type
        except ImportError:
            _region_type = contextlib.nullcontext
    return _region_type(name)
