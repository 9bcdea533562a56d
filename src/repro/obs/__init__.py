"""Observability layer: metrics registry, span tracing, offline checker.

Import-light by design: ``repro.obs`` depends only on the standard library,
so ``repro.core`` / ``repro.storage`` / ``repro.workflow`` can all import it
without cycles, and the offline checker (``repro.obs.checker``) can replay a
trace with no cluster code on the path.
"""

from .registry import Counter, Gauge, Histogram, QuantileSketch, Registry, Scope
from .trace import (
    TRACE_FILE_ENV,
    Tracer,
    base_uuid,
    configure_from_env,
    disable,
    enable,
    get_tracer,
    region,
    set_tracer,
    span_id,
    trace_id,
    txn_trace_id,
)

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "QuantileSketch",
    "Registry",
    "Scope",
    "TRACE_FILE_ENV",
    "Tracer",
    "base_uuid",
    "configure_from_env",
    "disable",
    "enable",
    "get_tracer",
    "region",
    "set_tracer",
    "span_id",
    "trace_id",
    "txn_trace_id",
]
