"""``obs.trace.region``: a span on the JAX profiler's clock, and a no-op
where jax is absent."""

import contextlib
import sys

import pytest

from repro.obs import trace as obs_trace


def test_region_is_a_profiler_annotation():
    jax = pytest.importorskip("jax")
    with obs_trace.region("aft.test") as r:
        assert isinstance(r, jax.profiler.TraceAnnotation)


def test_region_without_jax_is_a_no_op(monkeypatch):
    monkeypatch.setitem(sys.modules, "jax", None)
    monkeypatch.setitem(sys.modules, "jax.profiler", None)
    monkeypatch.setattr(obs_trace, "_region_type", None)
    ran = []
    with obs_trace.region("aft.test"):
        ran.append(1)
    assert ran == [1]
    assert obs_trace._region_type is contextlib.nullcontext
