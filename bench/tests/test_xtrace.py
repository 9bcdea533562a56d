"""The trace reduction, on hand-made intervals and on a small trace
recorded on the CPU (``record_cpu_trace.py``)."""

import pytest

import xtrace
from conftest import BENCH


def test_union_merges_overlaps():
    assert xtrace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_gaps_are_the_complement_in_the_window():
    busy = [(2, 3), (5, 8)]
    assert xtrace.gaps(busy, 0, 10) == [(0, 2), (3, 5), (8, 10)]
    assert xtrace.clip([(0, 4), (9, 12), (20, 30)], 1, 10) == [(1, 4), (9, 10)]


def test_gaps_go_to_the_span_that_covers_most_of_them():
    spans = [("bench.engine_step", 0, 10e9), ("bench.submit", 4e9, 5e9)]
    idle = [(1e9, 3e9), (4e9, 5e9), (11e9, 12e9)]
    got = xtrace.label_gaps(idle, spans)
    assert got == {"bench.engine_step": 2.0, "bench.submit": 1.0,
                   "no host span": 1.0}


def test_program_names_drop_their_suffixes():
    assert xtrace.program_of("jit_decode(12)") == "jit_decode"
    assert xtrace.program_of("jit_prefill.3") == "jit_prefill"


@pytest.fixture(scope="module")
def cpu_trace():
    from jax.profiler import ProfileData

    return ProfileData.from_file(
        str(BENCH / "tests" / "data" / "cpu.xplane.pb"))


def test_reduce_reads_the_recorded_cpu_trace(cpu_trace):
    line = "tf_XLAPjRtCpuClient"
    got = xtrace.reduce(cpu_trace, device_prefix="/host:CPU",
                        modules_line=line, ops_line=line)
    # the window is the bench.trace_window span: about 34.5 ms
    assert got["window_s"] == pytest.approx(0.034547074, rel=1e-6)
    assert 0 < got["busy_s"] < got["window_s"]
    idle = dict(got["idle_gaps"])
    assert sum(idle.values()) == pytest.approx(
        got["window_s"] - got["busy_s"], rel=1e-6)
    # three 10 ms host sleeps inside bench.engine_step spans
    assert idle["bench.engine_step"] > 0.025
    assert "dot_general" in got["programs"]
    assert got["device_ops"] and len(got["device_ops"]) <= 10


def test_reduce_without_a_window_or_device_gives_nothing(cpu_trace):
    assert xtrace.reduce(cpu_trace) is None  # no /device:TPU plane
