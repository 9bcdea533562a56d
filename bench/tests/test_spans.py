"""The reduction of the program's ``aft.`` spans (``spans.py``): nesting
and self time, idle gaps labelled by the innermost span, the engine's host
time per iteration, and a traced window of the tiny cell on the CPU."""

import json

import pytest

import spans
import xtrace
from conftest import TINY

MS = 1e6  # ns


def sp(name, a, b, thread="t0"):
    return (name, a * MS, b * MS, thread)


# one engine iteration inside the benchmark's wrapper, in ms
ITERATION = [sp("bench.engine_step", 0, 20), sp("aft.engine.step", 1, 19),
             sp("aft.engine.admit", 1, 2), sp("aft.engine.decode", 2, 5),
             sp("bench.decode_call", 3, 4), sp("aft.engine.decode_sync", 5, 15),
             sp("aft.engine.emit", 15, 18),
             sp("bench.submit", 16, 17, "t1")]


def test_nesting_and_self_time():
    nested = spans.nest(ITERATION)
    depth = {s[0]: d for s, d, _ in nested}
    assert depth["bench.engine_step"] == 0
    assert depth["aft.engine.step"] == 1
    assert depth["aft.engine.emit"] == 2
    assert depth["bench.decode_call"] == 3
    assert depth["bench.submit"] == 0  # another thread
    t = spans.table(nested)
    assert t["aft.engine.step"]["total_s"] == pytest.approx(0.018)
    # 18 ms less admit 1, decode 3, decode_sync 10 and emit 3
    assert t["aft.engine.step"]["self_s"] == pytest.approx(0.001)
    assert t["bench.engine_step"]["self_s"] == pytest.approx(0.002)
    assert t["aft.engine.decode"]["self_s"] == pytest.approx(0.002)
    assert t["aft.engine.emit"]["count"] == 1


def test_engine_host_time_leaves_out_the_syncs():
    assert spans.engine_host_ms(spans.nest(ITERATION)) == \
        [pytest.approx(8.0)]


def test_a_gap_goes_to_the_nested_aft_span():
    """Under the existing rule, a gap inside ``emit`` is the emit's, not
    the wrapper's; one that runs from ``emit`` into the wrapper goes to
    what covers most of it."""
    idle = [(15.5 * MS, 16.5 * MS), (17 * MS, 20 * MS)]
    got = xtrace.label_gaps(idle, [s[:3] for s in ITERATION])
    assert got == {"aft.engine.emit": pytest.approx(0.001),
                   "bench.engine_step": pytest.approx(0.003)}


def test_split_shares_a_gap_among_the_phases():
    idle = [(17 * MS, 20 * MS), (21 * MS, 22 * MS)]
    got = spans.split_gaps(idle, spans.nest(ITERATION))
    assert got == {"aft.engine.emit": pytest.approx(0.001),
                   "aft.engine.step": pytest.approx(0.001),
                   "bench.engine_step": pytest.approx(0.001),
                   spans.NO_SPAN: pytest.approx(0.001)}


def test_reduce_reads_a_cpu_profile(tmp_path):
    """Device work between host spans, profiled on the CPU: every idle
    second is labelled, and the engine's spans are in the table."""
    import time

    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(xtrace.WINDOW_SPAN):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("aft.engine.step"):
                    with jax.profiler.TraceAnnotation("aft.engine.decode"):
                        y = f(x)
                    with jax.profiler.TraceAnnotation(
                            "aft.engine.decode_sync"):
                        y.block_until_ready()
                    with jax.profiler.TraceAnnotation("aft.engine.emit"):
                        time.sleep(0.005)
    finally:
        jax.profiler.stop_trace()
    line = "tf_XLAPjRtCpuClient"
    got = spans.reduce(xtrace.load(str(tmp_path)), device_prefix="/host:CPU",
                       modules_line=line, ops_line=line)
    assert got["spans"]["aft.engine.step"]["count"] == 3
    assert got["engine_host_ms"]["count"] == 3
    assert got["engine_host_ms"]["p50"] >= 5.0
    idle = sum(v for _, v in got["idle_split"])
    assert idle == pytest.approx(got["idle_s"], rel=1e-6)
    assert dict(got["idle_split"])["aft.engine.emit"] > 0.012


def test_threads_keep_their_own_spans(tmp_path):
    """Two engine loops at once: their lines share a name, yet no span is
    taken for a child of the other thread's step."""
    import threading
    import time

    import jax

    both = threading.Barrier(2)

    def loop():
        both.wait()
        for _ in range(3):
            with jax.profiler.TraceAnnotation("aft.engine.step"):
                with jax.profiler.TraceAnnotation("aft.engine.emit"):
                    time.sleep(0.004)
                time.sleep(0.001)

    jax.profiler.start_trace(str(tmp_path))
    try:
        threads = [threading.Thread(target=loop) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
    finally:
        jax.profiler.stop_trace()
    assert not any(t.is_alive() for t in threads)
    got = spans.host_spans(xtrace.load(str(tmp_path)))
    assert len({thread for *_, thread in got}) == 2
    t = spans.table(spans.nest(got))
    assert t["aft.engine.step"]["count"] == 6
    assert 0.003 < t["aft.engine.step"]["self_s"] < 0.02


def test_tiny_traced_window_prints_the_span_table(capsys):
    rc = spans.main(["--workload", "tiny.chat", "--seed", str(2**33 + 7),
                     "--seconds", "2"], root=TINY, require_chip=False)
    assert rc == 0
    got = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    for name in ("aft.engine.step", "aft.engine.decode",
                 "aft.engine.decode_sync", "aft.engine.emit",
                 "aft.lane.submit", "bench.engine_step"):
        assert got["spans"][name]["count"] > 0, name
    assert got["engine_host_ms"]["p50"] > 0
