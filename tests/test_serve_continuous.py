"""Continuous-batching engine: equivalence, compile-once, refresh spans.

The continuous engine must produce exactly the tokens the static reference
produces (greedy, float32 KV cache), while compiling its jitted
prefill/decode pair at most once regardless of prompt-length / batch mix —
and ``install_weights`` must span every swap with the publishing
transaction's UUID for the offline checker."""

import dataclasses
import warnings

import pytest

jax = pytest.importorskip("jax")

from repro.models import Model  # noqa: E402
from repro.models.config import get_config  # noqa: E402
from repro.obs import trace as obs_trace  # noqa: E402
from repro.serve.engine import (  # noqa: E402
    ContinuousEngine,
    ServeConfig,
    ServeEngine,
)


@pytest.fixture(scope="module")
def setup():
    # float32 KV cache so chunked and full prefill agree bit-for-bit
    cfg = dataclasses.replace(
        get_config("tinyllama-1.1b").reduced(pattern_repeats=2),
        kv_cache_dtype="float32")
    model = Model(cfg)
    params = model.init_params(jax.random.key(0))
    return model, params


PROMPTS = [
    ([5, 6, 7], 5),
    ([11, 12, 13, 14, 15], 2),
    ([21, 22, 23, 24, 25, 26, 27, 28, 29], 7),
    ([31, 32, 33, 34, 35, 36, 37, 38, 39, 40, 41, 42], 3),
    ([51, 52, 53, 54, 55, 56], 4),
]


def drive(engine, tickets):
    while not all(t.done() for t in tickets):
        assert engine.step(), "engine stalled with work pending"
    return [t.result(timeout=0) for t in tickets]


def test_matches_static_reference(setup):
    model, params = setup
    scfg = ServeConfig(max_len=48, slots=4, prefill_chunk=4)
    ref = ServeEngine(model, None, scfg, params=params)
    eng = ContinuousEngine(model, None, scfg, params=params)

    expect = [ref.generate([p], n)[0] for p, n in PROMPTS]
    tickets = [eng.submit(p, n) for p, n in PROMPTS]
    got = drive(eng, tickets)
    assert got == expect
    assert eng.stats["completed"] == len(PROMPTS)


def test_compiles_exactly_once(setup):
    """The tentpole claim: mixed lengths, overlapping lifetimes, join/
    leave mid-flight — one compiled prefill, one compiled decode."""
    model, params = setup
    scfg = ServeConfig(max_len=48, slots=4, prefill_chunk=4)
    eng = ContinuousEngine(model, None, scfg, params=params)
    drive(eng, [eng.submit(p, n) for p, n in PROMPTS])
    # second wave with fresh length mix re-uses both compilations
    drive(eng, [eng.submit([9] * 7, 6), eng.submit([3], 1)])
    assert eng.compile_counts() == {"prefill": 1, "decode": 1}


def test_footprint_guard(setup):
    model, params = setup
    eng = ContinuousEngine(
        model, None, ServeConfig(max_len=16, slots=2, prefill_chunk=8),
        params=params)
    with pytest.raises(AssertionError):
        eng.submit(list(range(1, 18)), 1)   # padded prefill exceeds cache
    with pytest.raises(AssertionError):
        eng.submit(list(range(1, 10)), 12)  # prompt + max_new exceeds cache


def test_weight_swap_between_iterations(setup):
    """A swap mid-stream changes tokens only from the next iteration on,
    and the monotonic step guard rejects stale installs."""
    model, params = setup
    params2 = jax.tree.map(lambda x: x * 1.05, params)
    scfg = ServeConfig(max_len=48, slots=2, prefill_chunk=4)
    eng = ContinuousEngine(model, None, scfg, params=params)
    assert eng.install_weights(params, 1)
    t = eng.submit([5, 6, 7, 8], 6)
    eng.step()
    assert eng.install_weights(params2, 2)
    assert not eng.install_weights(params, 1)  # stale: rejected
    drive(eng, [t])
    assert eng.weights_step == 2
    assert len(t.result(timeout=0)) == 6


def test_fresh_default_config():
    """Engines built without a config must not share one mutable default."""
    cfg = get_config("tinyllama-1.1b").reduced(pattern_repeats=2)
    model = Model(cfg)
    a = ServeEngine(model, None)
    b = ServeEngine(model, None)
    assert a.config is not b.config
    a.config.max_len = 7
    assert b.config.max_len != 7


def test_stats_shim_and_registry(setup):
    model, params = setup
    eng = ContinuousEngine(
        model, None, ServeConfig(max_len=48, slots=2, prefill_chunk=4),
        params=params)
    drive(eng, [eng.submit([5, 6, 7], 2)])
    # dict surface still live
    assert eng.stats["tokens_out"] == 2
    assert eng.stats["completed"] == 1
    # registry carries the same counters (plus histograms/gauges)
    snap = eng.registry.snapshot()
    assert snap["tokens_out"] == 2
    # the callable shim warns once and returns the registry snapshot
    import repro.serve.engine as engine_mod
    engine_mod._stats_deprecation_warned = False
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        via_call = eng.stats()
    assert any(issubclass(w.category, DeprecationWarning) for w in caught)
    assert via_call["tokens_out"] == 2


def test_refresh_span_carries_publish_uuid(setup):
    model, params = setup
    eng = ContinuousEngine(
        model, None, ServeConfig(max_len=48, slots=2, prefill_chunk=4),
        params=params)
    prev = obs_trace.get_tracer()
    tracer = obs_trace.enable(capacity=1000)
    try:
        eng.install_weights(params, 3, publish_uuid="publish.run.3")
    finally:
        obs_trace.set_tracer(prev)
        tracer.close()
    spans = [e for e in tracer.events()
             if e.get("ev") == "span" and e.get("name") == "weight_refresh"]
    assert len(spans) == 1
    assert spans[0]["publish_uuid"] == "publish.run.3"
    assert spans[0]["step"] == 3
    assert spans[0]["trace"] == obs_trace.txn_trace_id("publish.run.3")


def test_install_places_weights_on_device(setup):
    """Host (NumPy) weights are put on the device once at install, so no
    jitted call re-copies them from the host."""
    import numpy as np

    model, params = setup
    host = jax.tree.map(np.asarray, params)
    eng = ContinuousEngine(
        model, None, ServeConfig(max_len=48, slots=2, prefill_chunk=4))
    assert eng.install_weights(host, 1)
    installed, step = eng.current_params()
    assert step == 1
    leaves = jax.tree.leaves(installed)
    assert leaves and all(isinstance(x, jax.Array) for x in leaves)
    assert drive(eng, [eng.submit([5, 6, 7], 2)])[0]


def test_decode_loop_failure_fails_tickets(setup):
    """A decode loop that raises fails every in-flight and queued ticket
    with the cause, and later submits fail at once instead of hanging."""
    model, params = setup
    eng = ContinuousEngine(
        model, None, ServeConfig(max_len=48, slots=1, prefill_chunk=4),
        params=params)
    boom = RuntimeError("device lost")

    def failing_decode(*_args, **_kwargs):
        raise boom

    eng._decode = failing_decode
    tickets = [eng.submit([5, 6, 7], 3), eng.submit([8, 9], 2)]
    eng.start()
    try:
        for t in tickets:
            with pytest.raises(RuntimeError, match="device lost"):
                t.result(timeout=60)
        assert eng.loop_error is boom
        late = eng.submit([1, 2], 2)
        with pytest.raises(RuntimeError, match="device lost"):
            late.result(timeout=0)
    finally:
        eng.stop()


def test_refresher_counts_errors(setup):
    """A refresh that raises is retried next round, and counted."""
    import time

    model, params = setup

    class BrokenCheckpointer:
        def restore(self, like):
            raise OSError("storage unreachable")

    eng = ContinuousEngine(
        model, BrokenCheckpointer(),
        ServeConfig(max_len=48, slots=2, prefill_chunk=4,
                    refresh_every_s=0.01),
        params=params)
    eng.start_refresher()
    try:
        deadline = time.monotonic() + 30
        while eng.stats["refresh_errors"] < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
    finally:
        eng.stop()
    assert eng.stats["refresh_errors"] >= 2
    assert isinstance(eng.refresh_error, OSError)


STAMPS = ("submitted_at", "admitted_at", "first_token_at", "finished_at")


def test_ticket_stamps_ordered_and_set_once(setup):
    """Each request is stamped at submit, at admission to a slot, at its
    first token and at finish, in that order, and no stamp moves later."""
    model, params = setup
    eng = ContinuousEngine(
        model, None, ServeConfig(max_len=48, slots=2, prefill_chunk=4),
        params=params)
    tickets = [eng.submit(p, n) for p, n in PROMPTS]
    assert all(t.admitted_at is None and t.first_token_at is None
               for t in tickets)
    seen = {}
    while not all(t.done() for t in tickets):
        assert eng.step()
        for i, t in enumerate(tickets):
            for name in STAMPS:
                value = getattr(t, name)
                if value is not None:
                    assert seen.setdefault((i, name), value) == value
    for t in tickets:
        stamps = [getattr(t, name) for name in STAMPS]
        assert stamps == sorted(stamps)


def test_one_slot_admits_after_the_previous_finishes(setup):
    model, params = setup
    eng = ContinuousEngine(
        model, None, ServeConfig(max_len=48, slots=1, prefill_chunk=4),
        params=params)
    first, second = eng.submit([5, 6, 7, 8, 9], 3), eng.submit([1, 2], 2)
    drive(eng, [first, second])
    assert second.admitted_at >= first.finished_at
    assert second.first_token_at > second.admitted_at


ENGINE_SPANS = ("aft.engine.admit", "aft.engine.prefill",
                "aft.engine.prefill_sync", "aft.engine.decode",
                "aft.engine.decode_sync", "aft.engine.emit")


def test_profile_nests_engine_spans_in_the_step(setup, tmp_path):
    """A CPU profile of a few iterations holds every phase span of the
    engine, each inside an ``aft.engine.step`` on the same thread, and the
    idle loop's ``aft.engine.wait_work`` outside any step."""
    import glob
    import time

    from jax.profiler import ProfileData

    model, params = setup
    eng = ContinuousEngine(
        model, None, ServeConfig(max_len=48, slots=2, prefill_chunk=4),
        params=params)
    drive(eng, [eng.submit([4, 5, 6], 2)])  # compile outside the profile
    jax.profiler.start_trace(str(tmp_path))
    try:
        drive(eng, [eng.submit(p, n) for p, n in PROMPTS[:3]])
        eng.start()
        time.sleep(0.1)
        eng.stop()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    lines = [[(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for e in line.events if e.name.startswith("aft.")]
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:") for line in plane.lines]
    found = set()
    for events in lines:
        steps = [(a, b) for n, a, b in events if n == "aft.engine.step"]
        for name, a, b in events:
            inside = any(s <= a and b <= e for s, e in steps)
            if name in ENGINE_SPANS:
                assert inside, name
            elif name == "aft.engine.wait_work":
                assert not inside
            found.add(name)
    assert found >= set(ENGINE_SPANS) | {"aft.engine.step",
                                         "aft.engine.wait_work"}
