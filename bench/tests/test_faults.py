"""The comparison catches the faults a serving cell can have.  Each test
skips the harness's look for a chip, drives the rest of a run on a tiny
configuration with the timed path broken underneath, and sees ``correct``
come out false."""

import json

import pytest

import run
from conftest import TINY


def broken_engine(monkeypatch, breaks):
    from repro.serve.engine import ContinuousEngine

    init = ContinuousEngine.__init__

    def patched(self, *a, **k):
        init(self, *a, **k)
        self._decode = breaks(self, self._decode)

    monkeypatch.setattr(ContinuousEngine, "__init__", patched)


def result(capsys, workload="tiny.chat", seed=11):
    assert run.main(["--workload", workload, "--seed", str(seed),
                     "--seconds", "2"], root=TINY, require_chip=False) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def altered_token(eng, decode):
    """Every seventh decode call emits each slot's token plus one."""
    def call(params, state, tokens, positions, key):
        nxt, state = decode(params, state, tokens, positions, key)
        if eng._iter % 7 == 3:
            nxt = (nxt + 1) % eng.model.cfg.vocab_size
        return nxt, state
    return call


def unchanged_state(eng, decode):
    """The decode step returns the cache it was given: generated tokens'
    keys and values are never written."""
    import jax
    import jax.numpy as jnp

    def call(params, state, tokens, positions, key):
        kept = jax.tree.map(jnp.copy, state)  # the call donates ``state``
        nxt, _ = decode(params, state, tokens, positions, key)
        return nxt, kept
    return call


@pytest.mark.parametrize("fault", [altered_token, unchanged_state])
def test_fault_makes_the_run_incorrect(monkeypatch, capsys, fault):
    broken_engine(monkeypatch, fault)
    res = result(capsys)
    assert res["correct"] is False
    gap, limit = res["compared"]["max_gap"]
    assert gap > limit


def test_sound_run_is_correct(capsys):
    res = result(capsys)
    assert res["correct"] is True


def test_torn_weight_read_makes_the_publish_run_incorrect(monkeypatch,
                                                           capsys):
    """A weight set whose shards disagree is counted as a torn read."""
    from repro.serve import lane as lane_mod

    real = lane_mod.shards_to_params
    calls = []

    def torn(blobs, like):
        calls.append(1)
        if len(calls) == 1:
            raise lane_mod.TornWeightSet("injected")
        return real(blobs, like)

    monkeypatch.setattr(lane_mod, "shards_to_params", torn)
    res = result(capsys, "tiny.publish")
    assert res["compared"]["torn_reads"][0] >= 1
    assert res["correct"] is False
