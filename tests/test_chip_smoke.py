"""``chip_smoke.py`` at a tiny size on the CPU: the same phases the chip
runs at full width, so a change that breaks the smoke's path shows here
before it costs chip time.  The kernels run in interpret mode."""

import importlib.util
from pathlib import Path

import pytest

jax = pytest.importorskip("jax")

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_a_tpu(smoke, monkeypatch, capsys):
    monkeypatch.setattr(smoke, "use_compile_cache", lambda: None)
    assert smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out
    assert f"kind={jax.devices()[0].device_kind}" in out


def test_lane_phase_tiny(smoke):
    from repro.models.config import get_config
    from repro.serve.engine import ServeConfig

    out = smoke.lane_phase(
        get_config("tinyllama-1.1b").reduced(pattern_repeats=2),
        ServeConfig(slots=4, max_len=64, prefill_chunk=8), seed=3,
        requests=8, sessions=3, prompt_lens=(8, 40), max_news=(4, 12))
    assert out["requests"] == 8 and out["tokens"] >= 8 * 4


def test_digest_phase_with_metrics(smoke):
    smoke.digest_phase(2, metrics=True)


def test_kernel_phase_interpret(smoke):
    smoke.kernel_phase(interpret=True, flash=(1, 4, 2, 128, 64),
                       ssd=(1, 64, 3, 8, 8, 16))


@pytest.mark.parametrize("served_by, ok", [(1, True), (2, False),
                                           (None, True)])
def test_request_ok_follows_the_swap(smoke, served_by, ok):
    """A request served on step 1 must match step 1 everywhere; one in
    flight across the swap may switch from step 1 to step 2 once."""
    import numpy as np

    gen = [5, 6, 7]
    wide = np.full(3, 10.0)

    def scores(argmax):
        am = np.asarray(argmax)
        return am, wide, np.where(am == gen, wide, 0.0)

    s1 = scores([5, 6, 7])
    s2 = scores([5, 9, 9])
    assert smoke.request_ok(gen, s1, s2, served_by)[0] is ok
    # a switch back from step 2 to step 1 is never accepted
    assert smoke.request_ok(gen, scores([9, 6, 7]), scores([5, 9, 9]),
                            None)[0] is False


def test_bf16_margin_scales_with_the_logit(smoke):
    import numpy as np

    m = smoke.bf16_margin(np.array([0.5, 1.0, 4.0, -4.0]))
    np.testing.assert_allclose(m, [3 / 128, 3 / 128, 3 / 32, 3 / 32])
