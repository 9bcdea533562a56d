"""The entry point: it refuses to run off the chip before any timing, and
a tiny rehearsal on the CPU prints the contract's last line."""

import json
import subprocess
import sys
from types import SimpleNamespace

import pytest

import run
from conftest import BENCH, TINY


def test_cpu_run_exits_non_zero_and_prints_no_result(tmp_path):
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
           "HOME": str(tmp_path), "TMPDIR": str(tmp_path),
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "qwen2-0.5b.chat", "--seed", "5", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert "correct" not in p.stdout
    assert "no accelerator" in p.stderr


def test_without_the_program_it_exits_non_zero(tmp_path):
    """A directory with only BENCHMARK.json and bench/ runs nothing."""
    import shutil

    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", ".jax_cache"))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen2-0.5b.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300,
        env={"PATH": "/usr/bin:/bin", "HOME": str(tmp_path),
             "JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0 and "correct" not in p.stdout


def dev(kind, platform="tpu"):
    return SimpleNamespace(platform=platform, device_kind=kind)


def test_device_checks():
    peaks = {"TPU v5 lite": {"bf16_flops_per_s": 1}}
    assert run.check_device([dev("TPU v5 lite")], 1, peaks) \
        == peaks["TPU v5 lite"]
    with pytest.raises(run.NoChip, match="peaks table"):
        run.check_device([dev("TPU v9 imaginary")], 1, peaks)
    with pytest.raises(run.NoChip, match="4 chips"):
        run.check_device([dev("TPU v5 lite")], 4, peaks)
    with pytest.raises(run.NoChip, match="no accelerator"):
        run.check_device([dev("cpu", "cpu")], 1, peaks)


def test_unknown_kind_stops_before_timing(monkeypatch, capsys):
    import jax

    monkeypatch.setattr(jax, "devices",
                        lambda *a: [dev("TPU v9 imaginary")])
    called = []
    monkeypatch.setattr(run, "one_run", lambda *a: called.append(1))
    assert run.main(["--workload", "qwen2-0.5b.chat"]) != 0
    assert not called
    assert "correct" not in capsys.readouterr().out


def rehearse(capsys, *argv):
    rc = run.main(list(argv), root=TINY, require_chip=False)
    out = capsys.readouterr()
    assert rc == 0
    return json.loads(out.out.strip().splitlines()[-1]), out.err


@pytest.mark.parametrize("workload, trace", [("tiny.chat", 0),
                                             ("tiny.chat", 1),
                                             ("tiny.publish", 0),
                                             ("tiny.batch", 0)])
def test_tiny_rehearsal_prints_the_contract_line(capsys, workload, trace):
    res, err = rehearse(capsys, "--workload", workload, "--seed",
                        str(2**33 + 5), "--seconds", "2", "--trace",
                        str(trace))
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "compared"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert set(res["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    if trace:
        assert {"busy_s", "window_s"} <= set(res["device"])
        assert {"idle_share", "lane_ms.p50", "slots_live.mean"} \
            <= set(res["metrics"])
        assert len(res["breakdown"]["idle_gaps"]) <= 10
    else:
        assert {"setup_s", "req_p95_ms", "tokens_per_s"} \
            <= set(res["metrics"])
    if workload == "tiny.publish":
        assert "refresh_s" in res["metrics"]
        assert res["compared"]["torn_reads"] == [0, 0]
    # the numbers compared are also the last lines of standard error
    assert err.strip().splitlines()[-1].startswith(
        list(res["compared"])[-1] + ":")
