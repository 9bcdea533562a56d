"""The readers of the program's per-request stamps, on hand-made runs and
in a traced window of the tiny cell on the CPU."""

import json
import shutil
from types import SimpleNamespace

import pytest

import run
import spec
from conftest import TINY
from harness import RunData, Sent

NEW = ("queue_wait_ms.p90", "first_token_ms.p90", "lane_wait_ms.p90")


def answer(stamps):
    payload = {"tokens": [1], "weights_step": 1}
    if stamps is not None:
        payload["stamps"] = stamps
    result = SimpleNamespace(results={"generate": payload})
    return SimpleNamespace(result=lambda timeout=None: result)


def request(sent, sub, adm, first, fin, ok=True):
    r = Sent("s", [1, 2], 2, due=sent, sent=sent, ok=ok)
    r.ticket = answer({"submitted_at": sub, "admitted_at": adm,
                       "first_token_at": first, "finished_at": fin})
    return r


def made_run(n=20):
    # request i: lane 1 ms + i/10 ms, queue i ms, first token 2i ms later
    reqs = [request(10.0 * i, 10.0 * i + (1 + i / 10) / 1e3,
                    10.0 * i + (1 + i / 10 + i) / 1e3,
                    10.0 * i + (1 + i / 10 + 3 * i) / 1e3, 10.0 * i + 1.0)
            for i in range(n)]
    reqs.append(request(500.0, None, None, None, None, ok=False))
    return RunData(cell=None, seconds=40.0, requests=reqs)


def readers():
    return {name: spec.reader(name) for name in NEW}


def test_readers_on_a_made_run():
    got = {k: f(made_run()) for k, f in readers().items()}
    # p90 of 20 values is the 18th smallest: i = 17
    assert got["lane_wait_ms.p90"] == pytest.approx(2.7)
    assert got["queue_wait_ms.p90"] == pytest.approx(17.0)
    assert got["first_token_ms.p90"] == pytest.approx(51.0)


def test_without_stamps_the_readers_find_nothing():
    """A program that returns no stamps gives no value and no error."""
    r = Sent("s", [1, 2], 2, due=0.0, sent=0.0, ok=True)
    r.ticket = answer(None)
    bare = RunData(cell=None, seconds=40.0, requests=[r])
    empty = RunData(cell=None, seconds=40.0)
    for f in readers().values():
        assert f(bare) is None and f(empty) is None


def test_tiny_traced_run_prints_the_stamp_metrics(tmp_path, capsys):
    root = tmp_path / "tiny"
    shutil.copytree(TINY, root)
    with open(root / "BENCHMARK.json") as f:
        bench = json.load(f)
    bench["per_layer"] += [{"name": n, "unit": "ms", "better": "lower",
                            "source": "program_counter", "layer": "x",
                            "moves": "req_p95_ms"} for n in NEW]
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    rc = run.main(["--workload", "tiny.chat", "--seed", str(2**33 + 9),
                   "--seconds", "2", "--trace", "1"], root=root,
                  require_chip=False)
    assert rc == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is True
    for name in NEW:
        assert res["metrics"][name]["value"] >= 0, name
    assert res["metrics"]["first_token_ms.p90"]["value"] >= \
        res["metrics"]["queue_wait_ms.p90"]["value"]
