"""The control: the float8 reference in the program's place reads a far
wider gap than the program does, at a size a test run can hold.  On the
chip the same readings, at each cell's own size, set the cell's limit
(``run.py --calibrate``; see PERF.md)."""

import json

import run
from conftest import TINY


def test_control_fails_where_the_program_passes(capsys):
    assert run.main(["--workload", "tiny.chat", "--seconds", "2",
                     "--calibrate", "21,22,23"], root=TINY,
                    require_chip=False) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    assert len(lines) == 3
    for got in lines:
        r = got["readings"]
        limit = got["compared"]["max_gap"][1]
        assert r["max_gap"] <= limit < r["control_gap"]
        assert got["correct"] is True
