"""Every cell of BENCHMARK.json resolves its pieces by name, and a cell, a
mix, a configuration or a metric can be added by adding files and
entries alone."""

import json
import shutil

import pytest

import spec
from conftest import BENCH


def test_every_cell_resolves_its_files():
    bench = spec.load_benchmark()
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.config["vocab_size"] > 0
        assert cell.traffic["loop"] in ("open", "closed")
        assert {"nodes", "serve", "reference_rows", "gap_limit"} \
            <= set(cell.settings)
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            assert callable(spec.reader(m["name"]))


def test_per_layer_metrics_move_an_end_to_end_metric_of_their_cells():
    bench = spec.load_benchmark()
    for m in bench["per_layer"]:
        for cell in m.get("workloads", []):
            names = [e["name"] for e in spec.load_cell(cell).end_to_end]
            assert m["moves"] in names, (m["name"], cell)


def test_a_cell_is_added_by_files_alone(tmp_path):
    """A new configuration, mix, cell and metric in a copy of the
    benchmark: found by name with no edit to an existing file."""
    root = tmp_path
    shutil.copy(spec.ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    for kind in ("configs", "traffic", "cells", "metrics"):
        (root / "bench" / kind).mkdir(parents=True)
    cj = json.loads((BENCH / "configs" / "qwen2-0.5b.json").read_text())
    cj["num_hidden_layers"] = 12
    cj["reduced"] = ["num_hidden_layers"]
    (root / "bench" / "configs" / "half.json").write_text(json.dumps(cj))
    mix = json.loads((BENCH / "traffic" / "chat.json").read_text())
    mix["arrivals"] = {"kind": "gamma", "cv": 2.0}
    (root / "bench" / "traffic" / "bursty.json").write_text(json.dumps(mix))
    (root / "bench" / "cells" / "half.bursty.json").write_text(
        (BENCH / "cells" / "qwen2-0.5b.chat.json").read_text())
    (root / "bench" / "metrics" / "answers.py").write_text(
        "def read(run):\n    return len(run.requests)\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "half", "source": "x",
                             "file": "bench/configs/half.json",
                             "reduced": ["num_hidden_layers"], "why": "x"})
    bench["workloads"].append({"name": "half.bursty", "config": "half",
                               "traffic": "bursty", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "answers", "unit": "requests",
                               "better": "higher", "source": "host_clock",
                               "layer": "load generator",
                               "moves": "req_p95_ms",
                               "workloads": ["half.bursty"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.load_cell("half.bursty", root)
    assert cell.config["num_hidden_layers"] == 12
    assert cell.traffic["arrivals"]["cv"] == 2.0
    assert [m["name"] for m in cell.per_layer] == ["answers"]

    class Run:
        requests = [1, 2, 3]

    got = spec.read_metrics(cell.per_layer, Run(), root)
    assert got == {"answers": {"value": 3.0, "unit": "requests"}}
    # the cells already there still resolve from the copy
    assert spec.load_cell("qwen2-0.5b.chat", root).settings["nodes"] == 2


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        spec.load_cell("no-such-cell")


def test_config_sizes_match_the_program_or_are_listed():
    import harness

    for name in ("qwen2-0.5b", "qwen1.5-110b-4l"):
        cj = json.loads((BENCH / "configs" / f"{name}.json").read_text())
        cfg = harness.arch_config(cj)
        assert cfg.d_model == cj["hidden_size"]
        assert cfg.num_layers == cj["num_hidden_layers"]
        bad = dict(cj, hidden_size=1024)
        with pytest.raises(ValueError):
            harness.arch_config(bad)
