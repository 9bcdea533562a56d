"""Find a cell's pieces by name.

``BENCHMARK.json`` at the root names each cell's configuration, traffic
mix and metrics.  Everything else is a file of its own, found by name:

- ``bench/configs/<config>.json``: the model configuration as it is run
  (the path is the config entry's ``file``);
- ``bench/families/<model_type>.py``: the configuration's model family,
  named by its ``model_type``: how it maps onto the program's
  configuration (``arch``), its weight tree (``dims``, ``layout``), the
  matrices the float8 control quantizes (``MATRICES``), its plain float32
  reference (``forward``) and the work of each recorded call
  (``call_work``);
- ``bench/traffic/<traffic>.json``: the traffic mix's parameters, read by
  the one generator in ``traffic.py``;
- ``bench/cells/<cell>.json``: the cell's serving settings (nodes,
  ``ServeConfig``, offered rate or outstanding requests, reference rows,
  and the limit of the output comparison);
- ``bench/metrics/<metric>.py``: a reader with ``read(run)`` that returns
  the metric's value, or None where the run has nothing to read.

So a later change adds a cell, a mix, a configuration, a model family or a
metric by adding files and entries, and edits none.
"""

from __future__ import annotations

import functools
import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    settings: dict
    end_to_end: List[dict] = field(default_factory=list)
    per_layer: List[dict] = field(default_factory=list)


def load_benchmark(root: Path = ROOT) -> dict:
    with open(Path(root) / "BENCHMARK.json") as f:
        return json.load(f)


def _find(root: Path, kind: str, name: str, suffix: str) -> Path:
    """``<root>/bench/<kind>/<name><suffix>``, else this directory's own."""
    for base in (Path(root) / "bench", BENCH):
        path = base / kind / f"{name}{suffix}"
        if path.is_file():
            return path
    raise FileNotFoundError(f"no {kind} file for {name!r} "
                            f"(looked for {kind}/{name}{suffix})")


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    conf = configs[w["config"]]
    path = Path(root) / conf["file"]
    with open(path if path.is_file() else ROOT / conf["file"]) as f:
        config = json.load(f)
    if "model_type" not in config:
        raise KeyError(f"{conf['file']} names no model_type")
    # the generic modules find the family through the configuration
    config["family_file"] = str(
        _find(root, "families", config["model_type"], ".py"))
    with open(_find(root, "traffic", w["traffic"], ".json")) as f:
        traffic = json.load(f)
    with open(_find(root, "cells", name, ".json")) as f:
        settings = json.load(f)
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        settings=settings,
        end_to_end=[m for m in bench["end_to_end"] if applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if applies(m, name)])


def _module(prefix: str, name: str, path: Path):
    spec = importlib.util.spec_from_file_location(
        prefix + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str, root: Path = ROOT) -> Callable:
    return _module("bench_metric_", metric,
                   _find(root, "metrics", metric, ".py")).read


def family(cj: dict):
    """The module of a configuration's model family: the file
    ``load_cell`` found for its ``model_type``, else this directory's
    ``families/<model_type>.py``."""
    return _family(cj.get("family_file") or str(
        _find(ROOT, "families", cj["model_type"], ".py")))


@functools.lru_cache(maxsize=None)
def _family(path: str):
    return _module("bench_family_", Path(path).stem, Path(path))


def read_metrics(metrics: List[dict], run, root: Path = ROOT
                 ) -> Dict[str, dict]:
    """Each metric's reader over ``run``; a reader that finds nothing
    returns None and the metric is left out."""
    out: Dict[str, dict] = {}
    for m in metrics:
        value: Optional[float] = reader(m["name"], root)(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def load_peaks() -> dict:
    with open(BENCH / "peaks.json") as f:
        return json.load(f)
