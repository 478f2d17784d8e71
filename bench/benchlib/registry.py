"""Finds everything a cell needs by name, so that a later change adds a
configuration, a mix, a generator, a driver or a metric as new files.

* ``BENCHMARK.json`` (the checkout's root): cells, configurations' files,
  metrics and which cells report each.
* ``bench/mixes/<traffic>.json``: a traffic mix; its ``driver`` names
  ``bench/drivers/<driver>.py``.
* a configuration's ``stream.generator`` names
  ``bench/generators/<generator>.py``.
* ``bench/metrics/<metric>.py``: one reader per metric, ``read(run)``.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass
from pathlib import Path
from typing import List

BENCH = Path(__file__).resolve().parents[1]


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def load_benchmark(root: Path) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def _reports(metric: dict, cell: str, e2e_names=None) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    if e2e_names is None:                       # end-to-end: every cell
        return True
    return metric["moves"] in e2e_names         # per-layer: where it moves


def load_cell(root: Path, name: str, bench: Path = BENCH) -> Cell:
    """The cell ``name`` as ``BENCHMARK.json`` under ``root`` defines it."""
    spec = load_benchmark(root)
    work = [w for w in spec["workloads"] if w["name"] == name]
    if not work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = work[0]
    conf = [c for c in spec["configs"] if c["name"] == w["config"]][0]
    config = json.loads((Path(root) / conf["file"]).read_text())
    mix = json.loads((bench / "mixes" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in spec["end_to_end"] if _reports(m, name)]
    names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"] if _reports(m, name, names)]
    return Cell(name=name, chips=int(w["chips"]), config=config, mix=mix,
                end_to_end=e2e, per_layer=layer)


def load_module(kind: str, name: str, bench: Path = BENCH):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = Path(bench) / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} {name!r}: {path} is missing")
    key = "bench_%s_%s_%x" % (kind, name.replace(".", "_").replace("-", "_"),
                               abs(hash(str(path.resolve()))))
    if key in sys.modules:
        return sys.modules[key]
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    spec.loader.exec_module(mod)
    return mod
