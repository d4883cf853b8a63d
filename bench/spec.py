"""Find a cell's pieces by name: ``BENCHMARK.json``, the configuration
file, the traffic file and the code each of them names.

Every piece is data or a small file of its own, so a later cell, mix,
distribution, model family or metric is a new file plus an entry:

  * ``bench/configs/<config>.json``     sizes, deployment, limits; names
                                        its ``family`` and ``reference``
  * ``bench/families/<family>.py``      the configuration's program
                                        config, weights and cost shape
  * ``bench/reference/<reference>.py``  the configuration's plain reference
  * ``bench/traffic/<traffic>.json``    a mix's parameters; names its
                                        ``generator``
  * ``bench/generators/<generator>.py`` ``make_plan(traffic, ...)``
  * ``bench/dists/<dist>.py``           ``ppf(spec, u, draws)``
  * ``bench/layer_metrics/<metric>.py`` ``read(run) -> float | None``

A lookup tries the cell's data directory first (a test passes its own
fixture directory) and then ``bench/``.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class Finder:
    """Files of one cell by kind and name."""

    def __init__(self, data_dir: Path = BENCH_DIR):
        self.dirs = [Path(data_dir)] + ([BENCH_DIR]
                                        if Path(data_dir) != BENCH_DIR
                                        else [])
        self._mods: Dict[Path, Any] = {}

    def path(self, kind: str, name: str, ext: str) -> Path:
        for d in self.dirs:
            p = d / kind / f"{name}{ext}"
            if p.is_file():
                return p
        raise FileNotFoundError(f"no {kind}/{name}{ext} under "
                                f"{[str(d) for d in self.dirs]}")

    def json(self, kind: str, name: str) -> Dict[str, Any]:
        with open(self.path(kind, name, ".json")) as f:
            return json.load(f)

    def module(self, kind: str, name: str):
        p = self.path(kind, name, ".py")
        if p not in self._mods:
            self._mods[p] = load_module(p, f"bench_{kind}_")
        return self._mods[p]


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    find: Finder

    @property
    def family(self):
        return self.find.module("families", self.config["family"])

    @property
    def reference(self):
        return self.find.module("reference", self.config["reference"])


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, *, benchmark: Optional[Dict[str, Any]] = None,
              data_dir: Path = BENCH_DIR) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` (or of ``benchmark``, as a
    test passes it) with its files found from ``data_dir``."""
    if benchmark is None:
        with open(ROOT / "BENCHMARK.json") as f:
            benchmark = json.load(f)
    cells = {w["name"]: w for w in benchmark["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    find = Finder(data_dir)
    return Cell(name=name, chips=int(w["chips"]),
                config=find.json("configs", w["config"]),
                traffic=find.json("traffic", w["traffic"]),
                end_to_end=[m for m in benchmark["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in benchmark["per_layer"]
                           if _applies(m, name)],
                find=find)


def load_module(path: Path, prefix: str):
    """Import one file by path (names may hold ``.`` and ``-``)."""
    mod_name = prefix + "".join(c if c.isalnum() else "_"
                                for c in path.stem)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, find: Optional[Finder] = None):
    return (find or Finder()).module("layer_metrics", name).read
