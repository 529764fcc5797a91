"""Find a cell's configuration, traffic mix and per-layer metrics by name.

Everything that belongs to one configuration, one mix or one metric is a
file of its own under ``bench/``; ``BENCHMARK.json`` names them:

  bench/configs/<config>.json   sizes, dtypes, source
  bench/traffic/<traffic>.json  the mix's parameters (harness/traffic.py)
  bench/metrics/<metric>.py     ``read(run) -> float | None``

A cell is added by adding those files and entries, never by editing the
harness.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, List, Optional

from bench.harness.traffic import check_mix

@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    read: Optional[Callable] = None   # per-layer metrics only


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[Metric]
    per_layer: List[Metric]


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_metric_reader(bench: Path, name: str) -> Callable:
    path = bench / "metrics" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"per-layer metric {name!r} has no reader "
                                f"at {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def from_files(name: str, config: str, traffic: str, root: Path,
               chips: int = 1, spec: Optional[dict] = None) -> Cell:
    """A cell made of the configuration file ``config`` (a path under
    ``root``) and ``bench/traffic/<traffic>.json``, with the metrics that
    ``spec`` (a ``BENCHMARK.json``) gives the cell ``name``; a pair that
    no cell of the spec runs (a sweep, a cell held back) gets none."""
    bench = root / "bench"
    cfg = json.loads((root / config).read_text())
    mix = json.loads((bench / "traffic" / f"{traffic}.json").read_text())
    check_mix(mix)
    spec = spec or {"end_to_end": [], "per_layer": []}
    e2e = [Metric(m["name"], m["unit"]) for m in spec["end_to_end"]
           if _applies(m, name)]
    layer = [Metric(m["name"], m["unit"], load_metric_reader(bench,
                                                             m["name"]))
             for m in spec["per_layer"] if _applies(m, name)]
    return Cell(name, chips, cfg, mix, e2e, layer)


def load_cell(name: str, root: Path) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json``, with its files."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c["file"] for c in spec["configs"]}
    return from_files(name, configs[w["config"]], w["traffic"], root,
                      int(w["chips"]), spec)
