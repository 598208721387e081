"""Find everything a cell needs by the names in `BENCHMARK.json`.

Nothing here names a configuration, a traffic mix or a metric: a later PR
adds a cell by adding files and entries, never by editing this one.

  configuration  <root>/<configs[].file>            sizes, as run (JSON)
  model          bench/models/<config["model"]>.py  plain reference, weight
                                                    init, work counts
  traffic mix    bench/traffic/<traffic>.json       parameters of the one
                                                    generator (traffic.py)
  cell           bench/cells/<workload>.json        optional: what the cell
                                                    fixes on top of its mix
                                                    (an open loop's rate)
  metric         bench/metrics/<metric>.py          `read(ctx)` -> number
                                                    or None
  peaks          bench/peaks.json                   keyed by device_kind
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

from . import traffic


@dataclasses.dataclass
class Cell:
    root: Path
    name: str
    chips: int
    config: Dict
    params: Dict               # traffic mix with the cell's own overrides
    model: ModuleType
    end_to_end: List[Dict]     # metric entries this cell reports, trace 0
    per_layer: List[Dict]      # ... and with --trace 1

    def reader(self, metric: str) -> ModuleType:
        return load_module(self.root / "bench" / "metrics" / f"{metric}.py")


def load_module(path: Path) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark file: {path}")
    name = "bench_" + "_".join(path.with_suffix("").parts[-2:]).replace(
        ".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def _for_cell(metrics: List[Dict], cell: str) -> List[Dict]:
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def load_cell(root: Path, workload: str) -> Cell:
    root = Path(root)
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; BENCHMARK.json has "
                       f"{sorted(cells)}")
    w = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = load_json(root / entry["file"])
    params = load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    own = root / "bench" / "cells" / f"{workload}.json"
    if own.is_file():
        params = {**params, **load_json(own)}
    traffic.check(params)
    model = load_module(root / "bench" / "models" / f"{config['model']}.py")
    return Cell(root=root, name=workload, chips=int(w["chips"]),
                config=config, params=params, model=model,
                end_to_end=_for_cell(bench["end_to_end"], workload),
                per_layer=_for_cell(bench["per_layer"], workload))


def peaks_for(root: Path, device_kind: str) -> Dict:
    """The published peaks of one chip. A kind that is not in the table is
    an error, never a default."""
    table = load_json(Path(root) / "bench" / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (has {sorted(table)})")
    return table[device_kind]


def maybe_peaks(root: Path, device_kind: str) -> Optional[Dict]:
    try:
        return peaks_for(root, device_kind)
    except KeyError:
        return None
