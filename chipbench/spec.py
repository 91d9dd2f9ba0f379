"""Finding a cell's parts by name.

``BENCHMARK.json`` at the checkout's root names each cell (a configuration,
a traffic mix and the chips it needs) and each metric.  Every part lives in
a file of its own that is found by its name, so a cell, a mix, a
configuration or a per-layer metric is added by adding files and an entry:

- a configuration: ``chipbench/configs/<config>.json``
- a traffic mix: ``chipbench/traffic/<traffic>.json``
- a metric's reader: ``chipbench/metrics/<metric>.py``, a module with a
  ``read(run)`` function returning a number, or None when the run holds
  nothing for it to read.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
from pathlib import Path
from typing import Callable, Dict, List, Optional

PKG = Path(__file__).resolve().parent
ROOT = PKG.parent                       # the checkout's root
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


class SpecError(Exception):
    """A cell, configuration, mix or metric that cannot be found or read."""


def _name(value: str, what: str) -> str:
    if not isinstance(value, str) or not NAME.match(value):
        raise SpecError(f"bad {what} name {value!r}")
    return value


def load_json(path: Path) -> Dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file {path}") from None


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    layer: str = ""
    moves: str = ""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict                 # the configuration's file, as run
    traffic: Dict                # the mix's file
    end_to_end: List[Metric]     # the cell's metrics with --trace 0
    per_layer: List[Metric]      # ... and with --trace 1

    def metrics(self, trace: bool) -> List[Metric]:
        return self.per_layer if trace else self.end_to_end


def load_config(name: str) -> Dict:
    cfg = load_json(PKG / "configs" / f"{_name(name, 'config')}.json")
    if cfg.get("name") != name:
        raise SpecError(f"configs/{name}.json names itself "
                        f"{cfg.get('name')!r}")
    return cfg


def load_traffic(name: str) -> Dict:
    mix = load_json(PKG / "traffic" / f"{_name(name, 'traffic')}.json")
    if mix.get("name") != name:
        raise SpecError(f"traffic/{name}.json names itself "
                        f"{mix.get('name')!r}")
    return mix


def load_reader(metric: str) -> Callable:
    path = PKG / "metrics" / f"{_name(metric, 'metric')}.py"
    if not path.is_file():
        raise SpecError(f"no reader for metric {metric!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench.metrics.{metric.replace('.', '_').replace('-', '_')}",
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _metrics(bench: Dict, key: str) -> List[Metric]:
    out = []
    for m in bench.get(key, []):
        out.append(Metric(
            name=_name(m["name"], "metric"), unit=m["unit"],
            better=m["better"], source=m["source"], layer=m.get("layer", ""),
            moves=m.get("moves", "")))
    return out


def load_benchmark(root: Path = ROOT) -> Dict:
    return load_json(root / "BENCHMARK.json")


def load_cell(workload: str, bench: Optional[Dict] = None) -> Cell:
    """The cell ``workload`` of ``BENCHMARK.json`` with its configuration,
    mix and the metrics it reports."""
    bench = bench if bench is not None else load_benchmark()
    cells = {w["name"]: w for w in bench.get("workloads", [])}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json; "
                        f"known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench.get("configs", [])}
    if w["config"] not in configs:
        raise SpecError(f"workload {workload!r} names unknown config "
                        f"{w['config']!r}")
    return Cell(
        name=workload, chips=int(w["chips"]),
        config=load_config(w["config"]), traffic=load_traffic(w["traffic"]),
        end_to_end=_metrics(bench, "end_to_end"),
        per_layer=_metrics(bench, "per_layer"))
