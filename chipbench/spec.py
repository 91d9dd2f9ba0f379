"""Finding a cell's parts by name.

``BENCHMARK.json`` at the checkout's root names each cell (a configuration,
a traffic mix and the chips it needs) and each metric.  Every part lives in
a file of its own that is found by its name, so a cell, a mix, a
configuration or a per-layer metric is added by adding files and an entry:

- a configuration: ``chipbench/configs/<config>.json``
- its architecture, which the configuration names under ``"arch"``:
  ``chipbench/arch/<arch>.py``, a module with the functions that
  ``chipbench/arch/__init__.py`` lists
- a traffic mix: ``chipbench/traffic/<traffic>.json``
- a metric's reader: ``chipbench/metrics/<metric>.py``, a module with a
  ``read(run)`` function returning a number, or None when the run holds
  nothing for it to read.

A metric with a ``workloads`` list is reported in those cells alone; one
without, in every cell.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
import sys
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional

from chipbench.arch import API as ARCH_API

PKG = Path(__file__).resolve().parent
ROOT = PKG.parent                       # the checkout's root
ARCHS = PKG / "arch"                    # where architectures are found
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
    arch: ModuleType             # the configuration's architecture

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


def _module_name(name: str) -> str:
    return name.replace(".", "_").replace("-", "_")


def load_reader(metric: str) -> Callable:
    path = PKG / "metrics" / f"{_name(metric, 'metric')}.py"
    if not path.is_file():
        raise SpecError(f"no reader for metric {metric!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench.metrics.{_module_name(metric)}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_arch(arch: str) -> ModuleType:
    """The architecture module ``<ARCHS>/<arch>.py``, loaded once a
    process (its jitted functions keep their compiled programs)."""
    path = ARCHS / f"{_name(arch, 'arch')}.py"
    if not path.is_file():
        raise SpecError(f"no architecture {arch!r} at {path}")
    name = f"chipbench.arch.{_module_name(arch)}"
    mod = sys.modules.get(name)
    if mod is None or Path(mod.__file__).resolve() != path.resolve():
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    missing = [f for f in ARCH_API if not callable(getattr(mod, f, None))]
    if missing:
        raise SpecError(f"architecture {path} lacks {missing}")
    return mod


def _metrics(bench: Dict, key: str,
             workload: Optional[str] = None) -> List[Metric]:
    """The metrics under ``key``; with ``workload``, those its cell
    reports."""
    out = []
    for m in bench.get(key, []):
        if workload is not None and workload not in m.get("workloads",
                                                          [workload]):
            continue
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
    config = load_config(w["config"])
    if "arch" not in config:
        raise SpecError(f"configs/{w['config']}.json names no arch")
    return Cell(
        name=workload, chips=int(w["chips"]), config=config,
        traffic=load_traffic(w["traffic"]),
        end_to_end=_metrics(bench, "end_to_end", workload),
        per_layer=_metrics(bench, "per_layer", workload),
        arch=load_arch(config["arch"]))
