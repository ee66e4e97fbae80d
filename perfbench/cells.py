"""Find a cell's pieces by name.

``BENCHMARK.json`` (at the root of the checkout) lists the configurations,
the cells (``workloads``) and the metrics.  Everything that belongs to one
of them sits in a file of its own, found by the name given there:

- a configuration: the JSON file that its entry names (``file``), whose
  ``reference`` key names its plain reference, ``references/<name>.py``;
- a traffic mix: ``traffic/<traffic>.json``, read by :mod:`perfbench.loadgen`,
  which refuses a setting it does not carry out;
- a metric: ``metrics/<name>.py``, a reader with ``read(run)`` that returns
  the metric's value, or None where the run holds nothing for it to read.

So a later change adds a configuration, a cell or a metric by adding files
and entries, without editing a file that is already here.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional, Tuple

from . import loadgen

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


# the planner's calibration a configuration may state: the repo's committed
# fit, as a user's process loads it, or the analytic model
CALIBRATIONS = ("default", "off")


class CellError(Exception):
    """A cell, or one of the files it names, cannot be found or read."""


@dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    kind: str  # "end_to_end" or "per_layer"
    layer: Optional[str]
    moves: Optional[str]
    workloads: Optional[Tuple[str, ...]]

    def applies(self, cell: str) -> bool:
        return self.workloads is None or cell in self.workloads


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    metrics: Tuple[Metric, ...]

    def metrics_of(self, kind: str) -> List[Metric]:
        return [m for m in self.metrics if m.kind == kind]


def _read_json(path: Path) -> Dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise CellError(f"missing file {path}") from None
    except json.JSONDecodeError as e:
        raise CellError(f"{path}: {e}") from None


def _load_module(path: Path, tag: str) -> ModuleType:
    if not path.is_file():
        raise CellError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_{tag}_{path.stem.replace('.', '_').replace('-', '_')}",
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics(bench: Dict) -> List[Metric]:
    out = []
    for kind in ("end_to_end", "per_layer"):
        for m in bench.get(kind, []):
            wl = m.get("workloads")
            out.append(Metric(
                name=m["name"], unit=m["unit"], better=m["better"],
                source=m["source"], kind=kind, layer=m.get("layer"),
                moves=m.get("moves"),
                workloads=tuple(wl) if wl is not None else None))
    return out


def find_cell(name: str, root: Path = ROOT, here: Path = HERE) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its configuration,
    traffic and metrics."""
    bench = _read_json(root / "BENCHMARK.json")
    work = {w["name"]: w for w in bench.get("workloads", [])}
    if name not in work:
        raise CellError(
            f"no workload {name!r} in BENCHMARK.json (have {sorted(work)})")
    w = work[name]
    configs = {c["name"]: c for c in bench.get("configs", [])}
    if w["config"] not in configs:
        raise CellError(f"workload {name!r} names unknown config "
                        f"{w['config']!r}")
    config = _read_json(root / configs[w["config"]]["file"])
    if config.get("planner_calibration") not in CALIBRATIONS:
        raise CellError(f"config {w['config']!r}: planner_calibration "
                        f"{config.get('planner_calibration')!r} is not one "
                        f"of {CALIBRATIONS}")
    traffic = _read_json(here / "traffic" / f"{w['traffic']}.json")
    try:
        loadgen.check(traffic)
    except ValueError as e:
        raise CellError(f"traffic {w['traffic']!r}: {e}") from None
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        metrics=tuple(m for m in metrics(bench) if m.applies(name)))


def reader(metric: str, here: Path = HERE) -> Callable:
    """The ``read(run)`` function of ``metrics/<metric>.py``."""
    mod = _load_module(here / "metrics" / f"{metric}.py", "metric")
    if not callable(getattr(mod, "read", None)):
        raise CellError(f"metrics/{metric}.py has no read(run)")
    return mod.read


def reference(config: Dict, here: Path = HERE) -> ModuleType:
    """The plain reference module that the configuration names."""
    return _load_module(here / "references" / f"{config['reference']}.py",
                        "reference")
