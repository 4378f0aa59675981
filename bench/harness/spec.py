"""What a cell is made of, found by name.

``BENCHMARK.json`` at the checkout's root names each cell's
configuration and traffic mix and lists the metrics. Everything that
belongs to one of them sits in a file of its own:

- a configuration: ``bench/configs/<config>.json`` (the ``file`` of its
  entry), whose ``architecture`` names the plain reference module
  ``bench/configs/<architecture>.py`` beside it;
- a traffic mix: ``bench/traffic/<mix>.json``;
- a metric: ``bench/metrics/<metric>.py``, a reader with
  ``read(run) -> float | None``.

A later change adds a cell, a configuration, a mix or a metric by adding
files and entries; nothing here names one.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


@dataclass
class Metric:
    name: str
    unit: str
    read: Callable
    moves: Optional[str] = None


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    arch: object                  # the configuration's reference module
    end_to_end: List[Metric] = field(default_factory=list)
    per_layer: List[Metric] = field(default_factory=list)


def load_module(path: Path, name: str):
    """Import the Python file ``path`` under the module name ``name``
    (file names carry dots, so the import system cannot find them)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def traffic_path(mix: str, bench_dir: Path = BENCH_DIR) -> Path:
    return bench_dir / "traffic" / f"{mix}.json"


def metric_path(metric: str, bench_dir: Path = BENCH_DIR) -> Path:
    return bench_dir / "metrics" / f"{metric}.py"


def arch_path(arch: str, bench_dir: Path = BENCH_DIR) -> Path:
    return bench_dir / "configs" / f"{arch}.py"


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def resolve(cell_name: str, spec: Optional[dict] = None,
            root: Path = ROOT, bench_dir: Path = BENCH_DIR,
            traffic_dir: Optional[Path] = None) -> Cell:
    """The cell ``cell_name`` with its configuration, traffic mix,
    reference module and metric readers loaded. Raises ``KeyError`` for
    a cell the spec does not hold and ``FileNotFoundError`` for a
    missing file. ``traffic_dir`` overrides where mixes are looked up."""
    spec = spec if spec is not None else load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if cell_name not in cells:
        raise KeyError(f"no workload {cell_name!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    w = cells[cell_name]
    configs = {c["name"]: c for c in spec["configs"]}
    centry = configs[w["config"]]
    config = load_json(root / centry["file"])
    arch = load_module(arch_path(config["architecture"], bench_dir),
                       f"bench_arch_{config['architecture']}")
    traffic = load_json(traffic_dir / f"{w['traffic']}.json" if traffic_dir
                        else traffic_path(w["traffic"], bench_dir))
    cell = Cell(name=cell_name, chips=int(w["chips"]),
                config_name=w["config"], config=config,
                traffic_name=w["traffic"], traffic=traffic, arch=arch)
    for kind in ("end_to_end", "per_layer"):
        for m in spec.get(kind, []):
            if not _applies(m, cell_name):
                continue
            mod = load_module(metric_path(m["name"], bench_dir),
                              "bench_metric_" + m["name"].replace(".", "_"))
            getattr(cell, kind).append(Metric(
                name=m["name"], unit=m["unit"], read=mod.read,
                moves=m.get("moves")))
    return cell


def metric_files(spec: dict) -> Dict[str, Path]:
    """Every metric's reader file, by metric name."""
    return {m["name"]: metric_path(m["name"])
            for kind in ("end_to_end", "per_layer") for m in spec[kind]}
