"""Resolve a cell of BENCHMARK.json to its files by name.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name the manifest uses:

    configuration  the `file` of its `configs` entry (JSON)
    traffic mix    bench/traffic/<traffic>.json
    driver         bench/drivers/<driver>.py, named by the traffic mix
    metric reader  bench/metrics/<metric name>.py, a `read(run)` function

Adding a cell, configuration, mix or metric adds files and entries; no
existing file changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
import zlib
from pathlib import Path
from typing import Callable, List

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def load_manifest(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(name: str, root: Path = ROOT) -> Cell:
    man = load_manifest(root)
    cells = {w["name"]: w for w in man["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    confs = {c["name"]: c for c in man["configs"]}
    conf = confs[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in man["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in man["per_layer"] if _applies(m, name)])


def _load_file(path: Path):
    """Import the Python file at `path` once per process."""
    if not path.is_file():
        raise FileNotFoundError(f"{path} not found")
    modname = f"bench_file_{zlib.crc32(str(path.resolve()).encode()):08x}"
    if modname in sys.modules:
        return sys.modules[modname]
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


def metric_reader(metric: str, root: Path = ROOT) -> Callable:
    """The `read(run)` function of bench/metrics/<metric>.py."""
    return _load_file(root / "bench" / "metrics" / f"{metric}.py").read


def driver(name: str, root: Path = ROOT):
    """The module bench/drivers/<name>.py; it defines `run(cell, args)`."""
    return _load_file(root / "bench" / "drivers" / f"{name}.py")

