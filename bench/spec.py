"""Everything a run needs, found by name.

``BENCHMARK.json`` names the cells; each cell names a configuration
(``bench/configs/<config>.json``, the file the entry points at) and a
traffic mix (``bench/traffic/mixes/<traffic>.json``). A mix names the
driver kind that runs it (``bench/drivers/<kind>.py``); each per-layer
metric is read by ``bench/metrics/<metric>.py`` and lists the cells
it is read in (``workloads``). Adding any of these is
adding a file and an entry; no existing file changes.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import NamedTuple

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class Cell(NamedTuple):
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list  # metric entries of BENCHMARK.json this cell reports
    per_layer: list


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def mix(name: str, bench: Path = BENCH) -> dict:
    return load_json(bench / "traffic" / "mixes" / f"{name}.json")


def cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` with its files loaded."""
    spec = benchmark(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    layer = [m for m in spec["per_layer"] if name in m["workloads"]]
    return Cell(name=name, chips=int(w["chips"]),
                config=load_json(root / conf["file"]),
                traffic=mix(w["traffic"], root / "bench"),
                end_to_end=e2e, per_layer=layer)


def load_module(kind: str, name: str, bench: Path = BENCH) -> ModuleType:
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = bench / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
