"""Find a cell and everything it names by file name.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``.  Its
configuration, traffic mix, fixed rate, limits, architecture module, plain
reference and metric readers each sit in a file of their own under
``<root>/chipbench/``, so a later cell, mix, configuration, architecture or
metric is added as new files and entries only.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List

PACKAGE = Path(__file__).resolve().parent
ROOT = PACKAGE.parent


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    moves: str = ""           # the end-to-end metric a per-layer one moves


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict              # configs/<config>.json
    traffic: Dict             # traffic/<mix>.json
    rate_per_s: float         # offered load, requests per second
    limits: Dict[str, float]  # correctness limits, by check name
    sample: int               # finished requests compared with the reference
    end_to_end: List[Metric]
    per_layer: List[Metric]
    root: Path
    arch: ModuleType          # arch/<name>.py, by the config's ``reference``
    reference: ModuleType     # reference/<name>.py, by the same name


def _json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def data_path(root: Path, kind: str, name: str, suffix: str = ".json") -> Path:
    path = Path(root) / "chipbench" / kind / f"{name}{suffix}"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
    return path


def _metrics(bench: Dict, key: str, cell: str) -> List[Metric]:
    out = []
    for m in bench.get(key, []):
        if "workloads" in m and cell not in m["workloads"]:
            continue
        out.append(Metric(m["name"], m["unit"], m.get("moves", "")))
    return out


def load_cell(name: str, root: Path = ROOT) -> Cell:
    root = Path(root)
    bench = _json(root / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in {root / 'BENCHMARK.json'}")
    cell = _json(data_path(root, "workloads", name))
    for key in ("config", "traffic", "chips"):
        if key in cell and cell[key] != entry[key]:
            raise ValueError(f"{name}: {key} is {cell[key]!r} in its cell "
                             f"file and {entry[key]!r} in BENCHMARK.json")
    config = _json(data_path(root, "configs", entry["config"]))
    arch = named_module(root, "arch", config["reference"])
    reference = named_module(root, "reference", config["reference"])
    e2e = _metrics(bench, "end_to_end", name)
    moved = {m.name for m in e2e}
    per_layer = [m for m in _metrics(bench, "per_layer", name)
                 if m.moves in moved]
    return Cell(
        name=name, chips=int(entry["chips"]), config=config,
        traffic=_json(data_path(root, "traffic", entry["traffic"])),
        rate_per_s=float(cell["rate_per_s"]),
        limits={k: float(v) for k, v in cell["limits"].items()},
        sample=int(cell.get("sample", 6)),
        end_to_end=e2e, per_layer=per_layer, root=root, arch=arch,
        reference=reference)


def _load(path: Path, module_name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(module_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def named_module(root: Path, kind: str, name: str) -> ModuleType:
    """The module ``<root>/chipbench/<kind>/<name>.py``: this package's own
    where ``root`` is its checkout, else loaded from the file.  A name
    with no such file raises, naming the file it looked for."""
    path = data_path(root, kind, name, ".py")
    if path.resolve() == PACKAGE / kind / path.name:
        return importlib.import_module(f"chipbench.{kind}.{name}")
    return _load(path, f"chipbench_{kind}_{name}")


def metric_reader(root: Path, name: str) -> ModuleType:
    """The module ``metrics/<name>.py``; its ``read(record)`` gives the
    metric's value or None where the run holds nothing to read."""
    return _load(data_path(root, "metrics", name, ".py"),
                 f"chipbench_metric_{name.replace('.', '_')}")
