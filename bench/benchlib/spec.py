"""Find a cell's pieces by the names in ``BENCHMARK.json``.

A cell (``workloads[]``) names a configuration (``configs[].file``) and a
traffic mix (``bench/mixes/<traffic>.json``).  The mix names the scenario,
whose plain reference is ``bench/refs/<scenario>.py``.  Each metric of the
cell has one reader, ``bench/metrics/<metric>.py``.  Adding a deployment,
a mix, a reference or a metric is adding a file and an entry; nothing here
changes.
"""

from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"


@dataclass
class Metric:
    name: str
    unit: str
    read: Callable


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    mix: Dict
    end_to_end: List[Metric]
    per_layer: List[Metric]


def _load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no module at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str) -> Callable:
    return _load_module(BENCH / "metrics" / f"{name}.py",
                        f"bench_metric_{name}").read


def load_reference(scenario: str):
    """The scenario's plain reference: a module with ``answer(call, *,
    real)``.  Its directory goes on ``sys.path`` so that references can
    share helpers kept beside them."""
    refs = str(BENCH / "refs")
    if refs not in sys.path:
        sys.path.insert(0, refs)
    return _load_module(BENCH / "refs" / f"{scenario}.py",
                        f"bench_ref_{scenario}")


def _metrics(entries: List[Dict], cell: str) -> List[Metric]:
    return [
        Metric(m["name"], m["unit"], load_reader(m["name"]))
        for m in entries
        if "workloads" not in m or cell in m["workloads"]
    ]


def load_cell(name: str) -> Cell:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((ROOT / configs[w["config"]]["file"]).read_text())
    mix = json.loads((BENCH / "mixes" / f"{w['traffic']}.json").read_text())
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        mix=mix,
        end_to_end=_metrics(bench["end_to_end"], name),
        per_layer=_metrics(bench["per_layer"], name),
    )
