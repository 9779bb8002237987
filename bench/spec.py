"""Find a cell's configuration, traffic, limits and metrics by name.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric is a file of its own, found by the name in
``BENCHMARK.json``: ``configs/<config>.json`` (the file the entry names),
``traffic/<traffic>.json``, ``limits/<cell>.json`` and
``metrics/<metric>.py``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list          # metric entries this cell reports, trace off
    per_layer: list           # metric entries this cell reports, trace on


def _load(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    bench = _load(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if workload in m.get("workloads", [workload] if m["moves"] in names else [])]
    return Cell(name=workload, chips=w["chips"],
                config=_load(root / conf["file"]),
                traffic=_load(BENCH / "traffic" / f"{w['traffic']}.json"),
                limits=_load(BENCH / "limits" / f"{workload}.json"),
                end_to_end=e2e, per_layer=per_layer)


def reader(metric: str):
    """The ``read(ctx)`` function of ``metrics/<metric>.py``."""
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
