"""Find a cell's configuration, family, traffic, limits and metrics by name.

Everything that belongs to one configuration, model family, traffic mix,
cell or per-layer metric is a file of its own, found by the name in
``BENCHMARK.json``: ``configs/<config>.json`` (the file the entry names),
``families/<family>.py`` (the configuration's ``family`` key),
``traffic/<traffic>.json``, ``limits/<cell>.json`` and
``metrics/<metric>.py``. A new one joins as new files and entries only.
"""
from __future__ import annotations

import dataclasses
import functools
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
    family: object            # the module families/<config's family>.py
    traffic: dict
    limits: dict
    end_to_end: list          # metric entries this cell reports, trace off
    per_layer: list           # metric entries this cell reports, trace on
    root: Path = ROOT


def _load(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    root = Path(root)
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
    config = _load(root / conf["file"])
    return Cell(name=workload, chips=w["chips"], config=config,
                family=family(config["family"], root),
                traffic=_load(root / "bench" / "traffic" / f"{w['traffic']}.json"),
                limits=_load(root / "bench" / "limits" / f"{workload}.json"),
                end_to_end=e2e, per_layer=per_layer, root=root)


@functools.lru_cache(maxsize=None)
def _module(path: Path, prefix: str):
    spec = importlib.util.spec_from_file_location(f"{prefix}{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family(name: str, root: Path = ROOT):
    """The module ``families/<name>.py``."""
    return _module(Path(root) / "bench" / "families" / f"{name}.py",
                   "bench_family_")


def reader(metric: str, root: Path = ROOT):
    """The ``read(ctx)`` function of ``metrics/<metric>.py``."""
    return _module(Path(root) / "bench" / "metrics" / f"{metric}.py",
                   "bench_metric_").read


def kernels(cell: Cell) -> dict:
    """Kernel name -> cost function: the fused optimizer's and the family's."""
    from bench import counts

    return {**counts.OPT_KERNELS, **cell.family.kernels}
