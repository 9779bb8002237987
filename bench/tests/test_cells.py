"""Whole runs of each cell of ``BENCHMARK.json`` at a small size on the CPU,
past the harness's look for a chip: sound, ``correct`` is true; with the
timed path broken underneath in each way the cell can be broken, it is
false."""
import json
import time

import pytest

from bench import faults, run, spec
from bench.tests import small

PEAK = json.loads((spec.BENCH / "peaks.json").read_text())["devices"]["TPU v5 lite"]
SEED = 2**33 + 12345


def _cells(kinds):
    """Names of the benchmark's cells whose traffic is of one of ``kinds``."""
    bench = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    kind = {w["name"]: json.loads((spec.BENCH / "traffic" / f"{w['traffic']}.json")
                                  .read_text())["kind"] for w in bench["workloads"]}
    return [w for w, k in kind.items() if k in kinds]


TRAIN = _cells({"train"})
SERVE = _cells({"serve_open", "serve_closed"})


def _run(workload, fault=None):
    cell, model = small.cell(workload)
    return run.run_cell(cell, SEED, 1.0, False, PEAK, time.perf_counter(),
                        model=model, fault=fault)


@pytest.mark.parametrize("workload", TRAIN + SERVE)
def test_sound_run_is_correct(workload):
    r = _run(workload)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    names = {m["name"] for m in spec.load_cell(workload).end_to_end}
    assert set(r["metrics"]) == names
    assert all(v["value"] > 0 for v in r["metrics"].values())


@pytest.mark.parametrize("workload,fault", [
    (w, f) for w in TRAIN for f in faults.TRAIN
] + [(w, f) for w in SERVE for f in faults.SERVE])
def test_broken_run_is_not_correct(workload, fault):
    planted = {**faults.TRAIN, **faults.SERVE}[fault]
    r = _run(workload, planted)
    assert not r["correct"], r["checks"]
    if fault == "frozen_pool":
        # a decode that stores no K/V serves wrong logits, and fails nothing
        assert r["failed"] == 0
        assert any(c["value"] > c["limit"] for c in r["checks"].values()), r["checks"]
