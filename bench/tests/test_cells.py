"""Whole runs of each cell at a small size on the CPU, past the harness's
look for a chip: sound, ``correct`` is true; with the timed path broken
underneath in each way the cell can be broken, it is false."""
import json
import time

import pytest

from bench import faults, run, spec
from bench.tests import small

PEAK = json.loads((spec.BENCH / "peaks.json").read_text())["devices"]["TPU v5 lite"]
SEED = 2**33 + 12345


TRAIN = ["gpt_small.train_b32", "gpt_small.train_b4"]
SERVE = ["smollm_135m.serve_chat", "smollm_135m.serve_offline"]


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
