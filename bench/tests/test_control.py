"""The control of ``correct`` at a small size: the plain reference put in
the program's place, computed with fp8 matmul operands (the precision below
the configurations' bfloat16), reads as not correct against each cell's
limits; so does the reference fed half of each batch or left unchanged. The same readings at
the cells' own sizes on the chip are in PERF.md (``bench/calibrate.py``)."""
import time

import pytest

from bench import calibrate, correct
from bench.tests import small

@pytest.mark.parametrize("workload", ["gpt_small.train_b32", "gpt_small.train_b4"])
def test_train_control_and_half_batch_fail(workload):
    from bench import train

    cell, model = small.cell(workload)
    seed = 2**32 + 3
    r = train.run(cell, seed, 0.0, None, time.perf_counter(), model=model)
    assert correct.verdict(r["numbers"], cell.limits)[0], r["numbers"]
    readings = calibrate.train_faults(cell, seed, r["reference"])
    for reading in ("control_fp8", "half_batch", "frozen_state"):
        ok, checks = correct.verdict(readings[reading], cell.limits)
        assert not ok, (reading, checks)


@pytest.mark.parametrize("workload", ["smollm_135m.serve_chat",
                                      "smollm_135m.serve_offline"])
def test_serve_control_fails(workload):
    from bench import serve

    cell, model = small.cell(workload)
    go = serve.run_open if cell.traffic["kind"] == "serve_open" else serve.run_closed
    r = go(cell, 2**32 + 5, 1.0, None, time.perf_counter(), model=model,
           control=True)
    numbers = r["numbers"]
    assert correct.verdict({k: numbers[k] for k in cell.limits}, cell.limits)[0]
    assert any(numbers[f"control_{k}"] > v for k, v in cell.limits.items()), numbers
