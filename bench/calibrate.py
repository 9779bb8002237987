#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from (not a cell run).

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 [--seconds 10]
    python3 bench/calibrate.py --workload <serving cell> --seeds 1,2,3 \
        --fault frozen_pool

Training cells, per seed: the program's numbers from a run of the cell with
a one-stride window, and, against the same float32 reference, the reference
put in the program's place and computed with fp8 matmul operands (the
control), fed half of each batch, and with its state left unchanged (two
planted faults). Serving cells, per seed: one run of the cell at
``--seconds``, printing the program's numbers beside the control's (the fp8
reference's logits and first tokens at the same positions); with
``--fault``, the run has that fault of ``bench/faults.py`` planted in the
engine. One JSON line per seed.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def train_faults(cell, seed: int, ref: dict) -> dict:
    """The control and the planted faults against the reference ``ref``
    (``train.run``'s) of the same seed."""
    import jax

    from bench import correct, traffic
    from bench.reference import adam
    from bench.reference.common import PRECISIONS, init_params
    from bench.train import CHECK_STEPS, hyper, moment_dims

    cfg, tr, fam = cell.config, cell.traffic, cell.family
    data = traffic.ZipfBatches(cfg["vocab_size"], tr["seq"], tr["batch"], seed,
                               **tr["data"])
    batches = [(b["tokens"], b["labels"])
               for b in (data.batch(s) for s in range(CHECK_STEPS))]
    make = jax.jit(lambda k: init_params(cfg, fam.param_shapes(cfg), k))
    key = jax.random.PRNGKey(traffic.seed32(seed))

    def readings(batches, quant, **hp):
        losses, grad, p3 = adam.train_steps(cfg, fam.forward, make(key), batches,
                                            dict(hyper(tr), **hp),
                                            moment_dims(cfg, tr),
                                            rows=tr["check_rows"], quant=quant)
        return {"losses": losses, "grad": correct.leaf_norms(grad),
                "change": correct.diff_norms(p3, make(key))}

    exact, low = PRECISIONS["float32"], PRECISIONS["fp8"]
    half = [(t[: len(t) // 2], lab[: len(lab) // 2]) for t, lab in batches]
    # A step that returns its state unchanged: the losses of the first
    # weights on every batch; its first moment and its change are 0, which
    # reads 1 on both norm gaps by construction.
    frozen = correct.train_numbers(readings(batches, exact, lr=0.0), ref)
    frozen.update(grad_gap=1.0, change_gap=1.0)
    return {"control_fp8": correct.train_numbers(readings(batches, low), ref),
            "half_batch": correct.train_numbers(readings(half, exact), ref),
            "frozen_state": frozen}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--fault", default=None,
                    help="a serving fault of bench/faults.py to plant")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import faults, run, serve, spec, train
    from repro.launch.compile_cache import enable_compile_cache

    cell = spec.load_cell(args.workload)
    device, _ = run.device_info(cell.chips)
    enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        if cell.traffic["kind"] == "train":
            r = train.run(cell, seed, 0.0, None, t0)
            out = {"program": r["numbers"],
                   **train_faults(cell, seed, r["reference"])}
        else:
            go = serve.run_open if cell.traffic["kind"] == "serve_open" else serve.run_closed
            fault = faults.SERVE[args.fault] if args.fault else None
            r = go(cell, seed, args.seconds, None, t0, fault=fault, control=True)
            out = {"program": r["numbers"], "fault": args.fault,
                   "served_checked": r["counts"]["served_checked"],
                   "requests": r["attempted"], "failed": r["failed"],
                   "end_to_end": r["end_to_end"], "setup_s": r["setup_s"]}
        out.update(workload=cell.name, seed=seed, device=device["kind"],
                   seconds=time.perf_counter() - t0)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
