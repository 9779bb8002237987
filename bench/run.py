#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python3 bench/run.py --workload <open-loop cell> --seed <n> --seconds <s> \
        --sweep 0.5,1,1.5          # knee sweep: backlog growth at each rate

Run from the root of a checkout, on the machine that holds the cell's chips.
It refuses to run without a TPU, with fewer chips than the cell asks for, or
on a device missing from ``bench/peaks.json``. With ``--trace 0`` the line
holds the cell's end-to-end metrics; with ``--trace 1`` its per-layer
metrics, read from a profile of the same window. Every number compared to
decide ``correct`` is printed beside its limit, last on standard error and
under the line's last key, ``checks``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


class Refused(RuntimeError):
    pass


@dataclasses.dataclass
class Context:
    """What a per-layer reader may read."""
    trace: object        # trace_reduce.Reduced (from xplane.reduce), or None
    counts: dict         # the runner's counts; "program": counters' change
    peak: dict
    config: dict
    traffic: dict
    family: object       # the configuration's families/<family>.py
    kernels: dict        # kernel name -> cost function (counts.kernel_roofline)


def device_info(chips: int) -> tuple:
    """(device dict, peak table row); refuses anything but enough TPUs of a
    kind the peak table knows."""
    import jax

    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise Refused(f"no TPU: JAX's default device is {d0.platform} "
                      f"({d0.device_kind}); the benchmark runs on TPUs only")
    if len(devs) < chips:
        raise Refused(f"the cell asks for {chips} chips, JAX finds {len(devs)}")
    with open(ROOT / "bench" / "peaks.json") as f:
        peaks = json.load(f)["devices"]
    if d0.device_kind not in peaks:
        raise Refused(f"device kind {d0.device_kind!r} is not in "
                      f"bench/peaks.json ({sorted(peaks)})")
    return ({"platform": d0.platform, "kind": d0.device_kind,
             "count": len(devs)}, peaks[d0.device_kind])


def runners():
    from bench import serve, train

    return {"train": train.run, "serve_open": serve.run_open,
            "serve_closed": serve.run_closed}


def run_cell(cell, seed: int, seconds: float, trace: bool, peak: dict,
             t_start: float, **test_hooks) -> dict:
    """Drive one run and reduce it to the result line's parts (and the run's
    ``counts``). The tests call this with a small ``model`` and a
    ``fault``; the CLI does not."""
    from bench import correct, spec, trace_reduce, xplane

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    out = runners()[cell.traffic["kind"]](cell, seed, seconds, trace_dir,
                                          t_start, **test_hooks)
    ok, checks = correct.verdict(out["numbers"], cell.limits)
    result = {"correct": ok, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": {}, "checks": checks,
              "memory_peak_bytes": out["memory_peak_bytes"],
              "counts": out["counts"]}
    if not trace:
        values = dict(out["end_to_end"], setup_s=out["setup_s"])
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
        return result
    t0 = time.perf_counter()
    reduced = xplane.reduce(trace_reduce.find_xplane(trace_dir))
    print(f"bench: trace reduced in {time.perf_counter() - t0:.1f} s",
          file=sys.stderr)
    shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = Context(trace=reduced, counts=out["counts"], peak=peak,
                  config=cell.config, traffic=cell.traffic, family=cell.family,
                  kernels=spec.kernels(cell))
    for m in cell.per_layer:
        value = spec.reader(m["name"], cell.root)(ctx)
        if value is None:
            print(f"bench: per-layer metric {m['name']} found nothing to read "
                  f"in {cell.name}", file=sys.stderr)
            continue
        result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    result["busy_s"], result["window_s"] = reduced.busy_s, reduced.window_s
    result["breakdown"] = reduced.breakdown()
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default=None,
                    help="comma-separated request rates for a knee sweep of "
                         "an open-loop cell; prints one line per rate")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: the system under test (src/repro) is not in {ROOT}",
              file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import spec

    cell = spec.load_cell(args.workload)
    try:
        device, peak = device_info(cell.chips)
    except Refused as e:
        print(f"bench: refused: {e}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.sweep:
        from bench import serve

        for rate in (float(r) for r in args.sweep.split(",")):
            print(json.dumps(serve.sweep(cell, args.seed, args.seconds, rate)),
                  flush=True)
        return 0
    r = run_cell(cell, args.seed, args.seconds, bool(args.trace), peak, T_START)
    device["memory_peak_bytes"] = r.pop("memory_peak_bytes")
    if args.trace:
        device["busy_s"], device["window_s"] = r.pop("busy_s"), r.pop("window_s")
    line = {"correct": r["correct"], "attempted": r["attempted"],
            "failed": r["failed"], "metrics": r["metrics"], "device": device}
    if "breakdown" in r:
        line["breakdown"] = r["breakdown"]
    line["checks"] = r["checks"]
    for name, c in r["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
