"""A model family joins the benchmark as new files only.

``data/families/`` holds one that the dense families are not: the Llama
decoder with an untied ``lm_head`` leaf, against the program's reduced
DeepSeek LLM 67B. Its family, configuration, traffic, limits and per-layer
reader are added to a copy of ``bench/``, and the entries of
``entries.json`` to ``BENCHMARK.json`` (the new cell's name to the
``workloads`` of the end-to-end metric it reports); every file that was
there stays as it was, and a run of the new cell at a small size on the CPU
is correct.
"""
import json
import shutil
import time
from pathlib import Path

from bench import run, spec
from bench.tests import small

FIXTURE = Path(__file__).resolve().parent / "data" / "families"
PEAK = json.loads((spec.BENCH / "peaks.json").read_text())["devices"]["TPU v5 lite"]
WORKLOAD = "deepseek_67b.fixture_offline"


def _join(root: Path) -> None:
    """Add the fixture's files and entries to the checkout at ``root``."""
    for src in (FIXTURE / "bench").rglob("*"):
        if src.is_file() and "__pycache__" not in src.parts:
            dst = root / src.relative_to(FIXTURE)
            assert not dst.exists(), dst
            dst.write_bytes(src.read_bytes())
    bench = json.loads((root / "BENCHMARK.json").read_text())
    extra = json.loads((FIXTURE / "entries.json").read_text())
    for name, cells in extra.pop("metric_workloads").items():
        {m["name"]: m for m in bench["end_to_end"]}[name]["workloads"].extend(cells)
    for key, entries in extra.items():
        bench[key].extend(entries)
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=2))


def test_a_family_joins_as_new_files_only(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(spec.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(spec.ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    old_bench = json.loads((root / "BENCHMARK.json").read_text())
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*") if p.is_file()}
    _join(root)
    for p, data in before.items():
        assert p.read_bytes() == data, p
    new_bench = json.loads((root / "BENCHMARK.json").read_text())
    assert {k: v for k, v in new_bench.items() if not isinstance(v, list)} == \
        {k: v for k, v in old_bench.items() if not isinstance(v, list)}
    for key in ("configs", "workloads", "per_layer"):
        assert new_bench[key][:len(old_bench[key])] == old_bench[key]
    assert len(new_bench["end_to_end"]) == len(old_bench["end_to_end"])
    for old, new in zip(old_bench["end_to_end"], new_bench["end_to_end"]):
        # an end-to-end entry gains cells and nothing else
        cells = old.get("workloads", [])
        assert new.get("workloads", [])[:len(cells)] == cells
        assert ({k: v for k, v in new.items() if k != "workloads"}
                == {k: v for k, v in old.items() if k != "workloads"})

    cell, model = small.cell(WORKLOAD, root)
    assert cell.family.__file__ == str(root / "bench" / "families" / "llama_untied.py")
    assert "lm_head" in cell.family.param_shapes(cell.config)
    r = run.run_cell(cell, 2**35 + 7, 1.0, False, PEAK, time.perf_counter(),
                     model=model)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    ctx = run.Context(trace=None, counts=r["counts"], peak=PEAK,
                      config=cell.config, traffic=cell.traffic,
                      family=cell.family, kernels=spec.kernels(cell))
    assert [m["name"] for m in cell.per_layer] == ["tokens_out.fixture_offline"]
    assert spec.reader("tokens_out.fixture_offline", root)(ctx) > 0
