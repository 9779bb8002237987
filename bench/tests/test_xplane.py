"""The XPlane reader and the reduction by the program's own names, on two
small traces recorded on a TPU v5e: ``tiny.xplane.pb`` (see
``test_trace_reduce.py``; no ``repro.*`` span, no named scope) and
``scoped.xplane.pb``: a jitted ``scoped`` whose first half runs under
``jax.named_scope("inner")``, called three times inside ``bench.window``,
each call and a 10 ms host sleep after it inside a ``repro.demo.step``
span."""
from pathlib import Path

import pytest

from bench import trace_reduce, xplane

DATA = Path(__file__).resolve().parent / "data"
TINY = str(DATA / "tiny.xplane.pb")
SCOPED = str(DATA / "scoped.xplane.pb")


def test_tf_op_paths_of_the_tiny_trace():
    (ops,) = xplane.device_ops(xplane.read_space(TINY))
    assert len(ops) == 6
    assert {path for *_, path in ops} == {"jit(<lambda>)/pallas_call",
                                          "jit(<lambda>)/add"}
    assert all(a < b for a, b, *_ in ops)


def test_modules_of_the_tiny_trace():
    (mods,) = xplane.device_modules(xplane.read_space(TINY))
    assert [name for *_, name in mods] == ["jit__lambda"] * 3
    layers = xplane.reduce(TINY)
    calls, seconds = layers.modules["jit__lambda"]
    assert calls == 3 and 0 < seconds < 1e-3
    # each call spans its two ops
    (ops,) = xplane.device_ops(xplane.read_space(TINY))
    assert seconds >= sum(b - a for a, b, *_ in ops) / 1e9


def test_without_repro_spans_gaps_are_trace_reduces():
    """The window and the gaps, with their lengths and bench.* spans, are
    what trace_reduce reads from ProfileData."""
    layers = xplane.reduce(TINY)
    reduced = trace_reduce.reduce_trace(TINY)
    assert layers.window_s == reduced.window_s
    assert [s for s, _ in layers.gaps] == [s for s, _ in reduced.gaps]
    same = [(a == b) or (a, b) == ("outside any span", "outside any bench span")
            for (_, a), (_, b) in zip(layers.gaps, reduced.gaps)]
    assert all(same)


@pytest.fixture(scope="module")
def scoped():
    return xplane.reduce(SCOPED)


def test_scope_seconds_of_the_scoped_trace(scoped):
    busy = trace_reduce.reduce_trace(SCOPED).busy_s
    assert 0 < scoped.scope_s["inner"] < busy
    assert "absent" not in scoped.scope_s
    (ops,) = xplane.device_ops(xplane.read_space(SCOPED))
    paths = {path for *_, path in ops}
    assert any("inner" in p.split("/") for p in paths)
    assert any("inner" not in p.split("/") for p in paths)
    assert scoped.modules["jit_scoped"][0] == 3


def test_gaps_are_named_by_the_repro_span(scoped):
    """The three sleeps lie inside repro.demo.step, which bench.window
    holds: the innermost span names them."""
    longest = scoped.gaps[:3]
    assert all(span == "repro.demo.step" and s >= 0.009 for s, span in longest)
    # trace_reduce still names them by bench.* spans only
    old = trace_reduce.reduce_trace(SCOPED).gaps[:3]
    assert all(span != "repro.demo.step" for _, span in old)


@pytest.mark.parametrize("trace", [TINY, SCOPED])
def test_reduce_reads_what_trace_reduce_reads(trace):
    """One pass over the file gives what trace_reduce reads through
    ProfileData: busy time, each op's self time and the Pallas calls."""
    mine, base = xplane.reduce(trace), trace_reduce.reduce_trace(trace)
    assert mine.window_s == base.window_s and mine.n_devices == base.n_devices
    assert mine.busy_s == pytest.approx(base.busy_s, abs=1e-8)
    assert mine.self_s.keys() == base.self_s.keys()
    for k, v in base.self_s.items():
        assert mine.self_s[k] == pytest.approx(v, abs=1e-8)
    assert mine.kernels.keys() == base.kernels.keys()
    for k, (n, s) in base.kernels.items():
        assert mine.kernels[k][0] == n and mine.kernels[k][1] == pytest.approx(s, abs=1e-8)
    assert sum(mine.unscoped_s.values()) <= sum(mine.self_s.values()) + 1e-12


def test_gaps_are_named_by_the_innermost_span():
    spans = [(0, 100, "bench.window"), (10, 60, "bench.engine.step"),
             (20, 40, "repro.serve.readback"), (70, 90, "bench.loadgen.wait")]
    gaps = [(22, 30), (45, 55), (62, 68), (75, 85), (95, 99)]
    assert sorted(xplane.name_gaps(gaps, spans)) == sorted([
        (8e-9, "repro.serve.readback"), (10e-9, "bench.engine.step"),
        (6e-9, "outside any span"), (10e-9, "bench.loadgen.wait"),
        (4e-9, "outside any span")])
