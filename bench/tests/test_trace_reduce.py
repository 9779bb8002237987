"""The trace reduction on a small trace recorded on a TPU v5e: a jitted
program with one Pallas kernel (``double_kernel``, 256 x 512 f32) run three
times inside ``bench.window``, each followed by a 10 ms host sleep in a
``bench.sleep`` span."""
from pathlib import Path

import pytest

from bench import counts, trace_reduce

DATA = Path(__file__).resolve().parent / "data"


def test_describe_names_an_op():
    hlo = ('%closed_call.12 = (f32[64,3,3,64]{3,2,1,0:T(4,128)S(1)}, '
           'f32[64,3,3,1]{3,2,1,0:T(4,128)}) custom-call(s32[64,128]{1,0} %a), '
           'custom_call_target="tpu_custom_call", backend_config={}')
    short, sig, pallas = trace_reduce.describe(hlo)
    assert pallas
    assert sig == "f32[64,3,3,64],f32[64,3,3,1]"
    assert short.startswith("closed_call.12 ") and short.endswith("tpu_custom_call")
    short, sig, pallas = trace_reduce.describe(
        "%copy.48 = bf16[1,8193,6,16,64]{4,2,3,1,0:T(8,128)(2,1)} copy(bf16[1] %x)")
    assert (sig, pallas) == ("bf16[1,8193,6,16,64]", False)
    assert short == "copy.48 bf16[1,8193,6,16,64] copy"


def test_union_counts_overlap_once():
    assert trace_reduce._union([(0, 10), (5, 20), (30, 40), (32, 35)]) == 30


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce_trace(str(DATA / "tiny.xplane.pb"))


def test_kernel_found_by_its_lowered_signature(reduced):
    lowered = (DATA / "tiny_lowered.txt").read_text()
    calls = counts.custom_calls(lowered, {"double_kernel"})
    assert len(calls) == 1
    sig = counts.signature(calls[0][2])
    assert sig == "f32[256,512]"
    n, seconds = reduced.kernel_time([sig])
    assert n == 3 and 0 < seconds < 1e-3
    assert reduced.kernel_time(["f32[1,1]"]) == (0, 0)


def test_busy_idle_and_gaps(reduced):
    assert reduced.n_devices == 1
    # three sleeps of 10 ms inside the window: most of it is idle
    assert reduced.window_s > 0.03
    assert 0 < reduced.busy_s < 0.2 * reduced.window_s
    assert reduced.idle_share > 0.8
    longest = reduced.gaps[:3]
    assert all(span == "bench.sleep" and s >= 0.009 for s, span in longest)
    b = reduced.breakdown()
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert sum(v for _, v in b["device_ops"]) <= reduced.busy_s + 1e-9
