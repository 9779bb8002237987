"""Per-layer readers and the kernel roofline on hand-made contexts: each
reads its number where the run and the trace have its names, and nothing
on a program or a trace that lacks them."""
import pytest

from bench import counts, run, spec
from bench.trace_reduce import Reduced


def Trace(busy_s=1.0, window_s=2.0, kernels=None, **layers):
    return Reduced(window_s=window_s, busy_s=busy_s, n_devices=1, self_s={},
                   kernels=kernels or {}, gaps=[], **layers)


PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
CFG = {"num_hidden_layers": 2, "hidden_size": 8, "num_attention_heads": 2,
       "num_key_value_heads": 1, "head_dim": 4, "intermediate_size": 16,
       "vocab_size": 32, "hidden_act": "silu"}
POOL = "bf16[9,1,8,8]"
FULL_TRACE = Trace(busy_s=2.0, window_s=4.0, kernels={}, scope_s={
    "optimizer": 0.03, "kv_pool": 0.1}, modules={
    "jit_paged_decode": [40, 0.8], "jit_paged_prefill": [10, 0.2]},
    unscoped_s={POOL: 0.1, "f32[3]": 5.0})
FULL_COUNTS = {"steps": 10, "pool_signatures": [POOL],
               "program": {"decode_rows": 30, "decode_rows_computed": 120},
               "admit_wait_p95_ms": 12.5}
BARE_TRACE = Trace(busy_s=2.0, window_s=4.0, kernels={}, scope_s={}, modules={},
                   unscoped_s={})
BARE_COUNTS = {"steps": 10, "pool_signatures": [], "program": {}}


def _ctx(trace, c, kernels=None):
    return run.Context(trace=trace, counts=c, peak=PEAK, config=CFG, traffic={},
                       family=spec.family("dense"), kernels=kernels or {})


@pytest.mark.parametrize("metric,value", [
    ("opt_step_ms.train", 3.0),
    ("decode_ms.tpot", 20.0),
    ("prefill_share.tpot", 20.0),
    ("decode_row_use.tpot", 25.0),
    ("admit_wait_p95_ms.ttft", 12.5),
    ("kv_pool_share.serve_tps", 10.0),
])
def test_reader_reads_its_names(metric, value):
    read = spec.reader(metric)
    assert read(_ctx(FULL_TRACE, FULL_COUNTS)) == pytest.approx(value)
    assert read(_ctx(BARE_TRACE, BARE_COUNTS)) is None
    assert read(_ctx(None, BARE_COUNTS)) is None


def _call(name, shape):
    return (name, [("f32", shape)], [("f32", shape)])


def test_kernel_roofline_charges_each_signature_once():
    # two calls of one signature (the smaller charged), one of another;
    # each moves its operand and result once at 1 GB/s
    calls = {"_k": [_call("_k", (256,)), _call("_k", (256,)), _call("_k", (64,))]}
    trace = Trace(kernels={"f32[256]": [3, 0.01], "f32[64]": [2, 0.001]})
    ctx = _ctx(trace, {"kernel_calls": calls}, {"_k": counts.bytes_moved})
    least = (3 * 2 * 256 * 4 + 2 * 2 * 64 * 4) / 1e9
    assert counts.kernel_roofline(ctx, ["_k"]) == pytest.approx(100 * least / 0.011)
    assert counts.kernel_time(ctx, ["_k"]) == (5, pytest.approx(0.011))
    assert counts.kernel_roofline(ctx, ["_absent"]) is None
    assert counts.kernel_roofline(_ctx(None, {}), ["_k"]) is None


def test_paged_kernel_costs_the_window_work():
    calls = {"_paged_kernel": [_call("_paged_kernel", (4, 4))]}
    trace = Trace(kernels={"f32[4,4]": [7, 1e-6]})
    work = [(1, 10), (3, 5)]
    ctx = _ctx(trace, {"kernel_calls": calls, "paged_calls": work},
               spec.family("llama").kernels)
    flops, nbytes = counts.paged_attention_cost(CFG, work)
    least = max(flops / PEAK["bf16_flops_per_s"], nbytes / PEAK["hbm_bytes_per_s"])
    assert counts.kernel_roofline(ctx, ["_paged_kernel"]) == pytest.approx(100 * least / 1e-6)
