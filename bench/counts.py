"""Operations and bytes the benchmark charges, computed from shapes, and the
Pallas kernels it finds in the programs' lowering and in the trace.

Model FLOPs (each family's ``matmul_params`` and the like) leave out
recomputation, and kernel bytes are the least each call must move, so a
share of a peak computed from them cannot pass 100% when the time counts
all the work.

A kernel's cost function gives the least (FLOPs, bytes) of the window's
calls of that kernel: ``cost(cfg, calls, counts)``, where ``calls`` lists
``(n, call)`` pairs, one per result signature that the trace timed, with
``n`` its calls there and ``call`` the lowered call of that signature that
moves the fewest bytes (calls sharing a signature are alike), and
``counts`` the run's counts.
"""
from __future__ import annotations

import re

import numpy as np

ITEMSIZE = {"f32": 4, "bf16": 2, "f16": 2, "i32": 4, "ui32": 4, "i8": 1,
            "ui8": 1, "i1": 1, "f8E4M3FN": 1}


def paged_attention_cost(cfg: dict, calls, kv_itemsize: int = 2,
                         act_itemsize: int = 2):
    """(FLOPs, bytes) of paged-attention calls in every layer. ``calls`` is
    a list of (queries, context) pairs, one per live row of a call: that
    row's query count and the positions its last query attends to. Each
    call reads the K and V of its live context once, in exact tokens, and
    its queries, and writes one output per query."""
    L, H, KV, hd = (cfg["num_hidden_layers"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], cfg["head_dim"])
    flops = nbytes = 0.0
    for q, ctx in calls:
        nbytes += 2 * KV * hd * ctx * kv_itemsize + 2 * q * H * hd * act_itemsize
        # query i of q attends to ctx - q + 1 + i positions
        flops += 4 * H * hd * (q * ctx - q * (q - 1) / 2)
    return L * flops, L * nbytes


_CALL = re.compile(r"stablehlo\.custom_call @tpu_custom_call\(.*?"
                   r'kernel_name = "([^"]+)".*?:\s*\((.*?)\)\s*->\s*(.*)$')
_TENSOR = re.compile(r"tensor<([0-9x]*)x?([a-zA-Z0-9]+)>")


def _types(text: str) -> list:
    out = []
    for dims, dt in _TENSOR.findall(text):
        shape = tuple(int(d) for d in dims.split("x") if d) if dims else ()
        out.append((dt, shape))
    return out


def custom_calls(lowered: str, names) -> list:
    """Pallas calls of a lowered (StableHLO) module whose kernel is in
    ``names``: a list of (kernel name, operand types, result types), each
    type a (dtype, shape) pair."""
    out = []
    for line in lowered.splitlines():
        if "@tpu_custom_call" not in line:
            continue
        m = _CALL.search(line)
        if m and m.group(1) in names:
            out.append((m.group(1), _types(m.group(2)), _types(m.group(3))))
    return out


def calls_by_kernel(lowered_texts, names) -> dict:
    """Kernel name -> its Pallas calls (``custom_calls``) in the lowered
    modules ``lowered_texts``, for the kernels ``names``."""
    out = {}
    for text in lowered_texts:
        for call in custom_calls(text, names):
            out.setdefault(call[0], []).append(call)
    return out


def type_bytes(types) -> int:
    return sum(ITEMSIZE[dt] * int(np.prod(shape, dtype=np.int64))
               for dt, shape in types)


def call_bytes(call) -> int:
    """Each operand read once and each result written once."""
    _, operands, results = call
    return type_bytes(operands) + type_bytes(results)


_HLO_DTYPE = {"i32": "s32", "i8": "s8", "i1": "pred", "ui32": "u32"}
_NUMPY_DTYPE = {"bfloat16": "bf16", "float32": "f32", "float16": "f16",
                "int32": "i32", "int8": "i8", "bool": "i1", "uint32": "ui32",
                "uint8": "ui8", "float8_e4m3fn": "f8E4M3FN"}


def signature(types) -> str:
    """Types in the trace's HLO spelling, e.g. ``f32[12,768],s32[4]``."""
    return ",".join(f"{_HLO_DTYPE.get(dt, dt)}[{','.join(map(str, s))}]"
                    for dt, s in types)


def array_signature(x) -> str:
    """An array's type in the trace's HLO spelling, e.g. ``bf16[8,16]``."""
    return signature([(_NUMPY_DTYPE[str(x.dtype)], tuple(x.shape))])


def bytes_moved(cfg: dict, calls, counts: dict) -> tuple:
    """Cost of an elementwise kernel: each operand read once and each result
    written once; its arithmetic is never the bound."""
    return 0.0, float(sum(n * call_bytes(c) for n, c in calls))


def paged_attention_window(cfg: dict, calls, counts: dict) -> tuple:
    """Cost of the paged-attention kernel: the work of every live row of
    every call in the window (``counts["paged_calls"]``), whichever program
    made the call."""
    return paged_attention_cost(cfg, counts["paged_calls"])


# Kernels of the fused optimizer (repro.kernels megaplan / slim_update /
# fused_adam), whichever family trains: each with its cost function.
OPT_KERNELS = {name: bytes_moved for name in (
    "_mega_adam_kernel", "_mega_slim_kernel", "_mega_slim_partial_kernel",
    "_mega_finalize_ek_kernel", "_mega_finalize_owner_kernel",
    "_slim_kernel", "_slim_precond_kernel", "_slim_partial_kernel",
    "_slim_finalize_kernel", "_slim_apply_line_kernel",
    "_adam_kernel", "_adam_precond_kernel",
)}


def _timed_calls(ctx, names) -> dict:
    """Result signature -> (kernel name, the call of fewest bytes), over the
    lowered calls of the kernels ``names``."""
    groups = {}
    for name in names:
        for call in ctx.counts.get("kernel_calls", {}).get(name, ()):
            sig = signature(call[2])
            if sig not in groups or call_bytes(call) < call_bytes(groups[sig][1]):
                groups[sig] = (name, call)
    return groups


def kernel_time(ctx, names) -> tuple:
    """(calls, device seconds) in the trace of the kernels ``names``, found
    by the result signatures their lowered calls have."""
    if ctx.trace is None:
        return 0, 0.0
    return ctx.trace.kernel_time(_timed_calls(ctx, names))


def kernel_roofline(ctx, names):
    """Share of their roofline, in %, that the kernels ``names`` reach in the
    traced window: the least time of their work (per kernel the larger of
    its FLOPs at the bf16 peak and its bytes at the HBM bandwidth, by its
    cost function in ``ctx.kernels``) over their device time. None where
    the trace holds none of their calls."""
    if ctx.trace is None:
        return None
    by_name, seconds = {}, 0.0
    for sig, (name, call) in _timed_calls(ctx, names).items():
        n, s = ctx.trace.kernel_time([sig])
        seconds += s
        if n:
            by_name.setdefault(name, []).append((n, call))
    if seconds == 0.0:
        return None
    least = 0.0
    for name, calls in by_name.items():
        flops, nbytes = ctx.kernels[name](ctx.config, calls, ctx.counts)
        least += max(flops / ctx.peak["bf16_flops_per_s"],
                     nbytes / ctx.peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds
