"""Operations and bytes the benchmark charges, computed from shapes.

Model FLOPs leave out recomputation, and kernel bytes are the least each
call must move, so a share of a peak computed from them cannot pass 100%
when the time counts all the work.
"""
from __future__ import annotations

import re

import numpy as np

ITEMSIZE = {"f32": 4, "bf16": 2, "f16": 2, "i32": 4, "ui32": 4, "i8": 1,
            "ui8": 1, "i1": 1, "f8E4M3FN": 1}


def matmul_params(cfg: dict) -> int:
    """Weights that multiply every token once in a forward pass: the
    attention and MLP matrices of every layer and the (tied) LM head. The
    token and position lookups and the norm scales are no matmuls."""
    D, F, L = cfg["hidden_size"], cfg["intermediate_size"], cfg["num_hidden_layers"]
    H, KV, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    attn = D * (H + 2 * KV) * hd + H * hd * D
    mlp = (3 if cfg["hidden_act"] == "silu" else 2) * D * F
    return L * (attn + mlp) + D * cfg["vocab_size"]


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """6 N + 12 L H hd T (PaLM, App. B): forward and backward of the
    matmuls, and of attention's two products over the whole sequence."""
    L, H, hd = (cfg["num_hidden_layers"], cfg["num_attention_heads"],
                cfg["head_dim"])
    return 6.0 * matmul_params(cfg) + 12.0 * L * H * hd * seq


def forward_flops(cfg: dict, contexts) -> float:
    """Forward FLOPs of tokens that attend over ``contexts`` positions each
    (one entry per token): 2 N per token plus 4 L H hd per attended position."""
    L, H, hd = (cfg["num_hidden_layers"], cfg["num_attention_heads"],
                cfg["head_dim"])
    ctx = np.asarray(contexts, np.float64)
    return 2.0 * matmul_params(cfg) * ctx.size + 4.0 * L * H * hd * ctx.sum()


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


def type_bytes(types) -> int:
    return sum(ITEMSIZE[dt] * int(np.prod(shape, dtype=np.int64))
               for dt, shape in types)


def call_bytes(call) -> int:
    """Each operand read once and each result written once."""
    _, operands, results = call
    return type_bytes(operands) + type_bytes(results)


_HLO_DTYPE = {"i32": "s32", "i8": "s8", "i1": "pred", "ui32": "u32"}


def signature(types) -> str:
    """Types in the trace's HLO spelling, e.g. ``f32[12,768],s32[4]``."""
    return ",".join(f"{_HLO_DTYPE.get(dt, dt)}[{','.join(map(str, s))}]"
                    for dt, s in types)
