"""FLOP and byte counts against numbers worked out by hand."""
import json

import pytest

from bench import counts, spec
from bench.spec import BENCH


def _cfg(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def _family(name):
    return spec.family(_cfg(name)["family"])


def test_matmul_params_gpt_small():
    # per layer: q,k,v,o 4 * 768^2 = 2,359,296; MLP 2 * 768 * 3072 =
    # 4,718,592; 12 layers = 84,934,656; tied head 768 * 50304 = 38,633,472
    assert _family("gpt_small").matmul_params(_cfg("gpt_small")) == 123_568_128


def test_matmul_params_smollm():
    # per layer: q,k,v 576 * (9 + 3 + 3) * 64 = 552,960; o 9 * 64 * 576 =
    # 331,776; SwiGLU 3 * 576 * 1536 = 2,654,208; 30 layers = 106,168,320;
    # tied head 576 * 49152 = 28,311,552
    assert _family("smollm_135m").matmul_params(_cfg("smollm_135m")) == 134_479_872


def test_train_flops_per_token():
    # 6 N = 741,408,768; attention 12 * 12 layers * 12 heads * 64 * 1024 =
    # 113,246,208
    cfg = _cfg("gpt_small")
    assert _family("gpt_small").train_flops_per_token(cfg, 1024) == 854_654_976


TINY = {"num_hidden_layers": 1, "hidden_size": 8, "num_attention_heads": 2,
        "num_key_value_heads": 1, "head_dim": 4, "intermediate_size": 16,
        "vocab_size": 32, "hidden_act": "silu"}


def test_forward_flops():
    # N = 8 * (2 + 2) * 4 + 2 * 4 * 8 + 3 * 8 * 16 + 8 * 32 = 128 + 64 + 384
    # + 256 = 832; two tokens at contexts 1 and 2: 2 * 832 * 2 + 4 * 2 * 4 * 3
    dense = spec.family("dense")
    assert dense.matmul_params(TINY) == 832
    assert dense.forward_flops(TINY, [1, 2]) == 2 * 832 * 2 + 4 * 2 * 4 * 3


def test_paged_attention_cost():
    # one decode row at context 10: K and V 2 * 1 kv head * 4 * 10 * 2 B =
    # 160 B, q and out 2 * 2 heads * 4 * 2 B = 32 B; 4 * 2 * 4 * 10 FLOPs
    assert counts.paged_attention_cost(TINY, [(1, 10)]) == (320.0, 192.0)
    # a chunk of 3 queries ending at context 5 attends 3 + 4 + 5 positions;
    # K and V of its 5 live positions once, 3 queries and 3 outputs
    flops, nbytes = counts.paged_attention_cost(TINY, [(3, 5)])
    assert flops == 4 * 2 * 4 * 12
    assert nbytes == 2 * 4 * 5 * 2 + 2 * 3 * 2 * 4 * 2


LOWERED = (
    '    %0 = stablehlo.custom_call @tpu_custom_call(%arg0, %arg1) '
    '{backend_config = "{}", kernel_name = "_mega_slim_kernel", '
    'operand_layouts = [dense<[1, 0]> : tensor<2xindex>]} : '
    '(tensor<12x768xf32>, tensor<4xi32>) -> (tensor<12x768xf32>, tensor<12x1xbf16>)\n'
    '    %1 = stablehlo.custom_call @tpu_custom_call(%0) {kernel_name = "other"} : '
    '(tensor<8xf32>) -> tensor<8xf32>\n')


def test_custom_calls_and_bytes():
    calls = counts.custom_calls(LOWERED, {"_mega_slim_kernel"})
    assert len(calls) == 1
    name, operands, results = calls[0]
    assert name == "_mega_slim_kernel"
    assert operands == [("f32", (12, 768)), ("i32", (4,))]
    assert results == [("f32", (12, 768)), ("bf16", (12, 1))]
    assert counts.call_bytes(calls[0]) == 12 * 768 * 4 + 16 + 12 * 768 * 4 + 24
    assert counts.signature(results) == "f32[12,768],bf16[12,1]"
    assert counts.signature(operands) == "f32[12,768],s32[4]"


@pytest.mark.parametrize("name", ["gpt_small", "smollm_135m"])
def test_configs_have_no_cut(name):
    assert _cfg(name)["reduced"] == []
