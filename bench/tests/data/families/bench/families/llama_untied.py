"""The Llama decoder with an untied head: its own ``lm_head`` leaf of shape
(hidden, vocab) beside the token embedding, as DeepSeek LLM 67B has it
(arXiv:2401.02954). The rest is ``dense.py``'s llama."""
from bench.families import dense
from bench.reference.common import dot, exact

counters, kernels, small = dense.counters, dense.kernels, dense.small
matmul_params = dense.matmul_params              # the head counts D x V either way
train_flops_per_token, forward_flops = dense.train_flops_per_token, dense.forward_flops
program_fields = dense.program_fields


def param_shapes(cfg: dict) -> dict:
    return dict(dense.param_shapes(cfg),
                lm_head=(cfg["hidden_size"], cfg["vocab_size"]))


def forward(cfg: dict, params, tokens, quant=exact):
    """tokens: (B, S) int32 -> logits (B, S, vocab) float32."""
    return dot("bsd,dv->bsv", dense.hidden(cfg, params, tokens, quant),
               params["lm_head"], quant)
