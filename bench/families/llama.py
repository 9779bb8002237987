"""Llama (SmolLM-135M's ``config.json``): RMSNorm, rotary positions,
grouped-query attention, SwiGLU MLP, the head tied to the token embedding
(``dense.py``)."""
from bench.families.dense import (counters, forward, forward_flops,  # noqa: F401
                                  kernels, matmul_params, param_shapes,
                                  program_fields, small, train_flops_per_token)
