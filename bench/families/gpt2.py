"""GPT-2 (Radford et al. 2019) as the paper trains it: learned positions,
pre-LayerNorm without biases, MLP ``gelu_tanh(x W_up) W_down``, the head tied
to the token embedding (``dense.py``)."""
from bench.families.dense import (counters, forward, forward_flops,  # noqa: F401
                                  kernels, matmul_params, param_shapes,
                                  program_fields, small, train_flops_per_token)
