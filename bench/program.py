"""The benchmark's hold on the system under test: its model configuration,
checked against the configuration file, and its parameter layout."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .reference.common import param_shapes

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def model_config(cfg: dict, override=None):
    """The program's ModelConfig for ``cfg`` (or ``override``, a
    ModelConfig the tests pass at a small size), refused where it departs
    from the configuration file."""
    from repro.configs import get_config

    mc = override if override is not None else get_config(cfg["program"])
    want = {
        "n_layers": cfg["num_hidden_layers"], "d_model": cfg["hidden_size"],
        "n_heads": cfg["num_attention_heads"],
        "n_kv_heads": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
        "d_ff": cfg["intermediate_size"], "vocab_size": cfg["vocab_size"],
        "norm": cfg["norm"], "pos": cfg["position"],
        "gated_mlp": cfg["hidden_act"] == "silu",
        "tie_embeddings": cfg["tie_word_embeddings"],
        "dtype": _DTYPES[cfg["compute_dtype"]],
        "param_dtype": _DTYPES[cfg["param_dtype"]],
    }
    if cfg["position"] == "learned":
        want["max_position"] = cfg["max_position_embeddings"]
    have = {k: getattr(mc, k) for k in want}
    bad = {k: (have[k], v) for k, v in want.items() if have[k] != v}
    if bad:
        raise ValueError(f"the program's {mc.name} departs from "
                         f"{cfg['name']}.json (have, want): {bad}")
    abstract, _ = mc.abstract()
    shapes = jax.tree.map(lambda a: tuple(a.shape), abstract)
    if shapes != jax.tree.map(tuple, param_shapes(cfg),
                              is_leaf=lambda x: isinstance(x, tuple)):
        raise ValueError(f"the program's {mc.name} parameter layout differs "
                         f"from {cfg['name']}.json's")
    return mc
