"""The benchmark's hold on the system under test: its model configuration,
checked against the configuration file, and its parameter layout, both as
the configuration's family (``bench/families/``) states them."""
from __future__ import annotations

import jax
import jax.numpy as jnp

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def dtypes(cfg: dict) -> dict:
    """The ``ModelConfig`` compute and parameter dtypes the file states."""
    return {"dtype": _DTYPES[cfg["compute_dtype"]],
            "param_dtype": _DTYPES[cfg["param_dtype"]]}


def model_config(cfg: dict, family, override=None):
    """The program's ModelConfig for ``cfg`` (or ``override``, a
    ModelConfig the tests pass at a small size), refused where it departs
    from the configuration file: a field of ``family.program_fields`` that
    differs, or a parameter tree other than ``family.param_shapes``."""
    from repro.configs import get_config

    mc = override if override is not None else get_config(cfg["program"])
    want = family.program_fields(cfg)
    have = {k: getattr(mc, k) for k in want}
    bad = {k: (have[k], v) for k, v in want.items() if have[k] != v}
    if bad:
        raise ValueError(f"the program's {mc.name} departs from "
                         f"{cfg['name']}.json (have, want): {bad}")
    abstract, _ = mc.abstract()
    shapes = jax.tree.map(lambda a: tuple(a.shape), abstract)
    if shapes != jax.tree.map(tuple, family.param_shapes(cfg),
                              is_leaf=lambda x: isinstance(x, tuple)):
        raise ValueError(f"the program's {mc.name} parameter layout differs "
                         f"from {cfg['name']}.json's")
    return mc


def counters(obj, names) -> dict:
    """The numbers to difference over the window: every numeric field of
    ``obj.counters`` (the engine's ``ServeCounters``) and each attribute of
    ``names`` (a family's ``counters``) that ``obj`` has as a number."""
    fields = getattr(obj, "counters", None)
    out = {k: getattr(fields, k) for k in getattr(type(fields), "__slots__", ())}
    out.update((k, getattr(obj, k, None)) for k in names)
    return {k: v for k, v in out.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}


def counter_deltas(before: dict, after: dict) -> dict:
    return {k: after[k] - v for k, v in before.items() if k in after}
