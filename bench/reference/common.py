"""Initialisation, matmul precision and the loss shared by the plain
references. Each family (``bench/families/``) gives the parameter layout
(``param_shapes``) and the forward pass; layer-stacked leaves, under
``blocks.``, carry the layer index first."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
# Largest normal of an IEEE-style 4-bit-exponent, 3-bit-mantissa float:
# ``reduce_precision`` rounds like float8 e4m3 below it.
FP8_MAX = 240.0


def exact(x):
    return x


@jax.custom_vjp
def fp8(x):
    """Round to float8 e4m3 (4 exponent, 3 mantissa bits) with one
    per-tensor scale: the operand of a low-precision matmul. Gradients pass
    straight through the rounding. ``reduce_precision`` rather than a round
    trip through ``float8_e4m3fn``, which XLA may drop as excess precision."""
    s = jnp.max(jnp.abs(x)) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return jax.lax.reduce_precision(x / s, exponent_bits=4, mantissa_bits=3) * s


fp8.defvjp(lambda x: (fp8(x), None), lambda _, g: (g,))

PRECISIONS = {"float32": exact, "fp8": fp8}


def dot(spec: str, a, b, quant=exact):
    """einsum of two float32 operands at full float32 precision, after
    rounding both operands with ``quant``."""
    return jnp.einsum(spec, quant(a.astype(jnp.float32)),
                      quant(b.astype(jnp.float32)), precision=HIGHEST)


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def leaf_names(tree, is_leaf=None) -> list:
    """Dotted names of a nested dict's leaves, in ``jax.tree`` order."""
    paths, _ = jax.tree_util.tree_flatten_with_path(tree, is_leaf=is_leaf)
    return [".".join(str(k.key) for k in path) for path, _ in paths]


def init_params(cfg: dict, shapes: dict, key) -> dict:
    """Random float32 weights of the layout ``shapes`` (the family's
    ``param_shapes``): normal(std), residual writers divided by sqrt(2 L),
    norm scales 1. Pure and jittable: one call makes them all."""
    spec = cfg["init"]
    std = spec["std"]
    resid = std / math.sqrt(2.0 * cfg["num_hidden_layers"])
    names = leaf_names(shapes, _is_shape)
    leaves, treedef = jax.tree_util.tree_flatten(shapes, is_leaf=_is_shape)
    out = []
    for i, (name, shape) in enumerate(zip(names, leaves)):
        if name.endswith(".scale"):
            out.append(jnp.ones(shape, jnp.float32))
            continue
        s = resid if any(name.endswith(r) for r in spec["residual"]) else std
        out.append(s * jax.random.normal(jax.random.fold_in(key, i), shape,
                                         jnp.float32))
    return jax.tree_util.tree_unflatten(treedef, out)


def norm(cfg: dict, x, scale):
    eps = cfg["norm_eps"]
    if cfg["norm"] == "rmsnorm":
        y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    else:
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        y = (x - mu) * jax.lax.rsqrt(var + eps)
    return y * scale


def nll_sum(forward, cfg: dict, params, tokens, labels, quant=exact):
    """Summed next-token negative log-likelihood over every position of the
    family's ``forward``."""
    logits = forward(cfg, params, tokens, quant)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - gold)
