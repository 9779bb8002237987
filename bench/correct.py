"""The numbers that decide ``correct``: the program's readings against the
plain float32 reference's, each held to its limit (``limits/<cell>.json``).

Training compares, by the worst leaf (each layer of a layer-stacked leaf
counts as a leaf of its own):

* ``loss_gap``: the largest |loss - reference loss| over the first three
  steps, in nats;
* ``grad_gap``: the gap between the norm of the first gradient as the
  optimizer received it (its first moment after one step over ``1 - b1``)
  and the reference's clipped gradient;
* ``change_gap``: the gap between the norm of each leaf's change over the
  three steps and the reference's, leaving out leaves whose reference
  gradient is under a thousandth of the median leaf's (Adam moves those
  by round-off alone).

A norm gap is ``|prog - ref| / max(ref, median ref leaf)``.

Serving compares, over the served positions of a sample of requests
(``serve.EngineSession.check``), ``logit_err``: the widest relative gap
between the logits the engine sampled from and the reference's, and
``logit_gap``: the widest gap by which a served token's reference logit
lies below the reference's best logit at that position.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .reference.common import leaf_names

SMALL_GRAD = 1e-3


def leaf_norms(tree) -> dict:
    """name -> float64 array of norms: one per layer of a stacked leaf."""
    names = leaf_names(tree)

    @jax.jit
    def norms(leaves):
        return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)),
                                 axis=tuple(range(1, x.ndim))))
                if n.startswith("blocks.") else
                jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))[None]
                for n, x in zip(names, leaves)]

    out = norms(jax.tree_util.tree_leaves(tree))
    return {n: np.asarray(v, np.float64) for n, v in zip(names, out)}


def diff_norms(a, b) -> dict:
    return leaf_norms(jax.tree.map(lambda x, y: x.astype(jnp.float32)
                                   - y.astype(jnp.float32), a, b))


def norm_gap(prog: dict, ref: dict, keep: dict | None = None) -> float:
    if set(prog) != set(ref):
        raise ValueError(f"leaf sets differ: {sorted(set(prog) ^ set(ref))}")
    names = sorted(ref)
    r = np.concatenate([ref[n] for n in names])
    p = np.concatenate([prog[n] for n in names])
    k = (np.concatenate([keep[n] for n in names]) if keep is not None
         else np.ones_like(r, bool))
    med = np.median(r[k])
    return float(np.max(np.abs(p - r)[k] / np.maximum(r[k], med)))


def train_numbers(prog: dict, ref: dict) -> dict:
    """``prog`` and ``ref`` hold ``losses`` (3,), ``grad`` and ``change``
    (leaf norms); ``ref`` also the reference gradient's norms it keeps by."""
    g = ref["grad"]
    med = np.median(np.concatenate(list(g.values())))
    keep = {n: v >= SMALL_GRAD * med for n, v in g.items()}
    return {
        "loss_gap": float(np.max(np.abs(np.asarray(prog["losses"])
                                        - np.asarray(ref["losses"])))),
        "grad_gap": norm_gap(prog["grad"], g),
        "change_gap": norm_gap(prog["change"], ref["change"], keep),
    }


def verdict(numbers: dict, limits: dict) -> tuple:
    """(correct, {name: {"value", "limit"}}). Every number needs a limit."""
    missing = set(numbers) - set(limits)
    if missing:
        raise KeyError(f"no limit for {sorted(missing)}")
    checks = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
    ok = all(np.isfinite(v) and v <= limits[k] for k, v in numbers.items())
    return bool(ok), checks
