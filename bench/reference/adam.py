"""Plain float32 SlimAdam (the paper's Eq. 2) inside AdamW, and its
three-step training reference.

Per step: clip the whole gradient to global norm ``grad_clip``; first moment
``m = b1 m + (1 - b1) g``; second moment stored as a mean over the leaf's
``dims`` (no dims: plain Adam) ``v = b2 v + (1 - b2) mean_dims(g^2)``;
update ``u = (m / (1 - b1^t)) / (sqrt(v / (1 - b2^t)) + eps)``, plus
``weight_decay * p`` on every leaf of rank 2 or more; ``p -= lr_t * u``,
with ``lr_t = lr * min((t - 1) / warmup_steps, 1)``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .common import exact, leaf_names, nll_sum


def make_update(hp: dict, dims: list):
    """Jitted step over flat leaf lists: (p, g, m, v, t, lr) -> (p, m, v, g
    clipped). ``dims[i]`` are leaf i's second-moment reduction dims."""
    b1, b2 = hp["b1"], hp["b2"]

    @jax.jit
    def step(p, g, m, v, t, lr):
        gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in g))
        scale = jnp.where(gnorm <= hp["grad_clip"], 1.0,
                          hp["grad_clip"] / (gnorm + 1e-16))
        g = [x * scale for x in g]
        tf = t.astype(jnp.float32)
        out_p, out_m, out_v = [], [], []
        for pi, gi, mi, vi, d in zip(p, g, m, v, dims):
            mi = b1 * mi + (1 - b1) * gi
            g2 = gi * gi
            vi = b2 * vi + (1 - b2) * (jnp.mean(g2, axis=d, keepdims=True)
                                        if d else g2)
            u = (mi / (1 - b1 ** tf)) / (jnp.sqrt(vi / (1 - b2 ** tf))
                                         + hp["eps"])
            if pi.ndim >= 2:
                u = u + hp["weight_decay"] * pi
            out_p.append(pi - lr * u)
            out_m.append(mi)
            out_v.append(vi)
        return out_p, out_m, out_v, g

    return step


def lr_at(hp: dict, t: int) -> float:
    """The learning rate of step t (from 1)."""
    return hp["lr"] * min((t - 1) / hp["warmup_steps"], 1.0)


def train_steps(cfg: dict, forward, params, batches, hp: dict, slim_dims: dict,
                *, rows: int, quant=exact):
    """Run one step per (tokens, labels) batch from ``params`` through the
    family's ``forward``. A batch's loss and gradient are summed over blocks
    of ``rows`` rows, so the reference fits the device. Returns (losses,
    clipped gradient of step 1, params after the last step), the trees in the
    layout of ``params``."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    dims = [tuple(slim_dims.get(n, ())) for n in leaf_names(params)]
    step = make_update(hp, dims)
    vg = jax.jit(jax.value_and_grad(
        lambda p, tok, lab: nll_sum(forward, cfg, p, tok, lab, quant)))
    m = [jnp.zeros_like(p) for p in leaves]
    v = [jnp.zeros(tuple(1 if i in d else s for i, s in enumerate(p.shape)),
                   jnp.float32) for p, d in zip(leaves, dims)]
    losses, first_grad = [], None
    for t, (tokens, labels) in enumerate(batches, start=1):
        total, grads = 0.0, None
        for lo in range(0, tokens.shape[0], rows):
            val, g = vg(jax.tree_util.tree_unflatten(treedef, leaves),
                        jnp.asarray(tokens[lo:lo + rows]),
                        jnp.asarray(labels[lo:lo + rows]))
            total += float(val)
            g = jax.tree_util.tree_leaves(g)
            grads = g if grads is None else [a + b for a, b in zip(grads, g)]
        grads = [x / tokens.size for x in grads]
        losses.append(total / tokens.size)
        leaves, m, v, clipped = step(leaves, grads, m, v, jnp.int32(t),
                                     jnp.float32(lr_at(hp, t)))
        if first_grad is None:
            first_grad = jax.tree_util.tree_unflatten(treedef, clipped)
    return (np.asarray(losses), first_grad,
            jax.tree_util.tree_unflatten(treedef, leaves))
