"""pjit-able train / serve step factories.

``make_train_step`` closes over (model config, optimizer) and returns the
pure function lowered by both the real trainer and the dry-run:

    train_step(params, opt_state, batch) -> (params, opt_state, metrics)
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from ..models import transformer
from ..optim.base import GradientTransformation, apply_updates, global_norm
from .loss import lm_loss


def make_train_step(cfg, tx: GradientTransformation, *, forward_fn=None,
                    grad_accum: int = 1, grad_shardings=None,
                    guard: bool = False) -> Callable:
    """One optimizer step. With ``grad_accum > 1`` the global batch is split
    into microbatches scanned with fp32 gradient accumulation (the paper's
    own recipe: micro-batch 32 x 40 accumulation steps), which is also what
    bounds saved-activation memory for the large dry-run cells.

    ``grad_shardings``: optional NamedSharding pytree (like params) pinned
    onto the gradient tree — without it GSPMD may propagate gradients
    replicated over the TP axis (measured: 12 GiB/device vs 0.5 GiB for a
    67B model on a 256-chip mesh).

    Sharded fused backend: when ``tx`` was built with ``backend='fused'``
    plus ``mesh``/``param_specs`` (see ``repro.train.trainer.make_optimizer``
    and the launchers), the ``tx.update`` inside this step runs under
    ``shard_map`` — pin ``grad_shardings`` to the same specs so the gradient
    tree arrives already laid out for the per-shard kernels and the
    shard_map boundary inserts no resharding collectives.

    ``guard=True`` returns the 4-arg fault-tolerant variant

        train_step(params, opt_state, batch, controls)

    where ``controls`` is ``{'lr_scale': f32, 'grad_scale': f32}`` (jnp
    scalars — traced operands, so host-side policy changes never recompile).
    The step reads the in-pass :class:`repro.optim.fused.StepHealth` the
    optimizer published (build ``tx`` with ``emit_health=True``; without it
    the step falls back to the finiteness of the grad norm), and on a bad
    step *selects the pre-step params/opt state* — a poisoned gradient can
    never advance moments or the count. Extra metrics: ``nonfinite_count``,
    ``step_skipped``, ``health_grad_norm``. The returned opt state always
    has ``health=None`` so the input/output jit layouts match."""
    fwd = forward_fn or transformer.forward

    def pin(tree):
        if grad_shardings is None:
            return tree
        return jax.tree.map(jax.lax.with_sharding_constraint, tree, grad_shardings)

    def grads_of(params, batch):
        def loss_fn(p):
            loss, metrics = lm_loss(cfg, p, batch, fwd)
            return loss, metrics

        g, metrics = jax.grad(loss_fn, has_aux=True)(params)
        return pin(g), metrics

    def compute_grads(params, batch):
        if grad_accum == 1:
            return grads_of(params, batch)
        from ..sharding.logical import constrain, current

        def split(a):
            a = a.reshape((grad_accum, a.shape[0] // grad_accum) + a.shape[1:])
            if current() is not None:
                a = constrain(a, None, "batch", *([None] * (a.ndim - 2)))
            return a

        micro = jax.tree.map(split, batch)

        def body(acc, mb):
            g, m = grads_of(params, mb)
            acc = jax.tree.map(lambda a, x: a + x.astype(jnp.float32) / grad_accum, acc, g)
            return pin(acc), m

        zeros = pin(jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params))
        grads, ms = jax.lax.scan(body, zeros, micro)
        metrics = jax.tree.map(lambda x: jnp.mean(x, axis=0), ms)
        return grads, metrics

    def train_step(params, opt_state, batch):
        grads, metrics = compute_grads(params, batch)
        with jax.named_scope("optimizer"):
            updates, new_opt_state = tx.update(grads, opt_state, params)
            new_params = apply_updates(params, updates)
        metrics = dict(metrics)
        metrics["grad_norm"] = global_norm(grads)
        return new_params, new_opt_state, metrics

    def guarded_train_step(params, opt_state, batch, controls):
        from .guard import (attach_slim_snr, find_slim_snr, find_step_health,
                            strip_slim_snr, strip_step_health)

        grads, metrics = compute_grads(params, batch)
        g_scale = jnp.asarray(controls["grad_scale"], jnp.float32)
        grads = jax.tree.map(lambda g: g * g_scale.astype(g.dtype), grads)
        with jax.named_scope("optimizer"):
            updates, new_opt_state = tx.update(grads, opt_state, params)
            lr_scale = jnp.asarray(controls["lr_scale"], jnp.float32)
            updates = jax.tree.map(lambda u: u * lr_scale.astype(u.dtype),
                                   updates)
            new_params = apply_updates(params, updates)

        gn = global_norm(grads)
        health = find_step_health(new_opt_state)
        if health is not None:
            bad = health.bad
            nonfinite = jnp.sum(health.nonfinite)
            health_gn = health.grad_norm
        else:
            # No emit_health transformation in the chain: fall back to the
            # finiteness of the (already computed) global grad norm.
            bad = ~jnp.isfinite(gn)
            nonfinite = jnp.where(bad, 1.0, 0.0)
            health_gn = gn
        # Strip health (and any ridden SNR snapshot) so old/new state
        # layouts match, select the pre-step state wherever the step is
        # bad — moments and count never advance on a poisoned gradient —
        # then put the SNR measurement back for the trainer to consume.
        snr = find_slim_snr(new_opt_state)
        new_clean = strip_slim_snr(strip_step_health(new_opt_state))
        keep_old = lambda n, o: jnp.where(bad, o, n)
        new_params = jax.tree.map(keep_old, new_params, params)
        new_clean = jax.tree.map(keep_old, new_clean, opt_state)
        new_clean = attach_slim_snr(new_clean, snr)

        metrics = dict(metrics)
        metrics["grad_norm"] = gn
        metrics["nonfinite_count"] = nonfinite
        metrics["step_skipped"] = bad.astype(jnp.float32)
        metrics["health_grad_norm"] = health_gn
        return new_params, new_clean, metrics

    return guarded_train_step if guard else train_step


def make_eval_step(cfg, *, forward_fn=None) -> Callable:
    fwd = forward_fn or transformer.forward

    def eval_step(params, batch):
        _, metrics = lm_loss(cfg, params, batch, fwd)
        return metrics

    return eval_step


def make_serve_step(cfg) -> Callable:
    """One batched decode step: (params, cache, tokens (B,1)) -> (next_tokens,
    logits, cache). Greedy argmax sampling (serving example adds temperature)."""

    def serve_step(params, cache, tokens):
        logits, new_cache = transformer.decode_step(cfg, params, cache, tokens)
        next_tokens = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        return next_tokens, logits, new_cache

    return serve_step
