"""Distributed training driver: mesh + sharded train loop + checkpointing.

    PYTHONPATH=src python -m repro.launch.train --arch smollm_135m \
        --steps 100 --mesh none            # single-device (this container)
    PYTHONPATH=src python -m repro.launch.train --arch deepseek_67b \
        --mesh multi --steps 1000          # on a real 2-pod v5e slice

On hardware, run one process per host (jax.distributed.initialize picks up
the TPU runtime); the data pipeline shards per host via (host_id,
host_count), and the elastic checkpoint restore re-lays state onto whatever
mesh the restarted job gets.
"""
from __future__ import annotations

import argparse
import time
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..checkpoint import store
from ..configs import ARCH_IDS, get_config, get_reduced
from ..data import DataConfig, ZipfLM
from ..sharding.logical import ShardingContext, param_specs, use_sharding
from ..sharding.state_shardings import opt_state_specs
from ..train.guard import ROLLBACK, Guard, GuardConfig
from ..train.step import make_train_step
from ..train.trainer import _SLIM_FAMILY, make_optimizer
from .compile_cache import enable_compile_cache
from .mesh import make_production_mesh


class TrainSetup(NamedTuple):
    """What :func:`build_train_setup` returns. Trace and call ``step_fn``
    inside ``use_sharding(ctx)``: the model's sharding constraints resolve
    against the active context when the step is traced."""

    ctx: Optional[ShardingContext]
    params: Any
    opt_state: Any
    step_fn: Callable


def build_train_setup(cfg, optimizer: str, lr, *, backend: str, mesh=None,
                      batch: int, seq: int, guard: bool = False,
                      grad_accum: int = 1, seed: int = 0) -> TrainSetup:
    """Parameters, optimizer state and the jitted train step of ``cfg``.

    With a ``mesh`` the state is laid out by the logical rule table
    (FSDP x TP), the fused backend runs its tree update under shard_map on
    the local shards, and psum-regime reduced moments keep their
    owner-slice layout across the step boundary. With ``mesh=None``
    everything lives on one device."""
    ctx = (ShardingContext(mesh, rules=dict(cfg.sharding_overrides) or None)
           if mesh is not None else None)
    with use_sharding(ctx):
        params, meta = cfg.init(jax.random.PRNGKey(seed))
        # Specs first: the fused backend wants mesh + param specs at
        # construction so its tree update runs under shard_map on the shards.
        p_specs = param_specs(meta, params) if ctx is not None else None
        emit_health = guard and optimizer in ("adam",) + _SLIM_FAMILY
        tx = make_optimizer(optimizer, lr, params, meta, backend=backend,
                            mesh=mesh, param_specs=p_specs,
                            emit_health=emit_health)
        opt_state = tx.init(params)
        if ctx is None:
            step_fn = jax.jit(make_train_step(cfg, tx, grad_accum=grad_accum,
                                              guard=guard))
            return TrainSetup(None, params, opt_state, step_fn)

        from ..optim.base import resolve_backend

        p_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), p_specs,
                            is_leaf=lambda x: isinstance(x, P))
        # Fused backend: pin psum-regime reduced moments to their
        # owner-slice storage layout so the pjit state boundary matches
        # the shard_map output (no per-step O(kept) re-gather).
        owner_mesh = mesh if resolve_backend(backend) == "fused" else None
        o_specs = opt_state_specs(jax.eval_shape(lambda: opt_state), params,
                                  p_specs, owner_mesh=owner_mesh)
        o_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), o_specs,
                            is_leaf=lambda x: isinstance(x, P))
        params = jax.device_put(params, p_sh)
        opt_state = jax.device_put(opt_state, o_sh)
        b_sh = NamedSharding(mesh, ctx.spec_for(("batch", None), (batch, seq)))
        batch_sh = {"tokens": b_sh, "labels": b_sh}
        in_sh = (p_sh, o_sh, batch_sh, None) if guard else (p_sh, o_sh, batch_sh)
        step_fn = jax.jit(make_train_step(cfg, tx, grad_accum=grad_accum,
                                          grad_shardings=p_sh, guard=guard),
                          in_shardings=in_sh,
                          out_shardings=(p_sh, o_sh, None), donate_argnums=(0, 1))
        return TrainSetup(ctx, params, opt_state, step_fn)


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="smollm_135m")
    ap.add_argument("--reduced", action="store_true", help="CPU-scale config")
    ap.add_argument("--mesh", choices=("none", "single", "multi"), default="none")
    ap.add_argument("--optimizer", default="slim")
    ap.add_argument("--backend", choices=("jnp", "fused", "auto"), default="auto",
                    help="Adam/SlimAdam execution path; 'fused' + a mesh runs "
                         "the Pallas kernels per-shard under shard_map")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--guard", action="store_true",
                    help="fault-tolerant step: in-pass anomaly health, "
                         "skip poisoned steps, lr backoff on loss spikes, "
                         "rollback to the last checkpoint on repeated faults")
    args = ap.parse_args(argv)

    cfg = get_reduced(args.arch) if args.reduced or args.mesh == "none" else get_config(args.arch)
    mesh = None if args.mesh == "none" else make_production_mesh(multi_pod=(args.mesh == "multi"))
    setup = build_train_setup(cfg, args.optimizer, args.lr, backend=args.backend,
                              mesh=mesh, batch=args.batch, seq=args.seq,
                              guard=args.guard, grad_accum=args.grad_accum)
    params, opt_state, step_fn = setup.params, setup.opt_state, setup.step_fn

    with use_sharding(setup.ctx):
        start = 0
        if args.ckpt and store.latest_step(args.ckpt) is not None:
            state, extra = store.restore(args.ckpt, {"params": params, "opt": opt_state})
            params, opt_state = state["params"], state["opt"]
            start = int(extra.get("step", 0))
            print(f"resumed from step {start}")

        data = ZipfLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                                 global_batch=args.batch))
        host_id = jax.process_index()
        host_count = jax.process_count()
        acp = store.AsyncCheckpointer()
        guard = Guard(GuardConfig()) if args.guard else None
        t0 = time.time()
        for s in range(start, args.steps):
            batch = {k: jnp.asarray(v) for k, v in
                     data.batch(s, host_id=host_id, host_count=host_count).items()}
            if guard is not None:
                controls = {"lr_scale": jnp.asarray(guard.lr_scale, jnp.float32),
                            "grad_scale": jnp.asarray(1.0, jnp.float32)}
                params, opt_state, metrics = step_fn(params, opt_state, batch, controls)
                action = guard.observe(
                    float(metrics["loss"]),
                    skipped=bool(metrics["step_skipped"] > 0),
                    nonfinite=float(metrics["nonfinite_count"]))
                if action == ROLLBACK:
                    guard.note_rollback()
                    if args.ckpt and store.latest_step(args.ckpt) is not None:
                        # Restore the last valid checkpoint; the step index
                        # keeps advancing, so the data stream naturally
                        # diverges from the poisoned trajectory.
                        state, extra = store.restore(
                            args.ckpt, {"params": params, "opt": opt_state})
                        params, opt_state = state["params"], state["opt"]
                        print(f"step {s+1}: guard rolled back to checkpoint "
                              f"step {int(extra.get('step', 0))}")
            else:
                params, opt_state, metrics = step_fn(params, opt_state, batch)
            if (s + 1) % args.log_every == 0:
                tput = (s + 1 - start) * args.batch * args.seq / (time.time() - t0)
                extra_log = ""
                if guard is not None:
                    c = guard.counters
                    extra_log = (f" skipped {c['skipped']} backoffs "
                                 f"{c['backoffs']} rollbacks {c['rollbacks']}"
                                 f" lr_scale {guard.lr_scale:.2f}")
                print(f"step {s+1}: loss {float(metrics['loss']):.4f} "
                      f"grad_norm {float(metrics['grad_norm']):.3f} tok/s {tput:.0f}"
                      + extra_log)
            if args.ckpt and (s + 1) % max(args.steps // 4, 1) == 0:
                acp.save(args.ckpt, s + 1, {"params": params, "opt": opt_state},
                         extra={"step": s + 1})
        acp.wait()
        print(f"done: {args.steps - start} steps in {time.time() - t0:.1f}s")
        if guard is not None:
            print("guard counters:", guard.counters)


if __name__ == "__main__":
    main()
