"""Training cells: the program's ``Trainer`` with the fused SlimAdam.

Set-up builds one trainer, gives it weights made on the device from the
seed, and drives it through steps 1-3 with the window's own call and feed;
those steps are what the reference checks. The window then calls
``Trainer.run`` in strides of ``stride_steps`` (the trainer reads its
metrics on the host only at a stride's last step) until ``--seconds`` have
passed, and ends with ``block_until_ready`` on parameters and optimizer
state. ``train_tokens_per_s`` is every token of every step in the window
over the window's seconds.
"""
from __future__ import annotations

import gc
import math
import sys
import time

import jax
import jax.numpy as jnp

from . import correct, counts, program, spec, traffic
from .reference import adam as ref_adam
from .reference.common import init_params

CHECK_STEPS = 3


def _first_moment(opt_state):
    """The ``mu`` tree of the optimizer state's Adam-family member."""
    stack = [opt_state]
    while stack:
        node = stack.pop()
        if hasattr(node, "mu") and hasattr(node, "count"):
            return node.mu
        if isinstance(node, (tuple, list)):
            stack.extend(node)
        elif hasattr(node, "_fields"):
            stack.extend(getattr(node, f) for f in node._fields)
    raise ValueError("no first moment in the optimizer state")


def hyper(tr: dict) -> dict:
    return {k: tr[k] for k in ("lr", "warmup_steps", "b1", "b2", "eps",
                               "weight_decay", "grad_clip")}


def learning_rate(hp: dict):
    """The schedule as the program's optimizer takes it: linear warmup from
    0 to ``lr`` over ``warmup_steps``; the update of step t reads count t-1."""
    peak, warm = hp["lr"], hp["warmup_steps"]
    return lambda count: peak * jnp.minimum(count.astype(jnp.float32) / warm, 1.0)


def moment_dims(cfg: dict, tr: dict) -> dict:
    """Second-moment reduction dims per leaf: the configuration's SlimAdam
    rules, or none (plain Adam)."""
    if tr["optimizer"] not in ("slim", "adam"):
        raise ValueError(f"unknown optimizer {tr['optimizer']!r}")
    return cfg["slim_dims"] if tr["optimizer"] == "slim" else {}


def run(cell, seed: int, seconds: float, trace_dir, clock, *, model=None,
        fault=None) -> dict:
    """One run of a training cell. ``model`` (a small ModelConfig) and
    ``fault`` (a callable that breaks the trainer) are for the tests."""
    from repro.optim import fused
    from repro.train import Trainer, TrainerConfig

    cfg, tr, fam = cell.config, cell.traffic, cell.family
    mc = program.model_config(cfg, fam, model)
    key = jax.random.PRNGKey(traffic.seed32(seed))
    make = jax.jit(lambda k: init_params(cfg, fam.param_shapes(cfg), k))
    params = make(key)
    data = traffic.ZipfBatches(cfg["vocab_size"], tr["seq"], tr["batch"], seed,
                               **tr["data"])
    hp = hyper(tr)
    trainer = Trainer(
        mc, tr["optimizer"], learning_rate(hp), data,
        TrainerConfig(total_steps=2**62, log_every=2**62,
                      seed=traffic.seed32(seed), backend="fused"),
        optimizer_kw={k: hp[k] for k in ("b1", "b2", "weight_decay",
                                          "grad_clip")})
    trainer.params = params
    if fault is not None:
        fault(trainer)
    fused.reset_kernel_degradation()

    # Steps 1-3 through the window's own call and feed: what the check reads.
    losses = []
    for k in range(1, CHECK_STEPS + 1):
        losses.append(trainer.run(k)["loss"])
        if k == 1:
            mu = _first_moment(trainer.opt_state)
            grad = {n: v / (1.0 - hp["b1"])
                    for n, v in correct.leaf_norms(mu).items()}
    change = correct.diff_norms(trainer.params, params)
    del params
    kernel_calls = {}
    if trace_dir:
        batch = {k: jnp.asarray(v) for k, v in data.batch(trainer.step).items()}
        lowered = trainer._train_step.lower(trainer.params, trainer.opt_state,
                                            batch).as_text()
        kernel_calls = counts.calls_by_kernel([lowered], spec.kernels(cell))

    stride, failed = tr["stride_steps"], 0
    if trace_dir:
        jax.profiler.start_trace(trace_dir, profiler_options=trace_options())
    before = program.counters(trainer, fam.counters)
    t_open = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            with jax.profiler.TraceAnnotation("bench.trainer.run"):
                last = trainer.run(trainer.step + stride)
            if not math.isfinite(last["loss"]):
                failed += stride
            if time.perf_counter() - t_open >= seconds:
                break
        jax.block_until_ready((trainer.params, trainer.opt_state))
    t_close = time.perf_counter()
    if trace_dir:
        stop_trace()
    moved = program.counter_deltas(before, program.counters(trainer, fam.counters))
    steps = trainer.step - CHECK_STEPS
    if fused.kernel_degraded_leaves():
        failed = steps
    window = t_close - t_open
    tokens = steps * tr["batch"] * tr["seq"]
    out = {
        "setup_s": t_open - clock,
        "attempted": steps,
        "failed": failed,
        "end_to_end": {"train_tokens_per_s": tokens / window},
        "memory_peak_bytes": peak_bytes(),
        "counts": {"steps": steps, "tokens": tokens, "window_s": window,
                   "flops_per_token": fam.train_flops_per_token(cfg, tr["seq"]),
                   "kernel_calls": kernel_calls, "program": moved},
    }
    prog = {"losses": losses, "grad": grad, "change": change}
    batches = [data.batch(s) for s in range(CHECK_STEPS)]
    del trainer, mu
    gc.collect()

    # The plain float32 reference, with nothing of the program left on the
    # device: the same weights from the seed, the same three batches.
    ref_losses, ref_grad, ref_params = ref_adam.train_steps(
        cfg, fam.forward, make(key),
        [(b["tokens"], b["labels"]) for b in batches], hp, moment_dims(cfg, tr),
        rows=tr["check_rows"])
    ref = {"losses": ref_losses, "grad": correct.leaf_norms(ref_grad),
           "change": correct.diff_norms(ref_params, make(key))}
    out["numbers"] = correct.train_numbers(prog, ref)
    out["reference"] = ref
    return out


def peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def stop_trace() -> None:
    """Stop the profiler, saying on standard error how long writing the
    trace took."""
    t0 = time.perf_counter()
    jax.profiler.stop_trace()
    print(f"bench: trace written in {time.perf_counter() - t0:.1f} s",
          file=sys.stderr)


def trace_options():
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts
