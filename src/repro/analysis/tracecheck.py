"""tracecheck — the guarded train step's no-recompile contract, statically.

PR 6's fault-tolerant step takes its guard policy as *traced* operands
(``controls = {'lr_scale': f32, 'grad_scale': f32}``) precisely so the
host-side Guard can back lr off after a spike without triggering a
recompile. That promise has three statically checkable halves:

  * **trace-stable** — ``make_jaxpr`` of the guarded 4-arg step over two
    *different* concrete control values yields the identical jaxpr: no
    control value leaks into the trace as a constant. (A step that calls
    ``float(controls[...])`` doesn't even trace — also a finding.)
  * **controls-used** — the control leaves are live invars of the jaxpr: a
    step that accepts the dict but ignores it (reading a closed-over Python
    float instead) would pass the stability check vacuously while baking
    policy into the executable.
  * **aval-stable** — the controls dict the Guard/trainer protocol emits has
    identical avals (shape/dtype/weak_type) before and after the guard
    reacts to a spike — jit's cache key is the aval, so this is the actual
    "compiles once" condition across guard state changes.

Runs on a reduced gpt_small (3 layers) with abstract params/batch — tracing
only, nothing executes.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.extend.core import Var

from repro.configs import get_reduced
from repro.core import rules_as_tree, table3_rules
from repro.core.slim_adam import slim_adam
from repro.train.guard import Guard, GuardConfig
from repro.train.step import make_train_step

from .report import PassResult


def build_guarded_step() -> Tuple[Callable, tuple]:
    """(guarded 4-arg step, (params_abs, opt_abs, batch_abs)) on the reduced
    gpt_small — everything abstract."""
    cfg = get_reduced("gpt_small")
    params_abs, meta = cfg.abstract()
    dims_tree = rules_as_tree(table3_rules(meta), params_abs, meta)
    tx = slim_adam(3e-4, dims_tree, emit_health=True)
    opt_abs = jax.eval_shape(tx.init, params_abs)
    batch_abs = {"tokens": jax.ShapeDtypeStruct((2, 16), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((2, 16), jnp.int32)}
    step = make_train_step(cfg, tx, guard=True)
    return step, (params_abs, opt_abs, batch_abs)


def trainer_controls(guard: Guard) -> Dict[str, jnp.ndarray]:
    """The controls dict exactly as the trainer builds it from guard state
    (see ``repro.train.trainer``) — the protocol whose aval stability the
    no-recompile promise rides on."""
    return {"lr_scale": jnp.asarray(guard.lr_scale, jnp.float32),
            "grad_scale": jnp.asarray(1.0, jnp.float32)}


def controls_like(lr: float, gs: float) -> Dict[str, jnp.ndarray]:
    return {"lr_scale": jnp.asarray(lr, jnp.float32),
            "grad_scale": jnp.asarray(gs, jnp.float32)}


def check_step_trace(step: Callable, abstract_args: tuple,
                     result: PassResult, where: str = "guarded_train_step",
                     controls_a: Optional[dict] = None,
                     controls_b: Optional[dict] = None) -> None:
    """trace-stable + controls-used on one 4-arg step (reusable against
    seeded bad steps in the regression tests)."""
    ca = controls_a if controls_a is not None else controls_like(1.0, 1.0)
    cb = controls_b if controls_b is not None else controls_like(0.25, 0.5)

    result.checks += 1
    try:
        # Fresh wrapper per trace: make_jaxpr rides jit's trace cache (keyed
        # on function identity + avals), which would silently reuse trace A
        # for trace B and mask any trace-time impurity.
        jx_a = jax.make_jaxpr(lambda *a: step(*a))(*abstract_args, ca)
        jx_b = jax.make_jaxpr(lambda *a: step(*a))(*abstract_args, cb)
    except Exception as e:  # noqa: BLE001 - a non-tracing step is the finding
        result.add("trace-stable", where,
                   f"step does not trace over abstract controls "
                   f"({type(e).__name__}: {e}) — it concretizes a traced "
                   f"control and would recompile (or crash) per policy change")
        return
    if str(jx_a) != str(jx_b):
        result.add("trace-stable", where,
                   "jaxprs differ across control values — a control leaked "
                   "into the trace as a constant, so every guard backoff "
                   "recompiles the step")

    # Control leaves are the trailing invars (args flatten in order); each
    # must be read by at least one equation.
    result.checks += 1
    n_controls = len(jax.tree_util.tree_leaves(ca))
    control_vars = jx_a.jaxpr.invars[-n_controls:]
    used = set()
    for eqn in jx_a.jaxpr.eqns:
        for v in eqn.invars:
            if isinstance(v, Var):
                used.add(id(v))
    outs = {id(v) for v in jx_a.jaxpr.outvars if isinstance(v, Var)}
    dead = [v for v in control_vars if id(v) not in used and id(v) not in outs]
    if dead:
        result.add("controls-used", where,
                   f"{len(dead)} control operand(s) are dead in the jaxpr — "
                   f"the step ignores the traced controls (policy must be "
                   f"baked in somewhere else, defeating the protocol)")


def check_guard_aval_stability(result: PassResult,
                               where: str = "Guard/trainer controls") -> None:
    """aval-stable across an actual guard state transition."""
    result.checks += 1
    guard = Guard(GuardConfig(min_history=2))
    before = trainer_controls(guard)
    for loss in (1.0, 1.01, 0.99, 1.0, 50.0):  # the last one is a spike
        guard.observe(loss)
    after = trainer_controls(guard)
    if guard.lr_scale >= 1.0:
        result.add("aval-stable", where,
                   "guard did not react to a 50x loss spike — the transition "
                   "this check exercises no longer exists; update tracecheck")
        return
    avals_before = [str(jax.typeof(x)) for x in jax.tree_util.tree_leaves(before)]
    avals_after = [str(jax.typeof(x)) for x in jax.tree_util.tree_leaves(after)]
    if avals_before != avals_after:
        result.add("aval-stable", where,
                   f"controls avals changed across a guard backoff "
                   f"({avals_before} -> {avals_after}) — jit would recompile "
                   f"on the first bad step")


def run() -> PassResult:
    t0 = time.monotonic()
    result = PassResult("tracecheck")
    step, abstract_args = build_guarded_step()
    check_step_trace(step, abstract_args, result)
    check_guard_aval_stability(result)
    result.seconds = time.monotonic() - t0
    return result
