"""Trainer: optimizer registry, SNR measurement hooks, checkpoint/restart.

This is the orchestration layer the examples and benchmarks drive. It runs
unsharded on one CPU device (paper-scale experiments) and under a mesh via
the same code path (the launcher supplies shardings).
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ..checkpoint import store
from ..core import (
    SNRTracker,
    derive_rules,
    measure_tree_snr,
    rules_as_tree,
    table3_rules,
)
from ..core.baselines import (
    adafactor,
    adalayer_ln_tl_rules,
    adalayer_rules,
    adam_mini_v1_rules,
    adam_mini_v2_rules,
    lion,
    sm3,
)
from ..core.slim_adam import slim_adam
from ..data.pipeline import ZipfLM
from ..optim.adam import adamw, sgdm
from .guard import (
    ROLLBACK,
    Guard,
    GuardConfig,
    find_slim_snr,
    strip_slim_snr as _strip_slim_snr,
)
from .step import make_eval_step, make_train_step

OPTIMIZERS = ("adam", "slim", "slim_snr", "adalayer", "adalayer_ln_tl",
              "adam_mini_v1", "adam_mini_v2", "adafactor", "adafactor_v2",
              "sm3", "lion", "sgdm")


_SLIM_FAMILY = ("slim", "slim_snr", "adalayer", "adalayer_ln_tl",
                "adam_mini_v1", "adam_mini_v2")


def slim_rule_dims(name: str, params, meta, rules: Optional[Dict[str, Any]] = None):
    """Per-leaf reduction-dims pytree the slim-family optimizer ``name``
    compresses with (None for optimizers without compressed moments). One
    derivation shared by :func:`make_optimizer` and the trainer's
    from-update SNR consumer, so the measurement pairs ridden stats with
    exactly the K the update reduced."""
    if name not in _SLIM_FAMILY:
        return None
    if name == "slim":
        r = table3_rules(meta)
    elif name == "slim_snr":
        if rules is None:
            raise ValueError("slim_snr requires derived rules")
        r = rules
    elif name == "adalayer":
        r = adalayer_rules(meta)
    elif name == "adalayer_ln_tl":
        r = adalayer_ln_tl_rules(meta)
    elif name == "adam_mini_v1":
        r = adam_mini_v1_rules(meta)
    else:
        r = adam_mini_v2_rules(meta)
    return rules_as_tree(r, params, meta)


def make_optimizer(name: str, lr, params, meta, *, weight_decay: float = 0.1,
                   b1: float = 0.9, b2: float = 0.95, grad_clip: float = 1.0,
                   rules: Optional[Dict[str, Any]] = None, backend: str = "jnp",
                   mesh=None, param_specs=None, emit_snr: bool = False,
                   emit_health: bool = False):
    """Build any of the paper's optimizers. ``rules`` overrides the rule set
    for 'slim_snr' (derived from a measured SNR pass). ``backend`` selects
    the execution path for the Adam/SlimAdam family ('jnp' | 'fused' |
    'auto', see repro.optim.base.BACKENDS); other optimizers ignore it.
    ``mesh``/``param_specs`` make the fused backend shard-aware (the tree
    update runs under shard_map on the local shards); only the Adam/SlimAdam
    family consumes them. ``emit_snr`` (slim family only) builds the
    measure-step variant whose update publishes from-update SNR scalars on
    the optimizer state (see ``repro.core.slim_adam.scale_by_slim_adam``).
    ``emit_health`` (Adam/slim family) publishes the in-pass StepHealth
    anomaly stats the guarded train step consumes (``repro.train.guard``)."""
    if emit_snr and name not in _SLIM_FAMILY:
        raise ValueError(f"emit_snr is only supported by the slim family "
                         f"{_SLIM_FAMILY}, not {name!r}")
    if emit_health and name not in ("adam",) + _SLIM_FAMILY:
        raise ValueError(f"emit_health is only supported by the Adam/slim "
                         f"family {('adam',) + _SLIM_FAMILY}, not {name!r}")
    if name == "adam":
        return adamw(lr, b1=b1, b2=b2, weight_decay=weight_decay, grad_clip=grad_clip,
                     backend=backend, mesh=mesh, param_specs=param_specs,
                     emit_health=emit_health)
    if name in _SLIM_FAMILY:
        dims = slim_rule_dims(name, params, meta, rules)
        return slim_adam(lr, dims, b1=b1, b2=b2, weight_decay=weight_decay,
                         grad_clip=grad_clip, backend=backend, mesh=mesh,
                         param_specs=param_specs, emit_snr=emit_snr,
                         emit_health=emit_health)
    if name == "adafactor":
        return adafactor(lr, weight_decay=weight_decay, grad_clip=grad_clip)
    if name == "adafactor_v2":
        return adafactor(lr, momentum=0.9, weight_decay=weight_decay, grad_clip=grad_clip)
    if name == "sm3":
        return sm3(lr, beta=0.95, weight_decay=weight_decay, grad_clip=grad_clip)
    if name == "lion":
        return lion(lr, weight_decay=weight_decay, grad_clip=grad_clip)
    if name == "sgdm":
        return sgdm(lr, weight_decay=weight_decay, grad_clip=grad_clip)
    raise ValueError(f"unknown optimizer {name!r}; choose from {OPTIMIZERS}")


def find_adam_nu(opt_state) -> Optional[Any]:
    """Extract the second-moment pytree from a (possibly chained) optimizer
    state — the tensor the paper's SNR analysis runs on."""
    from ..optim.adam import ScaleByAdamState
    from ..core.slim_adam import ScaleBySlimAdamState
    from ..optim.base import ChainState, MultiStepsState

    def walk(node):
        if isinstance(node, (ScaleByAdamState, ScaleBySlimAdamState)):
            return node.nu
        if isinstance(node, ChainState):
            for s in node.inner_states:
                out = walk(s)
                if out is not None:
                    return out
        if isinstance(node, MultiStepsState):
            return walk(node.inner_state)
        return None

    return walk(opt_state)


@dataclasses.dataclass
class TrainerConfig:
    total_steps: int = 1000
    log_every: int = 50
    ckpt_every: int = 0              # 0 = disabled
    ckpt_dir: Optional[str] = None
    ckpt_keep: int = 3
    measure_snr: bool = False
    snr_early_every: int = 100
    snr_late_every: int = 1000
    # Ride the SNR measurement on the update pass: measure steps run a
    # second jitted train step whose optimizer update also emits per-leaf
    # from-update SNR scalars (slim family only; O(kept) extra traffic on
    # the fused backend), and measure_tree_snr consumes them instead of
    # re-reading nu for the candidate K the optimizer already reduces.
    snr_from_update: bool = False
    seed: int = 0
    # Execution backend for the Adam/SlimAdam update and the SNR measurement
    # pass: 'jnp' | 'fused' | 'auto' (fused kernels on TPU, jnp elsewhere).
    # An explicit optimizer_kw['backend'] passed to Trainer wins.
    backend: str = "jnp"
    # Fault-tolerance policy: a GuardConfig turns on the guarded train step
    # (in-pass anomaly health + skip/backoff/rollback, see repro.train.guard);
    # None keeps the plain step with an unchanged jit signature.
    guard: Optional[GuardConfig] = None


class Trainer:
    def __init__(self, model_cfg, optimizer_name: str, lr, data: ZipfLM,
                 tc: TrainerConfig = TrainerConfig(), *, optimizer_kw: Optional[dict] = None,
                 rules: Optional[dict] = None, grad_accum: int = 1, faults=None):
        self.model_cfg = model_cfg
        self.tc = tc
        self.data = data
        # Host-side anomaly policy + (test/drill-only) fault injection plan.
        self.guard = Guard(tc.guard) if tc.guard is not None else None
        self.faults = faults
        self.ckpt_failures = 0
        key = jax.random.PRNGKey(tc.seed)
        self.params, self.meta = model_cfg.init(key)
        okw = dict(optimizer_kw or {})
        okw.setdefault("backend", tc.backend)
        self.backend = okw["backend"]  # one backend for update + SNR pass
        # Under an active ShardingContext the optimizer and the SNR pass get
        # the mesh + param specs, so the fused backend and the SNR
        # measurement run shard-aware (shard_map) instead of letting GSPMD
        # gather leaves around the Pallas optimization barriers.
        from ..sharding.logical import current as current_sharding, param_specs

        ctx = current_sharding()
        self.mesh = ctx.mesh if ctx is not None else None
        self.param_specs = param_specs(self.meta, self.params) if ctx is not None else None
        okw.setdefault("mesh", self.mesh)
        okw.setdefault("param_specs", self.param_specs)
        guarded = self.guard is not None
        # In-pass kernel health only exists on the Adam/slim family; other
        # optimizers still run guarded via the step's grad-norm fallback.
        emit_health = guarded and optimizer_name in ("adam",) + _SLIM_FAMILY
        self.tx = make_optimizer(optimizer_name, lr, self.params, self.meta,
                                 rules=rules, emit_health=emit_health, **okw)
        self.opt_state = self.tx.init(self.params)
        self.step = 0
        self.snr = SNRTracker()
        self.metrics_log: list = []
        self._train_step = jax.jit(make_train_step(
            model_cfg, self.tx, grad_accum=grad_accum, guard=guarded))
        # Measure-step variant: same optimizer built with emit_snr=True, so
        # on SNR cadence steps the update pass itself measures SNR_K along
        # each compressed leaf's own K (state.snr) and maybe_measure_snr
        # skips the extra nu read for that candidate.
        self._train_step_snr = None
        self._update_dims = None
        if tc.measure_snr and tc.snr_from_update and optimizer_name in _SLIM_FAMILY:
            self._update_dims = slim_rule_dims(optimizer_name, self.params,
                                               self.meta, rules)
            tx_snr = make_optimizer(optimizer_name, lr, self.params, self.meta,
                                    rules=rules, emit_snr=True,
                                    emit_health=emit_health, **okw)
            self._train_step_snr = jax.jit(make_train_step(
                model_cfg, tx_snr, grad_accum=grad_accum, guard=guarded))
        self._restored = False
        if tc.ckpt_dir and store.latest_step(tc.ckpt_dir) is not None:
            self.restore()

    # -- fault tolerance ---------------------------------------------------

    def restore(self):
        state = {"params": self.params, "opt": self.opt_state}
        state, extra = store.restore(self.tc.ckpt_dir, state)
        self.params, self.opt_state = state["params"], state["opt"]
        self.step = int(extra.get("step", 0))
        self._restored = True

    def checkpoint(self):
        if not self.tc.ckpt_dir:
            return
        try:
            store.save(self.tc.ckpt_dir, self.step,
                       {"params": self.params, "opt": self.opt_state},
                       extra={"step": self.step}, keep=self.tc.ckpt_keep)
        except OSError as e:
            # A failed save must not kill the run — the atomic tmp-dir
            # protocol guarantees no torn step_* dir was left behind, so we
            # log, count, and train on to the next checkpoint cadence.
            self.ckpt_failures += 1
            warnings.warn(f"checkpoint save failed at step {self.step} "
                          f"({e}); continuing without it")

    def _rollback(self):
        """Guard escalation: restore the last *valid* checkpoint and re-seed
        the data pipeline so the restored trajectory doesn't replay the
        exact batch sequence that diverged."""
        self.guard.note_rollback()
        restored = False
        if self.tc.ckpt_dir and store.latest_step(self.tc.ckpt_dir) is not None:
            try:
                self.restore()
                restored = True
            except FileNotFoundError:
                pass
        if not restored:
            warnings.warn("guard requested rollback but no valid checkpoint "
                          "is available; continuing with backed-off lr")
        bump = self.guard.counters["rollbacks"] * self.tc.guard.reseed_bump
        self.data = ZipfLM(dataclasses.replace(
            self.data.cfg, seed=self.data.cfg.seed + bump))

    # -- SNR hook ------------------------------------------------------------

    def maybe_measure_snr(self):
        if not self.tc.measure_snr:
            return
        if not SNRTracker.should_measure(self.step, self.tc.snr_early_every,
                                         self.tc.snr_late_every):
            return
        nu = find_adam_nu(self.opt_state)
        if nu is None:
            return
        with jax.profiler.TraceAnnotation("repro.train.snr"):
            from_upd = (find_slim_snr(self.opt_state)
                        if self._train_step_snr is not None else None)
            snapshot = measure_tree_snr(
                nu, self.meta, backend=self.backend,
                mesh=self.mesh, param_specs=self.param_specs,
                from_update=from_upd,
                update_dims=self._update_dims if from_upd is not None else None)
            self.snr.update(snapshot, self.step)
        if from_upd is not None:
            # Strip the consumed snapshot so checkpoints and the normal
            # step's jit signature keep the snr-less state layout.
            self.opt_state = _strip_slim_snr(self.opt_state)

    # -- main loop -----------------------------------------------------------

    def compile_step(self):
        """Ahead-of-time compile of the train step :meth:`run` takes next,
        for the shapes of the next batch. The result answers
        ``memory_analysis()`` (does this batch fit the device?) and
        ``as_text()`` (which kernels the step runs) before any step runs;
        :meth:`run` then reuses the compiled program through JAX's caches."""
        batch = {k: jnp.asarray(v) for k, v in self.data.batch(self.step).items()}
        args = (self.params, self.opt_state, batch)
        if self.guard is not None:
            args += ({"lr_scale": jnp.float32(1.0), "grad_scale": jnp.float32(1.0)},)
        return self._train_step.lower(*args).compile()

    def run(self, steps: Optional[int] = None) -> Dict[str, float]:
        """Train up to step ``steps``; returns the last logged metrics. In a
        profile each step's phases are ``repro.train.*`` spans: ``batch``
        (the host batch and its copy to the device), ``step`` (the
        dispatch), ``readback`` (metrics read on the host), ``snr`` and
        ``checkpoint`` where they run."""
        steps = steps if steps is not None else self.tc.total_steps
        t0 = time.time()
        if self.step >= steps:
            # A restored checkpoint can already be at/past the target step.
            # Returning {} here crashed callers that index last["loss"]; run
            # a forward-only eval instead so the no-op still yields the full
            # metrics dict (grad_norm 0: no update happened).
            batch = {k: jnp.asarray(v) for k, v in self.data.batch(self.step).items()}
            metrics = jax.jit(make_eval_step(self.model_cfg))(self.params, batch)
            last = {k: float(v) for k, v in metrics.items()}
            last.update(grad_norm=0.0, step=self.step,
                        wall_s=round(time.time() - t0, 2))
            self.metrics_log.append(last)
            return last
        last = {}
        while self.step < steps:
            with jax.profiler.TraceAnnotation("repro.train.batch"):
                batch = self.data.batch(self.step)
                batch = {k: jnp.asarray(v) for k, v in batch.items()}
            # On SNR-cadence steps, run the emit_snr step variant so the
            # measurement rides the update pass (state.snr) instead of
            # paying a separate nu read in maybe_measure_snr.
            step_fn = self._train_step
            if self._train_step_snr is not None and SNRTracker.should_measure(
                    self.step + 1, self.tc.snr_early_every, self.tc.snr_late_every):
                step_fn = self._train_step_snr
            if self.guard is not None:
                # Controls are traced jnp scalars: host policy (lr backoff)
                # and fault injection change them without a recompile.
                g_scale = (self.faults.grad_scale(self.step)
                           if self.faults is not None else 1.0)
                controls = {"lr_scale": jnp.asarray(self.guard.lr_scale, jnp.float32),
                            "grad_scale": jnp.asarray(g_scale, jnp.float32)}
                with jax.profiler.TraceAnnotation("repro.train.step"):
                    self.params, self.opt_state, metrics = step_fn(
                        self.params, self.opt_state, batch, controls)
                self.step += 1
                with jax.profiler.TraceAnnotation("repro.train.readback"):
                    loss = float(metrics["loss"])
                    skipped = bool(metrics["step_skipped"] > 0)
                    nonfinite = float(metrics["nonfinite_count"])
                if self.faults is not None:
                    loss = self.faults.corrupt_loss(self.step - 1, loss)
                action = self.guard.observe(loss, skipped=skipped,
                                            nonfinite=nonfinite)
                if skipped:
                    # A measure step that got skipped published SNR from the
                    # discarded update — drop it without consuming.
                    self.opt_state = _strip_slim_snr(self.opt_state)
                else:
                    self.maybe_measure_snr()
                if action == ROLLBACK:
                    self._rollback()
                    continue
            else:
                with jax.profiler.TraceAnnotation("repro.train.step"):
                    self.params, self.opt_state, metrics = step_fn(
                        self.params, self.opt_state, batch)
                self.step += 1
                self.maybe_measure_snr()
            if self.step % self.tc.log_every == 0 or self.step == steps:
                with jax.profiler.TraceAnnotation("repro.train.readback"):
                    last = {k: float(v) for k, v in metrics.items()}
                last.update(step=self.step, wall_s=round(time.time() - t0, 2))
                if self.guard is not None:
                    last.update(self.guard.stats(),
                                ckpt_failures=float(self.ckpt_failures))
                self.metrics_log.append(last)
            if self.tc.ckpt_every and self.step % self.tc.ckpt_every == 0:
                with jax.profiler.TraceAnnotation("repro.train.checkpoint"):
                    self.checkpoint()
        return last

    def derive_slim_rules(self, cutoff: float = 1.0):
        """Paper §5: turn the tracked SNR averages into SlimAdam rules."""
        return derive_rules(self.snr.averaged(), self.meta, cutoff=cutoff)
