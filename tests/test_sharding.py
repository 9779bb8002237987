"""Sharding logic + a small-mesh SPMD integration test (8 host devices via a
subprocess so the main pytest process keeps its single real CPU device)."""
import json
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.sharding.logical import ShardingContext, default_rules


class FakeMesh:
    """Just enough of a Mesh for spec_for tests (single-device env)."""

    def __init__(self, shape):
        self.shape = dict(shape)
        self.axis_names = tuple(self.shape)


def _ctx(shape=(("data", 4), ("model", 2))):
    return ShardingContext.__new__(ShardingContext), shape


def make_ctx(shape=(("data", 4), ("model", 2))):
    ctx = ShardingContext.__new__(ShardingContext)
    ctx.mesh = FakeMesh(shape)
    ctx.rules = default_rules(ctx.mesh)
    return ctx


class TestSpecFor:
    def test_param_specs(self):
        ctx = make_ctx()
        assert ctx.spec_for(("embed", "mlp"), (8, 16)) == P("data", "model")
        assert ctx.spec_for(("vocab", "embed"), (32, 8)) == P("model", "data")

    def test_divisibility_fallback_replicates(self):
        ctx = make_ctx()
        # 7 not divisible by model=2 -> replicated without allow_pad
        assert ctx.spec_for(("embed", "mlp"), (8, 7)) == P("data", None)
        # with allow_pad (activations), 7 >= 2 so padding is allowed
        assert ctx.spec_for(("embed", "mlp"), (8, 7), allow_pad=True) == P("data", "model")
        # smaller than axis: never padded
        assert ctx.spec_for((None, "mlp"), (8, 1), allow_pad=True) == P(None, None)

    def test_axis_used_once(self):
        ctx = make_ctx()
        # both 'heads' and 'mlp' map to model; second one must fall to None
        spec = ctx.spec_for(("heads", "mlp"), (4, 4))
        assert spec == P("model", None)

    def test_pod_axis_in_batch(self):
        ctx = make_ctx((("pod", 2), ("data", 2), ("model", 2)))
        assert ctx.spec_for(("batch", None), (8, 3)) == P(("pod", "data"), None)

    def test_structural_layers_never_sharded(self):
        ctx = make_ctx()
        assert ctx.spec_for(("layers", "embed", "mlp"), (12, 8, 16)) == P(None, "data", "model")


class TestOptStateSpecs:
    def test_slim_nu_masked(self):
        from repro.core.slim_adam import scale_by_slim_adam
        from repro.sharding.state_shardings import opt_state_specs

        params = {"w": jax.ShapeDtypeStruct((8, 16), jnp.float32)}
        spec_tree = {"w": P("data", "model")}
        tx = scale_by_slim_adam({"w": (1,)})
        state = jax.eval_shape(tx.init, params)
        specs = opt_state_specs(state, params, spec_tree)
        assert specs.mu["w"] == P("data", "model")      # full moment: param spec
        assert specs.nu["w"] == P("data", None)          # collapsed dim replicated
        assert specs.count == P()


SPMD_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_reduced
from repro.core import rules_as_tree, table3_rules
from repro.core.slim_adam import slim_adam
from repro.sharding.logical import ShardingContext, param_specs, use_sharding
from repro.sharding.state_shardings import opt_state_specs
from repro.train.step import make_train_step

from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
cfg = get_reduced("smollm_135m")
ctx = ShardingContext(mesh)
with use_sharding(ctx):
    params, meta = cfg.init(jax.random.PRNGKey(0))
    p_specs = param_specs(meta, params)
    p_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), p_specs, is_leaf=lambda x: isinstance(x, P))
    rules = table3_rules(meta)
    tx = slim_adam(1e-3, rules_as_tree(rules, params, meta))
    opt = tx.init(params)
    o_specs = opt_state_specs(jax.eval_shape(lambda: opt), params, p_specs)
    o_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), o_specs, is_leaf=lambda x: isinstance(x, P))
    params = jax.device_put(params, p_sh)
    opt = jax.device_put(opt, o_sh)
    batch = {
        "tokens": jnp.tile(jnp.arange(16, dtype=jnp.int32)[None], (4, 1)) % cfg.vocab_size,
        "labels": (jnp.tile(jnp.arange(16, dtype=jnp.int32)[None], (4, 1)) + 1) % cfg.vocab_size,
    }
    b_sh = {k: NamedSharding(mesh, P("data", None)) for k in batch}
    batch = jax.device_put(batch, b_sh)
    step = jax.jit(make_train_step(cfg, tx, grad_shardings=p_sh),
                   in_shardings=(p_sh, o_sh, b_sh), out_shardings=(p_sh, o_sh, None))
    new_params, new_opt, metrics = step(params, opt, batch)
    sharded_loss = float(metrics["loss"])

# single-device reference
params1, meta1 = cfg.init(jax.random.PRNGKey(0))
tx1 = slim_adam(1e-3, rules_as_tree(table3_rules(meta1), params1, meta1))
step1 = jax.jit(make_train_step(cfg, tx1))
new_params1, _, metrics1 = step1(params1, tx1.init(params1), jax.device_get(batch))
ref_loss = float(metrics1["loss"])

max_err = max(
    float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
    for a, b in zip(jax.tree.leaves(jax.device_get(new_params)), jax.tree.leaves(new_params1))
)
print(json.dumps({"sharded_loss": sharded_loss, "ref_loss": ref_loss, "max_err": max_err}))
"""


@pytest.mark.slow
def test_spmd_step_matches_single_device(tmp_path):
    """8-device SPMD SlimAdam step == single-device step (numerics + specs)."""
    script = tmp_path / "spmd_check.py"
    script.write_text(SPMD_SCRIPT)
    src = str(Path(__file__).resolve().parents[1] / "src")
    proc = subprocess.run([sys.executable, str(script)], capture_output=True, text=True,
                          env={**__import__("os").environ, "PYTHONPATH": src}, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert abs(out["sharded_loss"] - out["ref_loss"]) < 1e-3, out
    assert out["max_err"] < 5e-3, out


PIPE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax, jax.numpy as jnp
from repro.sharding.pipeline import gpipe, sequential_reference

from repro.launch.mesh import make_mesh
mesh = make_mesh((4,), ("pipe",))
P_stages, M, mb, d = 4, 8, 2, 16
key = jax.random.PRNGKey(0)
stage_params = {"w": jax.random.normal(key, (P_stages, d, d)) * 0.3,
                "b": jax.random.normal(key, (P_stages, d)) * 0.1}
x = jax.random.normal(jax.random.PRNGKey(1), (M, mb, d))

def stage_fn(p, x):
    return jnp.tanh(x @ p["w"] + p["b"])

out = jax.jit(lambda sp, x: gpipe(stage_fn, sp, x, mesh=mesh))(stage_params, x)
ref = sequential_reference(stage_fn, stage_params, x)
err = float(jnp.max(jnp.abs(out - ref)))
print(json.dumps({"err": err}))
"""


@pytest.mark.slow
def test_gpipe_matches_sequential(tmp_path):
    """4-stage GPipe pipeline over a 'pipe' mesh axis == sequential stages."""
    script = tmp_path / "pipe_check.py"
    script.write_text(PIPE_SCRIPT)
    src = str(Path(__file__).resolve().parents[1] / "src")
    proc = subprocess.run([sys.executable, str(script)], capture_output=True, text=True,
                          env={**__import__("os").environ, "PYTHONPATH": src}, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["err"] < 1e-5, out
