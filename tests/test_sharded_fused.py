"""Shard-aware fused backend: regime planning (in-process, device-free) and
shard_map-vs-single-device parity on an 8-host-device mesh (subprocess, the
pattern from tests/test_sharding.py)."""
import json
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.sharding.shardspec import (
    SpecMesh,
    dim_shards,
    even_spec,
    local_shape,
    masked_spec,
    mesh_is_trivial,
    normalize_spec_leaves,
    owning_axes,
    plan_sharded_leaf,
    regime_counts,
)

MESH = SpecMesh({"data": 4, "model": 2})


class TestShardGeometry:
    def test_dim_shards_and_local_shape(self):
        assert dim_shards((8, 16), P("data", "model"), MESH) == (4, 2)
        assert local_shape((8, 16), P("data", "model"), MESH) == (2, 8)

    def test_non_dividing_dim_replicates(self):
        # 6 % 4 != 0 -> defensive replication, and even_spec drops the entry
        assert dim_shards((6, 16), P("data", "model"), MESH) == (1, 2)
        assert even_spec((6, 16), P("data", "model"), MESH) == P(None, "model")

    def test_short_spec_pads(self):
        assert dim_shards((8, 16, 4), P("data"), MESH) == (4, 1, 1)

    def test_masked_spec_drops_reduced_entries(self):
        # fan_in-compressed moment of a TP-sharded matrix loses its TP axis
        assert masked_spec((8, 16), P("data", "model"), MESH, (1,)) == P("data", None)

    def test_owning_axes(self):
        assert owning_axes((8, 16), P("data", "model"), MESH, (1,)) == ("model",)
        assert owning_axes((8, 16), P("data", "model"), MESH, (0,)) == ("data",)
        assert owning_axes((8, 16), P(None, "model"), MESH, (0,)) == ()

    def test_trivial_mesh(self):
        assert mesh_is_trivial(SpecMesh({"data": 1, "model": 1}))
        assert not mesh_is_trivial(MESH)


class TestRegimePlans:
    def test_local_when_reduced_unsharded(self):
        pl = plan_sharded_leaf((8, 16), jnp.float32, (1,), P("data", None), MESH, n_bufs=5)
        assert pl.regime == "local" and pl.psum_axes == ()

    def test_psum_when_reduced_sharded(self):
        pl = plan_sharded_leaf((8, 16), jnp.float32, (1,), P("data", "model"), MESH, n_bufs=5)
        assert pl.regime == "psum"
        assert pl.psum_axes == ("model",) and pl.red_total == 16
        assert pl.red_spec == P("data", None)

    def test_jnp_for_interleaved_k(self):
        # reduced {0, 2} with kept {1, 3}: no contiguous reduced block
        pl = plan_sharded_leaf((4, 6, 8, 10), jnp.float32, (0, 2), P(), MESH, n_bufs=5)
        assert pl.regime == "jnp"

    def test_dense_always_local(self):
        pl = plan_sharded_leaf((8, 16), jnp.float32, (), P("data", "model"), MESH, n_bufs=5)
        assert pl.regime == "local"

    def test_regime_counts(self):
        plans = [
            plan_sharded_leaf((8, 16), jnp.float32, (1,), P("data", None), MESH, n_bufs=5),
            plan_sharded_leaf((8, 16), jnp.float32, (1,), P(None, "model"), MESH, n_bufs=5),
            plan_sharded_leaf((4, 6, 8, 10), jnp.float32, (0, 2), P(), MESH, n_bufs=5),
        ]
        assert regime_counts(plans) == {"local": 1, "psum": 1, "psum_jnp": 0,
                                        "degraded": 0,
                                        "jnp": 1}

    def test_normalize_spec_leaves_validates_structure(self):
        treedef = jax.tree_util.tree_structure({"a": 0, "b": 0, "c": 0})
        with pytest.raises(ValueError, match="does not mirror"):
            normalize_spec_leaves({"a": P(), "b": P()}, treedef, "test")
        # same leaf count but different structure must also be rejected
        with pytest.raises(ValueError, match="does not mirror"):
            normalize_spec_leaves({"a": P(), "b": P(), "z": P()}, treedef, "test")

    def test_normalize_spec_leaves_accepts_none_entries(self):
        # None = replicated, the standard pjit idiom
        treedef = jax.tree_util.tree_structure({"a": 0, "b": 0})
        leaves = normalize_spec_leaves({"a": P("data"), "b": None}, treedef, "test")
        assert leaves == [P("data"), None]
        # pre-flattened leaf-aligned list passes through
        assert normalize_spec_leaves([P("data"), None], treedef, "t") == [P("data"), None]

    def test_half_specified_pair_warns_and_runs_unsharded(self):
        from repro.sharding.shardspec import sharded_pair

        with pytest.warns(UserWarning, match="UNSHARDED"):
            mesh, specs = sharded_pair(MESH, None, "test")
        assert mesh is None and specs is None
        assert sharded_pair(None, None, "test") == (None, None)


class TestRebaseCenteredStats:
    def test_matches_common_shift_recompute(self):
        """Per-shard sums with local shifts, rebased to a common shift, must
        equal the sums computed directly under that shift."""
        from repro.kernels.ref import rebase_centered_stats

        rng = np.random.default_rng(0)
        line = 1.0 + 1e-5 * rng.standard_normal(32).astype(np.float64)
        shift = np.float64(line.mean())
        for lo, hi in ((0, 16), (16, 32)):
            seg = line[lo:hi]
            first = seg[0]
            s1c = np.sum(seg - first)
            s2c = np.sum((seg - first) ** 2)
            s1c_r, s2c_r = rebase_centered_stats(s1c, s2c, first, shift, len(seg))
            np.testing.assert_allclose(s1c_r, np.sum(seg - shift), rtol=1e-12)
            np.testing.assert_allclose(s2c_r, np.sum((seg - shift) ** 2), rtol=1e-12)


class TestOptStateSpecsValidation:
    def test_mismatched_state_raises_clear_error(self):
        from repro.core.slim_adam import scale_by_slim_adam
        from repro.sharding.state_shardings import opt_state_specs

        params = {"w": jax.ShapeDtypeStruct((8, 16), jnp.float32)}
        state = jax.eval_shape(scale_by_slim_adam({"w": (1,)}).init, params)
        other = {"w": jax.ShapeDtypeStruct((8, 16), jnp.float32),
                 "b": jax.ShapeDtypeStruct((16,), jnp.float32)}
        with pytest.raises(ValueError, match="does not mirror the parameter tree"):
            opt_state_specs(state, other, {"w": P(), "b": P()})

    def test_mismatched_spec_tree_raises(self):
        from repro.optim.adam import scale_by_adam
        from repro.sharding.state_shardings import opt_state_specs

        params = {"w": jax.ShapeDtypeStruct((8, 16), jnp.float32)}
        state = jax.eval_shape(scale_by_adam().init, params)
        with pytest.raises(ValueError, match="param_spec_tree"):
            opt_state_specs(state, params, {"w": P(), "extra": P()})


PARITY_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.slim_adam import scale_by_slim_adam
from repro.core.snr import snr_along_dims
from repro.optim.adam import scale_by_adam
from repro.optim import fused as F
from repro.sharding.shardspec import regime_counts

from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
key = jax.random.PRNGKey(0)
params = {
    "fanin": jax.random.normal(key, (32, 16)),        # K=(1,), kept dim sharded -> local kernel
    "psum":  jax.random.normal(key, (16, 32)),        # K=(1,), reduced dim sharded -> psum
    "inter": jax.random.normal(key, (4, 6, 8, 10)),   # K=(0,2) interleaved -> jnp fallback
    "dense": jax.random.normal(key, (24, 16)),        # K=() dense kernel
    "vec":   jnp.linspace(-1.0, 1.0, 64),             # small leaf (bucket path)
}
dims  = {"fanin": (1,), "psum": (1,), "inter": (0, 2), "dense": (), "vec": ()}
specs = {"fanin": P("data", None), "psum": P(None, "model"), "inter": P(),
         "dense": P("data", "model"), "vec": P("data")}
grads = jax.tree.map(
    lambda p: 0.1 * jax.random.normal(jax.random.PRNGKey(p.size % 13), p.shape), params)

out = {}

# regime report
gl, td = jax.tree_util.tree_flatten(params)
plans = F.sharded_tree_plans(gl, [tuple(d) for d in td.flatten_up_to(dims)],
                             td.flatten_up_to(specs), mesh)
out["regimes"] = regime_counts(plans)

def leaf_errs(u1, u2):
    return {k: {"exact": bool(np.array_equal(np.asarray(u1[k]), np.asarray(u2[k]))),
                "err": float(np.max(np.abs(np.asarray(u1[k]) - np.asarray(u2[k]))))}
            for k in u1}

# SlimAdam: single-device fused vs sharded fused, 2 steps
tx1 = scale_by_slim_adam(dims, backend="fused")
tx2 = scale_by_slim_adam(dims, backend="fused", mesh=mesh, param_specs=specs)
s1, s2 = tx1.init(params), tx2.init(params)
for _ in range(2):
    u1, s1 = jax.jit(tx1.update)(grads, s1)
    u2, s2 = jax.jit(tx2.update)(grads, s2)
out["slim_u"] = leaf_errs(u1, u2)
out["slim_nu"] = leaf_errs(s1.nu, s2.nu)

# dense Adam tree: elementwise -> bit-exact under sharding
ta1 = scale_by_adam(backend="fused")
ta2 = scale_by_adam(backend="fused", mesh=mesh, param_specs=specs)
a1, a2 = ta1.init(params), ta2.init(params)
ua1, a1 = jax.jit(ta1.update)(grads, a1)
ua2, a2 = jax.jit(ta2.update)(grads, a2)
out["adam_u"] = leaf_errs(ua1, ua2)

# SNR: sharded vs single device, both backends, incl. a psum leaf in the
# near-constant high-SNR regime the centered kernels exist for
snr = {}
v_hi = (1.0 + 1e-4 * jax.random.normal(key, (16, 32))) ** 2   # SNR >> 1
cases = {"fanin": (params["fanin"] ** 2, (1,)), "psum": (params["psum"] ** 2, (1,)),
         "psum_hi": (v_hi, (1,)), "inter": (params["inter"] ** 2, (0, 2))}
for name, (v, d) in cases.items():
    spec = specs.get(name, specs["psum"] if name == "psum_hi" else P())
    sharded_v = jax.device_put(v, NamedSharding(mesh, spec))
    for be in ("jnp", "fused"):
        a = float(snr_along_dims(v, d, backend=be))
        b = float(snr_along_dims(sharded_v, d, backend=be, mesh=mesh, spec=spec))
        snr[f"{name}_{be}"] = {"single": a, "sharded": b,
                               "rel": abs(a - b) / max(abs(a), 1e-30)}
out["snr"] = snr
print(json.dumps(out))
"""


@pytest.mark.slow
@pytest.mark.multidevice
def test_sharded_fused_parity(tmp_path):
    """shard_map fused SlimAdam/Adam + SNR == single-device fused path:
    bit-exact for local-regime leaves, <= 1e-6 for psum and jnp-fallback
    leaves (fp32 reassociation across the shard boundary)."""
    script = tmp_path / "sharded_parity.py"
    script.write_text(PARITY_SCRIPT)
    src = str(Path(__file__).resolve().parents[1] / "src")
    proc = subprocess.run([sys.executable, str(script)], capture_output=True, text=True,
                          env={**__import__("os").environ, "PYTHONPATH": src}, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])

    # fanin + dense + vec run the unchanged kernels on local shards; psum and
    # interleaved-K leaves take the cross-shard / per-shard jnp paths.
    assert out["regimes"] == {"local": 3, "psum": 1, "psum_jnp": 0,
                              "jnp": 1, "degraded": 0}, out["regimes"]

    for group in ("slim_u", "slim_nu", "adam_u"):
        for leaf, r in out[group].items():
            tol = 0.0 if group == "adam_u" or leaf in ("fanin", "dense", "vec") else 1e-6
            assert r["err"] <= tol, (group, leaf, r)

    for case, r in out["snr"].items():
        assert r["rel"] <= 1e-6, (case, r)
