"""Pallas-resident psum regime: partial-stats/finalize kernel parity, owner-
shard moment plans, from-update SNR, and the psum-path dtype boundary.

In-process tests cover the kernels against the jnp oracles (bf16, padded
strips, batched B > 1, both orientations) and the device-free owner-plan
geometry; an 8-host-device subprocess (the pattern from
tests/test_sharded_fused.py) covers shard_map parity of the owner-write/
broadcast scheme vs the single-device fused path, the bf16 psum-state
regression, and the sharded from-update SNR."""
import json
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.kernels.slim_update import (
    slim_finalize,
    slim_finalize_batched,
    slim_partial_stats,
    slim_partial_stats_batched,
    slim_precond_batched,
)
from repro.kernels.snr_stats import snr_update_stats_finalize
from repro.optim import fused as F
from repro.sharding.shardspec import (
    SpecMesh,
    owner_factor,
    owner_placement,
    plan_sharded_leaf,
    regime_counts,
)

KW = dict(b1=0.9, b2=0.95, eps=1e-8, count=3)


def _leaf(shape, axis, seed=0, dtype=jnp.float32):
    k = jax.random.PRNGKey(seed)
    red_ax = 2 if axis == 1 else 1
    g = (jax.random.normal(k, shape) * 0.3).astype(dtype)
    m = jax.random.normal(jax.random.PRNGKey(seed + 1), shape).astype(dtype)
    v_shape = tuple(1 if i == red_ax else s for i, s in enumerate(shape))
    v = jnp.abs(jax.random.normal(jax.random.PRNGKey(seed + 2), v_shape))
    return g, m, v, red_ax


class TestPartialFinalizeKernels:
    # Padded strips (kept % tile != 0), batched B > 1, both orientations.
    CASES = [(1, (2, 5, 33)), (0, (3, 17, 7)), (1, (1, 300, 64)), (0, (4, 64, 129))]

    @pytest.mark.parametrize("axis,shape", CASES)
    def test_partial_stats_matches_reference(self, axis, shape):
        g, m, _, red_ax = _leaf(shape, axis)
        m_new, part = slim_partial_stats_batched(g, m, axis=axis, b1=KW["b1"])
        np.testing.assert_allclose(m_new, KW["b1"] * m + (1 - KW["b1"]) * g,
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(part, jnp.sum(g * g, axis=red_ax, keepdims=True),
                                   rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("axis,shape", CASES)
    def test_partial_stats_snr_lines(self, axis, shape):
        g, m, _, red_ax = _leaf(shape, axis)
        _, _, s1c, s2c, first = slim_partial_stats_batched(
            g, m, axis=axis, b1=KW["b1"], with_snr=True)
        gg = g * g
        f_ref = jax.lax.slice_in_dim(gg, 0, 1, axis=red_ax)
        d = gg - f_ref
        np.testing.assert_allclose(first, f_ref, rtol=1e-6)
        np.testing.assert_allclose(s1c, jnp.sum(d, axis=red_ax, keepdims=True),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(s2c, jnp.sum(d * d, axis=red_ax, keepdims=True),
                                   rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("axis,shape", CASES)
    def test_finalize_matches_reference(self, axis, shape):
        g, m, v, red_ax = _leaf(shape, axis)
        n = shape[red_ax]
        m_new, part = slim_partial_stats_batched(g, m, axis=axis, b1=KW["b1"])
        ek = part / n
        u, v_new = slim_finalize_batched(m_new, v, axis=axis, ek=ek, **KW)
        bc1 = 1 - KW["b1"] ** KW["count"]
        bc2 = 1 - KW["b2"] ** KW["count"]
        v_ref = KW["b2"] * v + (1 - KW["b2"]) * ek
        np.testing.assert_allclose(v_new, v_ref, rtol=1e-6)
        np.testing.assert_allclose(
            u, (m_new / bc1) / (jnp.sqrt(v_ref / bc2) + KW["eps"]),
            rtol=1e-5, atol=1e-6)
        # ek=None form: v_line is already the completed moment -> u only
        u2 = slim_finalize_batched(m_new, v_ref, axis=axis, ek=None, **KW)
        np.testing.assert_allclose(u2, u, rtol=1e-6)

    @pytest.mark.parametrize("axis,shape", CASES)
    def test_composition_equals_single_kernel_leaf(self, axis, shape):
        """partial -> local mean -> finalize == the one-kernel precond (the
        unsharded oracle) when the 'psum group' is a single shard."""
        g, m, v, red_ax = _leaf(shape, axis)
        n = shape[red_ax]
        m_new, part = slim_partial_stats_batched(g, m, axis=axis, b1=KW["b1"])
        u, v_new = slim_finalize_batched(m_new, v, axis=axis, ek=part / n, **KW)
        u_ref, m_ref, v_ref = slim_precond_batched(g, m, v, axis=axis, **KW)
        np.testing.assert_allclose(u, u_ref, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(m_new, m_ref, rtol=1e-6)
        np.testing.assert_allclose(v_new, v_ref, rtol=1e-6)

    def test_bf16_inputs(self):
        g, m, v, red_ax = _leaf((2, 9, 40), 1, dtype=jnp.bfloat16)
        m_new, part = slim_partial_stats_batched(g, m, axis=1, b1=KW["b1"])
        assert m_new.dtype == jnp.float32 and part.dtype == jnp.float32
        g32, m32 = g.astype(jnp.float32), m.astype(jnp.float32)
        np.testing.assert_allclose(m_new, KW["b1"] * m32 + (1 - KW["b1"]) * g32,
                                   rtol=1e-2, atol=1e-2)
        np.testing.assert_allclose(part, jnp.sum(g32 * g32, axis=2, keepdims=True),
                                   rtol=1e-2, atol=1e-2)

    def test_2d_wrappers(self):
        g = jax.random.normal(jax.random.PRNGKey(0), (13, 21))
        m = jnp.zeros((13, 21))
        m_new, part = slim_partial_stats(g, m, axis=1)
        assert m_new.shape == (13, 21) and part.shape == (13, 1)
        v = jnp.ones((13, 1))
        u, v_new = slim_finalize(m_new, v, axis=1, ek=part / 21, **KW)
        assert u.shape == (13, 21) and v_new.shape == (13, 1)
        assert slim_finalize(m_new, v_new, axis=1, **KW).shape == (13, 21)


class TestFromUpdateSNR:
    def test_finalize_matches_jnp_oracle(self):
        g, m, v, red_ax = _leaf((2, 7, 48), 1)
        n = 48
        _, part, s1c, s2c, _ = slim_partial_stats_batched(
            g, m, axis=1, b1=KW["b1"], with_snr=True)
        v_new = KW["b2"] * v + (1 - KW["b2"]) * part / n
        snr = snr_update_stats_finalize(v_new, s1c, s2c, n, 1 - KW["b2"])
        ref = F.jnp_update_snr_leaf(g.reshape(14, 48), v_new.reshape(14, 1),
                                    (1,), b2=KW["b2"])
        np.testing.assert_allclose(snr, ref, rtol=1e-4)

    def test_tree_update_emit_snr(self):
        """emit_snr appends per-leaf scalars (None for K = ()) and does not
        perturb the update itself; bucketed small dense leaves included."""
        k = jax.random.PRNGKey(0)
        gs = [jax.random.normal(k, (32, 16)), jax.random.normal(k, (8, 6, 10)),
              jnp.linspace(-1, 1, 64)]
        dims = [(1,), (0,), ()]
        ms = [jnp.zeros(g.shape) for g in gs]
        vs = [jnp.zeros(tuple(1 if i in set(d) else s for i, s in enumerate(g.shape)))
              for g, d in zip(gs, dims)]
        u, m, v, s = F.slim_tree_update(gs, ms, vs, dims, emit_snr=True, **KW)
        u2, _, v2 = F.slim_tree_update(gs, ms, vs, dims, **KW)
        for a, b in zip(u, u2):
            np.testing.assert_allclose(a, b, rtol=1e-6)
        assert s[2] is None
        for i in (0, 1):
            ref = F.jnp_update_snr_leaf(gs[i], v[i], dims[i], b2=KW["b2"])
            np.testing.assert_allclose(s[i], ref, rtol=1e-4)

    def test_measure_tree_snr_from_update(self):
        """The candidate matching the update's K takes the ridden scalar;
        the others fall back to the standard nu measurement."""
        from repro.core.labels import ParamMeta
        from repro.core.snr import measure_tree_snr, snr_along_dims

        meta = {"w": ParamMeta(axes=("embed", "mlp"), role="mlp_up",
                               fan_in=("embed",), fan_out=("mlp",))}
        nu = {"w": jnp.abs(jax.random.normal(jax.random.PRNGKey(0), (8, 16))) + 0.1}
        ridden = jnp.asarray(42.0)
        out = measure_tree_snr(nu, meta, from_update={"w": ridden},
                               update_dims={"w": (0,)})
        assert float(out["w"]["fan_in"]) == 42.0
        np.testing.assert_allclose(out["w"]["fan_out"],
                                   snr_along_dims(nu["w"], (1,)), rtol=1e-6)
        with pytest.raises(ValueError, match="update_dims"):
            measure_tree_snr(nu, meta, from_update={"w": ridden})

    @pytest.mark.slow
    def test_trainer_snr_from_update(self):
        """Measure steps ride the update pass: the candidate matching each
        leaf's K comes from state.snr, the rest match the classic path; the
        published snapshot is stripped after consumption so checkpoints and
        the normal step keep the snr-less layout."""
        sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
        from benchmarks.common import gpt_nano, nano_data
        from repro.train import Trainer, TrainerConfig
        from repro.train.trainer import find_slim_snr

        cfg = gpt_nano()
        base = dict(total_steps=20, log_every=10, measure_snr=True,
                    snr_early_every=10, snr_late_every=100, backend="fused")
        tr = Trainer(cfg, "slim", 3e-3, nano_data(cfg),
                     TrainerConfig(snr_from_update=True, **base))
        tr.run()
        tr2 = Trainer(cfg, "slim", 3e-3, nano_data(cfg), TrainerConfig(**base))
        tr2.run()
        assert tr.snr.count == tr2.snr.count == 2
        assert find_slim_snr(tr.opt_state) is None
        assert tr.metrics_log[-1]["loss"] == tr2.metrics_log[-1]["loss"]
        avg, avg2 = tr.snr.averaged(), tr2.snr.averaged()
        assert avg.keys() == avg2.keys()
        for p, by_k in avg.items():
            assert by_k.keys() == avg2[p].keys()


class TestOwnerPlans:
    MESH = SpecMesh({"data": 4, "model": 2})
    PROD = SpecMesh({"data": 16, "model": 16})

    def test_placement_on_dividing_kept_dim(self):
        owner, nu_spec = owner_placement((16, 1), P(None, None), ("model",), self.MESH)
        assert owner == (("model", 0),)
        assert nu_spec == P("model", None)

    def test_placement_multi_axis(self):
        # both psum axes fit on the same kept dim (16 / (4*2))
        owner, nu_spec = owner_placement((16, 1), P(None, None),
                                         ("data", "model"), self.MESH)
        assert owner == (("data", 0), ("model", 0))
        assert nu_spec == P(("data", "model"), None)

    def test_placement_falls_back_when_indivisible(self):
        # 6 % 4 != 0 and 1-extent dims never take an axis
        owner, nu_spec = owner_placement((6, 1), P(None, None), ("data",), self.MESH)
        assert owner == ()
        assert nu_spec == P(None, None)

    def test_placement_is_all_or_nothing(self):
        """A *partial* placement would corrupt the moment: shards along an
        unplaced psum axis would each add an identical b2*v copy into the
        all-reduce, inflating v_new by that axis's size — so one unplaceable
        psum axis drops the whole placement."""
        # 'data' (4) fits dim0 (4 -> local 1) but 'model' (2) then has no
        # dim left; and in the other order 'model' fits while 'data' fails.
        for axes in (("data", "model"), ("model", "data")):
            owner, nu_spec = owner_placement((4, 1), P(None, None), axes, self.MESH)
            assert owner == () and nu_spec == P(None, None), axes
        # The reviewer case: vocab 50304 takes 16 but not 256.
        owner, nu_spec = owner_placement((50304, 1), P(None, None),
                                         ("data", "model"), self.PROD)
        assert owner == () and nu_spec == P(None, None)

    def test_production_plans(self):
        """The gpt_small psum leaves: owner dedupe everywhere except embed
        (50304 is not divisible by 256), all finalizes kernel-resident."""
        cases = [
            ((12, 768, 12, 64), (1,), P(None, "data", None, None), 16),
            ((12, 3072, 768), (2,), P(None, "model", "data"), 16),
            ((50304, 768), (1,), P("model", "data"), 1),
        ]
        plans = []
        for shape, dims, spec, want in cases:
            pl = plan_sharded_leaf(shape, jnp.float32, dims, spec, self.PROD, n_bufs=5)
            plans.append(pl)
            assert pl.regime == "psum" and pl.finalize == "kernel", (shape, pl)
            assert owner_factor(pl, self.PROD) == want, (shape, pl.owner)
        assert regime_counts(plans) == {"local": 0, "psum": 3, "psum_jnp": 0,
                                        "degraded": 0,
                                        "jnp": 0}

    def test_psum_jnp_counted(self):
        # Interleaved K *after* sharding with a sharded reduced dim: the
        # finalize cannot be kernel-served -> psum_jnp in regime_counts.
        pl = plan_sharded_leaf((4, 6, 8, 10), jnp.float32, (1, 3),
                               P(None, "model", None, None), self.MESH, n_bufs=5)
        assert pl.regime == "psum" and pl.finalize == "jnp"
        assert regime_counts([pl])["psum_jnp"] == 1


PARITY_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from repro.core.slim_adam import scale_by_slim_adam
from repro.optim import fused as F
from repro.sharding.shardspec import owner_factor, regime_counts

from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
key = jax.random.PRNGKey(0)
params = {
    "fanin": jax.random.normal(key, (32, 16)),       # local kernel
    "psum":  jax.random.normal(key, (16, 32)),       # psum, owner on dim0 x model
    "psum3": jax.random.normal(key, (12, 8, 20)),    # batched psum, owner x data
    "psumw": jax.random.normal(key, (6, 8)),         # 2-axis psum group, owner
                                                     # placement fails (6 % 4)
                                                     # -> replicated writes
    "inter": jax.random.normal(key, (4, 6, 8, 10)),  # interleaved -> jnp
    "dense": jax.random.normal(key, (24, 16)),
    "vec":   jnp.linspace(-1.0, 1.0, 64),            # bucket path
}
dims  = {"fanin": (1,), "psum": (1,), "psum3": (2,), "psumw": (1,),
         "inter": (0, 2), "dense": (), "vec": ()}
specs = {"fanin": P("data", None), "psum": P(None, "model"),
         "psum3": P(None, "model", "data"), "psumw": P(None, ("data", "model")),
         "inter": P(), "dense": P("data", "model"), "vec": P("data")}
grads = jax.tree.map(
    lambda p: 0.1 * jax.random.normal(jax.random.PRNGKey(p.size % 13), p.shape), params)

out = {}
gl, td = jax.tree_util.tree_flatten(params)
d_leaves = [tuple(d) for d in td.flatten_up_to(dims)]
plans = F.sharded_tree_plans(gl, d_leaves, td.flatten_up_to(specs), mesh)
out["regimes"] = regime_counts(plans)
out["owner"] = {n: owner_factor(pl, mesh)
                for n, pl in zip(sorted(params), plans) if pl.regime == "psum"}

# owner-write scheme vs single-device fused path, 3 steps (the owner-sharded
# nu layout must round-trip through consecutive updates)
tx1 = scale_by_slim_adam(dims, backend="fused")
tx2 = scale_by_slim_adam(dims, backend="fused", mesh=mesh, param_specs=specs)
s1, s2 = tx1.init(params), tx2.init(params)
for _ in range(3):
    u1, s1 = jax.jit(tx1.update)(grads, s1)
    u2, s2 = jax.jit(tx2.update)(grads, s2)
def errs(t1, t2):
    return {k: float(np.max(np.abs(np.asarray(t1[k]) - np.asarray(t2[k])))) for k in t1}
out["u_err"] = errs(u1, u2)
out["nu_err"] = errs(s1.nu, s2.nu)
out["mu_err"] = errs(s1.mu, s2.mu)

# from-update SNR: sharded == unsharded (psum leaves rebase+psum their stats)
kw = dict(b1=0.9, b2=0.95, eps=1e-8, count=s1.count + 1)
mu_l, nu_l, g_l = (td.flatten_up_to(t) for t in (s1.mu, s1.nu, grads))
_, _, _, snr_a = F.slim_tree_update(g_l, mu_l, nu_l, d_leaves, emit_snr=True, **kw)
mu2_l, nu2_l = td.flatten_up_to(s2.mu), td.flatten_up_to(s2.nu)
spec_l = td.flatten_up_to(specs)
_, _, _, snr_b = jax.jit(lambda g, m, v, c: F.slim_tree_update(
    g, m, v, d_leaves, emit_snr=True, mesh=mesh, spec_leaves=spec_l,
    b1=0.9, b2=0.95, eps=1e-8, count=c))(g_l, mu2_l, nu2_l, s2.count + 1)
out["snr"] = [None if a is None else
              {"single": float(a), "sharded": float(b),
               "rel": abs(float(a) - float(b)) / max(abs(float(a)), 1e-30)}
              for a, b in zip(snr_a, snr_b)]

# dtype regression: bf16 moments stay bf16 through the psum path
g = jax.random.normal(key, (16, 32))
m16 = jnp.zeros((16, 32), jnp.bfloat16)
v16 = jnp.zeros((16, 1), jnp.bfloat16)
ub, mb, vb = jax.jit(lambda *a: F.slim_tree_update(
    [a[0]], [a[1]], [a[2]], [(1,)], b1=0.9, b2=0.95, eps=1e-8, count=1,
    mesh=mesh, spec_leaves=[P(None, "model")]))(g, m16, v16)
out["dtypes"] = [str(ub[0].dtype), str(mb[0].dtype), str(vb[0].dtype)]
print(json.dumps(out))
"""


@pytest.mark.slow
@pytest.mark.multidevice
def test_owner_write_psum_parity(tmp_path):
    """8-device shard_map: the Pallas-resident psum regime with owner-shard
    moment writes matches the single-device fused path — exact on local
    leaves, <= 2e-6 on psum/jnp leaves (fp32 reassociation through the
    combined payload all-reduce) — and bf16 moments keep their dtype."""
    script = tmp_path / "owner_parity.py"
    script.write_text(PARITY_SCRIPT)
    src = str(Path(__file__).resolve().parents[1] / "src")
    proc = subprocess.run([sys.executable, str(script)], capture_output=True,
                          text=True, timeout=900,
                          env={**__import__("os").environ, "PYTHONPATH": src})
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])

    assert out["regimes"] == {"local": 3, "psum": 3, "psum_jnp": 0, "jnp": 1,
                              "degraded": 0}
    # owner dedupe engaged where the psum group divides a kept dim; psumw's
    # 2-axis group finds no placement (6 % 4) and must stay replicated —
    # the partial-placement regression (an unplaced psum axis would inflate
    # v_new by its size).
    assert out["owner"] == {"psum": 2, "psum3": 4, "psumw": 1}
    for group in ("u_err", "nu_err", "mu_err"):
        for leaf, err in out[group].items():
            tol = 0.0 if leaf in ("fanin", "dense", "vec") else 2e-6
            assert err <= tol, (group, leaf, err)
    snr = [s for s in out["snr"] if s is not None]
    assert len(snr) == 5  # fanin, inter, psum, psum3, psumw
    for s in snr:
        assert s["rel"] <= 1e-5, s
    assert out["dtypes"] == ["float32", "bfloat16", "bfloat16"]
