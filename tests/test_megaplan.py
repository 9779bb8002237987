"""Megaplan: whole-tree grouped Pallas launches.

Three layers of coverage:

  * planner invariants — segment tables tile each super-tensor injectively,
    groups + jnp fallback partition the leaves, grouping keys are uniform;
  * parity — the grouped path against the per-leaf dispatch
    (``megakernel=False``) on every regime: v state bit-exact (the
    concatenation axis is the kept axis, so no reduction line ever crosses
    a segment boundary), m and u within a couple of fp32 ULP (the
    ``b1 * m + (1 - b1) * g`` recurrence is one multiply-add that the
    backend may or may not contract into an FMA, and that choice is made
    per fusion, so it differs between super-tensor and leaf shapes; XLA
    also clones the m/v recurrences into the u fusion), SNR/health riding
    along;
  * launch counts — the O(leaves) -> O(groups) claim, decided on the jaxpr
    (``count_pallas_launches``), plus the one-sided ``bucket_min_size``
    boundary regression and an 8-device sharded parity subprocess.
"""
import json
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.jaxpr_tools import count_pallas_launches
from repro.core.slim_adam import scale_by_slim_adam
from repro.kernels.fused_adam import LANES
from repro.kernels.megaplan import (gather_group, plan_megagroups,
                                    scatter_group, segment_table)
from repro.kernels.slim_update import PRECOND_BUFS
from repro.kernels.tiling import VMEM_BUDGET
from repro.optim import fused, scale_by_adam


def _mixed_tree():
    """One leaf per regime: two same-cols minor leaves (one ragged), a major
    leaf, a scan-stacked batched leaf, an interleaved-K jnp-route leaf, a
    bf16 leaf sharing the minor group, dense odd/scalar/vector leaves, and a
    size-1-kept leaf."""
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 10)
    params = {
        "minor_a": jax.random.normal(ks[0], (24, 16)),
        "minor_b": jax.random.normal(ks[1], (7, 16)),
        "bf16": jax.random.normal(ks[2], (9, 16)).astype(jnp.bfloat16),
        "major": jax.random.normal(ks[3], (16, 24)),
        "batched": jax.random.normal(ks[4], (3, 8, 6, 4)),
        "inter": jax.random.normal(ks[5], (4, 6, 10)),
        "dense_odd": jax.random.normal(ks[6], (33, 5)),
        "scalar": jnp.asarray(0.5),
        "size1": jax.random.normal(ks[7], (1, 4)),
        "vec": jax.random.normal(ks[8], (37,)),
    }
    dims = {"minor_a": (1,), "minor_b": (1,), "bf16": (1,), "major": (0,),
            "batched": (1,), "inter": (0, 2), "dense_odd": (), "scalar": (),
            "size1": (1,), "vec": (0,)}
    return params, dims


def _leaf_geometry(params, dims):
    leaves, treedef = jax.tree_util.tree_flatten(params)
    d_leaves = tuple(tuple(d) for d in treedef.flatten_up_to(dims))
    return (tuple(tuple(p.shape) for p in leaves),
            tuple(str(p.dtype) for p in leaves), d_leaves)


def _grads(params, i):
    k = jax.random.PRNGKey(100 + i)
    return jax.tree.map(
        lambda x: 0.1 * jax.random.normal(k, x.shape).astype(x.dtype), params)


def _tree_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert x.shape == y.shape and x.dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _tree_close_ulp(a, b):
    # m and u: the two compilations may contract the m recurrence (and its
    # clone inside the u fusion) into FMAs differently — a couple of ULP.
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert x.shape == y.shape and x.dtype == y.dtype
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=5e-7, atol=5e-7)


class TestPlanner:
    def test_groups_partition_leaves(self):
        shapes, dts, d_leaves = _leaf_geometry(*_mixed_tree())
        plan = plan_megagroups(shapes, dts, d_leaves, n_bufs=PRECOND_BUFS)
        covered = list(plan.jnp_idx)
        for g in plan.groups:
            off = 0
            for seg in g.segments:
                assert seg.length > 0
                assert seg.offset == off
                off += seg.length
                covered.append(seg.index)
            assert off == g.extent
        assert sorted(covered) == list(range(len(shapes)))

    def test_same_geometry_leaves_share_a_group(self):
        shapes, dts, d_leaves = _leaf_geometry(*_mixed_tree())
        plan = plan_megagroups(shapes, dts, d_leaves, n_bufs=PRECOND_BUFS)
        by_kind = {}
        for g in plan.groups:
            by_kind.setdefault(g.kind, []).append(g)
        # minor_a, minor_b, bf16, size1 all reduce 16/4-col rows... only the
        # cols-16 leaves share; dtype must never split a group (bf16 rides
        # with f32 — the gather casts).
        minor_cols = {g.cols: len(g.segments) for g in by_kind["minor"]}
        assert minor_cols[16] == 3   # minor_a + minor_b + bf16
        assert len(by_kind["dense"]) == 1
        assert len(by_kind["batched"]) == 1

    def test_fallback_and_interleaved_routing(self):
        """0-d leaves take the jnp route; an interleaved-K leaf
        canonicalizes (permute + reshape) into an ordinary minor group."""
        shapes, dts, d_leaves = _leaf_geometry(*_mixed_tree())
        plan = plan_megagroups(shapes, dts, d_leaves, n_bufs=PRECOND_BUFS)
        names = sorted(_mixed_tree()[0])
        assert [names[i] for i in plan.jnp_idx] == ["scalar"]
        inter = [g for g in plan.groups
                 if any(names[s.index] == "inter" for s in g.segments)]
        assert len(inter) == 1 and inter[0].kind == "minor"

    def test_dense_lane_folding(self):
        shapes, dts, d_leaves = _leaf_geometry(*_mixed_tree())
        plan = plan_megagroups(shapes, dts, tuple(() for _ in shapes))
        (g,) = plan.groups
        assert g.kind == "dense" and g.cols == LANES
        for seg in g.segments:
            assert seg.length == -(-int(np.prod(seg.shape)) // LANES)

    def test_segment_table_contents(self):
        shapes, dts, d_leaves = _leaf_geometry(*_mixed_tree())
        plan = plan_megagroups(shapes, dts, d_leaves, n_bufs=PRECOND_BUFS)
        for g in plan.groups:
            tbl = segment_table(g)
            assert tbl.shape == (g.extent, 4)
            exp = np.repeat(np.asarray([s.index for s in g.segments]),
                            np.asarray([s.length for s in g.segments]))
            np.testing.assert_array_equal(tbl[:, 0], exp)
            assert (tbl[:, 2] > 0).all()

    def test_plan_is_cached(self):
        shapes, dts, d_leaves = _leaf_geometry(*_mixed_tree())
        a = plan_megagroups(shapes, dts, d_leaves, n_bufs=PRECOND_BUFS)
        b = plan_megagroups(shapes, dts, d_leaves, n_bufs=PRECOND_BUFS)
        assert a is b

    def test_gather_scatter_roundtrip(self):
        params, dims = _mixed_tree()
        shapes, dts, d_leaves = _leaf_geometry(params, dims)
        xs = jax.tree.leaves(params)
        plan = plan_megagroups(shapes, dts, d_leaves, n_bufs=PRECOND_BUFS)
        for g in plan.groups:
            y = gather_group(g, xs)
            back = scatter_group(g, y)
            for seg, arr in zip(g.segments, back):
                np.testing.assert_array_equal(
                    np.asarray(arr),
                    np.asarray(xs[seg.index].astype(jnp.float32)))


class TestMegaParity:
    """Grouped vs per-leaf dispatch: v bit-for-bit (concatenation is along
    the kept axis, so each reduction line's arithmetic is unchanged), m and
    u within a couple of ULP (see module docstring)."""

    def test_adam_tree_parity(self):
        params, _ = _mixed_tree()
        tx_m = scale_by_adam(0.9, 0.95, 1e-8, backend="fused")
        tx_p = scale_by_adam(0.9, 0.95, 1e-8, backend="fused",
                             megakernel=False, bucket_min_size=0)
        sm, sp = tx_m.init(params), tx_p.init(params)
        for i in range(2):
            g = _grads(params, i)
            um, sm = jax.jit(tx_m.update)(g, sm)
            up, sp = jax.jit(tx_p.update)(g, sp)
        _tree_close_ulp(um, up)
        _tree_close_ulp(sm.mu, sp.mu)
        _tree_equal(sm.nu, sp.nu)

    def test_slim_tree_parity(self):
        params, dims = _mixed_tree()
        tx_m = scale_by_slim_adam(dims, 0.9, 0.95, 1e-8, backend="fused")
        tx_p = scale_by_slim_adam(dims, 0.9, 0.95, 1e-8, backend="fused",
                                  megakernel=False, bucket_min_size=0)
        sm, sp = tx_m.init(params), tx_p.init(params)
        for i in range(2):
            g = _grads(params, i)
            um, sm = jax.jit(tx_m.update)(g, sm)
            up, sp = jax.jit(tx_p.update)(g, sp)
        _tree_close_ulp(um, up)
        _tree_close_ulp(sm.mu, sp.mu)
        _tree_equal(sm.nu, sp.nu)

    def test_snr_and_health_ride_along(self):
        params, dims = _mixed_tree()
        mk = lambda mega: scale_by_slim_adam(
            dims, 0.9, 0.95, 1e-8, backend="fused", emit_snr=True,
            emit_health=True, megakernel=mega,
            **({} if mega else {"bucket_min_size": 0}))
        tx_m, tx_p = mk(True), mk(False)
        sm, sp = tx_m.init(params), tx_p.init(params)
        g = _grads(params, 0)
        # poison one leaf so the non-finite count is exercised, not just zero
        g = dict(g, minor_a=g["minor_a"].at[0, 0].set(jnp.nan))
        um, sm = jax.jit(tx_m.update)(g, sm)
        up, sp = jax.jit(tx_p.update)(g, sp)
        for a, b in zip(jax.tree.leaves(sm.snr), jax.tree.leaves(sp.snr)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=0)
        np.testing.assert_array_equal(np.asarray(sm.health.nonfinite),
                                      np.asarray(sp.health.nonfinite))
        # grad sumsq differs only in float summation order across segments
        np.testing.assert_allclose(np.asarray(sm.health.grad_sumsq),
                                   np.asarray(sp.health.grad_sumsq),
                                   rtol=1e-5)

    @pytest.mark.slow
    def test_fit_edge_leaf(self):
        """A reduction line landing exactly on the VMEM fit boundary must
        still group (one-sided fits-gate) and stay bit-exact."""
        red = VMEM_BUDGET // (4 * PRECOND_BUFS)
        params = {"edge": jnp.ones((2, red)), "mate": jnp.ones((3, red))}
        dims = {"edge": (1,), "mate": (1,)}
        shapes, dts, d_leaves = _leaf_geometry(params, dims)
        plan = plan_megagroups(shapes, dts, d_leaves, n_bufs=PRECOND_BUFS)
        assert len(plan.groups) == 1 and not plan.jnp_idx
        tx_m = scale_by_slim_adam(dims, backend="fused")
        tx_p = scale_by_slim_adam(dims, backend="fused", megakernel=False,
                                  bucket_min_size=0)
        sm, sp = tx_m.init(params), tx_p.init(params)
        g = _grads(params, 0)
        um, sm = jax.jit(tx_m.update)(g, sm)
        up, sp = jax.jit(tx_p.update)(g, sp)
        _tree_close_ulp(um, up)
        _tree_equal(sm.nu, sp.nu)


class TestLaunchCounts:
    def test_mega_launches_equal_groups(self):
        params, dims = _mixed_tree()
        shapes, dts, d_leaves = _leaf_geometry(params, dims)
        plan = plan_megagroups(shapes, dts, d_leaves, n_bufs=PRECOND_BUFS)
        g = _grads(params, 0)

        def launches(tx):
            s = tx.init(params)
            return count_pallas_launches(
                lambda gg, ss, tx=tx: tx.update(gg, ss), g, s)

        n_mega = launches(scale_by_slim_adam(dims, backend="fused"))
        n_per = launches(scale_by_slim_adam(dims, backend="fused",
                                            megakernel=False,
                                            bucket_min_size=0))
        assert n_mega == len(plan.groups)
        assert n_mega < n_per

    def test_bucket_min_size_boundary_one_sided(self):
        """The boundary is strict everywhere: a leaf of size exactly
        ``bucket_min_size`` is NOT bucketed (``size < bucket_min_size``),
        one element below is."""
        assert not fused._bucket_eligible(64, 64)
        assert fused._bucket_eligible(63, 64)
        assert not fused._bucket_eligible(64, 0)   # 0 disables bucketing

        params = {"a": jnp.ones((8, 8)), "b": jnp.ones((8, 8))}  # size 64
        g = _grads(params, 0)

        def launches(bms):
            tx = scale_by_adam(backend="fused", megakernel=False,
                               bucket_min_size=bms)
            s = tx.init(params)
            return count_pallas_launches(
                lambda gg, ss, tx=tx: tx.update(gg, ss), g, s)

        assert launches(64) == 2   # at the boundary: per-leaf launches
        assert launches(65) == 1   # strictly below: one bucket launch


_SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from repro.analysis.jaxpr_tools import count_pallas_launches
from repro.core.slim_adam import scale_by_slim_adam
from repro.optim import fused as F
from repro.optim.adam import scale_by_adam
from repro.sharding.shardspec import regime_counts

from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
key = jax.random.PRNGKey(0)
params = {
    "own_a": jax.random.normal(key, (16, 32)),   # psum, owner-placed
    "own_b": jax.random.normal(key, (8, 32)),    # psum owner, same line geometry
    "plain": jax.random.normal(key, (15, 32)),   # psum, placement fails (15 odd)
    "local": jax.random.normal(key, (32, 16)),   # local minor
    "dense": jax.random.normal(key, (24, 16)),   # K=() dense
    "inter": jax.random.normal(key, (4, 6, 8, 10)),  # jnp fallback
}
dims  = {"own_a": (1,), "own_b": (1,), "plain": (1,), "local": (1,),
         "dense": (), "inter": (0, 2)}
specs = {"own_a": P(None, "model"), "own_b": P(None, "model"),
         "plain": P(None, "model"), "local": P("data", None),
         "dense": P("data", "model"), "inter": P()}
grads = jax.tree.map(
    lambda p: 0.1 * jax.random.normal(jax.random.PRNGKey(p.size % 13), p.shape),
    params)

gl, td = jax.tree_util.tree_flatten(params)
plans = F.sharded_tree_plans(gl, [tuple(d) for d in td.flatten_up_to(dims)],
                             td.flatten_up_to(specs), mesh)
out = {"regimes": regime_counts(plans),
       "owner": {k: bool(pl.owner) for k, pl in
                 zip(sorted(params), plans) if pl.regime == "psum"}}

def leaf_errs(u1, u2):
    return {k: float(np.max(np.abs(np.asarray(u1[k], np.float32)
                                   - np.asarray(u2[k], np.float32))))
            for k in u1}

mk = lambda mega, **kw: scale_by_slim_adam(
    dims, backend="fused", mesh=mesh, param_specs=specs, megakernel=mega,
    **({} if mega else {"bucket_min_size": 0}), **kw)

tx_m, tx_p = mk(True, emit_snr=True, emit_health=True), \
             mk(False, emit_snr=True, emit_health=True)
sm, sp = tx_m.init(params), tx_p.init(params)
for _ in range(2):
    um, sm = jax.jit(tx_m.update)(grads, sm)
    up, sp = jax.jit(tx_p.update)(grads, sp)
out["slim_u"] = leaf_errs(um, up)
out["slim_nu"] = leaf_errs(sm.nu, sp.nu)
out["snr"] = {k: [float(a), float(b)]
              for k, a, b in ((k, sm.snr[k], sp.snr[k]) for k in sm.snr)
              if a is not None}
out["health_nonfinite_equal"] = bool(np.array_equal(
    np.asarray(sm.health.nonfinite), np.asarray(sp.health.nonfinite)))

ta_m = scale_by_adam(backend="fused", mesh=mesh, param_specs=specs)
ta_p = scale_by_adam(backend="fused", mesh=mesh, param_specs=specs,
                     megakernel=False, bucket_min_size=0)
am, ap = ta_m.init(params), ta_p.init(params)
uam, am = jax.jit(ta_m.update)(grads, am)
uap, ap = jax.jit(ta_p.update)(grads, ap)
out["adam_u"] = leaf_errs(uam, uap)

out["launches"] = {
    "slim_mega": count_pallas_launches(lambda g, s: tx_m.update(g, s), grads, sm),
    "slim_perleaf": count_pallas_launches(lambda g, s: tx_p.update(g, s), grads, sp),
    "adam_mega": count_pallas_launches(lambda g, s: ta_m.update(g, s), grads, am),
    "adam_perleaf": count_pallas_launches(lambda g, s: ta_p.update(g, s), grads, ap),
}
print(json.dumps(out))
print("MEGA_SHARDED_OK")
"""


@pytest.mark.slow
@pytest.mark.multidevice
def test_sharded_mega_parity(tmp_path):
    """8-host-device shard_map: the grouped psum pair (owner and plain forms
    partitioned into separate groups, per-leaf collectives between the two
    launches) must match the per-leaf sharded dispatch — state exact up to
    psum-line contraction slack, u within ULP noise — with fewer launches."""
    script = tmp_path / "sharded_mega.py"
    script.write_text(_SHARDED_SCRIPT)
    src = str(Path(__file__).resolve().parents[1] / "src")
    proc = subprocess.run([sys.executable, str(script)], capture_output=True,
                          text=True,
                          env={**__import__("os").environ, "PYTHONPATH": src},
                          timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "MEGA_SHARDED_OK" in proc.stdout
    out = json.loads(proc.stdout.strip().splitlines()[-2])

    assert out["regimes"] == {"local": 2, "psum": 3, "psum_jnp": 0,
                              "jnp": 1, "degraded": 0}, out["regimes"]
    assert out["owner"] == {"own_a": True, "own_b": True, "plain": False}

    psum_leaves = {"own_a", "own_b", "plain"}
    for leaf, err in out["slim_nu"].items():
        # psum lines: the partial-stat recurrence is cloned into the
        # finalize fusion, so contraction slack applies there too
        bound = 1e-9 if leaf in psum_leaves else 0.0
        assert err <= bound, ("slim_nu", leaf, err)
    for group in ("slim_u", "adam_u"):   # couple-of-ULP FMA slack, as above
        for leaf, err in out[group].items():
            assert err <= 2e-6, (group, leaf, err)
    for leaf, (a, b) in out["snr"].items():
        np.testing.assert_allclose(a, b, rtol=1e-6)
    assert out["health_nonfinite_equal"]

    n = out["launches"]
    assert n["slim_mega"] < n["slim_perleaf"], n
    assert n["adam_mega"] < n["adam_perleaf"], n
    assert n["adam_mega"] == 1, n
