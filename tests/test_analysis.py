"""Seeded regressions for the static contract checker (`repro.analysis`).

Every check class the analyzer claims to catch gets a deliberately broken
artifact here — an inflated buffer constant, a parallel-dim write to a
shared accumulator, a blind (non-RMW) aliased write, an uncast bf16 read, a
partial owner placement, a drifted golden signature, a step that
concretizes its traced controls — plus the green path: the real repo must
produce zero findings on every pass.
"""
import json

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis import kernelcheck, lint, races, registry, shardcheck, tracecheck
from repro.analysis.jaxpr_tools import aliased_grid_dims
from repro.analysis.report import PassResult


def _infos(fn, *args, **kwargs):
    return kernelcheck.trace_infos(fn, args, kwargs)


# ---------------------------------------------------------------------------
# Seeded pallas kernels
# ---------------------------------------------------------------------------


def _acc_body(x_ref, o_ref):
    # zero-on-first-instance + accumulate: the legal RMW pattern
    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _zero():
        o_ref[...] = jnp.zeros((2,), jnp.float32)

    o_ref[...] = o_ref[...] + jnp.stack(
        [jnp.sum(x_ref[...]), jnp.float32(1.0)])


def _blind_body(x_ref, o_ref):
    # blind overwrite of the shared block: drops earlier instances
    o_ref[...] = jnp.stack([jnp.sum(x_ref[...]), jnp.float32(1.0)])


def _acc_call(body, x, semantics):
    r, c = x.shape
    return pl.pallas_call(
        body,
        grid=(r // 8, c // 128),
        in_specs=[pl.BlockSpec((8, 128), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((2,), lambda i, j: (0,)),
        out_shape=jax.ShapeDtypeStruct((2,), jnp.float32),
        interpret=True,
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
    )(x)


_X = jax.ShapeDtypeStruct((16, 256), jnp.float32)


class TestRaceDetector:
    def test_parallel_dim_write_to_shared_accumulator_flagged(self):
        (info,) = _infos(
            lambda x: _acc_call(_acc_body, x, ("parallel", "arbitrary")), _X)
        result = PassResult("races")
        races.check_output_races(info, result, "seeded")
        assert any(f.check == "race-parallel" for f in result.findings)

    def test_blind_write_to_shared_accumulator_flagged(self):
        (info,) = _infos(
            lambda x: _acc_call(_blind_body, x, ("arbitrary", "arbitrary")), _X)
        result = PassResult("races")
        races.check_output_races(info, result, "seeded")
        assert any(f.check == "race-rmw" for f in result.findings)

    def test_sequential_rmw_accumulator_is_clean(self):
        (info,) = _infos(
            lambda x: _acc_call(_acc_body, x, ("arbitrary", "arbitrary")), _X)
        # the shared block really is aliased across both grid dims ...
        assert aliased_grid_dims(info.blocks_out[0], info.grid) == {0, 1}
        # ... and still legal: sequential dims + read-modify-write
        result = PassResult("races")
        races.check_output_races(info, result, "seeded")
        assert not result.findings


class TestKernelcheck:
    def _sum3(self):
        def body(a_ref, b_ref, c_ref, o_ref):
            o_ref[...] = a_ref[...] + b_ref[...] + c_ref[...]

        def call(a, b, c):
            spec = pl.BlockSpec((8, 128), lambda i: (i, 0))
            return pl.pallas_call(
                body, grid=(2,), in_specs=[spec] * 3, out_specs=spec,
                out_shape=jax.ShapeDtypeStruct((16, 128), jnp.float32),
                interpret=True)(a, b, c)

        x = jax.ShapeDtypeStruct((16, 128), jnp.float32)
        (info,) = _infos(call, x, x, x)
        return info

    def test_inflated_buffer_constant_flagged(self):
        info = self._sum3()  # 4 live full-size blocks
        result = PassResult("kernelcheck")
        kernelcheck.check_bufs(info, 10, "SEEDED_BUFS", result, "seeded")
        assert any(f.check == "bufs" for f in result.findings)

    def test_honest_buffer_constant_passes(self):
        info = self._sum3()
        result = PassResult("kernelcheck")
        kernelcheck.check_bufs(info, 5, "SEEDED_BUFS", result, "seeded")
        assert not result.findings

    def test_vmem_blowout_flagged(self):
        def call(a):
            spec = pl.BlockSpec((2048, 2048), lambda i: (i, 0))
            return pl.pallas_call(
                lambda a_ref, o_ref: o_ref.__setitem__(..., a_ref[...] * 2.0),
                grid=(1,), in_specs=[spec], out_specs=spec,
                out_shape=jax.ShapeDtypeStruct((2048, 2048), jnp.float32),
                interpret=True)(a)

        (info,) = _infos(call, jax.ShapeDtypeStruct((2048, 2048), jnp.float32))
        result = PassResult("kernelcheck")
        kernelcheck.check_vmem(info, result, "seeded", gated=True)
        assert any(f.check == "vmem" for f in result.findings)

    def test_uncast_bf16_read_flagged(self):
        def call(a):
            spec = pl.BlockSpec((8, 128), lambda i: (i, 0))
            return pl.pallas_call(
                # consumes the bf16 read directly — no cast to f32
                lambda a_ref, o_ref: o_ref.__setitem__(..., a_ref[...] + 1.0),
                grid=(1,), in_specs=[spec], out_specs=spec,
                out_shape=jax.ShapeDtypeStruct((8, 128), jnp.bfloat16),
                interpret=True)(a)

        (info,) = _infos(call, jax.ShapeDtypeStruct((8, 128), jnp.bfloat16))
        result = PassResult("kernelcheck")
        kernelcheck.check_compute_dtype(info, result, "seeded")
        assert any(f.check == "dtype" for f in result.findings)

    def test_full_size_variant_output_flagged(self, monkeypatch):
        entry = registry.ENTRY_MAP["slim_precond_batched"]
        case, variant = entry.cases[0], entry.variants[1]
        monkeypatch.setattr(
            registry, "variant_extra_outputs",
            lambda *a: [jax.ShapeDtypeStruct(case.shape, jnp.float32)])
        result = PassResult("kernelcheck")
        kernelcheck.check_extra_outputs(entry, case, variant, result, "seeded")
        assert any(f.check == "okept" for f in result.findings)

    def test_golden_signature_drift_flagged(self, tmp_path):
        golden = json.loads(kernelcheck.GOLDEN_PATH.read_text())
        key = sorted(golden)[0]
        golden[key] = [["9x9x9", "float64"]]  # a kernel output silently grew
        drifted = tmp_path / "golden.json"
        drifted.write_text(json.dumps(golden))
        result, _ = kernelcheck.run(golden_path=drifted)
        assert any(f.check == "golden" and f.where == key
                   for f in result.findings)


class TestShardcheck:
    def test_partial_owner_placement_flagged(self):
        from repro.kernels.slim_update import PRECOND_BUFS
        from repro.sharding.logical import (ShardingContext, param_specs,
                                            use_sharding)
        from repro.sharding.shardspec import (SpecMesh, normalize_spec_leaves,
                                              plan_sharded_leaf)

        cfg, params_abs, meta, treedef, p_leaves, dims_flat = \
            shardcheck.arch_leaves("gpt_small")
        mesh = SpecMesh({"data": 16, "model": 16})
        ctx = ShardingContext(mesh, rules=dict(cfg.sharding_overrides) or None)
        with use_sharding(ctx):
            p_specs = param_specs(meta, params_abs)
        spec_flat = normalize_spec_leaves(p_specs, treedef, "test")

        corrupted = 0
        for leaf, spec, dims in zip(p_leaves, spec_flat, dims_flat):
            plan = plan_sharded_leaf(tuple(leaf.shape), leaf.dtype,
                                     tuple(dims), spec, mesh,
                                     n_bufs=PRECOND_BUFS)
            if plan.regime != "psum" or not plan.owner:
                continue
            # clean plan passes ...
            ok = PassResult("shardcheck")
            shardcheck.check_leaf_plan(plan, tuple(leaf.shape), tuple(dims),
                                       mesh, ok, "clean")
            assert not ok.findings
            # ... losing part of the placement (or all of it swapped onto a
            # mesh axis outside the psum group) fails all-or-nothing
            bad_owner = (plan.owner[:-1]
                         or ((("bogus",) + plan.owner[0][1:]),))
            bad = plan._replace(owner=tuple(bad_owner))
            res = PassResult("shardcheck")
            shardcheck.check_leaf_plan(bad, tuple(leaf.shape), tuple(dims),
                                       mesh, res, "seeded")
            assert any(f.check == "owner-all-or-nothing" for f in res.findings)
            corrupted += 1
            if corrupted >= 2:
                break
        assert corrupted, "no psum-with-owner leaf found to corrupt"


class TestTracecheck:
    def _tiny(self):
        p = {"w": jax.ShapeDtypeStruct((4,), jnp.float32)}
        return p, {}, {}

    def test_concretizing_step_flagged(self):
        def bad(params, opt, batch, controls):
            lr = float(controls["lr_scale"])  # concretizes a tracer
            return jax.tree.map(lambda x: x * lr, params), opt

        result = PassResult("tracecheck")
        tracecheck.check_step_trace(bad, self._tiny(), result, "seeded")
        assert any(f.check == "trace-stable" for f in result.findings)

    def test_control_ignoring_step_flagged(self):
        def bad(params, opt, batch, controls):
            return jax.tree.map(lambda x: x * 2.0, params), opt

        result = PassResult("tracecheck")
        tracecheck.check_step_trace(bad, self._tiny(), result, "seeded")
        assert any(f.check == "controls-used" for f in result.findings)

    def test_trace_dependent_step_flagged(self):
        calls = []

        def bad(params, opt, batch, controls):
            calls.append(1)  # trace depends on call count, not operands
            bump = 1.0 if len(calls) > 1 else 0.0
            return (jax.tree.map(lambda x: x * controls["lr_scale"] + bump,
                                 params), opt)

        result = PassResult("tracecheck")
        tracecheck.check_step_trace(bad, self._tiny(), result, "seeded")
        assert any(f.check == "trace-stable" for f in result.findings)

    def test_honest_step_is_clean(self):
        def good(params, opt, batch, controls):
            return (jax.tree.map(lambda x: x * controls["lr_scale"]
                                 * controls["grad_scale"], params), opt)

        result = PassResult("tracecheck")
        tracecheck.check_step_trace(good, self._tiny(), result, "seeded")
        assert not result.findings


class TestLint:
    def test_pallas_call_outside_kernels_flagged(self):
        hits = lint.lint_source(
            "import jax.experimental.pallas as pl\n"
            "def f(x):\n"
            "    return pl.pallas_call(lambda r, o: None)(x)\n",
            "repro/optim/rogue.py")
        assert any(rule == "RPR001" for rule, _, _ in hits)

    def test_host_numpy_and_traced_branch_in_kernel_flagged(self):
        hits = lint.lint_source(
            "import numpy as np\n"
            "def _k(g_ref, u_out, *, with_snr):\n"
            "    g = g_ref[...]\n"
            "    if with_snr:\n"          # static flag: legal
            "        pass\n"
            "    if g.sum() > 0:\n"        # traced: illegal
            "        u_out[...] = np.sqrt(g)\n",
            "repro/kernels/rogue.py")
        rules = [r for r, _, _ in hits]
        assert rules.count("RPR002") == 2  # the branch and the np. call

    def test_optional_state_field_without_default_flagged(self):
        hits = lint.lint_source(
            "from typing import NamedTuple, Optional\n"
            "class FooState(NamedTuple):\n"
            "    count: object\n"
            "    snr: Optional[object]\n",
            "repro/core/rogue.py")
        assert any(rule == "RPR003" for rule, _, _ in hits)

    def test_non_atomic_checkpoint_publish_flagged(self):
        hits = lint.lint_source(
            "import os, shutil\n"
            "def save(stage, final, ptr):\n"
            "    os.rename(stage, final)\n"
            "    shutil.move(stage, final)\n"
            "    os.replace(final, ptr)\n"
            "    open(ptr / 'LATEST', 'w')\n",
            "repro/checkpoint/rogue.py")
        assert [r for r, _, _ in hits].count("RPR004") == 4

    def test_repo_is_lint_clean(self):
        result = lint.run()
        assert not result.findings, [str(f) for f in result.findings]


class TestGreenPath:
    """The analyzer against the real repo: zero findings, every pass."""

    def test_kernelcheck_and_races_clean(self):
        result, computed = kernelcheck.run()
        assert not result.findings, [str(f) for f in result.findings]
        assert computed  # signatures flowed
        r2 = races.run()
        assert not r2.findings, [str(f) for f in r2.findings]
        assert r2.checks > 100

    def test_shardcheck_clean(self):
        result = shardcheck.run()
        assert not result.findings, [str(f) for f in result.findings]
        assert result.checks > 1000

    def test_tracecheck_clean(self):
        result = tracecheck.run()
        assert not result.findings, [str(f) for f in result.findings]
        assert result.checks == 3

    def test_registry_feeds_roofline_gates(self):
        # the opt_speed gates consume these exact contracts
        lines, oversize = registry.snr_stat_lines()
        assert set(lines) == {"psum", "local", "jnp"} and not oversize
        for name, extras in registry.health_stat_outputs():
            assert extras == [(2,)], (name, extras)

    def test_cli_gate(self, capsys):
        from repro.analysis.__main__ import main

        assert main(["--only", "lint"]) == 0
        out = capsys.readouterr().out
        assert "lint" in out and "PASS" in out
        with pytest.raises(SystemExit):
            main(["--only", "nonsense"])
