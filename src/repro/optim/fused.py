"""Fused optimizer backend: route Adam/SlimAdam pytree updates through the
Pallas kernels.

The jnp tree-map path materializes every intermediate (g^2, m_hat, v_hat, ...)
in HBM; the fused kernels stream each tensor exactly once. Per optimizer step
and leaf the bandwidth model is

    dense Adam       7 passes   (p, g, m, v read + p', m', v' write)
    SlimAdam (K)     5 passes + O(kept)   (V reduced over K never leaves VMEM)

and in GradientTransformation form (this module: update emitted, p untouched)

    dense precond    6 passes   (g, m, v read + u, m', v' write)
    slim precond     4 passes + O(kept)

This module implements the per-leaf routing used by
``repro.optim.adam.scale_by_adam`` and ``repro.core.slim_adam.scale_by_slim_adam``
when constructed with ``backend="fused"`` (or ``"auto"`` on TPU). Every
dispatch decision is one precomputed :func:`repro.kernels.leaf_plan` lookup —
canonicalization plan, VMEM fits-gate, and route in a single place:

  * canonicalization — compressed leaves go to the batched (B, R, C)
    canonical form via :func:`repro.kernels.canon_nd`: trailing K -> minor,
    leading K -> major, kept-prefix/K/kept-suffix (scan-stacked leaves) ->
    batched major, each reachable by pure reshape; only a genuinely
    interleaved K transposes. Dense leaves reshape to (rows, minor);
  * dispatch — dense leaves -> ``adam_precond``; compressed leaves ->
    ``slim_precond`` / ``slim_precond_major`` / ``slim_precond_batched``
    per the plan, with a per-leaf jnp fallback for anything the kernels
    can't serve (scalar leaves, non-float dtypes, empty tensors, reduction
    lines that outrun VMEM, the moment-less ``use_first_moment=False``
    variant);
  * bucketing — small dense-treated leaves (elementwise treatment, so
    flattening is exact) are concatenated into one flat super-tensor per
    bucket, updated in a single kernel call to amortize launch + padding
    overhead, and scattered back to the original leaves by an offset map.

All public entry points accept a traced ``count`` (the optimizer step is
jitted state), and every returned moment/update is fp32, matching the jnp
path bit-for-bit up to fp32 reassociation.
"""
from __future__ import annotations

import math
import warnings
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .. import injection
from ..kernels import megaplan
from ..kernels.fused_adam import LANES, bias_corrections
from ..kernels.ops import (
    CanonND,
    adam_precond,
    canon_apply,
    canon_restore,
    default_interpret,
    leaf_plan,
    slim_finalize_batched,
    slim_partial_stats_batched,
    slim_precond,
    slim_precond_batched,
    slim_precond_major,
)
from ..kernels.slim_update import PRECOND_BUFS, PRECOND_SNR_BUFS
from ..kernels.snr_stats import snr_update_stats_finalize

# 0/0 guard for exactly-constant lines in the from-update SNR (matches
# repro.core.snr._VAR_EPS so both measurement paths agree on the limit).
_SNR_EPS = 1e-30

Dims = Tuple[int, ...]

# Leaves below this element count get bucketed (one kernel call per bucket
# instead of per leaf). 16k elements ~ 64 KiB fp32: far below the per-call
# tile, so launch/pad overhead dominates any per-leaf call at this size.
DEFAULT_BUCKET_MIN = 1 << 14


def _bucket_eligible(size: int, bucket_min_size: int) -> bool:
    """Single definition of the small-leaf boundary: strictly below the
    threshold buckets, exactly at it runs per-leaf. Every site must call
    this — the bucketing decision and the flush path once disagreed at
    ``size == bucket_min_size``, splitting threshold-sized leaves between
    two dispatch shapes."""
    return bool(bucket_min_size) and size < bucket_min_size


class StepHealth(NamedTuple):
    """In-pass gradient health of one tree update.

    ``nonfinite``: (n_leaves,) fp32 — per-leaf count of non-finite gradient
    entries. ``grad_sumsq``: () fp32 — global sum of squares over the
    *finite* entries, so the gradient norm stays meaningful on a poisoned
    step. Kernel-served leaves accumulate both inside the update kernels
    (one O(1) output per call, zero extra tensor passes); jnp leaves fuse
    the same sums into their existing elementwise pass.
    """
    nonfinite: jnp.ndarray
    grad_sumsq: jnp.ndarray

    @property
    def bad(self) -> jnp.ndarray:
        """() bool — any non-finite gradient entry anywhere in the tree."""
        return (jnp.sum(self.nonfinite) > 0) | ~jnp.isfinite(self.grad_sumsq)

    @property
    def grad_norm(self) -> jnp.ndarray:
        """() fp32 — global norm over the finite gradient entries."""
        return jnp.sqrt(self.grad_sumsq)


def leaf_health(g) -> jnp.ndarray:
    """``[nonfinite_count, finite_masked_sumsq]`` of one leaf — the jnp
    twin of the kernels' in-pass accumulator
    (:func:`repro.kernels.fused_adam.health_terms`)."""
    g32 = g.astype(jnp.float32)
    fin = jnp.isfinite(g32)
    nf = jnp.sum(jnp.where(fin, 0.0, 1.0))
    ss = jnp.sum(jnp.where(fin, jnp.square(g32), 0.0))
    return jnp.stack([nf, ss])


def _health_from_rows(rows: Sequence[jnp.ndarray]) -> StepHealth:
    """Stack per-leaf (2,) health rows into a :class:`StepHealth`."""
    h = jnp.stack(list(rows)) if len(rows) else jnp.zeros((0, 2), jnp.float32)
    return StepHealth(nonfinite=h[:, 0], grad_sumsq=jnp.sum(h[:, 1]))


# ---------------------------------------------------------------------------
# Graceful kernel degradation
# ---------------------------------------------------------------------------
#
# A Pallas trace/compile failure on one leaf (driver regression, an exotic
# layout the backend rejects, an injected fault in tests) should cost that
# leaf its bandwidth win, not the whole run. Kernel leaf calls route through
# _guarded(): on any exception the leaf silently re-routes to the reference
# jnp math, a one-time warning names the first failure, and the count is
# queryable (and feeds regime_counts(..., degraded=...)).

_DEGRADED = {"leaves": 0, "warned": False}
KERNEL_FAULT_POINT = "optim.kernel"


def set_kernel_fault_hook(hook: Optional[Callable[[str], None]]) -> None:
    """Install a fault-injection hook called (with a leaf label) before every
    guarded kernel dispatch — raise from it to simulate a Pallas failure.
    ``None`` uninstalls. Registered at the shared ``"optim.kernel"`` point
    (:mod:`repro.injection`). Test/benchmark instrumentation only."""
    injection.install(KERNEL_FAULT_POINT, hook)


def kernel_degraded_leaves() -> int:
    """Leaf calls that degraded kernel -> jnp since the last reset."""
    return _DEGRADED["leaves"]


def reset_kernel_degradation() -> None:
    _DEGRADED["leaves"] = 0
    _DEGRADED["warned"] = False


def _guarded(label: str, kernel_fn: Callable[[], Any], jnp_fn: Callable[[], Any],
             *, leaves: int = 1):
    try:
        injection.fire(KERNEL_FAULT_POINT, label)
        return kernel_fn()
    except Exception as e:  # noqa: BLE001 — any kernel failure degrades
        _DEGRADED["leaves"] += leaves
        if not _DEGRADED["warned"]:
            _DEGRADED["warned"] = True
            warnings.warn(
                f"Pallas kernel path failed for {label} "
                f"({type(e).__name__}: {e}); degrading leaf to the jnp "
                f"reference path", stacklevel=2)
        return jnp_fn()


# ---------------------------------------------------------------------------
# Per-leaf paths
# ---------------------------------------------------------------------------


def jnp_adam_leaf(g, m, v, *, b1, b2, eps, count):
    """Reference Adam leaf update — the single jnp definition of the
    semantics; the 'jnp' backend and the fused backend's fallback leaves
    both call this, with :func:`bias_corrections` shared with the kernels."""
    g32 = g.astype(jnp.float32)
    m_new = b1 * m + (1 - b1) * g32
    v_new = b2 * v + (1 - b2) * jnp.square(g32)
    bc1, bc2 = bias_corrections(b1, b2, count)
    u = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps)
    return u, m_new, v_new


def jnp_slim_leaf(g, m, v, dims: Dims, *, b1, b2, eps, count, use_first_moment):
    """Reference SlimAdam leaf update (see :func:`jnp_adam_leaf`)."""
    g32 = g.astype(jnp.float32)
    g2 = jnp.square(g32)
    ek = jnp.mean(g2, axis=dims, keepdims=True) if dims else g2
    v_new = b2 * v + (1 - b2) * ek
    bc1, bc2 = bias_corrections(b1, b2, count)
    if use_first_moment:
        m_new = b1 * m + (1 - b1) * g32
        num = m_new / bc1
    else:
        m_new = None
        num = g32
    u = num / (jnp.sqrt(v_new / bc2) + eps)
    return u, m_new, v_new


def jnp_update_snr_leaf(g32, v_new, dims: Dims, *, b2) -> jnp.ndarray:
    """Reference from-update SNR for one compressed leaf (scalar).

    SNR_K of the step's dense reconstruction ``b2 * V_red + (1 - b2) * g^2``
    (whose per-line mean is exactly ``v_new``), the oracle for the
    ``with_snr`` kernel outputs — see
    :func:`repro.kernels.snr_stats.snr_update_stats_finalize`."""
    g2 = jnp.square(g32.astype(jnp.float32))
    var = jnp.var(g2, axis=dims, keepdims=True)
    return jnp.mean(jnp.square(v_new) / ((1 - b2) ** 2 * var + _SNR_EPS))


# adam_precond's tile width — imported from the kernel module so a block
# change there can't desync this lane-folding layout.
_LANES = LANES


def _fold_lanes(flat: jnp.ndarray) -> jnp.ndarray:
    """Pad a flat fp32 vector to a (rows, _LANES) layout. A (1, N) shape
    would tile as single-sublane blocks on TPU, wasting ~8x vector-lane
    utilization; lane-width rows fill whole tiles. Zero padding yields zero
    updates, sliced away by the caller."""
    n = flat.size
    rows = -(-n // _LANES)
    return jnp.pad(flat, (0, rows * _LANES - n)).reshape(rows, _LANES)


def _dense_kernel_leaf(g, m, v, *, b1, b2, eps, count, interpret,
                       with_health: bool = False):
    shape = g.shape
    if g.ndim == 1:
        n = g.size
        to2d = lambda x: _fold_lanes(x.astype(jnp.float32))
        un2d = lambda y: y.ravel()[:n]
    else:
        to2d = (lambda x: x) if g.ndim == 2 else (lambda x: x.reshape(-1, shape[-1]))
        un2d = lambda y: y.reshape(shape)
    outs = adam_precond(to2d(g), to2d(m), to2d(v), b1=b1, b2=b2, eps=eps,
                        count=count, interpret=interpret, with_health=with_health)
    out = (un2d(outs[0]), un2d(outs[1]), un2d(outs[2]))
    # lane-fold zero padding is finite -> the (2,) accumulator is exact as-is
    return out + (outs[3],) if with_health else out


def _slim_kernel_leaf(g, m, v_red, cn: CanonND, *, b1, b2, eps, count, interpret,
                      with_snr: bool = False, with_health: bool = False):
    """Run one compressed leaf through the kernel its plan names: minor /
    major for 2-D-canonical plans, the batched kernel for batch > 1. With
    ``with_snr`` the kernel's strip loop also emits the centered g^2 line
    sums and a from-update SNR scalar rides along (O(kept) extra traffic).
    With ``with_health`` the same strip loop folds the leaf's (2,) health
    accumulator (appended last) — O(1) extra output, zero extra passes."""
    g2 = canon_apply(g, cn)
    m2 = canon_apply(m, cn)
    v2 = canon_apply(v_red, cn, reduced_cols=True)
    kw = dict(b1=b1, b2=b2, eps=eps, count=count, interpret=interpret)
    health = None
    if with_snr or with_health or cn.batch > 1:
        to3 = (lambda x: x) if cn.batch > 1 else (lambda x: x[None])
        un3 = (lambda x: x) if cn.batch > 1 else (lambda x: x[0])
        outs = slim_precond_batched(to3(g2), to3(m2), to3(v2), axis=cn.axis,
                                    with_snr=with_snr, with_health=with_health,
                                    **kw)
        u2, m2o, v2o = un3(outs[0]), un3(outs[1]), un3(outs[2])
        snr = (snr_update_stats_finalize(outs[2], outs[3], outs[4],
                                         cn.red_size, 1.0 - b2, eps=_SNR_EPS)
               if with_snr else None)
        if with_health:
            health = outs[-1]
    else:
        fn = slim_precond if cn.axis == 1 else slim_precond_major
        u2, m2o, v2o = fn(g2, m2, v2, **kw)
        snr = None
    out = (canon_restore(u2, cn, g.shape), canon_restore(m2o, cn, g.shape),
           canon_restore(v2o, cn, v_red.shape))
    if with_snr:
        out = out + (snr,)
    return out + (health,) if with_health else out


# ---------------------------------------------------------------------------
# Bucketing: one kernel call over many small dense-treated leaves
# ---------------------------------------------------------------------------


def _bucket_update(gs: Sequence[jnp.ndarray], ms: Sequence[jnp.ndarray],
                   vs: Sequence[jnp.ndarray], *, b1, b2, eps, count, interpret):
    """Flatten + concatenate small leaves, update as one lane-folded 2-D
    super-tensor (see :func:`_fold_lanes`), scatter results back by offset.
    Dense Adam is elementwise, so the round-trip is exact."""
    flat2d = lambda xs: _fold_lanes(
        jnp.concatenate([x.astype(jnp.float32).ravel() for x in xs]))
    ub, mo, vo = adam_precond(flat2d(gs), flat2d(ms), flat2d(vs), b1=b1, b2=b2,
                              eps=eps, count=count, interpret=interpret)
    ub, mo, vo = ub.ravel(), mo.ravel(), vo.ravel()
    out_u: List[jnp.ndarray] = []
    out_m: List[jnp.ndarray] = []
    out_v: List[jnp.ndarray] = []
    off = 0
    for g in gs:
        sl = slice(off, off + g.size)
        out_u.append(ub[sl].reshape(g.shape))
        out_m.append(mo[sl].reshape(g.shape))
        out_v.append(vo[sl].reshape(g.shape))
        off += g.size
    return out_u, out_m, out_v


def _flush_bucket(bucket, gs, ms, vs, out_u, out_m, out_v, *, interpret,
                  out_h=None, **kw):
    """Resolve the collected small-leaf indices in place: a lone leaf skips
    the concat round-trip, two or more share one kernel call.

    With ``out_h`` (per-leaf health rows) bucketed leaves compute health via
    the jnp helper — the guard needs *per-leaf* non-finite counts, and these
    leaves are below ``bucket_min_size`` elements, so the extra read is
    noise next to the bucket's own concat round-trip."""
    with_health = out_h is not None
    if len(bucket) == 1:
        i = bucket[0]
        out = _guarded(
            f"dense:{gs[i].shape}",
            lambda: _dense_kernel_leaf(gs[i], ms[i], vs[i], interpret=interpret,
                                       with_health=with_health, **kw),
            lambda: jnp_adam_leaf(gs[i], ms[i], vs[i], **kw)
                    + ((leaf_health(gs[i]),) if with_health else ()))
        out_u[i], out_m[i], out_v[i] = out[:3]
        if with_health:
            out_h[i] = out[3]
    elif bucket:
        us, mss, vss = _guarded(
            f"bucket[{len(bucket)}]",
            lambda: _bucket_update([gs[i] for i in bucket],
                                   [ms[i] for i in bucket],
                                   [vs[i] for i in bucket],
                                   interpret=interpret, **kw),
            lambda: tuple(zip(*[jnp_adam_leaf(gs[i], ms[i], vs[i], **kw)
                                for i in bucket])))
        for i, u, m, v in zip(bucket, us, mss, vss):
            out_u[i], out_m[i], out_v[i] = u, m, v
            if with_health:
                out_h[i] = leaf_health(gs[i])


# ---------------------------------------------------------------------------
# Megaplan: whole-tree grouped launches (O(groups) pallas_calls per update)
# ---------------------------------------------------------------------------
#
# The default fused tree path. plan_megagroups buckets every kernel-eligible
# leaf by regime key (dense / minor / major / batched x line geometry); each
# group gathers into one f32 super-tensor along its kept axis and runs one
# mega kernel launch, with per-leaf scatter-back by segment offset. A group
# degrades as a unit (leaves=len(segments) in the counters); jnp-routed
# leaves keep their per-leaf reference path. The per-leaf dispatch below
# stays available behind megakernel=False as the parity oracle.


def _mega_dense_group(group, gs, ms, vs, *, b1, b2, eps, count, interpret,
                      with_health: bool = False):
    """One launch over a dense group's lane-folded super-tensor. Returns
    per-segment lists (u, m', v', health_rows) aligned with
    ``group.segments``."""
    n = len(group.segments)

    def kernel_fn():
        bc1, bc2 = bias_corrections(b1, b2, count)
        l1 = megaplan.segment_lines(group, [bc1] * n)
        l2 = megaplan.segment_lines(group, [bc2] * n)
        outs = megaplan.mega_adam_update(
            megaplan.gather_group(group, gs), megaplan.gather_group(group, ms),
            megaplan.gather_group(group, vs), l1, l2, b1=b1, b2=b2, eps=eps,
            with_health=with_health, interpret=interpret)
        us = megaplan.scatter_group(group, outs[0])
        mo = megaplan.scatter_group(group, outs[1])
        vo = megaplan.scatter_group(group, outs[2])
        if with_health:
            # per-line rows sum per segment; lane-fold zero padding is
            # finite and contributes 0 to both terms.
            hs = [jnp.stack([jnp.sum(nf), jnp.sum(ss)])
                  for nf, ss in zip(megaplan.scatter_lines(group, outs[3]),
                                    megaplan.scatter_lines(group, outs[4]))]
        else:
            hs = [None] * n
        return us, mo, vo, hs

    def jnp_fn():
        outs = [jnp_adam_leaf(gs[seg.index], ms[seg.index], vs[seg.index],
                              b1=b1, b2=b2, eps=eps, count=count)
                for seg in group.segments]
        hs = ([leaf_health(gs[seg.index]) for seg in group.segments]
              if with_health else [None] * n)
        return [o[0] for o in outs], [o[1] for o in outs], [o[2] for o in outs], hs

    return _guarded(f"mega:dense[{n}]", kernel_fn, jnp_fn, leaves=n)


def _mega_slim_group(group, gs, ms, vs, *, b1, b2, eps, count, interpret,
                     emit_snr: bool = False, with_health: bool = False):
    """One launch over a slim group's canonical super-tensor. Returns
    per-segment lists (u, m', v_red', snr, health_rows)."""
    n = len(group.segments)
    batched = group.kind == "batched"
    to3 = (lambda x: x) if batched else (lambda x: x[None])
    un3 = (lambda x: x) if batched else (lambda x: x[0])

    def kernel_fn():
        bc1, bc2 = bias_corrections(b1, b2, count)
        l1 = megaplan.segment_lines(group, [bc1] * n)
        l2 = megaplan.segment_lines(group, [bc2] * n)
        outs = megaplan.mega_slim_update_batched(
            to3(megaplan.gather_group(group, gs)),
            to3(megaplan.gather_group(group, ms)),
            to3(megaplan.gather_group(group, vs, reduced=True)),
            to3(l1), to3(l2), axis=group.axis, b1=b1, b2=b2, eps=eps,
            with_snr=emit_snr, with_health=with_health, interpret=interpret)
        us = megaplan.scatter_group(group, un3(outs[0]))
        mo = megaplan.scatter_group(group, un3(outs[1]))
        vo = megaplan.scatter_group(group, un3(outs[2]), reduced=True)
        k = 3
        snrs: List[Any] = [None] * n
        if emit_snr:
            snrs = [snr_update_stats_finalize(vl, s1, s2, group.red, 1.0 - b2,
                                              eps=_SNR_EPS)
                    for vl, s1, s2 in zip(
                        megaplan.scatter_lines(group, un3(outs[2])),
                        megaplan.scatter_lines(group, un3(outs[3])),
                        megaplan.scatter_lines(group, un3(outs[4])))]
            k = 5
        hs: List[Any] = [None] * n
        if with_health:
            hs = [jnp.stack([jnp.sum(nf), jnp.sum(ss)])
                  for nf, ss in zip(megaplan.scatter_lines(group, un3(outs[k])),
                                    megaplan.scatter_lines(group, un3(outs[k + 1])))]
        return us, mo, vo, snrs, hs

    def jnp_fn():
        us, mo, vo, snrs, hs = [], [], [], [], []
        for seg in group.segments:
            i = seg.index
            u, m_new, v_new = jnp_slim_leaf(gs[i], ms[i], vs[i], seg.dims,
                                            b1=b1, b2=b2, eps=eps, count=count,
                                            use_first_moment=True)
            us.append(u)
            mo.append(m_new)
            vo.append(v_new)
            snrs.append(jnp_update_snr_leaf(gs[i], v_new, seg.dims, b2=b2)
                        if emit_snr else None)
            hs.append(leaf_health(gs[i]) if with_health else None)
        return us, mo, vo, snrs, hs

    return _guarded(f"mega:{group.kind}[{n}]", kernel_fn, jnp_fn, leaves=n)


def _adam_tree_mega(g_leaves, mu_leaves, nu_leaves, *, b1, b2, eps, count,
                    interpret, with_health: bool = False):
    """Dense Adam over the whole tree in O(groups) launches (one dense group
    plus the per-leaf jnp fallbacks). Return shape matches
    :func:`_adam_tree_local`."""
    kw = dict(b1=b1, b2=b2, eps=eps, count=count)
    n = len(g_leaves)
    plan = megaplan.plan_megagroups([g.shape for g in g_leaves],
                                    [g.dtype for g in g_leaves], [()] * n)
    out_u: List[Any] = [None] * n
    out_m: List[Any] = [None] * n
    out_v: List[Any] = [None] * n
    out_h: List[Any] = [None] * n
    for i in plan.jnp_idx:
        out_u[i], out_m[i], out_v[i] = jnp_adam_leaf(
            g_leaves[i], mu_leaves[i], nu_leaves[i], **kw)
        if with_health:
            out_h[i] = leaf_health(g_leaves[i])
    for group in plan.groups:
        us, mo, vo, hs = _mega_dense_group(group, g_leaves, mu_leaves, nu_leaves,
                                           interpret=interpret,
                                           with_health=with_health, **kw)
        for seg, u, m, v, h in zip(group.segments, us, mo, vo, hs):
            out_u[seg.index], out_m[seg.index], out_v[seg.index] = u, m, v
            out_h[seg.index] = h
    if with_health:
        return out_u, out_m, out_v, out_h
    return out_u, out_m, out_v


def _slim_tree_mega(g_leaves, mu_leaves, nu_leaves, dims_leaves, *, b1, b2, eps,
                    count, interpret, emit_snr: bool = False,
                    with_health: bool = False):
    """SlimAdam over the whole tree in O(groups) launches. Return shape
    matches :func:`_slim_tree_local` (``use_first_moment=True`` form — the
    moment-less variant never reaches the kernels)."""
    kw = dict(b1=b1, b2=b2, eps=eps, count=count)
    n = len(g_leaves)
    n_bufs = PRECOND_SNR_BUFS if emit_snr else PRECOND_BUFS
    plan = megaplan.plan_megagroups([g.shape for g in g_leaves],
                                    [g.dtype for g in g_leaves],
                                    [tuple(d) for d in dims_leaves],
                                    n_bufs=n_bufs)
    out_u: List[Any] = [None] * n
    out_m: List[Any] = [None] * n
    out_v: List[Any] = [None] * n
    out_s: List[Any] = [None] * n
    out_h: List[Any] = [None] * n
    for i in plan.jnp_idx:
        dims = tuple(dims_leaves[i])
        out_u[i], out_m[i], out_v[i] = jnp_slim_leaf(
            g_leaves[i], mu_leaves[i], nu_leaves[i], dims,
            use_first_moment=True, **kw)
        if emit_snr and dims:
            out_s[i] = jnp_update_snr_leaf(g_leaves[i], out_v[i], dims, b2=b2)
        if with_health:
            out_h[i] = leaf_health(g_leaves[i])
    for group in plan.groups:
        if group.kind == "dense":
            us, mo, vo, hs = _mega_dense_group(
                group, g_leaves, mu_leaves, nu_leaves, interpret=interpret,
                with_health=with_health, **kw)
            snrs: List[Any] = [None] * len(group.segments)
        else:
            us, mo, vo, snrs, hs = _mega_slim_group(
                group, g_leaves, mu_leaves, nu_leaves, interpret=interpret,
                emit_snr=emit_snr, with_health=with_health, **kw)
        for seg, u, m, v, s, h in zip(group.segments, us, mo, vo, snrs, hs):
            out_u[seg.index], out_m[seg.index], out_v[seg.index] = u, m, v
            out_s[seg.index], out_h[seg.index] = s, h
    out = (out_u, out_m, out_v, out_s)
    return out + (out_h,) if with_health else out


# ---------------------------------------------------------------------------
# Sharded execution: shard_map wrapping with per-leaf regime plans
# ---------------------------------------------------------------------------


def _use_sharded(mesh, spec_leaves) -> bool:
    """The sharded path engages only when both a mesh and specs are supplied
    and the mesh actually shards something — a trivial mesh runs the plain
    per-leaf path so single-device traces stay byte-identical."""
    if mesh is None or spec_leaves is None:
        return False
    from ..sharding.shardspec import mesh_is_trivial

    return not mesh_is_trivial(mesh)


def sharded_tree_plans(g_leaves: Sequence[Any], dims_leaves: Sequence[Dims],
                       spec_leaves: Sequence[Any], mesh, *, n_bufs: int = PRECOND_BUFS):
    """Per-leaf :class:`repro.sharding.shardspec.ShardLeafPlan` list for a
    tree update — the single planning step the sharded dispatchers below
    run, exposed so callers (tests, the sharded roofline) can inspect and
    count the regimes (`repro.sharding.shardspec.regime_counts`)."""
    from ..sharding.shardspec import plan_sharded_tree, spec_dtype

    return plan_sharded_tree([tuple(g.shape) for g in g_leaves],
                             [spec_dtype(g) for g in g_leaves],
                             [tuple(d) for d in dims_leaves],
                             list(spec_leaves), mesh, n_bufs=n_bufs)


def _owner_scatter(v_slice, owner, sizes):
    """Embed this shard's owner slice of the reduced moment into a zeros
    full-line buffer at its owned offset — the additive ``b2 * v`` term of
    the combined psum payload. Inverse of :func:`_owner_slice`."""
    out = v_slice
    for ax, dim in reversed(owner):
        blk = out.shape[dim]
        full = list(out.shape)
        full[dim] = blk * int(sizes[ax])
        out = jax.lax.dynamic_update_slice_in_dim(
            jnp.zeros(full, out.dtype), out, jax.lax.axis_index(ax) * blk, axis=dim)
    return out


def _owner_slice(v_full, owner, sizes):
    """This shard's owner slice of a completed full-line reduced moment."""
    for ax, dim in owner:
        blk = v_full.shape[dim] // int(sizes[ax])
        v_full = jax.lax.dynamic_slice_in_dim(
            v_full, jax.lax.axis_index(ax) * blk, blk, axis=dim)
    return v_full


def _psum_snr(s1c, s2c, first, v_new, pl, *, n_loc, red_total, b2):
    """Complete from-update SNR stats across the psum group: rebase each
    shard's centered g^2 sums to a mesh-common shift (exact O(spread)
    algebra), psum, finalize against the completed moment, and average the
    ratio over the kept-line shards."""
    from ..kernels.ref import rebase_centered_stats

    shift = jax.lax.pmean(first, pl.psum_axes)
    s1c, s2c = rebase_centered_stats(s1c, s2c, first, shift, n_loc)
    s1c = jax.lax.psum(s1c, pl.psum_axes)
    s2c = jax.lax.psum(s2c, pl.psum_axes)
    snr = snr_update_stats_finalize(v_new, s1c, s2c, red_total, 1.0 - b2,
                                    eps=_SNR_EPS)
    if pl.kept_axes:
        snr = jax.lax.pmean(snr, pl.kept_axes)
    return snr


def _psum_slim_leaf(g, m, v_red, dims: Dims, *, pl, sizes, b1, b2, eps, count,
                    use_first_moment: bool, interpret: bool,
                    emit_snr: bool = False, with_health: bool = False):
    """SlimAdam leaf whose reduced dims are split across ``pl.psum_axes``,
    Pallas-resident: pass 1 (``slim_partial_stats``) reads g, m and writes
    m_new plus per-line partial g^2 sums; a ``lax.psum`` over the owning
    mesh axes completes the lines; pass 2 (``slim_finalize``) reads m_new
    and writes the preconditioned update. The collective carries O(kept)
    bytes over ICI — the compressed moment's tininess is exactly what keeps
    the cross-shard completion cheap — and the leaf streams the slim path's
    5 full-size passes (g, m read; m' write; m' read; u write), charged
    exactly so by the sharded roofline.

    Owner-shard moment writes (``pl.owner``): instead of every shard in the
    psum group redundantly writing the same O(kept) v_new, each shard folds
    ``b2 * v`` for the kept lines it *owns* into the partial-sums payload —
    the all-reduce then delivers the completed v_new to every shard (the
    broadcast rides the collective, zero extra ICI) while the persistent
    store is each shard's 1/A owner slice. Leaves with no evenly-dividing
    kept dim (``pl.owner == ()``) keep PR-4's replicated write.

    ``emit_snr``: the partial-stats strip loop also emits centered g^2 line
    sums; the completed from-update SNR scalar (see
    :func:`jnp_update_snr_leaf`) is appended to the return.

    ``with_health``: the partial-stats strip loop also folds this shard's
    (2,) health accumulator (appended last, *local* — the caller completes
    it in the tree-wide stacked psum) — no extra pass over g, no extra
    collective on this leaf.

    Moments are computed in fp32 and cast back to the *stored* dtypes at the
    boundary, so bf16 optimizer states stay bf16 across the psum path
    (states/checkpoints used to silently promote to fp32 here).
    """
    m_dtype = m.dtype if m is not None else None
    v_dtype = v_red.dtype
    g32 = g.astype(jnp.float32)
    v32 = v_red.astype(jnp.float32)
    dset = {d % g.ndim for d in dims}
    red_local_shape = tuple(1 if i in dset else s for i, s in enumerate(g.shape))
    n_loc = 1
    for i in sorted(dset):
        n_loc *= g.shape[i]
    scale = (1.0 - b2) / pl.red_total

    def kernel_branch():
        cn = pl.cn
        to3 = (lambda x: x) if cn.batch > 1 else (lambda x: x[None])
        un3 = (lambda x: x) if cn.batch > 1 else (lambda x: x[0])
        outs = slim_partial_stats_batched(
            to3(canon_apply(g32, cn)), to3(canon_apply(m.astype(jnp.float32), cn)),
            axis=cn.axis, b1=b1, with_snr=emit_snr, with_health=with_health,
            interpret=interpret)
        m_new2, part2 = outs[0], outs[1]
        part = canon_restore(un3(part2), cn, red_local_shape)
        if pl.owner:
            payload = scale * part + b2 * _owner_scatter(v32, pl.owner, sizes)
            v_new = jax.lax.psum(payload, pl.psum_axes)
            u2 = slim_finalize_batched(
                m_new2, to3(canon_apply(v_new, cn, reduced_cols=True)),
                axis=cn.axis, ek=None, b1=b1, b2=b2, eps=eps, count=count,
                interpret=interpret)
            v_out = _owner_slice(v_new, pl.owner, sizes).astype(v_dtype)
        else:
            ek = jax.lax.psum(part, pl.psum_axes) / pl.red_total
            u2, v_new2 = slim_finalize_batched(
                m_new2, to3(canon_apply(v32, cn, reduced_cols=True)),
                axis=cn.axis, ek=to3(canon_apply(ek, cn, reduced_cols=True)),
                b1=b1, b2=b2, eps=eps, count=count, interpret=interpret)
            v_new = canon_restore(un3(v_new2), cn, red_local_shape)
            v_out = v_new.astype(v_dtype)
        u = canon_restore(un3(u2), cn, g.shape)
        m_new = canon_restore(un3(m_new2), cn, g.shape).astype(m_dtype)
        out = (u, m_new, v_out)
        if emit_snr:
            s1c, s2c, first = (canon_restore(un3(o), cn, red_local_shape)
                               for o in outs[2:5])
            out = out + (_psum_snr(s1c, s2c, first, v_new, pl, n_loc=n_loc,
                                   red_total=pl.red_total, b2=b2),)
        return out + (outs[-1],) if with_health else out

    def jnp_branch():
        # moment-less variant, a local plan the kernel pair cannot serve
        # ('psum_jnp' in regime_counts), or a degraded kernel leaf. Same
        # psum/owner algebra as the kernel pair.
        part = jnp.sum(g32 * g32, axis=tuple(sorted(dset)), keepdims=True)
        bc1, bc2 = bias_corrections(b1, b2, count)
        m_new = b1 * m.astype(jnp.float32) + (1 - b1) * g32 if use_first_moment else None
        if pl.owner:
            payload = scale * part + b2 * _owner_scatter(v32, pl.owner, sizes)
            v_new = jax.lax.psum(payload, pl.psum_axes)
            v_out = _owner_slice(v_new, pl.owner, sizes).astype(v_dtype)
        else:
            ek = jax.lax.psum(part, pl.psum_axes) / pl.red_total
            v_new = b2 * v32 + (1 - b2) * ek
            v_out = v_new.astype(v_dtype)
        num = m_new / bc1 if use_first_moment else g32
        u = num / (jnp.sqrt(v_new / bc2) + eps)
        m_out = m_new.astype(m_dtype) if use_first_moment else None
        out = (u, m_out, v_out)
        if emit_snr:
            from ..kernels.ref import snr_stats_centered_partial_ref

            _, s1c, s2c, first = snr_stats_centered_partial_ref(
                g32 * g32, tuple(sorted(dset)))
            out = out + (_psum_snr(s1c, s2c, first, v_new, pl, n_loc=n_loc,
                                   red_total=pl.red_total, b2=b2),)
        return out + (leaf_health(g32),) if with_health else out

    # The plan's local CanonND was gated by plan_sharded_leaf on the
    # partial/finalize pair's working sets — run exactly that plan (the
    # moment-less variant streams a discarded m, so it stays on jnp).
    from ..sharding.shardspec import psum_kernel_eligible

    if psum_kernel_eligible(pl, use_first_moment):
        return _guarded(f"psum:{g.shape}", kernel_branch, jnp_branch)
    return jnp_branch()


def _psum_mega_group(group, form: str, plans, gs, ms, vs, *, sizes, b1, b2,
                     eps, count, interpret, emit_snr: bool,
                     with_health: bool) -> Dict[int, tuple]:
    """One partial-stats launch + one finalize launch over a grouped psum
    super-tensor; the per-leaf cross-shard algebra (psum over each leaf's
    own ``psum_axes``, owner scatter/slice) runs between the two on the
    O(kept) lines, exactly as :func:`_psum_slim_leaf` does per leaf. The
    finalize pass consumes the partial pass's canonical m_new output
    directly — no re-gather. ``form`` is 'owner' or 'plain': the two
    finalize kernel signatures differ, so the caller partitions before
    grouping. Returns ``{leaf_index: _psum_slim_leaf-format tuple}``."""
    n = len(group.segments)
    batched = group.kind == "batched"
    to3 = (lambda x: x) if batched else (lambda x: x[None])
    un3 = (lambda x: x) if batched else (lambda x: x[0])
    cat = lambda lines: to3(jnp.concatenate(lines, axis=group.concat_axis))

    outs = megaplan.mega_slim_partial_stats_batched(
        to3(megaplan.gather_group(group, gs)),
        to3(megaplan.gather_group(group, ms)),
        axis=group.axis, b1=b1, with_snr=emit_snr, with_health=with_health,
        interpret=interpret)
    parts = megaplan.scatter_group(group, un3(outs[1]), reduced=True)

    v_lines: List[Any] = []
    ek_lines: List[Any] = []
    v_news: List[Any] = []   # per-leaf completed full-line moment (SNR)
    v_outs: List[Any] = [None] * n
    for j, seg in enumerate(group.segments):
        i = seg.index
        pl = plans[i]
        v32 = vs[i].astype(jnp.float32)
        scale = (1.0 - b2) / pl.red_total
        if form == "owner":
            payload = scale * parts[j] + b2 * _owner_scatter(v32, pl.owner, sizes)
            v_new = jax.lax.psum(payload, pl.psum_axes)
            v_lines.append(canon_apply(v_new, seg.cn, reduced_cols=True))
            v_outs[j] = _owner_slice(v_new, pl.owner, sizes).astype(vs[i].dtype)
        else:
            ek = jax.lax.psum(parts[j], pl.psum_axes) / pl.red_total
            v_lines.append(canon_apply(v32, seg.cn, reduced_cols=True))
            ek_lines.append(canon_apply(ek, seg.cn, reduced_cols=True))
            # same elementwise form the finalize kernel applies — kept full-
            # line for the SNR rebase; the stored slice comes from the kernel.
            v_new = b2 * v32 + (1 - b2) * ek
        v_news.append(v_new)

    bc1, bc2 = bias_corrections(b1, b2, count)
    l1 = to3(megaplan.segment_lines(group, [bc1] * n))
    l2 = to3(megaplan.segment_lines(group, [bc2] * n))
    if form == "owner":
        u_cat = megaplan.mega_slim_finalize_batched(
            outs[0], cat(v_lines), l1, l2, axis=group.axis, ek=None, b2=b2,
            eps=eps, interpret=interpret)
    else:
        u_cat, v_new_cat = megaplan.mega_slim_finalize_batched(
            outs[0], cat(v_lines), l1, l2, axis=group.axis, ek=cat(ek_lines),
            b2=b2, eps=eps, interpret=interpret)
        for j, (seg, v_red) in enumerate(zip(
                group.segments, megaplan.scatter_group(group, un3(v_new_cat),
                                                       reduced=True))):
            v_outs[j] = v_red.astype(vs[seg.index].dtype)
    us = megaplan.scatter_group(group, un3(u_cat))
    m_news = megaplan.scatter_group(group, un3(outs[0]))

    snrs: List[Any] = [None] * n
    if emit_snr:
        s1s = megaplan.scatter_group(group, un3(outs[2]), reduced=True)
        s2s = megaplan.scatter_group(group, un3(outs[3]), reduced=True)
        firsts = megaplan.scatter_group(group, un3(outs[4]), reduced=True)
        for j, seg in enumerate(group.segments):
            pl = plans[seg.index]
            dset = {d % len(seg.shape) for d in seg.dims}
            n_loc = math.prod(seg.shape[k] for k in sorted(dset))
            snrs[j] = _psum_snr(s1s[j], s2s[j], firsts[j], v_news[j], pl,
                                n_loc=n_loc, red_total=pl.red_total, b2=b2)
    hs: List[Any] = [None] * n
    if with_health:
        k = 5 if emit_snr else 2
        hs = [jnp.stack([jnp.sum(nf), jnp.sum(ss)])
              for nf, ss in zip(megaplan.scatter_lines(group, un3(outs[k])),
                                megaplan.scatter_lines(group, un3(outs[k + 1])))]

    res: Dict[int, tuple] = {}
    for j, seg in enumerate(group.segments):
        out = (us[j], m_news[j].astype(ms[seg.index].dtype), v_outs[j])
        if emit_snr:
            out = out + (snrs[j],)
        if with_health:
            out = out + (hs[j],)
        res[seg.index] = out
    return res


def _psum_mega_leaves(idx, plans, gs, ms, vs, dims_leaves, *, sizes, b1, b2,
                      eps, count, interpret, emit_snr: bool,
                      with_health: bool) -> Dict[int, tuple]:
    """Group the kernel-eligible psum leaves (``idx``) and run each group
    through the two-launch :func:`_psum_mega_group` pipeline. Owner-write
    and plain leaves partition first (different finalize forms); within a
    form, differing ``psum_axes`` don't split a group — each leaf's
    collective stays its own between the launches. A failing group degrades
    to per-leaf :func:`_psum_slim_leaf` calls."""
    owner_items: List[tuple] = []
    plain_items: List[tuple] = []
    for i in idx:
        pl = plans[i]
        dims = tuple(dims_leaves[i])
        shape = tuple(gs[i].shape)
        dset = {d % len(shape) for d in dims}
        red_shape = tuple(1 if j in dset else s for j, s in enumerate(shape))
        item = (i, shape, red_shape, dims, pl.cn)
        (owner_items if pl.owner else plain_items).append(item)
    out: Dict[int, tuple] = {}
    for form, items in (("owner", owner_items), ("plain", plain_items)):
        for group in megaplan.groups_from_plans(items):
            n = len(group.segments)

            def per_leaf(group=group):
                return {seg.index: _psum_slim_leaf(
                            gs[seg.index], ms[seg.index], vs[seg.index],
                            seg.dims, pl=plans[seg.index], sizes=sizes, b1=b1,
                            b2=b2, eps=eps, count=count, use_first_moment=True,
                            interpret=interpret, emit_snr=emit_snr,
                            with_health=with_health)
                        for seg in group.segments}

            out.update(_guarded(
                f"mega:psum:{group.kind}[{n}]",
                lambda group=group, form=form: _psum_mega_group(
                    group, form, plans, gs, ms, vs, sizes=sizes, b1=b1, b2=b2,
                    eps=eps, count=count, interpret=interpret,
                    emit_snr=emit_snr, with_health=with_health),
                per_leaf, leaves=n))
    return out


def _repl_factors(g_leaves, spec_leaves, mesh) -> jnp.ndarray:
    """(n, 1) fp32 — how many mesh devices hold a replica of each leaf's
    shard. Dividing a per-shard additive stat by this before a psum over
    *all* mesh axes yields the exact global total (replicas contribute
    duplicates; genuinely sharded leaves have factor mesh.size / n_shards)."""
    import math

    from ..sharding.shardspec import dim_shards

    total = math.prod(mesh.shape.values())
    repl = [total / math.prod(dim_shards(g.shape, s, mesh))
            for g, s in zip(g_leaves, spec_leaves)]
    return jnp.asarray(repl, jnp.float32)[:, None]


def _psum_health_rows(rows, repl, axes) -> jnp.ndarray:
    """Complete per-shard health rows across the mesh: one tiny (n, 2)
    psum for the whole tree — O(leaves) scalars over ICI, nothing per-leaf."""
    return jax.lax.psum(jnp.stack(list(rows)) / repl, axes)


def _sharded_adam_tree(g_leaves, mu_leaves, nu_leaves, spec_leaves, mesh, *,
                       b1, b2, eps, count, interpret, bucket_min_size,
                       with_health: bool = False, megakernel: bool = True):
    """Dense Adam under shard_map: elementwise math never crosses shards, so
    every device just runs the plain per-leaf path on its local shards (the
    leaf plans and bucketing decisions re-derive from local shapes). With
    ``with_health`` each shard's in-pass rows are completed by one stacked
    (n, 2) psum and returned as a replicated :class:`StepHealth`."""
    from ..sharding.shardspec import even_spec
    from jax.sharding import PartitionSpec as P

    specs = [even_spec(g.shape, s, mesh) for g, s in zip(g_leaves, spec_leaves)]
    axes = tuple(mesh.shape.keys())
    repl = _repl_factors(g_leaves, spec_leaves, mesh) if with_health else None

    def local_fn(count, gs, ms, vs):
        out = _adam_tree_local(gs, ms, vs, b1=b1, b2=b2, eps=eps, count=count,
                               interpret=interpret, bucket_min_size=bucket_min_size,
                               with_health=with_health, megakernel=megakernel)
        if not with_health:
            return out
        return out[:3] + (_psum_health_rows(out[3], repl, axes),)

    out_specs = (specs, specs, specs) + ((P(),) if with_health else ())
    fn = jax.shard_map(local_fn, mesh=mesh,
                       in_specs=(P(), specs, specs, specs),
                       out_specs=out_specs, check_vma=False)
    out = fn(count, list(g_leaves), list(mu_leaves), list(nu_leaves))
    if not with_health:
        return out
    h = out[3]
    return out[:3] + (StepHealth(nonfinite=h[:, 0], grad_sumsq=jnp.sum(h[:, 1])),)


def _sharded_slim_tree(g_leaves, mu_leaves, nu_leaves, dims_leaves, spec_leaves, mesh, *,
                       b1, b2, eps, count, use_first_moment, interpret,
                       bucket_min_size, emit_snr: bool = False,
                       with_health: bool = False, megakernel: bool = True):
    """SlimAdam under shard_map, three regimes per leaf (see
    ``repro.sharding.shardspec``): 'local' leaves run the unchanged kernel
    dispatch on their shard (kernels, bucketing, jnp fits-gate fallback all
    re-derived from local shapes); 'psum' leaves run the Pallas-resident
    partial-stats/finalize pair around a cross-shard ``lax.psum`` (with
    owner-shard moment storage where the plan found a placement); 'jnp'
    leaves (interleaved K after sharding) run the reference math on their
    shard. ``emit_snr`` appends a per-leaf from-update SNR scalar (None for
    K = () leaves) — the stats ride the update kernels' strip loops, psum-
    completed for sharded lines, so a measure step adds O(kept) traffic.
    ``with_health`` appends a replicated :class:`StepHealth`: every regime's
    local rows come from its own in-pass accumulator (psum leaves from the
    partial-stats kernel), completed by one stacked (n, 2) psum."""
    from jax.sharding import PartitionSpec as P

    plans = sharded_tree_plans(g_leaves, dims_leaves, spec_leaves, mesh,
                               n_bufs=PRECOND_SNR_BUFS if emit_snr else PRECOND_BUFS)
    sizes = dict(mesh.shape)
    g_specs = [pl.spec for pl in plans]
    v_specs = [pl.nu_spec if pl.nu_spec is not None else pl.red_spec
               for pl in plans]
    n = len(g_leaves)
    snr_idx = [i for i in range(n) if tuple(dims_leaves[i])] if emit_snr else []
    axes = tuple(mesh.shape.keys())
    repl = (_repl_factors(g_leaves, [pl.spec for pl in plans], mesh)
            if with_health else None)
    kw = dict(b1=b1, b2=b2, eps=eps)

    def dispatch(count, gs, ms, vs):
        out_u: List[Any] = [None] * n
        out_m: List[Any] = [None] * n
        out_v: List[Any] = [None] * n
        out_s: List[Any] = [None] * n
        out_h: List[Any] = [None] * n
        # Grouped psum launches: kernel-eligible psum leaves share one
        # partial-stats + one finalize launch per (form, regime key) group;
        # each leaf's cross-shard collective stays its own in between.
        mega_psum: Dict[int, tuple] = {}
        if megakernel and use_first_moment:
            from ..sharding.shardspec import psum_kernel_eligible

            elig = [i for i, pl in enumerate(plans)
                    if pl.regime == "psum"
                    and psum_kernel_eligible(pl, use_first_moment)]
            if elig:
                mega_psum = _psum_mega_leaves(
                    elig, plans, gs, ms, vs, dims_leaves, sizes=sizes,
                    count=count, interpret=interpret, emit_snr=emit_snr,
                    with_health=with_health, **kw)
        local_idx = [i for i, pl in enumerate(plans) if pl.regime == "local"]
        if local_idx:
            out = _slim_tree_local(
                [gs[i] for i in local_idx],
                [ms[i] for i in local_idx] if use_first_moment else None,
                [vs[i] for i in local_idx],
                [tuple(dims_leaves[i]) for i in local_idx],
                count=count, use_first_moment=use_first_moment,
                interpret=interpret, bucket_min_size=bucket_min_size,
                emit_snr=emit_snr, with_health=with_health,
                megakernel=megakernel, **kw)
            u, mo, vo = out[:3]
            for j, i in enumerate(local_idx):
                out_u[i] = u[j]
                out_m[i] = mo[j] if use_first_moment else None
                out_v[i] = vo[j]
                if with_health:
                    out_h[i] = out[4][j]
                if emit_snr and out[3][j] is not None:
                    s = out[3][j]
                    pl = plans[i]
                    # lines are sharded over the kept axes: the global ratio
                    # mean is the mean of the equal-count per-shard means.
                    out_s[i] = jax.lax.pmean(s, pl.kept_axes) if pl.kept_axes else s
        for i, pl in enumerate(plans):
            if pl.regime == "local":
                continue
            dims = tuple(dims_leaves[i])
            m_i = ms[i] if use_first_moment else None
            if pl.regime == "psum":
                if i in mega_psum:
                    out = mega_psum[i]
                else:
                    out = _psum_slim_leaf(gs[i], m_i, vs[i], dims, pl=pl, sizes=sizes,
                                          count=count, use_first_moment=use_first_moment,
                                          interpret=interpret, emit_snr=emit_snr,
                                          with_health=with_health, **kw)
            else:  # 'jnp': reduced dims whole on the shard, reference math
                out = jnp_slim_leaf(gs[i], m_i, vs[i], dims, count=count,
                                    use_first_moment=use_first_moment, **kw)
                if emit_snr:
                    s = jnp_update_snr_leaf(gs[i], out[2], dims, b2=b2)
                    s = jax.lax.pmean(s, pl.kept_axes) if pl.kept_axes else s
                    out = out + (s,)
                if with_health:
                    out = out + (leaf_health(gs[i]),)
            out_u[i], out_m[i], out_v[i] = out[:3]
            if with_health:
                out_h[i] = out[-1]
            if emit_snr:
                out_s[i] = out[3]
        res = (out_u, out_m, out_v)
        if emit_snr:
            res = res + ([out_s[i] for i in snr_idx],)
        if with_health:
            res = res + (_psum_health_rows(out_h, repl, axes),)
        return res

    snr_specs = [P() for _ in snr_idx]

    def unpack(res):
        """Normalize dispatch's variadic return to (u, m, v, snr_list_or_None,
        health_rows_or_None) with snr scattered back to all-leaves indexing."""
        h = res[-1] if with_health else None
        if emit_snr:
            snr = res[3]
            out_s: List[Any] = [None] * n
            for j, i in enumerate(snr_idx):
                out_s[i] = snr[j]
        else:
            out_s = None
        return res[0], res[1], res[2], out_s, h

    health_spec = (P(),) if with_health else ()
    if use_first_moment:
        def local_fn(count, gs, ms, vs):
            return dispatch(count, gs, ms, vs)

        out_specs = ((g_specs, g_specs, v_specs)
                     + ((snr_specs,) if emit_snr else ()) + health_spec)
        fn = jax.shard_map(local_fn, mesh=mesh,
                           in_specs=(P(), g_specs, g_specs, v_specs),
                           out_specs=out_specs, check_vma=False)
        res = fn(count, list(g_leaves), list(mu_leaves), list(nu_leaves))
        u, mo, vo, out_s, h = unpack(res)
    else:
        def local_fn_no_mu(count, gs, vs):
            out = dispatch(count, gs, None, vs)
            return (out[0],) + out[2:]

        out_specs = ((g_specs, v_specs)
                     + ((snr_specs,) if emit_snr else ()) + health_spec)
        fn = jax.shard_map(local_fn_no_mu, mesh=mesh,
                           in_specs=(P(), g_specs, v_specs),
                           out_specs=out_specs, check_vma=False)
        res = fn(count, list(g_leaves), list(nu_leaves))
        u, _, vo, out_s, h = unpack((res[0], None) + res[1:])
        mo = None
    out = (u, mo, vo)
    if emit_snr:
        out = out + (out_s,)
    if with_health:
        out = out + (StepHealth(nonfinite=h[:, 0], grad_sumsq=jnp.sum(h[:, 1])),)
    return out


# ---------------------------------------------------------------------------
# Tree-level entry points (operate on flat leaf lists; the transformations
# own flatten/unflatten so pytree structure stays their concern)
# ---------------------------------------------------------------------------


def _adam_tree_local(g_leaves, mu_leaves, nu_leaves, *, b1, b2, eps, count,
                     interpret, bucket_min_size, with_health: bool = False,
                     megakernel: bool = True):
    """Unsharded dense-Adam dispatch; with ``with_health`` also returns the
    per-leaf (2,) health rows (kernel accumulators for kernel leaves, the
    fused jnp sums otherwise). The default is the megaplan path (one grouped
    launch for the whole tree — ``bucket_min_size`` is moot there, every
    kernel leaf joins the dense group); ``megakernel=False`` keeps the
    per-leaf/bucketed loop as the parity oracle."""
    if megakernel:
        return _adam_tree_mega(g_leaves, mu_leaves, nu_leaves, b1=b1, b2=b2,
                               eps=eps, count=count, interpret=interpret,
                               with_health=with_health)
    kw = dict(b1=b1, b2=b2, eps=eps, count=count)
    n = len(g_leaves)
    out_u: List[Any] = [None] * n
    out_m: List[Any] = [None] * n
    out_v: List[Any] = [None] * n
    out_h: List[Any] = [None] * n
    bucket: List[int] = []
    for i, (g, m, v) in enumerate(zip(g_leaves, mu_leaves, nu_leaves)):
        if leaf_plan(g.shape, g.dtype, ()).route == "jnp":
            out_u[i], out_m[i], out_v[i] = jnp_adam_leaf(g, m, v, **kw)
            if with_health:
                out_h[i] = leaf_health(g)
        elif _bucket_eligible(g.size, bucket_min_size):
            bucket.append(i)
        else:
            out = _guarded(
                f"dense:{g.shape}",
                lambda g=g, m=m, v=v: _dense_kernel_leaf(
                    g, m, v, interpret=interpret, with_health=with_health, **kw),
                lambda g=g, m=m, v=v: jnp_adam_leaf(g, m, v, **kw)
                    + ((leaf_health(g),) if with_health else ()))
            out_u[i], out_m[i], out_v[i] = out[:3]
            if with_health:
                out_h[i] = out[3]
    _flush_bucket(bucket, g_leaves, mu_leaves, nu_leaves, out_u, out_m, out_v,
                  interpret=interpret, out_h=out_h if with_health else None, **kw)
    if with_health:
        return out_u, out_m, out_v, out_h
    return out_u, out_m, out_v


def adam_tree_update(g_leaves: Sequence[jnp.ndarray], mu_leaves: Sequence[jnp.ndarray],
                     nu_leaves: Sequence[jnp.ndarray], *, b1: float, b2: float,
                     eps: float, count, interpret: Optional[bool] = None,
                     bucket_min_size: int = DEFAULT_BUCKET_MIN,
                     mesh=None, spec_leaves=None, with_health: bool = False,
                     megakernel: bool = True):
    """Dense Adam over a leaf list: by default one megaplan group launch for
    every kernel-eligible leaf (O(1) pallas_calls per update), jnp fallback
    per excluded leaf. ``megakernel=False`` restores the per-leaf dispatch
    (small leaves bucketed) — the parity oracle the megaplan tests diff
    against. Returns (updates, new_mu, new_nu).

    With ``mesh`` + ``spec_leaves`` (one PartitionSpec per leaf) the whole
    update runs under ``shard_map`` — each device updates its local shards —
    instead of letting GSPMD gather full leaves around the pallas_call
    optimization barrier.

    ``with_health=True`` appends a :class:`StepHealth` — per-leaf non-finite
    counts and the finite-masked global grad sumsq, accumulated in the same
    kernel/XLA passes that stream the update (O(leaves) scalar outputs, no
    extra tensor traffic; under a mesh, one stacked (n, 2) psum)."""
    interpret = default_interpret(interpret)
    if _use_sharded(mesh, spec_leaves) and len(g_leaves):
        return _sharded_adam_tree(g_leaves, mu_leaves, nu_leaves, spec_leaves, mesh,
                                  b1=b1, b2=b2, eps=eps, count=count,
                                  interpret=interpret, bucket_min_size=bucket_min_size,
                                  with_health=with_health, megakernel=megakernel)
    out = _adam_tree_local(g_leaves, mu_leaves, nu_leaves, b1=b1, b2=b2, eps=eps,
                           count=count, interpret=interpret,
                           bucket_min_size=bucket_min_size, with_health=with_health,
                           megakernel=megakernel)
    if with_health:
        return out[:3] + (_health_from_rows(out[3]),)
    return out


def _slim_tree_local(g_leaves, mu_leaves, nu_leaves, dims_leaves, *, b1, b2, eps,
                     count, use_first_moment, interpret, bucket_min_size,
                     emit_snr: bool = False, with_health: bool = False,
                     megakernel: bool = True):
    """Unsharded SlimAdam dispatch. Returns ``(u, m, v, snr_list)`` plus,
    with ``with_health``, the per-leaf (2,) health rows as a fifth element.
    Default is the megaplan path (O(groups) launches); ``megakernel=False``
    keeps the per-leaf/bucketed loop as the parity oracle. The moment-less
    variant runs entirely on jnp either way."""
    kw = dict(b1=b1, b2=b2, eps=eps, count=count)
    n = len(g_leaves)
    out_s: List[Any] = [None] * n
    out_h: List[Any] = [None] * n
    if not use_first_moment:
        outs = [jnp_slim_leaf(g, None, v, tuple(d), use_first_moment=False, **kw)
                for g, v, d in zip(g_leaves, nu_leaves, dims_leaves)]
        if emit_snr:
            out_s = [jnp_update_snr_leaf(g, o[2], tuple(d), b2=b2) if tuple(d) else None
                     for g, o, d in zip(g_leaves, outs, dims_leaves)]
        if with_health:
            out_h = [leaf_health(g) for g in g_leaves]
        out = ([o[0] for o in outs], None, [o[2] for o in outs], out_s)
        return out + (out_h,) if with_health else out
    if megakernel:
        return _slim_tree_mega(g_leaves, mu_leaves, nu_leaves, dims_leaves,
                               interpret=interpret, emit_snr=emit_snr,
                               with_health=with_health, **kw)
    out_u: List[Any] = [None] * n
    out_m: List[Any] = [None] * n
    out_v: List[Any] = [None] * n
    bucket: List[int] = []
    # The with_snr kernel variant keeps an extra shifted-g^2 copy live, so
    # measure steps gate the VMEM fit on its larger working set (a leaf near
    # the budget may route jnp on measure steps while staying fused on
    # plain steps — different jitted executables anyway).
    n_bufs = PRECOND_SNR_BUFS if emit_snr else PRECOND_BUFS
    for i, (g, v, dims) in enumerate(zip(g_leaves, nu_leaves, dims_leaves)):
        dims = tuple(dims)
        plan = leaf_plan(g.shape, g.dtype, dims, n_bufs=n_bufs)
        if plan.route == "jnp":
            out_u[i], out_m[i], out_v[i] = jnp_slim_leaf(
                g, mu_leaves[i], v, dims, use_first_moment=True, **kw)
            if emit_snr and dims:
                out_s[i] = jnp_update_snr_leaf(g, out_v[i], dims, b2=b2)
            if with_health:
                out_h[i] = leaf_health(g)
        elif plan.route == "dense":
            if _bucket_eligible(g.size, bucket_min_size):
                bucket.append(i)
            else:
                out = _guarded(
                    f"dense:{g.shape}",
                    lambda g=g, m=mu_leaves[i], v=v: _dense_kernel_leaf(
                        g, m, v, interpret=interpret, with_health=with_health, **kw),
                    lambda g=g, m=mu_leaves[i], v=v: jnp_adam_leaf(g, m, v, **kw)
                        + ((leaf_health(g),) if with_health else ()))
                out_u[i], out_m[i], out_v[i] = out[:3]
                if with_health:
                    out_h[i] = out[3]
        else:
            def slim_jnp_fallback(g=g, m=mu_leaves[i], v=v, dims=dims):
                out = jnp_slim_leaf(g, m, v, dims, use_first_moment=True, **kw)
                if emit_snr:
                    out = out + (jnp_update_snr_leaf(g, out[2], dims, b2=b2)
                                 if dims else None,)
                return out + ((leaf_health(g),) if with_health else ())

            out = _guarded(
                f"slim:{g.shape}",
                lambda g=g, m=mu_leaves[i], v=v, cn=plan.cn: _slim_kernel_leaf(
                    g, m, v, cn, interpret=interpret, with_snr=emit_snr,
                    with_health=with_health, **kw),
                slim_jnp_fallback)
            out_u[i], out_m[i], out_v[i] = out[:3]
            if with_health:
                out_h[i] = out[-1]
            if emit_snr:
                out_s[i] = out[3]
    _flush_bucket(bucket, g_leaves, mu_leaves, nu_leaves, out_u, out_m, out_v,
                  interpret=interpret, out_h=out_h if with_health else None, **kw)
    out = (out_u, out_m, out_v, out_s)
    return out + (out_h,) if with_health else out


def slim_tree_update(g_leaves: Sequence[jnp.ndarray], mu_leaves: Optional[Sequence[jnp.ndarray]],
                     nu_leaves: Sequence[jnp.ndarray], dims_leaves: Sequence[Dims], *,
                     b1: float, b2: float, eps: float, count,
                     use_first_moment: bool = True, interpret: Optional[bool] = None,
                     bucket_min_size: int = DEFAULT_BUCKET_MIN,
                     mesh=None, spec_leaves=None, emit_snr: bool = False,
                     with_health: bool = False, megakernel: bool = True):
    """SlimAdam over a leaf list with per-leaf reduction-dim tuples.

    Each leaf's route comes from one :func:`leaf_plan` lookup: K = () leaves
    take the dense route; K != () leaves the slim kernel named by their
    canonical plan; leaves no kernel can serve fall back to jnp. By default
    kernel leaves run through the megaplan (same-regime leaves concatenated,
    O(groups) launches per tree — see ``repro.kernels.megaplan``);
    ``megakernel=False`` restores the per-leaf dispatch (small dense leaves
    bucketed), the parity oracle. ``use_first_moment=False`` runs entirely
    on the jnp path — the kernels read/write a first moment, so serving the
    moment-less variant would stream a discarded full-size m and forfeit the
    bandwidth win. Returns (updates, new_mu_or_None, new_nu).

    ``emit_snr=True`` appends a fourth element: a per-leaf list of
    from-update SNR scalars (None for K = () leaves) — SNR_K of the step's
    dense reconstruction ``b2 * V + (1 - b2) * g^2``, the paper's
    compressibility diagnostic riding the update pass. Kernel-served leaves
    emit the centered g^2 line sums from the same strip loop (O(kept) extra
    traffic, zero extra full-size passes); jnp-fallback leaves compute the
    same quantity in the already-fused XLA pass.

    With ``mesh`` + ``spec_leaves`` the update runs under ``shard_map`` with
    per-leaf regime plans (``repro.sharding.shardspec``): leaves whose
    reduced dims are whole per shard run the kernels locally on the shard,
    leaves whose reduced dims are split run the Pallas partial-stats /
    finalize pair around a ``lax.psum`` over the owning mesh axes (with
    owner-shard moment storage riding the collective), and interleaved-K-
    after-sharding leaves run the reference jnp math per shard.

    ``with_health=True`` appends a :class:`StepHealth` (always the last
    element): per-leaf non-finite counts + finite-masked global grad sumsq,
    accumulated by the update kernels' own strip loops (O(leaves) scalar
    outputs, no new tensor traffic; under a mesh, one stacked (n, 2) psum).

    Kernel-ineligible or Pallas-failing leaves degrade to the reference jnp
    math per leaf (see :func:`set_kernel_fault_hook` /
    :func:`kernel_degraded_leaves`) — a compile regression costs bandwidth,
    not the run."""
    interpret = default_interpret(interpret)
    if _use_sharded(mesh, spec_leaves) and len(g_leaves):
        return _sharded_slim_tree(g_leaves, mu_leaves, nu_leaves, dims_leaves,
                                  spec_leaves, mesh, b1=b1, b2=b2, eps=eps,
                                  count=count, use_first_moment=use_first_moment,
                                  interpret=interpret, bucket_min_size=bucket_min_size,
                                  emit_snr=emit_snr, with_health=with_health,
                                  megakernel=megakernel)
    res = _slim_tree_local(g_leaves, mu_leaves, nu_leaves, dims_leaves,
                           b1=b1, b2=b2, eps=eps, count=count,
                           use_first_moment=use_first_moment, interpret=interpret,
                           bucket_min_size=bucket_min_size, emit_snr=emit_snr,
                           with_health=with_health, megakernel=megakernel)
    out = res[:3] + ((res[3],) if emit_snr else ())
    if with_health:
        out = out + (_health_from_rows(res[4]),)
    return out
