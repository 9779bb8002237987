"""jit'd public wrappers + the canonicalization planner for the Pallas kernels.

The slim/SNR kernels operate on one batched canonical form: ``(B, R, C)``
with the reduction confined to a single trailing-ish axis of the per-batch
2-D problem — lanes (minor, reduce C) or sublanes (major, reduce R). The
planner (:func:`canon_nd`) maps any leaf shape and any reduction-dims
subset onto that form by *pure reshape* whenever memory order allows:

  * reduced dims trailing                  -> (1, kept, red), minor;
  * reduced dims leading                   -> (1, red, kept), major;
  * kept prefix / reduced block / kept suffix
    (scan-stacked leaves: ``(layers, embed, heads, hd)`` reducing embed)
                                           -> (B, red, kept), batched major
    — the kept prefix splits off as a batch axis walked by the kernel grid,
    so each batch slice is a transpose-free major-axis 2-D problem.

Size-1 axes never affect reachability (moving them never changes memory
order). Only a genuinely interleaved K — the non-trivial reduced dims not
forming one contiguous block that is trailing, leading, or kept-flanked on
both sides (e.g. a kept dim inside the reduced span, or reduced blocks on
both ends of a kept dim) — falls back to a kept-dims-major transpose,
which *materializes*: a pallas_call is an optimization barrier, so XLA
cannot fuse a re-layout into the kernel, costing extra HBM passes per
transposed operand (``is_transpose`` exposes this so byte models can
account for it).

:func:`leaf_plan` is the single per-leaf dispatch decision built on top:
plan -> VMEM fits-gate -> route (dense kernel / slim kernel / jnp
fallback), consumed by ``repro.optim.fused``, ``repro.core.snr``, and
:func:`slim_update_nd`; the opt_speed roofline byte model consumes the raw
:func:`canon_nd` plans (it charges bytes per layout, not per route).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .fused_adam import adam_precond, bias_corrections, fused_adam
from .ref import snr_from_centered_stats
from .slim_update import (
    PRECOND_BUFS,
    UPDATE_BUFS,
    slim_finalize,
    slim_finalize_batched,
    slim_partial_stats,
    slim_partial_stats_batched,
    slim_precond,
    slim_precond_batched,
    slim_precond_major,
    slim_update,
    slim_update_batched,
    slim_update_major,
)
from .snr_stats import (
    snr_stats,
    snr_stats_centered,
    snr_stats_centered_batched,
    snr_stats_centered_major,
    snr_stats_centered_partial,
    snr_stats_centered_partial_batched,
)
from .tiling import default_interpret, strip_fits

__all__ = ["fused_adam_op", "slim_update_op", "slim_update_nd", "snr_op",
           "snr_partial_op", "fused_adam", "slim_update", "slim_update_major",
           "slim_update_batched", "adam_precond", "slim_precond",
           "slim_precond_major", "slim_precond_batched",
           "slim_partial_stats", "slim_partial_stats_batched",
           "slim_finalize", "slim_finalize_batched", "snr_stats",
           "snr_stats_centered", "snr_stats_centered_major",
           "snr_stats_centered_batched", "snr_stats_centered_partial",
           "snr_stats_centered_partial_batched", "CanonND", "Canon2D",
           "canon_nd", "canon2d", "canon_apply", "canon_restore", "LeafPlan",
           "leaf_plan", "default_interpret"]


class CanonND(NamedTuple):
    """Plan for canonicalizing an n-D reduction to the kernels' batched
    (B, R, C) layouts.

    ``axis`` is the reduction axis of the *per-batch 2-D problem* (1 = minor
    / lanes, 0 = major / sublanes), matching the kernel orientations. The
    canonical view is 2-D ``(rows, cols)`` when ``batch == 1`` and 3-D
    ``(batch, rows, cols)`` otherwise; batched plans are always major-axis
    (a trailing reduction folds every kept prefix into rows instead, so
    minor never needs a batch dim) and always reshape-only.
    """

    perm: Tuple[int, ...]       # permutation applied before the reshape
    inv: Tuple[int, ...]        # inverse permutation
    batch: int                  # kept-prefix batch extent (1 = plain 2-D)
    rows: int                   # per-batch leading extent
    cols: int                   # per-batch trailing extent
    axis: int                   # per-batch 2-D reduction axis: 1 | 0
    reshape_only: bool          # True -> canon_apply is a pure reshape

    @property
    def orientation(self) -> str:
        return "minor" if self.axis == 1 else "major"

    @property
    def kept_size(self) -> int:
        """Stored reduced-moment extent (the O(kept) side channel),
        including the batch dim."""
        return self.batch * (self.rows if self.axis == 1 else self.cols)

    @property
    def red_size(self) -> int:
        """Reduction extent — the line a kernel instance must hold whole
        (batch-independent: batch rides on the grid, not in VMEM)."""
        return self.cols if self.axis == 1 else self.rows

    @property
    def view(self) -> Tuple[int, ...]:
        """Shape of the canonical view ``canon_apply`` produces."""
        if self.batch > 1:
            return (self.batch, self.rows, self.cols)
        return (self.rows, self.cols)

    @property
    def red_axis(self) -> int:
        """Reduction axis within :attr:`view` (for jnp means over it)."""
        return self.axis + 1 if self.batch > 1 else self.axis

    @property
    def is_transpose(self) -> bool:
        return not self.reshape_only


# Back-compat alias: pre-batched callers imported the 2-D plan class.
Canon2D = CanonND


def canon_nd(shape: Tuple[int, ...], dims: Tuple[int, ...]) -> CanonND:
    """Plan a batched canonical view of ``shape`` for reduction dims ``dims``
    (any non-empty subset of axes), preferring a transpose-free plan."""
    ndim = len(shape)
    if not dims:
        raise ValueError("canon_nd needs a non-empty reduction dim set")
    for d in dims:
        if not -ndim <= d < ndim:
            # Match the jnp path's behavior (jnp.mean raises) — a silent
            # d % ndim wrap would reduce the wrong axis.
            raise ValueError(f"reduction dim {d} out of range for shape {shape}")
    dset = {d % ndim for d in dims}
    if len(dset) != len(dims):
        # jnp.mean also rejects aliased axes like (1, -1); keep parity.
        raise ValueError(f"duplicate reduction dims in {dims} for shape {shape}")
    red = tuple(sorted(dset))
    kept = tuple(i for i in range(ndim) if i not in dset)
    red_size = kept_size = 1
    for i in red:
        red_size *= shape[i]
    for i in kept:
        kept_size *= shape[i]

    # Reshape-reachability ignores size-1 axes: shuffling them around never
    # changes memory order, so only the relative order of the non-trivial
    # reduced vs kept axes matters.
    nt_red = [i for i in red if shape[i] > 1]
    nt_kept = [i for i in kept if shape[i] > 1]
    minor_ok = not nt_red or not nt_kept or max(nt_kept) < min(nt_red)
    major_ok = not nt_red or not nt_kept or max(nt_red) < min(nt_kept)

    def _plan(perm, batch, rows, cols, axis, reshape_only):
        inv = [0] * ndim
        for newpos, old in enumerate(perm):
            inv[old] = newpos
        return CanonND(perm=perm, inv=tuple(inv), batch=batch, rows=rows,
                       cols=cols, axis=axis, reshape_only=reshape_only)

    if minor_ok:
        return _plan(kept + red, 1, kept_size, red_size, 1, True)
    if major_ok:
        return _plan(red + kept, 1, red_size, kept_size, 0, True)
    # Batched major: a contiguous non-trivial reduced block with kept axes
    # on both sides — split the kept prefix off as the batch dim, leaving
    # each batch slice a pure-reshape major-axis 2-D problem. Covers every
    # scan-stacked leaf (layers leading, reduction inner).
    lo, hi = min(nt_red), max(nt_red)
    if all(k < lo or k > hi for k in nt_kept):
        batch = math.prod(shape[:lo])
        rows = math.prod(shape[lo:hi + 1])      # == red_size (interior kept are size-1)
        cols = math.prod(shape[hi + 1:])
        return _plan(tuple(range(ndim)), batch, rows, cols, 0, True)
    return _plan(kept + red, 1, kept_size, red_size, 1, False)


# Back-compat alias: ``canon_nd`` subsumes the 2-D planner (batch-free
# shapes get identical plans with batch == 1).
canon2d = canon_nd


def canon_apply(x: jnp.ndarray, cn: CanonND, *, reduced_cols: bool = False) -> jnp.ndarray:
    """Bring a full tensor (or a size-1-reduced-dims reduced moment, with
    ``reduced_cols=True``) into the kernel's canonical layout — 2-D
    (rows, cols) for batch-free plans, 3-D (batch, rows, cols) for batched
    ones. The reduced moment collapses the plan's reduction axis to 1."""
    if cn.batch > 1:
        # Batched plans are always reshape-only major (reduce rows).
        target = (cn.batch, 1, cn.cols) if reduced_cols else cn.view
        return x.reshape(target)
    if reduced_cols:
        target = (cn.rows, 1) if cn.axis == 1 else (1, cn.cols)
    else:
        target = (cn.rows, cn.cols)
    if cn.reshape_only:
        return x.reshape(target)
    return jnp.transpose(x, cn.perm).reshape(target)


def canon_restore(y2: jnp.ndarray, cn: CanonND, shape: Tuple[int, ...]) -> jnp.ndarray:
    """Inverse of :func:`canon_apply` back to the original layout ``shape``
    (pass the reduced/stored shape for reduced moments)."""
    if cn.reshape_only:
        return y2.reshape(shape)
    permuted = tuple(shape[i] for i in cn.perm)
    return jnp.transpose(y2.reshape(permuted), cn.inv)


class LeafPlan(NamedTuple):
    """Precomputed per-leaf dispatch decision: plan -> fits-gate -> route,
    in one place. ``route`` is 'dense' (K = (), dense kernels), 'slim'
    (compressed, ``cn`` holds the canonical plan), or 'jnp' (the per-leaf
    fallback: scalar/empty/non-float leaves, reduction lines that outrun
    VMEM, or transposing plans when the caller forbids them)."""

    route: str                  # 'dense' | 'slim' | 'jnp'
    cn: Optional[CanonND]       # set iff route == 'slim'


def leaf_plan(shape: Tuple[int, ...], dtype, dims: Tuple[int, ...], *,
              n_bufs: int = PRECOND_BUFS, allow_transpose: bool = True) -> LeafPlan:
    """Plan one leaf's kernel dispatch.

    ``n_bufs`` is the consuming kernel's live full-size fp32 buffer count
    per instance (``slim_update.PRECOND_BUFS`` / ``UPDATE_BUFS``,
    ``snr_stats.CENTERED_BUFS``) — the VMEM fits-gate is orientation-aware
    through the plan's ``red_size`` and batch-independent (batch rides on
    the grid). ``allow_transpose=False`` routes genuinely interleaved-K
    leaves to jnp instead — right for consumers whose single-pass win a
    materialized boundary transpose would forfeit (SNR stats).
    """
    if not (len(shape) >= 1 and math.prod(shape) > 0
            and jnp.issubdtype(dtype, jnp.floating)):
        return LeafPlan("jnp", None)
    dims = tuple(dims)
    if not dims:
        return LeafPlan("dense", None)
    cn = canon_nd(shape, dims)
    if not strip_fits(cn.red_size, n_bufs):
        # A single canonical reduction line outruns VMEM (full-reduction K
        # on a big tensor) — no strip kernel can serve it on a real TPU.
        return LeafPlan("jnp", None)
    if cn.is_transpose and not allow_transpose:
        return LeafPlan("jnp", None)
    return LeafPlan("slim", cn)


@functools.partial(jax.jit, static_argnames=("lr", "b1", "b2", "eps", "wd", "count", "interpret"))
def fused_adam_op(p, g, m, v, *, lr, b1=0.9, b2=0.95, eps=1e-8, wd=0.0, count=1,
                  interpret=None):
    shape = p.shape
    p2 = p.reshape(-1, shape[-1]) if p.ndim != 2 else p
    g2 = g.reshape(p2.shape)
    m2 = m.reshape(p2.shape)
    v2 = v.reshape(p2.shape)
    po, mo, vo = fused_adam(p2, g2, m2, v2, lr=lr, b1=b1, b2=b2, eps=eps, wd=wd,
                            count=count, interpret=interpret)
    return po.reshape(shape), mo.reshape(shape), vo.reshape(shape)


@functools.partial(jax.jit, static_argnames=("axis", "lr", "b1", "b2", "eps", "wd", "count", "interpret"))
def slim_update_op(p, g, m, v_red, *, axis: int, lr, b1=0.9, b2=0.95, eps=1e-8,
                   wd=0.0, count=1, interpret=None):
    """2-D params; ``axis`` is the compressed (reduced) dim. v_red keeps the
    reduced dim as size 1 (matching repro.core.slim_adam state layout).
    axis=0 runs the major-axis (sublane-reduction) kernel — no transpose."""
    assert p.ndim == 2 and axis in (0, 1)
    if axis == 0:
        return slim_update_major(p, g, m, v_red, lr=lr, b1=b1, b2=b2, eps=eps,
                                 wd=wd, count=count, interpret=interpret)
    return slim_update(p, g, m, v_red, lr=lr, b1=b1, b2=b2, eps=eps, wd=wd,
                       count=count, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("dims", "lr", "b1", "b2", "eps", "wd", "count", "interpret"))
def slim_update_nd(p, g, m, v_red, *, dims: Tuple[int, ...], lr, b1=0.9, b2=0.95,
                   eps=1e-8, wd=0.0, count=1, interpret=None):
    """n-D params, any reduction-dims subset (the general SlimAdam spec).

    ``v_red`` keeps the reduced axes as size 1, matching
    ``repro.core.slim_adam`` state layout. :func:`leaf_plan` picks whichever
    batched (B, R, C) layout avoids a transpose — including the
    batched-major form for scan-stacked leaves — and this dispatches to the
    matching kernel, restoring the original layout after. Leaves the strip
    kernels can't serve (a reduction line that outruns VMEM, odd dtypes)
    run the same semantics in plain jnp.
    """
    plan = leaf_plan(p.shape, p.dtype, dims, n_bufs=UPDATE_BUFS)
    if plan.route != "slim":
        g32 = g.astype(jnp.float32)
        m_new = b1 * m + (1 - b1) * g32
        ek = jnp.mean(jnp.square(g32), axis=dims, keepdims=True)
        v_new = b2 * v_red + (1 - b2) * ek
        bc1, bc2 = bias_corrections(b1, b2, count)
        update = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps)
        if wd:
            update = update + wd * p.astype(jnp.float32)
        p_new = (p.astype(jnp.float32) - lr * update).astype(p.dtype)
        return p_new, m_new, v_new
    cn = plan.cn
    p2 = canon_apply(p, cn)
    g2 = canon_apply(g, cn)
    m2 = canon_apply(m, cn)
    v2 = canon_apply(v_red, cn, reduced_cols=True)
    kw = dict(lr=lr, b1=b1, b2=b2, eps=eps, wd=wd, count=count, interpret=interpret)
    if cn.batch > 1:
        po, mo, vo = slim_update_batched(p2, g2, m2, v2, axis=cn.axis, **kw)
    else:
        fn = slim_update if cn.axis == 1 else slim_update_major
        po, mo, vo = fn(p2, g2, m2, v2, **kw)
    return (canon_restore(po, cn, p.shape), canon_restore(mo, cn, m.shape),
            canon_restore(vo, cn, v_red.shape))


@functools.partial(jax.jit, static_argnames=("axis", "interpret"))
def snr_partial_op(v, *, axis: int = 1, interpret=None):
    """Per-line partial centered stats of a canonical moment view, flattened
    to 1-D: (line_sum, shifted_line_sum, shifted_line_sumsq, line_first).

    The sharded-SNR building block: each device runs this on its local shard
    of the canonical (rows, cols) / (batch, rows, cols) view, rebases the
    shifted sums to a mesh-common shift
    (:func:`repro.kernels.ref.rebase_centered_stats`), and ``lax.psum``-s
    them over the mesh axes owning the reduction dim before the
    :func:`repro.kernels.ref.snr_from_centered_stats` finalization."""
    if v.ndim == 2:
        v = v[None]
    s1, s1c, s2c, f = snr_stats_centered_partial_batched(v, axis=axis, interpret=interpret)
    return s1.ravel(), s1c.ravel(), s2c.ravel(), f.ravel()


@functools.partial(jax.jit, static_argnames=("axis", "interpret"))
def snr_op(v, *, axis: int = 1, interpret=None) -> jnp.ndarray:
    """Scalar SNR over a canonical moment view via the fused centered-stats
    kernels (accurate for near-constant, high-SNR lines). ``v`` is 2-D
    (rows, cols) or batched 3-D (batch, rows, cols); ``axis`` is the
    per-batch 2-D reduction axis (1 = lanes, 0 = sublanes)."""
    n = v.shape[-1] if axis == 1 else v.shape[-2]
    if v.ndim == 3:
        s1, s1c, s2c = snr_stats_centered_batched(v, axis=axis, interpret=interpret)
    elif axis == 0:
        s1, s1c, s2c = snr_stats_centered_major(v, interpret=interpret)
    else:
        s1, s1c, s2c = snr_stats_centered(v, interpret=interpret)
    return snr_from_centered_stats(s1, s1c, s2c, n)
