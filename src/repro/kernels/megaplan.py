"""Whole-tree megaplan: O(groups) Pallas launches per optimizer step.

The per-leaf dispatch in ``repro.optim.fused`` pays one ``pallas_call`` per
leaf (or small-leaf bucket) — the byte roofline is at its floor, but launch
count is O(leaves) and dominates the wall clock of a whole-tree update.
SlimAdam's update is elementwise-after-canonicalization per regime, so
same-regime canonical leaves are concatenation-compatible by construction.
This module generalizes the lane-folded small-leaf bucketing into a plan
over the *entire* tree:

  * :func:`plan_megagroups` runs :func:`repro.kernels.ops.leaf_plan` per
    leaf and groups every kernel-eligible leaf by regime key —

      - ``dense``   — K = () leaves, lane-folded flat (elementwise, so any
        concatenation order is exact); one group for the whole tree;
      - ``minor``   — 2-D canonical plans reducing lanes, keyed by the
        reduction extent (lines must share geometry); concatenated along
        the kept rows;
      - ``major``   — 2-D canonical plans reducing sublanes, keyed by the
        reduction extent; concatenated along the kept columns;
      - ``batched`` — 3-D scan-stacked plans, keyed by (batch, reduction
        extent); concatenated along the kept columns.

    Concatenation always runs along the *kept* axis, so no reduction line
    ever crosses a segment boundary — each group is one bigger instance of
    exactly the per-leaf problem, and results are bit-identical to the
    per-leaf kernels (per-line math never sees the neighbors). dtype does
    not split groups: every gather casts to the f32 compute form the
    kernels would build internally anyway (the stored dtype only matters
    at the caller's cast-back).

  * Each group carries a segment table (:class:`MegaSegment` per leaf:
    leaf id, offset and extent along the concat axis, the K-line geometry
    via the group key, and the leaf's bias-correction slot). The table
    must tile the super-tensor injectively — offsets contiguous from 0,
    lengths positive, indices a partition — which
    ``repro.analysis.races`` verifies statically. Per-leaf bias
    corrections enter the kernels as O(kept) *lines* built by
    :func:`segment_lines` (slot value repeated over the segment's extent),
    so a future per-leaf step count needs no kernel change.

  * The mega kernel entries walk the shared strip grid once per group:
    :func:`mega_adam_update` (lane-folded dense 2-D),
    :func:`mega_slim_update_batched` (fused precondition),
    :func:`mega_slim_partial_stats_batched` /
    :func:`mega_slim_finalize_batched` (the sharded psum pair — the
    cross-shard ``lax.psum`` stays per-leaf between the two launches, only
    the kernel launches amortize). ``with_health`` emits per-*line*
    counts instead of the per-leaf kernels' shared (2,) accumulator — the
    caller sums each segment's lines at scatter time, so every output
    block keeps an injective index map (nothing for the race pass to vet).

  * :func:`gather_group` / :func:`scatter_group` round-trip leaves through
    the super-tensor by offset (:func:`scatter_lines` for O(kept) stat
    outputs). Zero padding (dense lane-fold tails, ragged kept strips) is
    trimmed before scatter; bias-correction lines pad with ones so padded
    lanes never divide by zero.

Leaves :func:`leaf_plan` routes to jnp (scalars, non-float dtypes,
VMEM-exceeding reduction lines) are excluded from grouping and reported in
:attr:`MegaPlan.jnp_idx` — they keep their per-leaf reference path.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .fused_adam import LANES
from .ops import CanonND, canon_apply, canon_restore, leaf_plan
from .slim_update import (FINALIZE_BUFS, PARTIAL_BUFS, PRECOND_BUFS,
                          PRECOND_SNR_BUFS)
from .snr_stats import centered_line_stats
from .tiling import default_interpret, pad_kept, strip_grid, trim_kept

# Live full-size fp32 buffers per instance (the n_bufs VMEM-fitting
# argument / the kernelcheck BUFS bracket). The slim mega kernels hold the
# same working sets as their per-leaf twins — only the grid extent grows —
# so they share the constants. The dense mega kernel streams all six Adam
# tensors (g, m, v in; u, m', v' out) plus the cast copy; its bias lines
# are O(rows).
MEGA_ADAM_BUFS = 7
MEGA_PRECOND_BUFS = PRECOND_BUFS
MEGA_PRECOND_SNR_BUFS = PRECOND_SNR_BUFS
MEGA_PARTIAL_BUFS = PARTIAL_BUFS
MEGA_FINALIZE_BUFS = FINALIZE_BUFS

_DEFAULT_BLOCK = {1: 32, 0: 256}
_ADAM_BLOCK = 256

Dims = Tuple[int, ...]


class MegaSegment(NamedTuple):
    """One leaf's slot in a group's super-tensor (the segment table row)."""

    index: int                  # leaf index in the caller's tree order
    shape: Tuple[int, ...]      # original leaf shape
    red_shape: Tuple[int, ...]  # reduced-moment shape (size-1 reduced dims)
    dims: Dims                  # reduction dims (for the jnp fallback)
    cn: Optional[CanonND]       # canonical plan (None for dense segments)
    offset: int                 # start along the group's concat axis
    length: int                 # extent along the concat axis


class MegaGroup(NamedTuple):
    """One concatenation-compatible leaf group = one kernel launch.

    ``(batch, rows, cols)`` is the super-tensor's canonical view (2-D with
    ``batch == 1``); ``axis`` the per-batch 2-D reduction axis (1 minor /
    0 major, -1 for the elementwise dense group).
    """

    kind: str                   # 'dense' | 'minor' | 'major' | 'batched'
    batch: int
    rows: int
    cols: int
    axis: int
    segments: Tuple[MegaSegment, ...]

    @property
    def concat_axis(self) -> int:
        """Kept axis the segments stack along, in the canonical view."""
        return {"dense": 0, "minor": 0, "major": 1, "batched": 2}[self.kind]

    @property
    def red(self) -> int:
        """Shared reduction extent (1 for the elementwise dense group)."""
        if self.kind == "dense":
            return 1
        return self.cols if self.axis == 1 else self.rows

    @property
    def extent(self) -> int:
        """Total kept extent — what the segment table must tile exactly."""
        return sum(s.length for s in self.segments)

    @property
    def view(self) -> Tuple[int, ...]:
        if self.kind == "batched":
            return (self.batch, self.rows, self.cols)
        return (self.rows, self.cols)


class MegaPlan(NamedTuple):
    groups: Tuple[MegaGroup, ...]
    jnp_idx: Tuple[int, ...]    # leaves excluded from grouping (jnp route)


def _slim_key(cn: CanonND) -> Tuple[str, int, int]:
    """Group key of a canonical plan: orientation + the line geometry that
    must be uniform within a launch (lines of different extents cannot
    share a strip grid)."""
    if cn.batch > 1:
        return ("batched", cn.batch, cn.rows)
    if cn.axis == 1:
        return ("minor", 1, cn.cols)
    return ("major", 1, cn.rows)


def _dense_group(items: Sequence[Tuple[int, Tuple[int, ...], Tuple[int, ...],
                                       Dims, Optional[CanonND]]]) -> MegaGroup:
    segs: List[MegaSegment] = []
    off = 0
    for i, shape, red_shape, dims, cn in items:
        length = -(-math.prod(shape) // LANES)   # lane-folded row count
        segs.append(MegaSegment(i, shape, red_shape, dims, cn, off, length))
        off += length
    return MegaGroup("dense", 1, off, LANES, -1, tuple(segs))


def _slim_group(key: Tuple[str, int, int],
                items: Sequence[Tuple[int, Tuple[int, ...], Tuple[int, ...],
                                      Dims, CanonND]]) -> MegaGroup:
    kind, batch, red = key
    segs: List[MegaSegment] = []
    off = 0
    for i, shape, red_shape, dims, cn in items:
        length = cn.rows if kind == "minor" else cn.cols
        segs.append(MegaSegment(i, shape, red_shape, dims, cn, off, length))
        off += length
    if kind == "minor":
        return MegaGroup("minor", 1, off, red, 1, tuple(segs))
    if kind == "major":
        return MegaGroup("major", 1, red, off, 0, tuple(segs))
    return MegaGroup("batched", batch, red, off, 0, tuple(segs))


def groups_from_plans(items: Sequence[Tuple[int, Tuple[int, ...], Tuple[int, ...],
                                            Dims, CanonND]]) -> Tuple[MegaGroup, ...]:
    """Group pre-planned canonical leaves ``(index, shape, red_shape, dims,
    cn)`` — the sharded psum dispatcher's entry point, whose local plans
    come from ``ShardLeafPlan.cn`` rather than :func:`leaf_plan`."""
    by_key: Dict[Tuple[str, int, int], list] = {}
    for it in items:
        by_key.setdefault(_slim_key(it[4]), []).append(it)
    return tuple(_slim_group(k, by_key[k]) for k in sorted(by_key))


@functools.lru_cache(maxsize=512)
def _plan_cached(shapes: Tuple[Tuple[int, ...], ...], dtype_names: Tuple[str, ...],
                 dims_leaves: Tuple[Dims, ...], n_bufs: int) -> MegaPlan:
    dense_items: List[tuple] = []
    slim_items: Dict[Tuple[str, int, int], list] = {}
    jnp_idx: List[int] = []
    for i, (shape, dname, dims) in enumerate(zip(shapes, dtype_names, dims_leaves)):
        plan = leaf_plan(shape, jnp.dtype(dname), dims, n_bufs=n_bufs)
        if plan.route == "jnp":
            jnp_idx.append(i)
        elif plan.route == "dense":
            dense_items.append((i, shape, shape, (), None))
        else:
            dset = {d % len(shape) for d in dims}
            red_shape = tuple(1 if j in dset else s for j, s in enumerate(shape))
            slim_items.setdefault(_slim_key(plan.cn), []).append(
                (i, shape, red_shape, dims, plan.cn))
    groups: List[MegaGroup] = []
    if dense_items:
        groups.append(_dense_group(dense_items))
    for key in sorted(slim_items):
        groups.append(_slim_group(key, slim_items[key]))
    return MegaPlan(tuple(groups), tuple(jnp_idx))


def plan_megagroups(shapes: Sequence[Tuple[int, ...]], dtypes: Sequence[Any],
                    dims_leaves: Sequence[Dims], *,
                    n_bufs: int = PRECOND_BUFS) -> MegaPlan:
    """Plan the whole-tree grouping (cached — pure function of the static
    leaf geometry). ``n_bufs`` is the consuming kernel's buffer count,
    forwarded to the per-leaf VMEM fits-gate exactly as the per-leaf
    dispatch would."""
    return _plan_cached(tuple(tuple(int(d) for d in s) for s in shapes),
                        tuple(jnp.dtype(dt).name for dt in dtypes),
                        tuple(tuple(int(d) for d in ds) for ds in dims_leaves),
                        int(n_bufs))


def segment_table(group: MegaGroup) -> np.ndarray:
    """The declarative per-row segment table of one group: ``(extent, 4)``
    int64 rows ``[leaf_index, position_within_leaf, line_extent, bc_slot]``
    — one row per kept line of the super-tensor (per lane-folded row for
    the dense group). Static metadata: the race pass checks it tiles the
    super-tensor injectively, and the CI artifact dumps it on gate
    failure; the kernels themselves consume only its reductions (offsets
    for scatter, bc slots expanded to lines by :func:`segment_lines`)."""
    line = group.cols if group.kind == "dense" else group.red
    rows = [(seg.index, p, line, slot)
            for slot, seg in enumerate(group.segments)
            for p in range(seg.length)]
    return np.asarray(rows, np.int64).reshape(-1, 4)


# ---------------------------------------------------------------------------
# Gather / scatter: leaf lists <-> super-tensors, by segment offset
# ---------------------------------------------------------------------------


def gather_group(group: MegaGroup, xs: Sequence[Any], *,
                 reduced: bool = False) -> jnp.ndarray:
    """Concatenate the group's leaves (indexed by segment) into the f32
    super-tensor: lane-folded flat for dense, canonical views stacked along
    the kept axis otherwise (``reduced=True`` gathers size-1-reduced moment
    lines into the O(kept) line operand)."""
    if group.kind == "dense":
        parts = []
        for seg in group.segments:
            flat = xs[seg.index].astype(jnp.float32).ravel()
            parts.append(jnp.pad(flat, (0, seg.length * LANES - flat.size)))
        return jnp.concatenate(parts).reshape(group.rows, LANES)
    return jnp.concatenate(
        [canon_apply(xs[seg.index].astype(jnp.float32), seg.cn, reduced_cols=reduced)
         for seg in group.segments], axis=group.concat_axis)


def scatter_group(group: MegaGroup, y: jnp.ndarray, *,
                  reduced: bool = False) -> List[jnp.ndarray]:
    """Slice a super-tensor output back into per-leaf arrays (original
    layouts), aligned with ``group.segments``."""
    out: List[jnp.ndarray] = []
    if group.kind == "dense":
        for seg in group.segments:
            rows = jax.lax.slice_in_dim(y, seg.offset, seg.offset + seg.length,
                                        axis=0)
            out.append(rows.ravel()[:math.prod(seg.shape)].reshape(seg.shape))
        return out
    for seg in group.segments:
        sl = jax.lax.slice_in_dim(y, seg.offset, seg.offset + seg.length,
                                  axis=group.concat_axis)
        out.append(canon_restore(sl, seg.cn,
                                 seg.red_shape if reduced else seg.shape))
    return out


def scatter_lines(group: MegaGroup, y: jnp.ndarray) -> List[jnp.ndarray]:
    """Slice an O(kept) line output into raw per-segment line arrays (no
    layout restore) — for per-segment stat sums (health) and per-leaf SNR
    finalization, which are layout-independent."""
    return [jax.lax.slice_in_dim(y, seg.offset, seg.offset + seg.length,
                                 axis=group.concat_axis)
            for seg in group.segments]


def segment_lines(group: MegaGroup, values: Sequence[Any]) -> jnp.ndarray:
    """Expand one per-leaf scalar slot (e.g. a bias correction) into the
    group's line operand: value repeated over each segment's kept extent,
    shaped like the reduced-moment line."""
    lens = np.asarray([seg.length for seg in group.segments])
    flat = jnp.repeat(jnp.stack([jnp.asarray(v, jnp.float32) for v in values]),
                      lens, total_repeat_length=int(lens.sum()))
    if group.kind in ("dense", "minor"):
        return flat[:, None]
    if group.kind == "major":
        return flat[None, :]
    return jnp.broadcast_to(flat[None, None, :], (group.batch, 1, flat.size))


# ---------------------------------------------------------------------------
# Mega kernels
# ---------------------------------------------------------------------------


def _line_health(g, g2, red_axis: int):
    """Per-line health terms (non-finite count, finite-masked sumsq),
    keepdims — the megakernels' injective replacement for the per-leaf
    kernels' shared (2,) accumulator; callers sum each segment's lines."""
    fin = jnp.isfinite(g)
    nf = jnp.sum(jnp.where(fin, 0.0, 1.0), axis=red_axis, keepdims=True)
    ss = jnp.sum(jnp.where(fin, g2, 0.0), axis=red_axis, keepdims=True)
    return nf, ss


def _mega_adam_kernel(g_ref, m_ref, v_ref, bc1_ref, bc2_ref, u_out, m_out,
                      v_out, *h_outs, b1, b2, eps, with_health):
    g = g_ref[...].astype(jnp.float32)
    m_new = b1 * m_ref[...] + (1 - b1) * g
    v_new = b2 * v_ref[...] + (1 - b2) * g * g
    u_out[...] = (m_new / bc1_ref[...]) / (jnp.sqrt(v_new / bc2_ref[...]) + eps)
    m_out[...] = m_new
    v_out[...] = v_new
    if with_health:
        nf, ss = _line_health(g, g * g, 1)
        h_outs[0][...] = nf
        h_outs[1][...] = ss


def mega_adam_update(g, m, v, bc1, bc2, *, b1=0.9, b2=0.999, eps=1e-8,
                     with_health: bool = False, block: int = _ADAM_BLOCK,
                     interpret: Optional[bool] = None):
    """Dense Adam over a lane-folded (rows, LANES) super-tensor with per-row
    bias-correction lines ``bc1`` / ``bc2`` (rows, 1). Returns
    ``(u, m', v')`` (+ per-row ``(nf, ss)`` health lines with
    ``with_health``), all f32. Ragged row counts pad-and-recurse; the bias
    lines pad with ones so padded rows never divide by zero."""
    assert g.ndim == 2 and bc1.shape == (g.shape[0], 1)
    r, c = g.shape
    tr = min(block, r)
    if r % tr:
        rp = -(-r // tr) * tr
        padz = lambda x: jnp.pad(x, ((0, rp - r), (0, 0)))
        pad1 = lambda x: jnp.pad(x, ((0, rp - r), (0, 0)), constant_values=1.0)
        outs = mega_adam_update(padz(g), padz(m), padz(v), pad1(bc1), pad1(bc2),
                                b1=b1, b2=b2, eps=eps, with_health=with_health,
                                block=block, interpret=interpret)
        return tuple(o[:r] for o in outs)
    kernel = functools.partial(_mega_adam_kernel, b1=b1, b2=b2, eps=eps,
                               with_health=with_health)
    full = pl.BlockSpec((tr, c), lambda i: (i, 0))
    line = pl.BlockSpec((tr, 1), lambda i: (i, 0))
    n_h = 2 if with_health else 0
    return pl.pallas_call(
        kernel,
        grid=(r // tr,),
        in_specs=[full, full, full, line, line],
        out_specs=[full] * 3 + [line] * n_h,
        out_shape=([jax.ShapeDtypeStruct((r, c), jnp.float32)] * 3
                   + [jax.ShapeDtypeStruct((r, 1), jnp.float32)] * n_h),
        interpret=default_interpret(interpret),
    )(g, m, v, bc1, bc2)


def _mega_slim_kernel(g_ref, m_ref, v_ref, bc1_ref, bc2_ref, u_out, m_out,
                      v_out, *extra_outs, b1, b2, eps, red_axis, n_red,
                      with_snr, with_health):
    g = g_ref[...].astype(jnp.float32)
    m_new = b1 * m_ref[...] + (1 - b1) * g
    g2 = g * g
    ek = jnp.sum(g2, axis=red_axis, keepdims=True) * (1.0 / n_red)
    v_new = b2 * v_ref[...] + (1 - b2) * ek
    u_out[...] = (m_new / bc1_ref[...]) / (jnp.sqrt(v_new / bc2_ref[...]) + eps)
    m_out[...] = m_new
    v_out[...] = v_new
    k = 0
    if with_snr:
        s1c, s2c, _ = centered_line_stats(g2, red_axis)
        extra_outs[0][...] = s1c
        extra_outs[1][...] = s2c
        k = 2
    if with_health:
        nf, ss = _line_health(g, g2, red_axis)
        extra_outs[k][...] = nf
        extra_outs[k + 1][...] = ss


def _pad_kept_ones(x, sg):
    """`tiling.pad_kept` with ones — for bias-correction line operands,
    whose padded lanes must stay division-safe."""
    cfg = [(0, 0)] * x.ndim
    cfg[sg.kept_axis] = (0, -(-sg.kept // sg.tile) * sg.tile - sg.kept)
    return jnp.pad(x, cfg, constant_values=1.0)


def mega_slim_update_batched(g, m, v_line, bc1, bc2, *, axis: int, b1=0.9,
                             b2=0.95, eps=1e-8, with_snr: bool = False,
                             with_health: bool = False,
                             block: Optional[int] = None,
                             interpret: Optional[bool] = None):
    """Fused SlimAdam precondition over a (B, R, C) super-tensor whose kept
    axis concatenates same-line-geometry leaves; ``bc1`` / ``bc2`` are
    per-line bias-correction operands (:func:`segment_lines`). Per line the
    math is exactly ``repro.kernels.slim_update._slim_precond_kernel`` —
    concatenation only moves kept positions, so results are bit-identical
    to the per-leaf launches. Returns ``(u, m', v_line')`` + 2 centered-g^2
    stat lines with ``with_snr`` + 2 per-line health lines with
    ``with_health``, all f32."""
    assert g.ndim == 3 and axis in (0, 1)
    b, r, c = g.shape
    block = _DEFAULT_BLOCK[axis] if block is None else block
    n_bufs = MEGA_PRECOND_SNR_BUFS if with_snr else MEGA_PRECOND_BUFS
    sg = strip_grid(b, r, c, axis=axis, n_bufs=n_bufs, block=block)
    if sg.kept % sg.tile:
        pz = lambda x: pad_kept(x, sg)
        outs = mega_slim_update_batched(
            pz(g), pz(m), pz(v_line), _pad_kept_ones(bc1, sg),
            _pad_kept_ones(bc2, sg), axis=axis, b1=b1, b2=b2, eps=eps,
            with_snr=with_snr, with_health=with_health, block=block,
            interpret=interpret)
        return tuple(trim_kept(o, sg) for o in outs)
    kernel = functools.partial(_mega_slim_kernel, b1=b1, b2=b2, eps=eps,
                               red_axis=sg.red_axis, n_red=sg.n_red,
                               with_snr=with_snr, with_health=with_health)
    n_extra = (2 if with_snr else 0) + (2 if with_health else 0)
    line_shape = (b, r, 1) if axis == 1 else (b, 1, c)
    return pl.pallas_call(
        kernel,
        grid=sg.grid,
        in_specs=[sg.full, sg.full, sg.line, sg.line, sg.line],
        out_specs=[sg.full, sg.full] + [sg.line] * (1 + n_extra),
        out_shape=([jax.ShapeDtypeStruct((b, r, c), jnp.float32)] * 2
                   + [jax.ShapeDtypeStruct(line_shape, jnp.float32)]
                   * (1 + n_extra)),
        interpret=default_interpret(interpret),
    )(g, m, v_line, bc1, bc2)


def mega_slim_update(g, m, v_line, bc1, bc2, *, axis: int, **kw):
    """2-D (batch-free) wrapper of :func:`mega_slim_update_batched`."""
    outs = mega_slim_update_batched(g[None], m[None], v_line[None], bc1[None],
                                    bc2[None], axis=axis, **kw)
    return tuple(o[0] for o in outs)


def _mega_slim_partial_kernel(g_ref, m_ref, m_out, part_out, *extra_outs, b1,
                              red_axis, with_snr, with_health):
    g = g_ref[...].astype(jnp.float32)
    m_out[...] = b1 * m_ref[...] + (1 - b1) * g
    g2 = g * g
    part_out[...] = jnp.sum(g2, axis=red_axis, keepdims=True)
    k = 0
    if with_snr:
        s1c, s2c, f = centered_line_stats(g2, red_axis)
        extra_outs[0][...] = s1c
        extra_outs[1][...] = s2c
        extra_outs[2][...] = f
        k = 3
    if with_health:
        nf, ss = _line_health(g, g2, red_axis)
        extra_outs[k][...] = nf
        extra_outs[k + 1][...] = ss


def mega_slim_partial_stats_batched(g, m, *, axis: int, b1=0.9,
                                    with_snr: bool = False,
                                    with_health: bool = False,
                                    block: Optional[int] = None,
                                    interpret: Optional[bool] = None):
    """Pass 1 of the grouped psum pair: m' plus per-line partial g^2 sums
    (un-normalized — the caller's cross-shard ``lax.psum`` completes them
    per leaf). ``with_snr`` adds the 3 centered partial-stat lines,
    ``with_health`` the 2 per-line health lines."""
    assert g.ndim == 3 and axis in (0, 1)
    b, r, c = g.shape
    block = _DEFAULT_BLOCK[axis] if block is None else block
    sg = strip_grid(b, r, c, axis=axis, n_bufs=MEGA_PARTIAL_BUFS, block=block)
    if sg.kept % sg.tile:
        pz = lambda x: pad_kept(x, sg)
        outs = mega_slim_partial_stats_batched(
            pz(g), pz(m), axis=axis, b1=b1, with_snr=with_snr,
            with_health=with_health, block=block, interpret=interpret)
        return tuple(trim_kept(o, sg) for o in outs)
    kernel = functools.partial(_mega_slim_partial_kernel, b1=b1,
                               red_axis=sg.red_axis, with_snr=with_snr,
                               with_health=with_health)
    n_extra = (3 if with_snr else 0) + (2 if with_health else 0)
    line_shape = (b, r, 1) if axis == 1 else (b, 1, c)
    return pl.pallas_call(
        kernel,
        grid=sg.grid,
        in_specs=[sg.full, sg.full],
        out_specs=[sg.full] + [sg.line] * (1 + n_extra),
        out_shape=([jax.ShapeDtypeStruct((b, r, c), jnp.float32)]
                   + [jax.ShapeDtypeStruct(line_shape, jnp.float32)]
                   * (1 + n_extra)),
        interpret=default_interpret(interpret),
    )(g, m)


def _mega_finalize_ek_kernel(m_ref, v_ref, bc1_ref, bc2_ref, ek_ref, u_out,
                             v_out, *, b2, eps):
    m_new = m_ref[...].astype(jnp.float32)
    v_new = b2 * v_ref[...] + (1 - b2) * ek_ref[...]
    u_out[...] = (m_new / bc1_ref[...]) / (jnp.sqrt(v_new / bc2_ref[...]) + eps)
    v_out[...] = v_new


def _mega_finalize_owner_kernel(m_ref, v_ref, bc1_ref, bc2_ref, u_out, *, eps):
    m_new = m_ref[...].astype(jnp.float32)
    u_out[...] = (m_new / bc1_ref[...]) / (jnp.sqrt(v_ref[...] / bc2_ref[...])
                                           + eps)


def mega_slim_finalize_batched(m_new, v_line, bc1, bc2, *, axis: int, ek=None,
                               b2=0.95, eps=1e-8, block: Optional[int] = None,
                               interpret: Optional[bool] = None):
    """Pass 2 of the grouped psum pair. With completed per-leaf mean lines
    ``ek`` returns ``(u, v_line')``; with ``ek=None`` (owner-write form,
    ``v_line`` already the psum-completed moment) returns ``u`` alone."""
    assert m_new.ndim == 3 and axis in (0, 1)
    b, r, c = m_new.shape
    block = _DEFAULT_BLOCK[axis] if block is None else block
    sg = strip_grid(b, r, c, axis=axis, n_bufs=MEGA_FINALIZE_BUFS, block=block)
    if sg.kept % sg.tile:
        pz = lambda x: pad_kept(x, sg)
        outs = mega_slim_finalize_batched(
            pz(m_new), pz(v_line), _pad_kept_ones(bc1, sg),
            _pad_kept_ones(bc2, sg), axis=axis,
            ek=pz(ek) if ek is not None else None, b2=b2, eps=eps,
            block=block, interpret=interpret)
        if ek is None:
            return trim_kept(outs, sg)
        return tuple(trim_kept(o, sg) for o in outs)
    line_shape = (b, r, 1) if axis == 1 else (b, 1, c)
    if ek is None:
        kernel = functools.partial(_mega_finalize_owner_kernel, eps=eps)
        return pl.pallas_call(
            kernel,
            grid=sg.grid,
            in_specs=[sg.full, sg.line, sg.line, sg.line],
            out_specs=[sg.full],
            out_shape=[jax.ShapeDtypeStruct((b, r, c), jnp.float32)],
            interpret=default_interpret(interpret),
        )(m_new, v_line, bc1, bc2)[0]
    kernel = functools.partial(_mega_finalize_ek_kernel, b2=b2, eps=eps)
    return pl.pallas_call(
        kernel,
        grid=sg.grid,
        in_specs=[sg.full, sg.line, sg.line, sg.line, sg.line],
        out_specs=[sg.full, sg.line],
        out_shape=[jax.ShapeDtypeStruct((b, r, c), jnp.float32),
                   jax.ShapeDtypeStruct(line_shape, jnp.float32)],
        interpret=default_interpret(interpret),
    )(m_new, v_line, bc1, bc2, ek)
