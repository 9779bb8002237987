"""Shared tiling policy + grid/BlockSpec builder for strip kernels.

The slim-update and snr-stats kernels all share one canonical layout: a
``(B, R, C)`` tensor whose reduction axis is held *whole* inside each kernel
instance while a grid walks the batch dim and strips of the kept axis. Two
orientations cover every reshape-reachable reduction:

  * **minor** (reduce lanes, per-batch 2-D axis 1): blocks are
    ``(1, tile, C)``, the grid is ``(B, R / tile)``;
  * **major** (reduce sublanes, per-batch 2-D axis 0): blocks are
    ``(1, R, tile)``, the grid is ``(B, C / tile)``.

:func:`strip_grid` builds the grid and every BlockSpec a kernel in that
layout needs (full-tensor strips, the reduced O(kept) line, and per-line
stat outputs), so the kernel modules declare *what* they stream, not how it
tiles.

VMEM fitting is batch-aware in the sense that matters: the batch dim rides
on the *grid* (one batch slice per instance), so the per-instance working
set depends only on the reduction extent — a vocab-width reduction line
(50k+) at the default block would blow VMEM on TPU regardless of B. Never
seen in interpret mode, so the bound lives here rather than in CI.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Per-call VMEM working-set budget: conservative slice of the ~16 MiB/core,
# leaving room for double buffering.
VMEM_BUDGET = 8 << 20

# In-VMEM itemsize the fit math charges per element. The strip kernels cast
# every operand to f32 on load and do all arithmetic in f32 (bf16/f16 inputs
# included — the stored dtype only matters at the final cast-back), so 4 bytes
# is the *actual* working-set cost per element, not a guess: gating bf16
# strips on their 2-byte storage width would under-count VMEM by 2x. Callers
# that ever keep a genuinely wider compute copy must pass ``itemsize``
# explicitly; `repro.analysis.kernelcheck` verifies the f32-compute contract
# (and the resulting footprint bound) statically for every registered kernel.
COMPUTE_ITEMSIZE = 4


def default_interpret(interpret: Optional[bool] = None) -> bool:
    """The interpret flag a kernel entry runs with: the caller's when given,
    otherwise Pallas interpret mode everywhere except a real TPU backend
    (where the compiled kernel is the point; elsewhere the interpreter is
    the correctness harness). Every kernel entry defaults to ``None``, so no
    kernel runs interpreted on a TPU unless its caller asks for it."""
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def fit_strip_block(red_size: int, block: int, kept_size: int, n_bufs: int,
                    *, itemsize: int = COMPUTE_ITEMSIZE) -> int:
    """Shrink a strip tile so ``n_bufs`` (tile, red_size) compute buffers of
    ``itemsize`` bytes/element fit in :data:`VMEM_BUDGET`. Callers must gate
    on :func:`strip_fits` first — when a single reduction line already
    exceeds the budget (full-reduction K on a big tensor), no tile count can
    enforce it."""
    cap = max(1, VMEM_BUDGET // (red_size * itemsize * n_bufs))
    return max(1, min(block, cap, kept_size))


def strip_fits(red_size: int, n_bufs: int, *, itemsize: int = COMPUTE_ITEMSIZE) -> bool:
    """Whether a single reduction line's working set (``n_bufs`` compute
    copies at ``itemsize`` bytes/element — f32 by default, see
    :data:`COMPUTE_ITEMSIZE`) fits the budget. When it doesn't, the strip
    kernels can't serve the tensor on a real TPU (interpret mode wouldn't
    notice) — dispatchers fall back to jnp. Independent of the batch extent:
    batch rides on the grid, not in VMEM."""
    return red_size * itemsize * n_bufs <= VMEM_BUDGET


class StripGrid(NamedTuple):
    """Grid + BlockSpecs for one batched strip kernel launch over (B, R, C).

    ``axis`` is the per-batch 2-D reduction axis (1 = minor/lanes,
    0 = major/sublanes); ``red_axis`` is the same axis inside a 3-D block
    (2 or 1), which is what kernel bodies reduce over.
    """

    grid: Tuple[int, int]   # (B, kept / tile)
    axis: int               # per-batch 2-D reduction axis: 1 | 0
    red_axis: int           # reduction axis of a (1, ., .) block: 2 | 1
    kept_axis: int          # grid-tiled kept axis of the (B, R, C) view: 1 | 2
    n_red: int              # reduction extent (held whole per instance)
    kept: int               # kept extent per batch (must divide by tile)
    tile: int               # strip width along the kept axis
    full: Any               # BlockSpec for full (B, R, C) operands
    line: Any               # BlockSpec for the reduced O(kept) operand
    stat: Any               # BlockSpec for (B, kept) per-line stat outputs


def strip_grid(b: int, r: int, c: int, *, axis: int, n_bufs: int, block: int,
               itemsize: int = COMPUTE_ITEMSIZE) -> StripGrid:
    """Plan the grid and BlockSpecs for a (B, R, C) strip kernel.

    ``axis=1`` reduces the trailing axis (minor): grid over row strips, each
    instance holds a (1, tile, C) block. ``axis=0`` reduces the middle axis
    (major): grid over column strips, each instance holds a (1, R, tile)
    block. ``n_bufs`` is the caller's live full-size compute buffer count per
    instance (``itemsize`` bytes/element, f32 by default — see
    :data:`COMPUTE_ITEMSIZE`); the tile shrinks until they fit
    :data:`VMEM_BUDGET`. The kept extent must already be a multiple of the
    returned tile — callers pad first (see the kernel modules'
    pad-and-recurse entries).
    """
    assert axis in (0, 1)
    if axis == 1:
        n_red, kept = c, r
        tile = fit_strip_block(n_red, block, kept, n_bufs, itemsize=itemsize)
        full = pl.BlockSpec((1, tile, c), lambda bi, i: (bi, i, 0))
        line = pl.BlockSpec((1, tile, 1), lambda bi, i: (bi, i, 0))
        red_axis, kept_axis = 2, 1
    else:
        n_red, kept = r, c
        tile = fit_strip_block(n_red, block, kept, n_bufs, itemsize=itemsize)
        full = pl.BlockSpec((1, r, tile), lambda bi, j: (bi, 0, j))
        line = pl.BlockSpec((1, 1, tile), lambda bi, j: (bi, 0, j))
        red_axis, kept_axis = 1, 2
    stat = pl.BlockSpec((1, tile), lambda bi, i: (bi, i))
    return StripGrid(grid=(b, kept // tile), axis=axis, red_axis=red_axis,
                     kept_axis=kept_axis, n_red=n_red, kept=kept, tile=tile,
                     full=full, line=line, stat=stat)


def pad_kept(x: jnp.ndarray, sg: StripGrid) -> jnp.ndarray:
    """Pad ``x``'s kept axis up to the plan's tile multiple (the reduction
    axis is never padded, so padded lines cannot contaminate real ones;
    callers slice the padding back off with :func:`trim_kept`)."""
    cfg = [(0, 0)] * x.ndim
    cfg[sg.kept_axis] = (0, -(-sg.kept // sg.tile) * sg.tile - sg.kept)
    return jnp.pad(x, cfg)


def trim_kept(x: jnp.ndarray, sg: StripGrid) -> jnp.ndarray:
    """Inverse of :func:`pad_kept` on a kernel output."""
    idx = [slice(None)] * x.ndim
    idx[sg.kept_axis] = slice(sg.kept)
    return x[tuple(idx)]
