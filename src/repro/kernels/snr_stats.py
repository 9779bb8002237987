"""Fused per-line reductions for the SNR analysis — Pallas TPU kernels.

SNR_K(V) needs mean and variance along K; a single fused pass computes the
moments of V per reduction line, so the measurement adds one read of V (and
O(kept) writes) to a training step instead of XLA's separate mean/var
reductions.

Like the slim-update kernels, everything runs on the batched canonical form
``(B, R, C)`` (see ``repro.kernels.ops.canon_nd``) through the shared
grid/BlockSpec builder (``repro.kernels.tiling.strip_grid``), with one
kernel body per stats flavor parameterized by the in-block reduction axis:
minor (``axis=1``, stats per row) or major (``axis=0``, stats per column —
the transpose-free pass for moments whose compression dims are leading or
batch-interleaved). The 2-D entries (``snr_stats`` /
``snr_stats_centered`` / ``snr_stats_centered_major``) are B=1 wrappers.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .tiling import default_interpret, pad_kept, strip_grid

# Live full-size fp32 buffers per instance (the n_bufs VMEM-fitting
# argument): input + cast copy (+ shifted copy for the centered form).
STATS_BUFS = 2
CENTERED_BUFS = 3

_DEFAULT_BLOCK = {1: 64, 0: 256}


def _first_along(v: jnp.ndarray, red_axis: int) -> jnp.ndarray:
    """The reduction line's first entry, kept broadcastable (the centered
    kernels' shift)."""
    return jax.lax.slice_in_dim(v, 0, 1, axis=red_axis)


def centered_line_stats(v: jnp.ndarray, red_axis: int):
    """Shift-centered per-line sums of an in-VMEM block: (s1c, s2c, first),
    each keepdims along ``red_axis``. The shared body the snr_stats kernels
    and the slim partial-stats kernel (``repro.kernels.slim_update``, which
    rides these sums on the update pass's strip loop) both inline, so the
    centering semantics — shift by the line's local first entry, making both
    sums O(spread) instead of O(magnitude) — have one definition."""
    f = _first_along(v, red_axis)
    d = v - f
    return (jnp.sum(d, axis=red_axis, keepdims=True),
            jnp.sum(d * d, axis=red_axis, keepdims=True), f)


def snr_update_stats_finalize(v_new: jnp.ndarray, s1c: jnp.ndarray, s2c: jnp.ndarray,
                              n: int, one_minus_b2: float,
                              eps: float = 1e-30) -> jnp.ndarray:
    """Finalize the from-update SNR of one leaf (scalar).

    ``s1c``/``s2c`` are the completed centered line sums of g^2 along the
    leaf's compression dims K (from the update kernels' ``with_snr`` outputs,
    psum-completed for sharded lines); ``v_new`` the completed reduced moment
    (same layout). The measured quantity is SNR_K of the step's dense
    reconstruction ``V_dense = b2 * V_red + (1 - b2) * g^2`` — the second
    moment dense Adam would hold this step given the compressed history:
    ``E_K[V_dense]`` is exactly ``v_new`` and ``Var_K[V_dense] =
    (1 - b2)^2 * Var_K[g^2]``, so the whole diagnostic costs O(kept) on top
    of the update pass. High SNR -> the compression rule is still valid."""
    mean_c = s1c / n
    var = s2c / n - jnp.square(mean_c)
    var = jnp.maximum(var, 0.0) * (one_minus_b2 * one_minus_b2)
    return jnp.mean(jnp.square(v_new) / (var + eps))


def _snr_kernel(v_ref, s1_out, s2_out, *, red_axis: int):
    v = v_ref[...].astype(jnp.float32)        # (1, TR, C) | (1, R, TC)
    s1_out[...] = jnp.sum(v, axis=red_axis)
    s2_out[...] = jnp.sum(v * v, axis=red_axis)


def _snr_centered_kernel(v_ref, s1_out, s1c_out, s2c_out, *, red_axis: int):
    v = v_ref[...].astype(jnp.float32)        # (1, TR, C) | (1, R, TC)
    s1c, s2c, _ = centered_line_stats(v, red_axis)
    s1_out[...] = jnp.sum(v, axis=red_axis)
    s1c_out[...] = jnp.squeeze(s1c, axis=red_axis)
    s2c_out[...] = jnp.squeeze(s2c, axis=red_axis)


def _snr_centered_partial_kernel(v_ref, s1_out, s1c_out, s2c_out, f_out, *, red_axis: int):
    """Centered stats + the shift itself (the line's local first entry), the
    partial-sums form a cross-shard reduction composes: shards rebase their
    sums to a common shift (exact O(spread) algebra, see
    ``repro.kernels.ref.rebase_centered_stats``) and ``lax.psum`` them."""
    v = v_ref[...].astype(jnp.float32)        # (1, TR, C) | (1, R, TC)
    s1c, s2c, f = centered_line_stats(v, red_axis)
    s1_out[...] = jnp.sum(v, axis=red_axis)
    s1c_out[...] = jnp.squeeze(s1c, axis=red_axis)
    s2c_out[...] = jnp.squeeze(s2c, axis=red_axis)
    f_out[...] = jnp.squeeze(f, axis=red_axis)


def _stats_call(v, *, axis: int, n_bufs: int, n_outs: int, kernel_body,
                block: Optional[int], interpret: Optional[bool]):
    """Shared pad-fit-launch path for both stats flavors. Returns ``n_outs``
    arrays of shape (B, kept)."""
    assert v.ndim == 3 and axis in (0, 1)
    b, r, c = v.shape
    block = _DEFAULT_BLOCK[axis] if block is None else block
    sg = strip_grid(b, r, c, axis=axis, n_bufs=n_bufs, block=block)
    if sg.kept % sg.tile:
        outs = _stats_call(pad_kept(v, sg), axis=axis, n_bufs=n_bufs,
                           n_outs=n_outs, kernel_body=kernel_body,
                           block=block, interpret=interpret)
        return tuple(o[:, :sg.kept] for o in outs)  # stats are (B, kept)
    kernel = functools.partial(kernel_body, red_axis=sg.red_axis)
    return pl.pallas_call(
        kernel,
        grid=sg.grid,
        in_specs=[sg.full],
        out_specs=[sg.stat] * n_outs,
        out_shape=[jax.ShapeDtypeStruct((b, sg.kept), jnp.float32)] * n_outs,
        interpret=default_interpret(interpret),
    )(v)


def snr_stats_batched(v, *, axis: int, block: Optional[int] = None,
                      interpret: Optional[bool] = None):
    """v: (B, R, C) -> (line_sum, line_sumsq), each (B, kept)."""
    return _stats_call(v, axis=axis, n_bufs=STATS_BUFS, n_outs=2,
                       kernel_body=_snr_kernel, block=block, interpret=interpret)


def snr_stats_centered_batched(v, *, axis: int, block: Optional[int] = None,
                               interpret: Optional[bool] = None):
    """v: (B, R, C) -> (line_sum, shifted_line_sum, shifted_line_sumsq),
    each (B, kept).

    The naive one-pass E[v^2] - E[v]^2 variance cancels catastrophically in
    fp32 for near-constant lines (the high-SNR regime the analysis exists to
    detect): abs error ~ eps * mean^2 swamps a true variance orders of
    magnitude smaller. Shifting each line by its first entry makes both sums
    O(spread) instead of O(magnitude) — variance is shift-invariant, so
    ``var = s2c/n - (s1c/n)^2`` is accurate to the spread's own precision,
    still in a single pass over V. The unshifted line sum rides along for
    the mean (V >= 0, so its summation is stable).
    """
    return _stats_call(v, axis=axis, n_bufs=CENTERED_BUFS, n_outs=3,
                       kernel_body=_snr_centered_kernel, block=block,
                       interpret=interpret)


def snr_stats_centered_partial_batched(v, *, axis: int, block: Optional[int] = None,
                                       interpret: Optional[bool] = None):
    """v: (B, R, C) -> (line_sum, shifted_line_sum, shifted_line_sumsq,
    line_first), each (B, kept) — the partial-sums entry point for sharded
    reduction lines.

    Same one-pass centered trick as :func:`snr_stats_centered_batched`, but
    when the reduction dim is split across devices each shard shifts by its
    *own* first entry, so the sums cannot be added directly. Emitting the
    shift alongside lets callers rebase every shard to a mesh-common shift
    (``shift = lax.pmean(first, axes)``; the rebase is exact algebra whose
    terms are all O(spread), see ``repro.kernels.ref.rebase_centered_stats``)
    and *then* ``lax.psum`` the three sums — preserving the catastrophic-
    cancellation protection across the shard boundary. The working set is
    identical to the centered kernel (the shift is a reused register line),
    hence the shared ``CENTERED_BUFS``."""
    return _stats_call(v, axis=axis, n_bufs=CENTERED_BUFS, n_outs=4,
                       kernel_body=_snr_centered_partial_kernel, block=block,
                       interpret=interpret)


# ---------------------------------------------------------------------------
# 2-D entry points: B=1 wrappers over the batched canonical form.
# ---------------------------------------------------------------------------


def snr_stats(v, *, row_block: int = 64, interpret: Optional[bool] = None):
    """v: (R, C) -> (row_sum (R,), row_sumsq (R,))."""
    s1, s2 = snr_stats_batched(v[None], axis=1, block=row_block, interpret=interpret)
    return s1[0], s2[0]


def snr_stats_centered(v, *, row_block: int = 64, interpret: Optional[bool] = None):
    """v: (R, C) -> (row_sum, shifted_row_sum, shifted_row_sumsq), all (R,).
    See :func:`snr_stats_centered_batched` for the shift-centering argument."""
    s1, s1c, s2c = snr_stats_centered_batched(v[None], axis=1, block=row_block,
                                              interpret=interpret)
    return s1[0], s1c[0], s2c[0]


def snr_stats_centered_partial(v, *, row_block: int = 64, interpret: Optional[bool] = None):
    """v: (R, C) -> (row_sum, shifted_row_sum, shifted_row_sumsq, row_first),
    all (R,). B=1 wrapper over the partial-sums entry point."""
    s1, s1c, s2c, f = snr_stats_centered_partial_batched(
        v[None], axis=1, block=row_block, interpret=interpret)
    return s1[0], s1c[0], s2c[0], f[0]


def snr_stats_centered_major(v, *, col_block: int = 256, interpret: Optional[bool] = None):
    """v: (R, C) -> (col_sum, shifted_col_sum, shifted_col_sumsq), all (C,).
    Major-axis twin of :func:`snr_stats_centered` — the reduction runs over
    sublanes, so a moment whose compression dims are leading gets its
    one-pass centered stats without a boundary transpose."""
    s1, s1c, s2c = snr_stats_centered_batched(v[None], axis=0, block=col_block,
                                              interpret=interpret)
    return s1[0], s1c[0], s2c[0]
