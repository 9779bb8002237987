"""Fused SlimAdam update (compressed second moment) — Pallas TPU kernels.

The paper's memory saving becomes a *bandwidth* saving here: the second
moment is reduced over the compression dims K, so one optimizer step streams
p, g, m (read) + p', m' (write) + O(kept) for V — 5 tensor passes vs dense
Adam's 7, and the squared gradient / E_K[g^2] reduction never touches HBM.

All kernels operate on the batched canonical form ``(B, R, C)`` planned by
``repro.kernels.ops.canon_nd`` (B = 1 for plain 2-D leaves; B = layers for
scan-stacked leaves whose reduction sits between kept axes), in one of two
orientations so every reshape-reachable reduction layout runs without a
boundary transpose (a pallas_call is an optimization barrier — XLA can't
fuse a re-layout into the kernel, so a transpose would materialize extra
HBM passes):

  * minor (``axis=1``): V is (B, R, 1); grid over (batch, row strips), each
    instance holds a full (1, TR, C) strip in VMEM and reduces along lanes;
  * major (``axis=0``): V is (B, 1, C); grid over (batch, column strips),
    each instance holds a full (1, R, TC) strip and reduces along sublanes
    — the transpose-free path for leading *or* batch-interleaved reduced
    dims (fan_out, conv fan_in, scan-stacked fan_in).

Both orientations share one kernel body per form (update / precond),
parameterized by the in-block reduction axis, and one grid/BlockSpec
builder (``repro.kernels.tiling.strip_grid``). Each instance computes the
strip's E_K[g^2] on the VPU, updates the reduced moment, and applies the
preconditioned update in the same pass. The 2-D entry points
(``slim_update`` / ``slim_update_major`` / ``slim_precond`` /
``slim_precond_major``) are B=1 wrappers kept for callers that speak 2-D.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .fused_adam import bias_corrections, health_terms
from .snr_stats import centered_line_stats
from .tiling import default_interpret, pad_kept, strip_grid, trim_kept

# Live full-size fp32 buffers per kernel instance (inputs + outputs + cast
# headroom) — the n_bufs VMEM-fitting argument for each form. Dispatchers
# gate un-servable leaves with ``tiling.strip_fits(red_size, *_BUFS)``.
UPDATE_BUFS = 6    # p, g, m in + p', m' out + cast headroom
PRECOND_BUFS = 5   # g, m in + u, m' out + cast headroom
PRECOND_SNR_BUFS = 6   # + the shifted g^2 copy for the centered SNR sums
PARTIAL_BUFS = 5   # g, m in + m' out + g^2 / shifted-copy headroom (with_snr)
FINALIZE_BUFS = 3  # m' in + u out + cast headroom (v/ek lines are O(kept))

_DEFAULT_BLOCK = {1: 32, 0: 256}  # kept-axis strip width per orientation


def _slim_kernel(p_ref, g_ref, m_ref, v_ref, scal_ref, p_out, m_out, v_out,
                 *, b1: float, b2: float, eps: float, wd: float,
                 red_axis: int, n_red: int):
    lr = scal_ref[0]
    bc1 = scal_ref[1]
    bc2 = scal_ref[2]
    g = g_ref[...].astype(jnp.float32)                   # (1, TR, C) | (1, R, TC)
    m_new = b1 * m_ref[...] + (1.0 - b1) * g
    ek = jnp.sum(g * g, axis=red_axis, keepdims=True) * (1.0 / n_red)
    v_new = b2 * v_ref[...] + (1.0 - b2) * ek            # reduced line
    update = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps)
    if wd:
        update = update + wd * p_ref[...].astype(jnp.float32)
    p_out[...] = (p_ref[...].astype(jnp.float32) - lr * update).astype(p_out.dtype)
    m_out[...] = m_new
    v_out[...] = v_new


def slim_update_batched(p, g, m, v_line, *, axis: int, lr: float, b1: float = 0.9,
                        b2: float = 0.95, eps: float = 1e-8, wd: float = 0.0,
                        count=1, block: Optional[int] = None,
                        interpret: Optional[bool] = None):
    """Batched SlimAdam step on the (B, R, C) canonical form.

    p, g, m: (B, R, C); v_line: (B, R, 1) fp32 (axis=1, reduce over C) or
    (B, 1, C) fp32 (axis=0, reduce over R). Returns (p', m', v'). ``count``
    may be a traced int array (the corrections ride in via the scalar
    operand — see :func:`repro.kernels.fused_adam.bias_corrections`, the one
    definition of the bias-correction semantics for every kernel entry).
    """
    assert p.ndim == 3 and axis in (0, 1)
    b, r, c = p.shape
    block = _DEFAULT_BLOCK[axis] if block is None else block
    sg = strip_grid(b, r, c, axis=axis, n_bufs=UPDATE_BUFS, block=block)
    if sg.kept % sg.tile:
        po, mo, vo = slim_update_batched(pad_kept(p, sg), pad_kept(g, sg),
                                         pad_kept(m, sg), pad_kept(v_line, sg),
                                         axis=axis, lr=lr, b1=b1, b2=b2, eps=eps,
                                         wd=wd, count=count, block=block,
                                         interpret=interpret)
        return trim_kept(po, sg), trim_kept(mo, sg), trim_kept(vo, sg)

    scal = jnp.concatenate([jnp.full((1,), lr, jnp.float32),
                            bias_corrections(b1, b2, count)])
    kernel = functools.partial(_slim_kernel, b1=b1, b2=b2, eps=eps, wd=wd,
                               red_axis=sg.red_axis, n_red=sg.n_red)
    v_shape = (b, r, 1) if axis == 1 else (b, 1, c)
    return pl.pallas_call(
        kernel,
        grid=sg.grid,
        in_specs=[sg.full, sg.full, sg.full, sg.line,
                  pl.BlockSpec((3,), lambda bi, i: (0,))],
        out_specs=[sg.full, sg.full, sg.line],
        out_shape=[
            jax.ShapeDtypeStruct((b, r, c), p.dtype),
            jax.ShapeDtypeStruct((b, r, c), jnp.float32),
            jax.ShapeDtypeStruct(v_shape, jnp.float32),
        ],
        interpret=default_interpret(interpret),
    )(p, g, m, v_line, scal)


def _accumulate_health(h_ref, g):
    """Fold one strip's health terms into the shared (2,) accumulator.

    Every grid instance maps to the same output block; the TPU grid is
    sequential, so zeroing on the first instance then adding per-strip
    contributions is race-free (and interpret mode preserves the order).
    """
    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _zero():
        h_ref[...] = jnp.zeros((2,), jnp.float32)

    h_ref[...] = h_ref[...] + health_terms(g)


def _slim_precond_kernel(g_ref, m_ref, v_ref, scal_ref, u_out, m_out, v_out,
                         *extra_outs, b1: float, b2: float, eps: float,
                         red_axis: int, n_red: int, with_snr: bool = False,
                         with_health: bool = False):
    bc1 = scal_ref[0]
    bc2 = scal_ref[1]
    g = g_ref[...].astype(jnp.float32)                   # (1, TR, C) | (1, R, TC)
    m_new = b1 * m_ref[...] + (1.0 - b1) * g
    g2 = g * g
    ek = jnp.sum(g2, axis=red_axis, keepdims=True) * (1.0 / n_red)
    v_new = b2 * v_ref[...] + (1.0 - b2) * ek            # reduced line
    u_out[...] = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps)
    m_out[...] = m_new
    v_out[...] = v_new
    if with_snr:
        s1c, s2c, _ = centered_line_stats(g2, red_axis)
        extra_outs[0][...] = s1c
        extra_outs[1][...] = s2c
    if with_health:
        _accumulate_health(extra_outs[-1], g)


def slim_precond_batched(g, m, v_line, *, axis: int, b1: float = 0.9,
                         b2: float = 0.95, eps: float = 1e-8, count=1,
                         with_snr: bool = False, with_health: bool = False,
                         block: Optional[int] = None, interpret: Optional[bool] = None):
    """Preconditioned batched SlimAdam update: (g, m, v_line) -> (u, m', v').

    The GradientTransformation form of :func:`slim_update_batched` — no
    parameter read/write, lr / weight decay applied downstream, traced
    ``count`` fine. Streams 4 full passes (g, m read + u, m' write) plus
    O(B * kept) for the reduced moment.

    ``with_snr=True`` appends ``(s1c, s2c)`` — shift-centered sums of g^2
    per reduction line (reduced-line layout), computed in the same strip
    loop — so a from-update SNR measurement (see
    ``repro.kernels.snr_stats.snr_update_stats_finalize``) costs O(kept)
    extra writes and zero extra full-size passes.

    ``with_health=True`` appends one ``(2,)`` fp32 accumulator
    ``[nonfinite_count, finite_sumsq]`` of ``g`` (always the *last* output),
    folded in by the same strip loop — the anomaly guard's per-leaf stats
    cost O(1) output bytes and zero extra tensor passes.
    """
    assert g.ndim == 3 and axis in (0, 1)
    b, r, c = g.shape
    block = _DEFAULT_BLOCK[axis] if block is None else block
    n_bufs = PRECOND_SNR_BUFS if with_snr else PRECOND_BUFS
    sg = strip_grid(b, r, c, axis=axis, n_bufs=n_bufs, block=block)
    if sg.kept % sg.tile:
        outs = slim_precond_batched(pad_kept(g, sg), pad_kept(m, sg),
                                    pad_kept(v_line, sg), axis=axis,
                                    b1=b1, b2=b2, eps=eps, count=count,
                                    with_snr=with_snr, with_health=with_health,
                                    block=block, interpret=interpret)
        # zero padding is finite and contributes 0 to both health terms, so
        # the trailing (2,) accumulator passes through untrimmed
        n_t = 3 + (2 if with_snr else 0)
        return tuple(trim_kept(o, sg) for o in outs[:n_t]) + tuple(outs[n_t:])

    scal = bias_corrections(b1, b2, count)
    kernel = functools.partial(_slim_precond_kernel, b1=b1, b2=b2, eps=eps,
                               red_axis=sg.red_axis, n_red=sg.n_red,
                               with_snr=with_snr, with_health=with_health)
    v_shape = (b, r, 1) if axis == 1 else (b, 1, c)
    n_snr = 2 if with_snr else 0
    out_specs = [sg.full, sg.full, sg.line] + [sg.line] * n_snr
    out_shape = [
        jax.ShapeDtypeStruct((b, r, c), jnp.float32),
        jax.ShapeDtypeStruct((b, r, c), jnp.float32),
        jax.ShapeDtypeStruct(v_shape, jnp.float32),
    ] + [jax.ShapeDtypeStruct(v_shape, jnp.float32)] * n_snr
    if with_health:
        out_specs = out_specs + [pl.BlockSpec((2,), lambda bi, i: (0,))]
        out_shape = out_shape + [jax.ShapeDtypeStruct((2,), jnp.float32)]
    return pl.pallas_call(
        kernel,
        grid=sg.grid,
        in_specs=[sg.full, sg.full, sg.line,
                  pl.BlockSpec((2,), lambda bi, i: (0,))],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=default_interpret(interpret),
    )(g, m, v_line, scal)


# ---------------------------------------------------------------------------
# Sharded psum regime: partial-stats / finalize kernel pair
# ---------------------------------------------------------------------------
#
# When a leaf's reduction dims are split across mesh shards, the E_K[g^2]
# line mean needs a cross-shard ``lax.psum`` between reading g and applying
# the preconditioner — a collective cannot run inside a pallas_call, so the
# leaf becomes two kernels around it:
#
#   pass 1  slim_partial_stats: read g, m -> write m_new + the per-line
#           partial g^2 sums (O(kept)); with ``with_snr`` the same strip
#           loop also emits shift-centered partial sums of g^2 (the
#           from-update SNR diagnostic, see
#           ``repro.kernels.snr_stats.snr_update_stats_finalize``) — three
#           more O(kept) lines, zero extra full-size traffic;
#   (psum)  the collective completes the line sums — and, for owner-sharded
#           reduced moments, simultaneously broadcasts v_new: each shard
#           folds ``b2 * v`` for the lines it owns into the payload, so the
#           moment's broadcast rides the all-reduce instead of adding ICI;
#   pass 2  slim_finalize: read m_new (+ the completed line mean / moment)
#           -> write u (+ v_new when the kernel owns the moment update).
#
# Full-size traffic stays at the psum regime's 5-pass floor (g, m read;
# m' write; m' read; u write); everything else is O(kept).


def _slim_partial_kernel(g_ref, m_ref, m_out, part_out, *extra_outs, b1: float,
                         red_axis: int, with_snr: bool = False,
                         with_health: bool = False):
    g = g_ref[...].astype(jnp.float32)                   # (1, TR, C) | (1, R, TC)
    m_out[...] = b1 * m_ref[...] + (1.0 - b1) * g
    g2 = g * g
    part_out[...] = jnp.sum(g2, axis=red_axis, keepdims=True)
    if with_snr:
        s1c, s2c, f = centered_line_stats(g2, red_axis)
        extra_outs[0][...] = s1c
        extra_outs[1][...] = s2c
        extra_outs[2][...] = f
    if with_health:
        _accumulate_health(extra_outs[-1], g)


def slim_partial_stats_batched(g, m, *, axis: int, b1: float = 0.9,
                               with_snr: bool = False, with_health: bool = False,
                               block: Optional[int] = None,
                               interpret: Optional[bool] = None):
    """Pass 1 of the sharded psum regime on the (B, R, C) canonical form.

    g, m: (B, R, C). Returns ``(m_new, part)`` — m_new fp32 full shape, part
    the per-line partial sum of g^2 in the reduced-line layout ((B, R, 1) for
    axis=1, (B, 1, C) for axis=0) ready for a ``lax.psum`` over the owning
    mesh axes. With ``with_snr=True`` also returns ``(s1c, s2c, first)``:
    shift-centered partial sums of g^2 per line (same layout), which compose
    across shards via ``repro.kernels.ref.rebase_centered_stats`` exactly
    like the snr_stats partial entries — the SNR measurement rides the
    update's strip loop for free.

    ``with_health=True`` appends one ``(2,)`` fp32 accumulator
    ``[nonfinite_count, finite_sumsq]`` of the *local* g shard (always the
    last output). Health composes across shards by summation, so psum-regime
    leaves fold it into the same all-reduce that completes the line sums —
    no extra collective, no extra pass.
    """
    assert g.ndim == 3 and axis in (0, 1)
    b, r, c = g.shape
    block = _DEFAULT_BLOCK[axis] if block is None else block
    sg = strip_grid(b, r, c, axis=axis, n_bufs=PARTIAL_BUFS, block=block)
    if sg.kept % sg.tile:
        outs = slim_partial_stats_batched(pad_kept(g, sg), pad_kept(m, sg),
                                          axis=axis, b1=b1, with_snr=with_snr,
                                          with_health=with_health,
                                          block=block, interpret=interpret)
        # the (2,) health accumulator is padding-invariant — no trim
        n_t = 2 + (3 if with_snr else 0)
        return tuple(trim_kept(o, sg) for o in outs[:n_t]) + tuple(outs[n_t:])

    kernel = functools.partial(_slim_partial_kernel, b1=b1, red_axis=sg.red_axis,
                               with_snr=with_snr, with_health=with_health)
    line_shape = (b, r, 1) if axis == 1 else (b, 1, c)
    n_lines = 1 + (3 if with_snr else 0)
    out_specs = [sg.full] + [sg.line] * n_lines
    out_shape = [jax.ShapeDtypeStruct((b, r, c), jnp.float32)] \
                + [jax.ShapeDtypeStruct(line_shape, jnp.float32)] * n_lines
    if with_health:
        out_specs = out_specs + [pl.BlockSpec((2,), lambda bi, i: (0,))]
        out_shape = out_shape + [jax.ShapeDtypeStruct((2,), jnp.float32)]
    return pl.pallas_call(
        kernel,
        grid=sg.grid,
        in_specs=[sg.full, sg.full],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=default_interpret(interpret),
    )(g, m)


def _slim_finalize_kernel(m_ref, v_ref, ek_ref, scal_ref, u_out, v_out,
                          *, b2: float, eps: float):
    bc1 = scal_ref[0]
    bc2 = scal_ref[1]
    v_new = b2 * v_ref[...] + (1.0 - b2) * ek_ref[...]   # reduced line
    u_out[...] = (m_ref[...] / bc1) / (jnp.sqrt(v_new / bc2) + eps)
    v_out[...] = v_new


def _slim_apply_line_kernel(m_ref, v_ref, scal_ref, u_out, *, eps: float):
    bc1 = scal_ref[0]
    bc2 = scal_ref[1]
    u_out[...] = (m_ref[...] / bc1) / (jnp.sqrt(v_ref[...] / bc2) + eps)


def slim_finalize_batched(m_new, v_line, *, axis: int, ek=None, b1: float = 0.9,
                          b2: float = 0.95, eps: float = 1e-8, count=1,
                          block: Optional[int] = None, interpret: Optional[bool] = None):
    """Pass 2 of the sharded psum regime (post-psum finalize).

    m_new: (B, R, C) fp32 from :func:`slim_partial_stats_batched`. With
    ``ek`` (the psum-completed line *mean* of g^2, reduced-line layout) this
    is the ISSUE-form finalize: reads m_new + v_line (the stored reduced
    moment) and returns ``(u, v_new)``. With ``ek=None``, ``v_line`` is the
    *already-completed* new moment — the owner-sharded flow, where each
    shard's ``b2 * v`` contribution rode the partial-sums psum and the
    collective delivered v_new directly — and only ``u`` is returned (the
    moment's O(kept) store is the caller's owner-slice, not a full
    replicated kernel write).
    """
    assert m_new.ndim == 3 and axis in (0, 1)
    b, r, c = m_new.shape
    block = _DEFAULT_BLOCK[axis] if block is None else block
    sg = strip_grid(b, r, c, axis=axis, n_bufs=FINALIZE_BUFS, block=block)
    if sg.kept % sg.tile:
        pads = (pad_kept(m_new, sg), pad_kept(v_line, sg))
        if ek is not None:
            uo, vo = slim_finalize_batched(pads[0], pads[1], axis=axis,
                                           ek=pad_kept(ek, sg), b1=b1, b2=b2,
                                           eps=eps, count=count, block=block,
                                           interpret=interpret)
            return trim_kept(uo, sg), trim_kept(vo, sg)
        uo = slim_finalize_batched(pads[0], pads[1], axis=axis, ek=None, b1=b1,
                                   b2=b2, eps=eps, count=count, block=block,
                                   interpret=interpret)
        return trim_kept(uo, sg)

    scal = bias_corrections(b1, b2, count)
    line_shape = (b, r, 1) if axis == 1 else (b, 1, c)
    if ek is None:
        kernel = functools.partial(_slim_apply_line_kernel, eps=eps)
        return pl.pallas_call(
            kernel,
            grid=sg.grid,
            in_specs=[sg.full, sg.line, pl.BlockSpec((2,), lambda bi, i: (0,))],
            out_specs=[sg.full],
            out_shape=[jax.ShapeDtypeStruct((b, r, c), jnp.float32)],
            interpret=default_interpret(interpret),
        )(m_new, v_line, scal)[0]
    kernel = functools.partial(_slim_finalize_kernel, b2=b2, eps=eps)
    return pl.pallas_call(
        kernel,
        grid=sg.grid,
        in_specs=[sg.full, sg.line, sg.line,
                  pl.BlockSpec((2,), lambda bi, i: (0,))],
        out_specs=[sg.full, sg.line],
        out_shape=[jax.ShapeDtypeStruct((b, r, c), jnp.float32),
                   jax.ShapeDtypeStruct(line_shape, jnp.float32)],
        interpret=default_interpret(interpret),
    )(m_new, v_line, ek, scal)


# ---------------------------------------------------------------------------
# 2-D entry points: B=1 wrappers over the batched canonical form.
# ---------------------------------------------------------------------------


def _b1(*xs):
    return tuple(x[None] for x in xs)


def _unb1(outs):
    return tuple(o[0] for o in outs)


def slim_update(p, g, m, v_row, *, lr: float, b1: float = 0.9, b2: float = 0.95,
                eps: float = 1e-8, wd: float = 0.0, count=1,
                row_block: int = 32, interpret: Optional[bool] = None):
    """p, g, m: (R, C); v_row: (R, 1) fp32 reduced moment. Returns (p', m', v')."""
    return _unb1(slim_update_batched(*_b1(p, g, m, v_row), axis=1, lr=lr, b1=b1,
                                     b2=b2, eps=eps, wd=wd, count=count,
                                     block=row_block, interpret=interpret))


def slim_update_major(p, g, m, v_col, *, lr: float, b1: float = 0.9, b2: float = 0.95,
                      eps: float = 1e-8, wd: float = 0.0, count=1,
                      col_block: int = 256, interpret: Optional[bool] = None):
    """p, g, m: (R, C); v_col: (1, C) fp32 moment reduced over rows.
    Returns (p', m', v')."""
    return _unb1(slim_update_batched(*_b1(p, g, m, v_col), axis=0, lr=lr, b1=b1,
                                     b2=b2, eps=eps, wd=wd, count=count,
                                     block=col_block, interpret=interpret))


def slim_precond(g, m, v_row, *, b1: float = 0.9, b2: float = 0.95,
                 eps: float = 1e-8, count=1, row_block: int = 32,
                 interpret: Optional[bool] = None):
    """Preconditioned SlimAdam update only: (g, m, v_row) -> (u, m', v_row').

    g, m: (R, C); v_row: (R, 1) fp32 reduced moment; u is fp32 full-shape.
    """
    return _unb1(slim_precond_batched(*_b1(g, m, v_row), axis=1, b1=b1, b2=b2,
                                      eps=eps, count=count, block=row_block,
                                      interpret=interpret))


def slim_precond_major(g, m, v_col, *, b1: float = 0.9, b2: float = 0.95,
                       eps: float = 1e-8, count=1, col_block: int = 256,
                       interpret: Optional[bool] = None):
    """Preconditioned major-axis SlimAdam update: (g, m, v_col) -> (u, m', v').

    g, m: (R, C); v_col: (1, C) fp32 moment reduced over rows; u is fp32
    full-shape.
    """
    return _unb1(slim_precond_batched(*_b1(g, m, v_col), axis=0, b1=b1, b2=b2,
                                      eps=eps, count=count, block=col_block,
                                      interpret=interpret))


def slim_partial_stats(g, m, *, axis: int = 1, b1: float = 0.9,
                       with_snr: bool = False, block: Optional[int] = None,
                       interpret: Optional[bool] = None):
    """2-D wrapper of :func:`slim_partial_stats_batched`: g, m (R, C) ->
    (m_new, part[, s1c, s2c, first]); lines are (R, 1) (axis=1) / (1, C)
    (axis=0)."""
    return _unb1(slim_partial_stats_batched(*_b1(g, m), axis=axis, b1=b1,
                                            with_snr=with_snr, block=block,
                                            interpret=interpret))


def slim_finalize(m_new, v_line, *, axis: int = 1, ek=None, b1: float = 0.9,
                  b2: float = 0.95, eps: float = 1e-8, count=1,
                  block: Optional[int] = None, interpret: Optional[bool] = None):
    """2-D wrapper of :func:`slim_finalize_batched`: m_new (R, C) + lines ->
    (u, v_new) with ``ek``, or just u when ``v_line`` is already the
    completed new moment (owner-sharded flow)."""
    if ek is None:
        out = slim_finalize_batched(*_b1(m_new, v_line), axis=axis, ek=None,
                                    b1=b1, b2=b2, eps=eps, count=count,
                                    block=block, interpret=interpret)
        return out[0]
    return _unb1(slim_finalize_batched(*_b1(m_new, v_line), axis=axis,
                                       ek=ek[None], b1=b1, b2=b2, eps=eps,
                                       count=count, block=block,
                                       interpret=interpret))
