"""Fused dense AdamW update — Pallas TPU kernel.

The optimizer step is pure HBM bandwidth: XLA:CPU materializes ~9 fp32
temporaries per tensor (measured in the dry-run buffer dump: 6-9 copies of
each (95, 512, 1376) stacked moment). This kernel streams each tile of
(p, g, m, v) through VMEM exactly once and writes (p', m', v') — 7 tensor
passes total, the bandwidth floor for Adam.

Grid: (rows/TR, cols/TC) tiles; every operand uses the same BlockSpec, so
the working set is 7 * TR * TC * 4 B (fp32) — TR=256, TC=512 -> 3.5 MiB,
comfortably inside the ~16 MiB VMEM with double buffering.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .tiling import default_interpret

# Default (sublane, lane) tile for the dense kernels. The lane width is the
# single source of truth for any layout folded to match these tiles
# (repro.optim.fused lane-folds 1-D/bucketed leaves to LANES-wide rows);
# deriving from one constant keeps a block change from desyncing them.
BLOCK = (256, 512)
LANES = BLOCK[1]


def bias_corrections(b1, b2, count) -> jnp.ndarray:
    """(1-b1^t, 1-b2^t) as a length-2 fp32 operand vector.

    ``count`` may be a Python int or a traced int array — inside a
    GradientTransformation's jitted update the step counter is state, so the
    corrections ride in through the scalar operand instead of being baked
    into the kernel as compile-time constants.
    """
    c = jnp.asarray(count, jnp.float32)
    return jnp.stack([1.0 - jnp.asarray(b1, jnp.float32) ** c,
                      1.0 - jnp.asarray(b2, jnp.float32) ** c])


def _adam_kernel(p_ref, g_ref, m_ref, v_ref, scal_ref,
                 p_out, m_out, v_out, *, b1: float, b2: float, eps: float, wd: float):
    lr = scal_ref[0]
    bc1 = scal_ref[1]
    bc2 = scal_ref[2]
    g = g_ref[...].astype(jnp.float32)
    m_new = b1 * m_ref[...] + (1.0 - b1) * g
    v_new = b2 * v_ref[...] + (1.0 - b2) * g * g
    update = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps)
    if wd:
        update = update + wd * p_ref[...].astype(jnp.float32)
    p_out[...] = (p_ref[...].astype(jnp.float32) - lr * update).astype(p_out.dtype)
    m_out[...] = m_new
    v_out[...] = v_new


def fused_adam(p, g, m, v, *, lr: float, b1: float = 0.9, b2: float = 0.95,
               eps: float = 1e-8, wd: float = 0.0, count: int = 1,
               block: tuple = BLOCK, interpret: Optional[bool] = None):
    """p, g: (R, C) any float dtype; m, v: (R, C) fp32. Returns (p', m', v')."""
    r, c = p.shape
    tr = min(block[0], r)
    tc = min(block[1], c)
    if r % tr or c % tc:
        # pad to tile multiples (pallas grids need exact tiling)
        rp, cp = -(-r // tr) * tr, -(-c // tc) * tc
        pad = lambda x: jnp.pad(x, ((0, rp - r), (0, cp - c)))
        p2, g2, m2, v2 = pad(p), pad(g), pad(m), pad(v)
        po, mo, vo = fused_adam(p2, g2, m2, v2, lr=lr, b1=b1, b2=b2, eps=eps,
                                wd=wd, count=count, block=block, interpret=interpret)
        return po[:r, :c], mo[:r, :c], vo[:r, :c]

    scal = jnp.concatenate([jnp.full((1,), lr, jnp.float32),
                            bias_corrections(b1, b2, count)])
    spec = pl.BlockSpec((tr, tc), lambda i, j: (i, j))
    grid = (r // tr, c // tc)
    kernel = functools.partial(_adam_kernel, b1=b1, b2=b2, eps=eps, wd=wd)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec, spec, spec, spec,
                  pl.BlockSpec((3,), lambda i, j: (0,))],
        out_specs=[pl.BlockSpec((tr, tc), lambda i, j: (i, j))] * 3,
        out_shape=[
            jax.ShapeDtypeStruct((r, c), p.dtype),
            jax.ShapeDtypeStruct((r, c), jnp.float32),
            jax.ShapeDtypeStruct((r, c), jnp.float32),
        ],
        interpret=default_interpret(interpret),
    )(p, g, m, v, scal)


def health_terms(g32) -> jnp.ndarray:
    """``[nonfinite_count, finite_masked_sumsq]`` of one gradient block.

    The sum-of-squares is masked to the finite entries so the global grad
    norm stays usable even on a step where some entries are NaN/Inf — the
    guard layer reports both "how many entries were poisoned" and "how big
    was the rest of the gradient".
    """
    fin = jnp.isfinite(g32)
    nf = jnp.sum(jnp.where(fin, 0.0, 1.0))
    ss = jnp.sum(jnp.where(fin, g32 * g32, 0.0))
    return jnp.stack([nf, ss])


def _adam_precond_kernel(g_ref, m_ref, v_ref, scal_ref, u_out, m_out, v_out,
                         *h_out, b1: float, b2: float, eps: float):
    bc1 = scal_ref[0]
    bc2 = scal_ref[1]
    g = g_ref[...].astype(jnp.float32)
    m_new = b1 * m_ref[...] + (1.0 - b1) * g
    v_new = b2 * v_ref[...] + (1.0 - b2) * g * g
    u_out[...] = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps)
    m_out[...] = m_new
    v_out[...] = v_new
    if h_out:
        # (2,) accumulator shared by every grid instance: the TPU grid is
        # sequential, so zero on the first instance, then add each tile's
        # contribution. Costs one O(1) output — no extra tensor pass.
        @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
        def _zero():
            h_out[0][...] = jnp.zeros((2,), jnp.float32)

        h_out[0][...] = h_out[0][...] + health_terms(g)


def adam_precond(g, m, v, *, b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
                 count=1, block: tuple = BLOCK, interpret: Optional[bool] = None,
                 with_health: bool = False):
    """Preconditioned Adam update only: (g, m, v) -> (u, m', v'), all fp32.

    The GradientTransformation form of the fused step — lr / weight decay /
    the parameter write happen downstream in the chain, so this streams 6
    tensor passes (g, m, v read + u, m', v' write) and never touches p.
    ``count`` may be a traced int array (see :func:`bias_corrections`).

    ``with_health=True`` appends one ``(2,)`` fp32 output
    ``[nonfinite_count, finite_sumsq]`` of ``g``, accumulated in-pass by the
    same kernel (see :func:`health_terms`) — the anomaly guard's per-leaf
    stats ride the update's existing HBM traffic.
    """
    r, c = g.shape
    tr = min(block[0], r)
    tc = min(block[1], c)
    if r % tr or c % tc:
        rp, cp = -(-r // tr) * tr, -(-c // tc) * tc
        pad = lambda x: jnp.pad(x, ((0, rp - r), (0, cp - c)))
        outs = adam_precond(pad(g), pad(m), pad(v), b1=b1, b2=b2, eps=eps,
                            count=count, block=block, interpret=interpret,
                            with_health=with_health)
        trimmed = tuple(o[:r, :c] for o in outs[:3])
        # zero padding is finite and contributes 0 to both health terms, so
        # the accumulator needs no trimming
        return trimmed + tuple(outs[3:])

    scal = bias_corrections(b1, b2, count)
    spec = pl.BlockSpec((tr, tc), lambda i, j: (i, j))
    kernel = functools.partial(_adam_precond_kernel, b1=b1, b2=b2, eps=eps)
    out_specs = [spec] * 3
    out_shape = [jax.ShapeDtypeStruct((r, c), jnp.float32)] * 3
    if with_health:
        out_specs = out_specs + [pl.BlockSpec((2,), lambda i, j: (0,))]
        out_shape = out_shape + [jax.ShapeDtypeStruct((2,), jnp.float32)]
    return pl.pallas_call(
        kernel,
        grid=(r // tr, c // tc),
        in_specs=[spec, spec, spec, pl.BlockSpec((2,), lambda i, j: (0,))],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=default_interpret(interpret),
    )(g, m, v, scal)
