"""Mamba selective-scan — Pallas TPU kernel.

The jnp chunked scan (repro.models.ssm.selective_scan, this kernel's oracle)
materializes (B, chunk, d_inner, d_state) fp32 tensors in HBM — a 16x
(d_state) expansion of every activation it touches; the falcon-mamba
train_4k roofline shows it as a ~50 s/step memory term. This kernel keeps
the expansion entirely in VMEM: the hidden state (d_tile, N) lives in
scratch across sequence chunks, and HBM sees only the x/dt/B/C input streams
and the y output — ~5 fp32 passes of (S, d_inner) per layer, ~N times less
traffic.

Grid: (batch, d_tiles, seq_chunks); the last (minor) grid dim is sequential
on TPU, so the scratch state carries across the chunk steps of one
(b, d_tile) program — the standard revisiting pattern. VMEM working set at
(chunk=512, d_tile=256, N=16): x/dt/y blocks 0.5 MB each + B/C 32 KB + state
16 KB ≈ 1.6 MB.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tiling import default_interpret


def _ssm_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, dskip_ref, h0_ref,
                y_ref, hout_ref, h_scratch, *, chunk: int, n_chunks: int):
    ck = pl.program_id(2)

    @pl.when(ck == 0)
    def _init():
        h_scratch[...] = h0_ref[0]                      # (d_tile, N)

    x = x_ref[0].astype(jnp.float32)                     # (chunk, d_tile)
    dt = dt_ref[0].astype(jnp.float32)
    bc = b_ref[0].astype(jnp.float32)                    # (chunk, N)
    cc = c_ref[0].astype(jnp.float32)
    a = a_ref[...].astype(jnp.float32)                   # (d_tile, N)
    dskip = dskip_ref[...].astype(jnp.float32)           # (d_tile,)

    def step(t, h):
        decay = jnp.exp(dt[t][:, None] * a)              # (d_tile, N)
        h = decay * h + (dt[t] * x[t])[:, None] * bc[t][None, :]
        y_ref[0, t, :] = (jnp.sum(h * cc[t][None, :], axis=1)
                          + dskip * x[t]).astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk, step, h_scratch[...])
    h_scratch[...] = h

    @pl.when(ck == n_chunks - 1)
    def _out():
        hout_ref[0] = h


def ssm_scan(x, dt, a, b_t, c_t, d_skip, h0, *, chunk: int = 512,
             d_tile: int = 256, interpret: Optional[bool] = None):
    """x, dt: (B, S, D); a: (D, N); b_t, c_t: (B, S, N); h0: (B, D, N).

    Returns (y (B, S, D) fp32, h_final (B, D, N) fp32). Semantics match
    ``repro.models.ssm.selective_scan`` (the oracle).
    """
    bsz, s, d = x.shape
    n = a.shape[-1]
    chunk = min(chunk, s)
    while s % chunk:
        chunk -= 1
    d_tile = min(d_tile, d)
    while d % d_tile:
        d_tile -= 1
    n_chunks = s // chunk
    grid = (bsz, d // d_tile, n_chunks)

    kernel = functools.partial(_ssm_kernel, chunk=chunk, n_chunks=n_chunks)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, d_tile), lambda b, dd, cc_: (b, cc_, dd)),   # x
            pl.BlockSpec((1, chunk, d_tile), lambda b, dd, cc_: (b, cc_, dd)),   # dt
            pl.BlockSpec((d_tile, n), lambda b, dd, cc_: (dd, 0)),               # a
            pl.BlockSpec((1, chunk, n), lambda b, dd, cc_: (b, cc_, 0)),         # b_t
            pl.BlockSpec((1, chunk, n), lambda b, dd, cc_: (b, cc_, 0)),         # c_t
            pl.BlockSpec((d_tile,), lambda b, dd, cc_: (dd,)),                   # d_skip
            pl.BlockSpec((1, d_tile, n), lambda b, dd, cc_: (b, dd, 0)),         # h0
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, d_tile), lambda b, dd, cc_: (b, cc_, dd)),   # y
            pl.BlockSpec((1, d_tile, n), lambda b, dd, cc_: (b, dd, 0)),         # h_final
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, s, d), jnp.float32),
            jax.ShapeDtypeStruct((bsz, d, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((d_tile, n), jnp.float32)],
        interpret=default_interpret(interpret),
    )(x, dt, a, b_t, c_t, d_skip, h0)
