"""Ragged paged-attention Pallas kernel (decode + chunked prefill).

The serving fast path stores each attention layer's KV cache as a pool of
fixed-size *pages* in the lane-dense layout ``(n_pages, kv_heads,
page_size, 2 * head_dim)``: row ``(p, j, s)`` holds K of kv head ``j`` at
slot ``s`` of page ``p`` on lanes ``[0, hd)`` and its V on ``[hd, 2hd)``.
One page DMA streams K and V of every head, and the minor dim (128 at
head_dim 64, 256 at 128) fills whole 128-lane vregs, so XLA keeps the pool
row-major with no lane padding and every program reads and writes it in
place. This module owns the layout: :func:`pool_shape` sizes a pool,
:func:`pool_write` stores K/V into it, and the kernel and
:func:`paged_attention_ref` read it; the page accounting is
:mod:`repro.serve.kvpool`'s. A request
owns a row of a page *table* (``(B, max_pages) int32``; entry 0 is the
reserved null page) and a ``lengths`` entry saying how many positions are
live. The pools of all layers may share one array: a layer's pages are then
addressed by its offset added to the table.

One kernel serves both serving phases:

  * **decode** — ``q`` is ``(B, 1, H, hd)``: each grid row walks its page
    list and reduces an online softmax across pages;
  * **chunked prefill** — ``q`` is ``(1, C, H, hd)`` holding C prompt
    tokens at absolute positions ``length - C .. length - 1``; the causal
    in-kernel mask (``k_abs <= q_abs``) subsumes the ragged length mask, so
    prefill costs ``ceil(S/C)`` steps instead of S decode steps.

Inside the kernel the queries are grouped per kv head: the wrapper lays q
out as ``(B, KV, C * rep, hd)`` (row ``c * rep + r`` is query position c,
head ``kv * rep + r``), so each kv head is one ``(C * rep, hd) x (hd,
page)`` matmul against its K half and one ``(C * rep, page) x (page, hd)``
against its V half — leading-dim indexing and one lane split, no strided
head split.

Grid is ``(B, max_pages)`` with the page dim innermost and *sequential*, so
Pallas double-buffers the page stream: the next page's DMA (its block index
comes from the scalar-prefetched table, ``tbl[b, p]``) overlaps the current
page's compute. The accumulator outputs (acc, m, l) alias one block per
batch row across the page dim — the same zero-on-first-instance + RMW
pattern as the health accumulators in :mod:`repro.kernels.slim_update`,
which the :mod:`repro.analysis.races` pass verifies (including that the
pool block's index map really is the page-table lookup).

Rows past their page count are skipped (``pl.when``), padded table entries
point at the null page and are masked by construction, and the final
``acc / l`` normalization happens outside the kernel, so no blind output
write exists anywhere.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tiling import COMPUTE_ITEMSIZE, VMEM_BUDGET, default_interpret

NEG_INF = -1e30

# Full-size VMEM blocks the kernel keeps live per instance: the current KV
# page, the next page's double-buffer in flight, and one compute copy
# (cast / exp scratch) — q, the accumulator and the softmax stats are
# O(C * H) blocks charged separately by paged_fits.
PAGED_ATTN_BUFS = 3


def pool_shape(n_pages: int, page_size: int, n_kv_heads: int,
               head_dim: int) -> tuple:
    """Device array shape of a pool of ``n_pages`` pages in the lane-dense
    layout."""
    return (n_pages, n_kv_heads, page_size, 2 * head_dim)


def pool_write(pool: jnp.ndarray, table: jnp.ndarray, rows: jnp.ndarray,
               positions: jnp.ndarray, valid: jnp.ndarray, k: jnp.ndarray,
               v: jnp.ndarray) -> jnp.ndarray:
    """Store the K/V of N positions into their pages and return the pool.

    rows, positions, valid: (N,) — the table row each position belongs to,
    its absolute position, and whether the write is live (dead writes go to
    page 0, which no live table entry points at). k, v: (N, KV, hd).

    One scatter indexed by (page, kv head, slot in page) whose update window
    is the minor ``2 * hd`` dim alone, so XLA writes the row-major pool in
    place instead of choosing a layout of its own for the scatter.
    """
    page_size = pool.shape[2]
    pidx = jnp.clip(positions // page_size, 0, table.shape[1] - 1)
    page = jnp.where(valid, table[rows, pidx], 0)
    heads = jnp.arange(k.shape[1])
    kv_rows = jnp.concatenate([k, v], axis=-1).astype(pool.dtype)  # (N, KV, 2hd)
    return pool.at[page[:, None], heads[None, :],
                   (positions % page_size)[:, None]].set(kv_rows)


def _vmem_elems(*shape: int) -> int:
    """Elements a VMEM block of ``shape`` occupies: its last two dims are
    padded to the (8, 128) f32 tile."""
    lead = math.prod(shape[:-2])
    return lead * -(-shape[-2] // 8) * 8 * -(-shape[-1] // 128) * 128


def paged_fits(chunk: int, n_heads: int, head_dim: int, page_size: int,
               n_kv_heads: int, *, itemsize: int = COMPUTE_ITEMSIZE) -> bool:
    """Whether one grid instance's working set fits :data:`VMEM_BUDGET`:
    ``PAGED_ATTN_BUFS`` page blocks ``(KV, page, 2 * hd)`` (current +
    prefetched + compute copy) plus the q and acc blocks ``(KV, C * rep,
    hd)`` and the two softmax stats ``(KV, C * rep, 1)``, each at its
    (8, 128)-tile-padded footprint (a stats column takes a full 128-lane
    tile) and the f32 compute itemsize (same policy as
    :func:`repro.kernels.tiling.strip_fits`)."""
    kv = n_kv_heads
    cr = chunk * (n_heads // kv)
    page_block = _vmem_elems(kv, page_size, 2 * head_dim)
    qacc = _vmem_elems(kv, cr, head_dim)
    stats = _vmem_elems(kv, cr, 1)
    working = PAGED_ATTN_BUFS * page_block + 2 * qacc + 2 * stats
    return working * itemsize <= VMEM_BUDGET


def _paged_kernel(tbl_ref, len_ref, q_ref, pool_ref, acc_ref, m_ref, l_ref,
                  *, page: int, kv: int, rep: int):
    b = pl.program_id(0)
    p = pl.program_id(1)
    cr = q_ref.shape[2]                 # C * rep query rows per kv head
    c = cr // rep
    hd = q_ref.shape[3]
    scale = 1.0 / math.sqrt(hd)

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[b]
    n_pages = (length + page - 1) // page

    @pl.when(p < n_pages)
    def _page():
        # Causal/ragged mask in absolute positions: queries sit at the last
        # C positions of the row, so k <= q also bounds k < length. Row
        # c * rep + r holds query c, and c >= x  <=>  row >= x * rep, so the
        # mask needs no integer division.
        row = jax.lax.broadcasted_iota(jnp.int32, (cr, page), 0)
        k_abs = p * page + jax.lax.broadcasted_iota(jnp.int32, (cr, page), 1)
        mask = row >= (k_abs - (length - c)) * rep          # (C*rep, page)
        for j in range(kv):
            k = pool_ref[0, j, :, :hd].astype(jnp.float32)   # (page, hd)
            v = pool_ref[0, j, :, hd:].astype(jnp.float32)
            q = q_ref[0, j].astype(jnp.float32) * scale      # (C*rep, hd)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(mask, s, NEG_INF)
            m_old = m_ref[0, j]                              # (C*rep, 1)
            m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
            pexp = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_old - m_new)
            l_ref[0, j] = l_ref[0, j] * corr + jnp.sum(pexp, axis=-1,
                                                       keepdims=True)
            pv = jax.lax.dot_general(pexp, v, (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            acc_ref[0, j] = acc_ref[0, j] * corr + pv
            m_ref[0, j] = m_new


def paged_attention_ref(q: jnp.ndarray, pool: jnp.ndarray, table: jnp.ndarray,
                        lengths: jnp.ndarray) -> jnp.ndarray:
    """jnp oracle / fallback: gather every table page densely and run a full
    masked softmax. O(max_pages * page) memory per row — correct everywhere,
    used when :func:`paged_fits` rejects the geometry and as the parity
    oracle in tests."""
    b, c, h, hd = q.shape
    _, kv, page, _ = pool.shape
    rep = h // kv
    gathered = pool[table].astype(jnp.float32)   # (B, max_pages, KV, page, 2hd)
    s_max = table.shape[1] * page

    def heads(x):   # (B, max_pages, KV, page, hd) -> (B, S, KV, hd)
        return jnp.swapaxes(x, 2, 3).reshape(b, s_max, kv, hd)

    k = heads(gathered[..., :hd])
    v = heads(gathered[..., hd:])
    qg = q.astype(jnp.float32).reshape(b, c, kv, rep, hd) / math.sqrt(hd)
    s = jnp.einsum("bckrd,bpkd->bckrp", qg, k)
    q_abs = lengths[:, None] - c + jnp.arange(c)[None, :]      # (B, C)
    mask = jnp.arange(s_max)[None, None, :] <= q_abs[:, :, None]
    s = jnp.where(mask[:, :, None, None, :], s, NEG_INF)
    s = s - jnp.max(s, axis=-1, keepdims=True)
    pexp = jnp.where(mask[:, :, None, None, :], jnp.exp(s), 0.0)
    num = jnp.einsum("bckrp,bpkd->bckrd", pexp, v)
    den = jnp.maximum(jnp.sum(pexp, axis=-1), 1e-30)
    return (num / den[..., None]).reshape(b, c, h, hd).astype(q.dtype)


def paged_attention(q: jnp.ndarray, pool: jnp.ndarray, table: jnp.ndarray,
                    lengths: jnp.ndarray, *,
                    interpret: Optional[bool] = None,
                    use_ref: bool = False) -> jnp.ndarray:
    """Ragged paged attention over a fused-layout page pool.

    q: (B, C, H, hd) — C == 1 for decode, C == chunk for chunked prefill
    (queries at absolute positions ``lengths - C .. lengths - 1``).
    pool: (n_pages, KV, page, 2 * hd), K on lanes [0, hd), V on [hd, 2hd).
    table: (B, max_pages) int32 page ids (0 = null page for padding).
    lengths: (B,) int32 live positions per row (0 = inactive row -> zeros).

    Returns (B, C, H, hd) in q.dtype. Falls back to the dense jnp reference
    when the geometry exceeds the VMEM fit gate, or unconditionally with
    ``use_ref=True`` — the serving engine's graceful-degradation path
    retraces through the reference when a kernel launch fails mid-serve.
    """
    b, c, h, hd = q.shape
    _, kv, page, hd2 = pool.shape
    assert hd2 == 2 * hd, (pool.shape, q.shape)
    assert h % kv == 0, (h, kv)
    rep = h // kv
    max_pages = table.shape[1]
    if use_ref or not paged_fits(c, h, hd, page, kv):
        return paged_attention_ref(q, pool, table, lengths)

    cr = c * rep
    # (B, C, KV, rep, hd) -> (B, KV, C * rep, hd): row c * rep + r per kv head
    qg = jnp.swapaxes(q.reshape(b, c, kv, rep, hd), 1, 2).reshape(b, kv, cr, hd)
    kernel = functools.partial(_paged_kernel, page=page, kv=kv, rep=rep)
    f32 = jnp.float32
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, max_pages),
            in_specs=[
                pl.BlockSpec((1, kv, cr, hd), lambda bi, p, tbl, ln: (bi, 0, 0, 0)),
                pl.BlockSpec((1, kv, page, 2 * hd),
                             lambda bi, p, tbl, ln: (tbl[bi, p], 0, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, kv, cr, hd), lambda bi, p, tbl, ln: (bi, 0, 0, 0)),
                pl.BlockSpec((1, kv, cr, 1), lambda bi, p, tbl, ln: (bi, 0, 0, 0)),
                pl.BlockSpec((1, kv, cr, 1), lambda bi, p, tbl, ln: (bi, 0, 0, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, kv, cr, hd), f32),
            jax.ShapeDtypeStruct((b, kv, cr, 1), f32),
            jax.ShapeDtypeStruct((b, kv, cr, 1), f32),
        ],
        interpret=default_interpret(interpret),
    )(table, lengths, qg, pool)
    out = acc / jnp.maximum(l, 1e-30)
    out = jnp.swapaxes(out.reshape(b, kv, c, rep, hd), 1, 2).reshape(b, c, h, hd)
    return out.astype(q.dtype)
