"""GQA attention: training forward (chunked online-softmax), prefill, decode.

Long sequences (prefill_32k) cannot materialize (S, S) score matrices — at
32k that is 4 GB fp32 *per (batch, head)*. ``chunked_attention`` is a
flash-attention-style jnp formulation: lax.scan over KV blocks with a running
(max, sum, acc) online softmax, O(S * block) memory. XLA fuses it well on
TPU; a Pallas kernel would go here if attention were the paper's hot spot —
the paper's hot spot is the optimizer, which does get kernels
(``repro.kernels``).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from ..sharding.logical import constrain
from .common import ParamSpec, apply_rotary, rotary_embedding, zeros_init

NEG_INF = -1e30


def attention_specs(
    d_model: int,
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
    *,
    qkv_bias: bool = False,
    o_init,
    w_init,
):
    """Projection params stored 3-D: (embed, heads, head_dim) so per-head
    moment partitioning (Adam-mini) and head-stacked SNR dims are first-class."""
    specs = {
        "wq": ParamSpec((d_model, n_heads, head_dim), ("embed", "heads", "head_dim"), "attn_q",
                        w_init, fan_in=("embed",), fan_out=("heads", "head_dim")),
        "wk": ParamSpec((d_model, n_kv_heads, head_dim), ("embed", "kv_heads", "head_dim"), "attn_k",
                        w_init, fan_in=("embed",), fan_out=("kv_heads", "head_dim")),
        "wv": ParamSpec((d_model, n_kv_heads, head_dim), ("embed", "kv_heads", "head_dim"), "attn_v",
                        w_init, fan_in=("embed",), fan_out=("kv_heads", "head_dim")),
        "wo": ParamSpec((n_heads, head_dim, d_model), ("heads", "head_dim", "embed"), "attn_o",
                        o_init, fan_in=("heads", "head_dim"), fan_out=("embed",)),
    }
    if qkv_bias:
        specs["bq"] = ParamSpec((n_heads, head_dim), ("heads", "head_dim"), "attn_qkv_bias", zeros_init())
        specs["bk"] = ParamSpec((n_kv_heads, head_dim), ("kv_heads", "head_dim"), "attn_qkv_bias", zeros_init())
        specs["bv"] = ParamSpec((n_kv_heads, head_dim), ("kv_heads", "head_dim"), "attn_qkv_bias", zeros_init())
    return specs


def _project_qkv(p, x, rope_sincos, positions):
    """x: (B, S, D) -> q (B,S,H,hd), k/v (B,S,KV,hd)."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    if rope_sincos is not None:
        sin, cos = rope_sincos
        q = apply_rotary(q, sin, cos)
        k = apply_rotary(k, sin, cos)
    q = constrain(q, "batch", "seq", "act_heads", None)
    k = constrain(k, "batch", "seq", "act_heads", None)
    v = constrain(v, "batch", "seq", "act_heads", None)
    return q, k, v


def _repeat_kv(k: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    if n_rep == 1:
        return k
    b, s, kv, hd = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, kv, n_rep, hd)).reshape(b, s, kv * n_rep, hd)


def dense_attention(q, k, v, *, causal: bool) -> jnp.ndarray:
    """Reference O(S^2)-memory attention (small S / oracle for tests)."""
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        scores = jnp.where(mask[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return out


def chunked_attention(q, k, v, *, causal: bool, kv_block: int = 1024) -> jnp.ndarray:
    """Online-softmax attention, O(S * kv_block) live memory.

    q: (B, Sq, H, hd); k, v: (B, Sk, H, hd). Scans KV blocks carrying
    (running max, running denom, running numerator).
    """
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    kv_block = min(kv_block, sk)
    if sk % kv_block != 0:
        raise ValueError(f"seq {sk} not divisible by kv_block {kv_block}")
    n_blocks = sk // kv_block
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))

    qf = q.astype(jnp.float32) * scale
    kb = k.reshape(b, n_blocks, kv_block, h, hd)
    vb = v.reshape(b, n_blocks, kv_block, h, hd)
    # scan over kv blocks: put block dim first
    kb = jnp.moveaxis(kb, 1, 0)  # (n, B, kv_block, H, hd)
    vb = jnp.moveaxis(vb, 1, 0)

    q_pos = jnp.arange(sq)[:, None]  # query positions (offset = sk - sq for self-attn suffix)
    q_abs = q_pos + (sk - sq)

    def body(carry, blk):
        m, l, acc = carry
        k_i, v_i, i = blk
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, k_i.astype(jnp.float32))
        if causal:
            k_abs = i * kv_block + jnp.arange(kv_block)[None, :]
            mask = q_abs >= k_abs  # (Sq, kv_block)
            s = jnp.where(mask[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum("bhqk,bkhd->bhqd", p, v_i.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    acc0 = jnp.zeros((b, h, sq, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0), (kb, vb, jnp.arange(n_blocks)))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return jnp.moveaxis(out, 1, 2).astype(q.dtype)  # (B, Sq, H, hd)


def _largest_block(s: int, pref: int) -> int:
    """Largest divisor of s that is <= pref (VLM cells have S = text + patches,
    e.g. 4352, which plain power-of-two blocks don't divide)."""
    if s <= pref:
        return s
    for b in range(min(pref, s), 0, -1):
        if s % b == 0:
            return b
    return 1


# ---------------------------------------------------------------------------
# Flash attention with a hand-written VJP.
#
# Differentiating the chunked scan above makes jax save the per-block softmax
# probabilities (B, H, Sq, block) for backward — ~600 MB/layer/sample at 4k —
# which is exactly the memory wall flash attention exists to break. The
# custom VJP saves only (out, lse) and *recomputes* each probability block in
# the backward scan, so live attention memory is O(S * d) per layer.
# ---------------------------------------------------------------------------


def _flash_fwd_inner(q, k, v, *, causal: bool, kv_block: int):
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    n_blocks = sk // kv_block
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    qf = q.astype(jnp.float32) * scale
    kb = jnp.moveaxis(k.reshape(b, n_blocks, kv_block, h, hd), 1, 0)
    vb = jnp.moveaxis(v.reshape(b, n_blocks, kv_block, h, hd), 1, 0)
    q_abs = jnp.arange(sq)[:, None] + (sk - sq)

    def body(carry, blk):
        m, l, acc = carry
        k_i, v_i, i = blk
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, k_i.astype(jnp.float32))
        if causal:
            k_abs = i * kv_block + jnp.arange(kv_block)[None, :]
            s = jnp.where((q_abs >= k_abs)[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum("bhqk,bkhd->bhqd", p, v_i.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    # derive init carries from q so they inherit its varying-manual-axes type
    # (required when this runs inside shard_map; free otherwise)
    zero = jnp.moveaxis(qf, 1, 2) * 0.0                    # (B,H,Sq,hd)
    m0 = zero[..., 0] + NEG_INF
    l0 = zero[..., 0]
    acc0 = zero
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0), (kb, vb, jnp.arange(n_blocks)))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    return jnp.moveaxis(out, 1, 2).astype(q.dtype), lse  # out (B,Sq,H,hd); lse (B,H,Sq)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q, k, v, causal: bool = True, kv_block: int = 1024):
    out, _ = _flash_fwd_inner(q, k, v, causal=causal, kv_block=kv_block)
    return out


def _flash_fwd(q, k, v, causal, kv_block):
    out, lse = _flash_fwd_inner(q, k, v, causal=causal, kv_block=kv_block)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, kv_block, res, d_out):
    q, k, v, out, lse = res
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    n_blocks = sk // kv_block
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    qf = q.astype(jnp.float32) * scale
    do = jnp.moveaxis(d_out.astype(jnp.float32), 2, 1)   # (B,H,Sq,hd)
    of = jnp.moveaxis(out.astype(jnp.float32), 2, 1)
    delta = jnp.sum(do * of, axis=-1)                     # (B,H,Sq)
    kb = jnp.moveaxis(k.reshape(b, n_blocks, kv_block, h, hd), 1, 0)
    vb = jnp.moveaxis(v.reshape(b, n_blocks, kv_block, h, hd), 1, 0)
    q_abs = jnp.arange(sq)[:, None] + (sk - sq)

    def body(dq, blk):
        k_i, v_i, i = blk
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, k_i.astype(jnp.float32))
        if causal:
            k_abs = i * kv_block + jnp.arange(kv_block)[None, :]
            s = jnp.where((q_abs >= k_abs)[None, None], s, NEG_INF)
        p = jnp.exp(s - lse[..., None])                   # recomputed, O(block)
        dv_i = jnp.einsum("bhqk,bhqd->bkhd", p, do)
        dp = jnp.einsum("bhqd,bkhd->bhqk", do, v_i.astype(jnp.float32))
        ds = p * (dp - delta[..., None])
        dq = dq + jnp.einsum("bhqk,bkhd->bqhd", ds, k_i.astype(jnp.float32)) * scale
        dk_i = jnp.einsum("bhqk,bqhd->bkhd", ds, qf)
        return dq, (dk_i, dv_i)

    dq0 = qf * 0.0  # varying-typed zeros (see fwd)
    dq, (dk_b, dv_b) = jax.lax.scan(body, dq0, (kb, vb, jnp.arange(n_blocks)))
    dk = jnp.moveaxis(dk_b, 0, 1).reshape(b, sk, h, hd)
    dv = jnp.moveaxis(dv_b, 0, 1).reshape(b, sk, h, hd)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


flash_attention.defvjp(_flash_fwd, _flash_bwd)


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    causal: bool = True
    rope: bool = True
    rope_base: float = 10000.0
    qkv_bias: bool = False
    kv_block: int = 1024
    dense_threshold: int = 2048  # use O(S^2) path only below this seq length


def _attention_explicit_tp(p, x: jnp.ndarray, cfg: AttnConfig):
    """Explicit Megatron-SP tensor parallelism for attention (see
    ``_mlp_explicit_tp``): one bf16 all-gather of the SP activations in, local
    flash attention over this shard's query heads, one bf16 reduce-scatter of
    the out-projection partial sums. GQA with kv_heads < tp keeps K/V compute
    replicated (it is ~kv/heads of the work) and gathers each shard's kv
    group by index. Returns None when shapes don't allow it."""
    import math as _math

    from ..sharding.logical import current
    from jax.sharding import PartitionSpec as P

    ctx = current()
    if ctx is None or "model" not in ctx.mesh.axis_names:
        return None
    mesh = ctx.mesh
    tp = mesh.shape["model"]
    b, s, d = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    if tp == 1 or s % tp or h % tp or cfg.qkv_bias:
        return None
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    if b % _math.prod(mesh.shape[a] for a in batch_axes):
        return None

    h_l = h // tp
    dtype = x.dtype
    xspec = P(batch_axes, "model", None)
    kv_sharded = kv % tp == 0

    def body(x_l, wq, wk, wv, wo):
        x_full = jax.lax.all_gather(x_l, "model", axis=1, tiled=True)
        q = jnp.einsum("bsd,dhk->bshk", x_full, wq.astype(dtype))
        k = jnp.einsum("bsd,dhk->bshk", x_full, wk.astype(dtype))
        v = jnp.einsum("bsd,dhk->bshk", x_full, wv.astype(dtype))
        if cfg.rope:
            sin, cos = rotary_embedding(jnp.arange(s), hd, cfg.rope_base)
            q = apply_rotary(q, sin, cos)
            k = apply_rotary(k, sin, cos)
        idx = jax.lax.axis_index("model")
        if kv_sharded:
            # each shard already holds its kv slice; expand to local q heads
            k_l = _repeat_kv(k, h_l // k.shape[2])
            v_l = _repeat_kv(v, h_l // v.shape[2])
        else:
            groups = (idx * h_l + jnp.arange(h_l)) * kv // h
            k_l = jnp.take(k, groups, axis=2)
            v_l = jnp.take(v, groups, axis=2)
        if s <= cfg.dense_threshold:
            out = dense_attention(q, k_l, v_l, causal=cfg.causal)
        else:
            out = flash_attention(q, k_l, v_l, cfg.causal, _largest_block(s, cfg.kv_block))
        y_part = jnp.einsum("bshk,hkd->bsd", out, wo.astype(dtype)).astype(dtype)
        return jax.lax.psum_scatter(y_part, "model", scatter_dimension=1, tiled=True)

    kvspec = P(None, "model", None) if kv_sharded else P(None, None, None)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(xspec, P(None, "model", None), kvspec, kvspec, P("model", None, None)),
        out_specs=xspec,
    )(x, p["wq"], p["wk"], p["wv"], p["wo"])


def attention_forward(p, x: jnp.ndarray, cfg: AttnConfig) -> jnp.ndarray:
    """Full-sequence forward (training / prefill)."""
    y = _attention_explicit_tp(p, x, cfg)
    if y is not None:
        return y
    b, s, d = x.shape
    rope_sincos = None
    if cfg.rope:
        rope_sincos = rotary_embedding(jnp.arange(s), cfg.head_dim, cfg.rope_base)
    q, k, v = _project_qkv(p, x, rope_sincos, None)
    n_rep = cfg.n_heads // cfg.n_kv_heads
    k, v = _repeat_kv(k, n_rep), _repeat_kv(v, n_rep)
    if s <= cfg.dense_threshold:
        out = dense_attention(q, k, v, causal=cfg.causal)
    else:
        out = flash_attention(q, k, v, cfg.causal, _largest_block(s, cfg.kv_block))
    out = constrain(out, "batch", "seq", "act_heads", None)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    # constrain the partial-sum output directly to the sequence-parallel spec:
    # GSPMD then lowers the TP reduction as reduce-scatter instead of
    # all-reduce + slice (half the ICI bytes)
    return constrain(y, "batch", "seq_sp", "act_embed")


class KVCache(NamedTuple):
    """Per-attention-layer decode cache. k/v: (B, S_max, KV, hd); index: ().

    With ``quant=True`` k/v are int8 with per-(batch, position, head) fp32
    scales — halving cache HBM vs bf16. This is what makes the qwen1.5-32b
    decode_32k cell (64L MHA kv=40: a 5.5 TB bf16 cache) fit a single pod.
    """

    k: jnp.ndarray
    v: jnp.ndarray
    k_scale: jnp.ndarray  # (B, S_max, KV) fp32 for int8; (1,) placeholder otherwise
    v_scale: jnp.ndarray
    index: jnp.ndarray    # current fill length (int32 scalar)

    @property
    def quantized(self) -> bool:
        return self.k.dtype == jnp.int8


def init_kv_cache(batch: int, max_seq: int, n_kv: int, head_dim: int,
                  dtype=jnp.bfloat16, *, quant: bool = False) -> KVCache:
    if quant:
        return KVCache(
            k=jnp.zeros((batch, max_seq, n_kv, head_dim), jnp.int8),
            v=jnp.zeros((batch, max_seq, n_kv, head_dim), jnp.int8),
            k_scale=jnp.zeros((batch, max_seq, n_kv), jnp.float32),
            v_scale=jnp.zeros((batch, max_seq, n_kv), jnp.float32),
            index=jnp.zeros((), jnp.int32),
        )
    return KVCache(
        k=jnp.zeros((batch, max_seq, n_kv, head_dim), dtype),
        v=jnp.zeros((batch, max_seq, n_kv, head_dim), dtype),
        k_scale=jnp.zeros((1,), jnp.float32),
        v_scale=jnp.zeros((1,), jnp.float32),
        index=jnp.zeros((), jnp.int32),
    )


def _quantize_kv(x: jnp.ndarray):
    """x: (B, S, KV, hd) -> (int8 values, (B, S, KV) scales)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


# ---------------------------------------------------------------------------
# Paged serving path: KV lives in a page pool (layout owned by
# repro.kernels.paged_attention), addressed through a per-request page table.
# Decode and chunked prefill both reduce through the same ragged Pallas
# kernel.
# ---------------------------------------------------------------------------


def attention_paged_decode(p, x: jnp.ndarray, pool: jnp.ndarray,
                           table: jnp.ndarray, lengths: jnp.ndarray,
                           active: jnp.ndarray, cfg: AttnConfig,
                           *, interpret=None,
                           use_ref=False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One-token paged decode. x: (B, 1, D); pool: (pages, KV, P, 2 * hd);
    table: (B, max_pages) page ids of this layer's pages in ``pool``;
    lengths: (B,) positions already stored. Writes the new token's K/V at
    position ``lengths`` (inactive rows are routed to page 0, which no table
    entry of a live row ever points at), then attends over ``lengths + 1``
    positions. Returns (y (B, 1, D), pool).
    """
    from ..kernels.paged_attention import paged_attention, pool_write

    b = x.shape[0]
    pos = lengths
    rope_sincos = None
    if cfg.rope:
        rope_sincos = rotary_embedding(pos[:, None], cfg.head_dim, cfg.rope_base)
    q, k_new, v_new = _project_qkv(p, x, rope_sincos, None)

    with jax.named_scope("kv_pool"):
        pool = pool_write(pool, table, jnp.arange(b), pos, active,
                          k_new[:, 0], v_new[:, 0])

    kv_len = jnp.where(active, pos + 1, 0).astype(jnp.int32)
    out = paged_attention(q, pool, table, kv_len, interpret=interpret,
                          use_ref=use_ref)
    y = jnp.einsum("bshk,hkd->bsd", out.astype(x.dtype), p["wo"].astype(x.dtype))
    return constrain(y, "batch", "seq", "act_embed"), pool


def attention_paged_prefill(p, x: jnp.ndarray, pool: jnp.ndarray,
                            table_row: jnp.ndarray, pos0, n_valid,
                            cfg: AttnConfig,
                            *, interpret=None,
                            use_ref=False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One chunk of paged prefill for a single request. x: (1, C, D) holding
    the prompt tokens at absolute positions ``pos0 .. pos0 + C - 1``;
    positions at chunk index >= ``n_valid`` are padding — their K/V writes are
    routed to page 0 and their outputs are garbage nobody reads (the
    caller samples at chunk index ``n_valid - 1``). The in-kernel causal mask
    ``k_abs <= q_abs`` keeps every *valid* query's reduction inside the
    row's live pages. pool and table_row as in
    :func:`attention_paged_decode`. Returns (y (1, C, D), pool)."""
    from ..kernels.paged_attention import paged_attention, pool_write

    c = x.shape[1]
    positions = pos0 + jnp.arange(c)
    rope_sincos = None
    if cfg.rope:
        rope_sincos = rotary_embedding(positions, cfg.head_dim, cfg.rope_base)
    q, k_new, v_new = _project_qkv(p, x, rope_sincos, None)

    valid = jnp.arange(c) < n_valid
    with jax.named_scope("kv_pool"):
        pool = pool_write(pool, table_row, jnp.zeros(c, jnp.int32), positions,
                          valid, k_new[0], v_new[0])

    kv_len = jnp.asarray(pos0 + c, jnp.int32)[None]
    out = paged_attention(q, pool, table_row, kv_len, interpret=interpret,
                          use_ref=use_ref)
    y = jnp.einsum("bshk,hkd->bsd", out.astype(x.dtype), p["wo"].astype(x.dtype))
    return constrain(y, "batch", "seq", "act_embed"), pool


def attention_decode(p, x: jnp.ndarray, cache: KVCache, cfg: AttnConfig) -> Tuple[jnp.ndarray, KVCache]:
    """One-token decode: x (B, 1, D), cache holds `index` previous positions."""
    b, s1, d = x.shape
    assert s1 == 1
    pos = cache.index
    rope_sincos = None
    if cfg.rope:
        rope_sincos = rotary_embedding(pos[None], cfg.head_dim, cfg.rope_base)
    q, k_new, v_new = _project_qkv(p, x, rope_sincos, None)

    if cache.quantized:
        k_q, k_s = _quantize_kv(k_new)
        v_q, v_s = _quantize_kv(v_new)
        k_cache = jax.lax.dynamic_update_slice(cache.k, k_q, (0, pos, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(cache.v, v_q, (0, pos, 0, 0))
        k_scale = jax.lax.dynamic_update_slice(cache.k_scale, k_s, (0, pos, 0))
        v_scale = jax.lax.dynamic_update_slice(cache.v_scale, v_s, (0, pos, 0))
    else:
        k_cache = jax.lax.dynamic_update_slice(cache.k, k_new.astype(cache.k.dtype), (0, pos, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(cache.v, v_new.astype(cache.v.dtype), (0, pos, 0, 0))
        k_scale, v_scale = cache.k_scale, cache.v_scale
    k_cache = constrain(k_cache, "batch", "seq_kv", None, None)
    v_cache = constrain(v_cache, "batch", "seq_kv", None, None)

    n_rep = cfg.n_heads // cfg.n_kv_heads
    s_max = k_cache.shape[1]
    scale = 1.0 / jnp.sqrt(jnp.asarray(cfg.head_dim, jnp.float32))
    # grouped-query scores against the whole cache, masked beyond `index`
    qg = q.reshape(b, 1, cfg.n_kv_heads, n_rep, cfg.head_dim).astype(jnp.float32) * scale
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k_cache.astype(jnp.float32))
    if cache.quantized:
        # fold the int8 dequant scale into the (b, k, g) score/value terms
        scores = scores * jnp.moveaxis(k_scale, 1, 2)[:, :, None, None, :]
    valid = (jnp.arange(s_max) <= pos)[None, None, None, None, :]
    scores = jnp.where(valid, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    if cache.quantized:
        probs = probs * jnp.moveaxis(v_scale, 1, 2)[:, :, None, None, :]
    out = jnp.einsum("bgrqk,bkgd->bqgrd", probs, v_cache.astype(jnp.float32))
    out = out.reshape(b, 1, cfg.n_heads, cfg.head_dim).astype(x.dtype)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return constrain(y, "batch", "seq", "act_embed"), KVCache(
        k=k_cache, v=v_cache, k_scale=k_scale, v_scale=v_scale, index=pos + 1)
