"""Unified decoder/encoder backbone covering all 10 assigned architectures.

One config describes a *period* of heterogeneous layer slots (attention or
mamba mixer x dense/MoE/absent FFN); the model scans over ``n_layers /
period`` repetitions with per-slot parameters stacked along a leading
'layers' axis — keeping the HLO O(period), not O(depth), which is what makes
95-layer dry-runs compile fast and cheap.

Covers: dense GQA (command-r, deepseek, smollm, qwen1.5), MoE (qwen3-moe,
olmoe), SSM (falcon-mamba), hybrid SSM+attn+MoE (jamba), encoder-only
(hubert), VLM backbone (internvl2), plus the paper's own GPT-small/medium and
ViT variants.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..kernels.paged_attention import pool_shape
from ..sharding.logical import constrain
from .attention import (
    AttnConfig,
    attention_decode,
    attention_forward,
    attention_specs,
    init_kv_cache,
)
from .common import (
    ParamSpec,
    init_params,
    abstract_params,
    layer_norm,
    meta_tree,
    mitchell_residual_init,
    normal_init,
    ones_init,
    rms_norm,
    stack_specs,
    torch_default_init,
)
from .mlp_moe import MoEConfig, mlp_forward, mlp_specs, moe_forward, moe_specs
from .ssm import SSMConfig, init_ssm_cache, ssm_decode, ssm_forward, ssm_specs


@dataclasses.dataclass(frozen=True)
class LayerSlot:
    mixer: Optional[str]  # 'attn' | 'mamba' | None
    ffn: Optional[str]    # 'dense' | 'moe' | None


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                    # 0 -> d_model // n_heads
    pattern: Tuple[LayerSlot, ...] = (LayerSlot("attn", "dense"),)
    causal: bool = True
    # embeddings / head
    tie_embeddings: bool = True
    pos: str = "rope"                    # 'rope' | 'learned' | 'none'
    max_position: int = 8192             # learned-pos table size
    embed_inputs: bool = True            # False: model consumes (B, S, D) embeddings (audio stub)
    extra_embed_len: int = 0             # VLM: prepended frontend embeddings
    input_proj_dim: int = 0              # >0: learned projection from raw patch/frame features
    # norms / mlp flavor
    norm: str = "rmsnorm"                # 'rmsnorm' | 'layernorm'
    gated_mlp: bool = True
    qkv_bias: bool = False
    # MoE
    n_experts: int = 0
    top_k: int = 0
    # SSM
    ssm_state: int = 16
    ssm_expand: int = 2
    ssm_conv: int = 4
    ssm_chunk: int = 256
    # numerics
    dtype: Any = jnp.bfloat16            # activation/compute dtype
    param_dtype: Any = jnp.float32
    remat: bool = True
    init_scheme: str = "mitchell"        # 'mitchell' | 'torch_default'
    attn_kv_block: int = 1024
    attn_dense_threshold: int = 2048
    kv_quant: bool = False               # int8 KV cache (serving): halves cache HBM
    logical_batch_axes: Tuple[str, ...] = ("batch",)
    # per-arch logical->mesh rule overrides as (name, axes) pairs; e.g. small
    # models repurpose the 'model' axis as extra data parallelism
    sharding_overrides: Tuple[Tuple[str, Any], ...] = ()

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def n_periods(self) -> int:
        assert self.n_layers % len(self.pattern) == 0, (self.n_layers, len(self.pattern))
        return self.n_layers // len(self.pattern)

    def attn_cfg(self) -> AttnConfig:
        return AttnConfig(
            d_model=self.d_model, n_heads=self.n_heads, n_kv_heads=self.n_kv_heads,
            head_dim=self.hd, causal=self.causal, rope=(self.pos == "rope"),
            qkv_bias=self.qkv_bias, kv_block=self.attn_kv_block,
            dense_threshold=self.attn_dense_threshold,
        )

    def ssm_cfg(self) -> SSMConfig:
        return SSMConfig(
            d_model=self.d_model, d_inner=self.ssm_expand * self.d_model,
            d_state=self.ssm_state, d_conv=self.ssm_conv, chunk=self.ssm_chunk,
        )

    def moe_cfg(self) -> MoEConfig:
        return MoEConfig(
            n_experts=self.n_experts, top_k=self.top_k, d_model=self.d_model,
            d_ff=self.d_ff, gated=self.gated_mlp,
        )

    def param_count(self, params=None) -> int:
        tree = params if params is not None else abstract_params(self.specs())
        return sum(int(jnp.size(jax.ShapeDtypeStruct(p.shape, p.dtype))) if hasattr(p, "shape") else 0
                   for p in jax.tree.leaves(tree))

    # ------------------------------------------------------------------
    # Parameter specification
    # ------------------------------------------------------------------

    def _inits(self):
        if self.init_scheme == "torch_default":
            w = torch_default_init()
            return w, w, w
        w = normal_init(0.02)
        if self.init_scheme == "normal":
            # mitchell minus the 1/depth residual scaling (ablation)
            return w, w, normal_init(0.02)
        resid = mitchell_residual_init(0.02, self.n_layers)
        return w, resid, normal_init(0.02)

    def _norm_specs(self, prefix_role: str = "norm"):
        d = self.d_model
        specs = {"scale": ParamSpec((d,), ("embed",), "norm",
                                    ones_init(), dtype=self.param_dtype)}
        return specs

    def slot_specs(self, slot: LayerSlot) -> Dict[str, Any]:
        w_init, resid_init, emb_init = self._inits()
        dt = self.param_dtype
        specs: Dict[str, Any] = {}

        def with_dtype(tree):
            return jax.tree.map(
                lambda s: dataclasses.replace(s, dtype=dt),
                tree, is_leaf=lambda x: isinstance(x, ParamSpec),
            )

        if slot.mixer == "attn":
            specs["mixer_norm"] = self._norm_specs()
            specs["attn"] = with_dtype(attention_specs(
                self.d_model, self.n_heads, self.n_kv_heads, self.hd,
                qkv_bias=self.qkv_bias, o_init=resid_init, w_init=w_init))
        elif slot.mixer == "mamba":
            specs["mixer_norm"] = self._norm_specs()
            specs["ssm"] = with_dtype(ssm_specs(self.ssm_cfg(), w_init=w_init, out_init=resid_init))
        if slot.ffn == "dense":
            specs["ffn_norm"] = self._norm_specs()
            specs["mlp"] = with_dtype(mlp_specs(self.d_model, self.d_ff,
                                                gated=self.gated_mlp, w_init=w_init, down_init=resid_init))
        elif slot.ffn == "moe":
            specs["ffn_norm"] = self._norm_specs()
            specs["moe"] = with_dtype(moe_specs(self.moe_cfg(), w_init=w_init, down_init=resid_init))
        return specs

    def specs(self) -> Dict[str, Any]:
        w_init, resid_init, emb_init = self._inits()
        dt = self.param_dtype
        specs: Dict[str, Any] = {}
        if self.embed_inputs:
            specs["embed"] = ParamSpec((self.vocab_size, self.d_model), ("vocab", "embed"),
                                       "token_embedding", emb_init,
                                       fan_in=("vocab",), fan_out=("embed",), dtype=dt)
        if self.pos == "learned":
            specs["pos_embed"] = ParamSpec((self.max_position, self.d_model), ("pos", "embed"),
                                           "pos_embedding", emb_init, dtype=dt)
        if self.input_proj_dim:
            specs["input_proj"] = ParamSpec((self.input_proj_dim, self.d_model), ("patch", "embed"),
                                            "patch_embed", w_init,
                                            fan_in=("patch",), fan_out=("embed",), dtype=dt)
        blocks = {}
        for i, slot in enumerate(self.pattern):
            blocks[f"slot_{i}"] = stack_specs(self.slot_specs(slot), self.n_periods)
        specs["blocks"] = blocks
        specs["final_norm"] = self._norm_specs()
        if not self.tie_embeddings or not self.embed_inputs:
            specs["lm_head"] = ParamSpec((self.d_model, self.vocab_size), ("embed", "vocab"),
                                         "lm_head", w_init,
                                         fan_in=("embed",), fan_out=("vocab",), dtype=dt)
        return specs

    def init(self, key: jax.Array):
        spec = self.specs()
        return init_params(spec, key), meta_tree(spec)

    def abstract(self):
        spec = self.specs()
        return abstract_params(spec), meta_tree(spec)


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------


def _norm(cfg: ModelConfig, p, x):
    if cfg.norm == "rmsnorm":
        return rms_norm(x, p["scale"])
    return layer_norm(x, p["scale"], None)


def _slot_forward(cfg: ModelConfig, slot: LayerSlot, p, x):
    """One layer slot (mixer + ffn residual blocks). Returns (x, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    if slot.mixer == "attn":
        x = x + attention_forward(p["attn"], _norm(cfg, p["mixer_norm"], x), cfg.attn_cfg())
    elif slot.mixer == "mamba":
        x = x + ssm_forward(p["ssm"], _norm(cfg, p["mixer_norm"], x), cfg.ssm_cfg())
    if slot.ffn == "dense":
        x = x + mlp_forward(p["mlp"], _norm(cfg, p["ffn_norm"], x), gated=cfg.gated_mlp)
    elif slot.ffn == "moe":
        y, a = moe_forward(p["moe"], _norm(cfg, p["ffn_norm"], x), cfg.moe_cfg())
        x = x + y
        aux = aux + a
    return constrain(x, "batch", "seq_sp", "act_embed"), aux


def _embed(cfg: ModelConfig, params, batch: Dict[str, jnp.ndarray]) -> jnp.ndarray:
    if cfg.embed_inputs:
        tokens = batch["tokens"]
        x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
        if cfg.pos == "learned":
            s = tokens.shape[1]
            x = x + params["pos_embed"][:s][None].astype(cfg.dtype)
        if cfg.extra_embed_len:
            ve = batch["frontend_embeds"].astype(cfg.dtype)  # (B, P, D) from stub frontend
            x = jnp.concatenate([ve, x], axis=1)
    elif cfg.input_proj_dim:
        x = jnp.einsum("bsp,pd->bsd", batch["patches"].astype(cfg.dtype),
                       params["input_proj"].astype(cfg.dtype))
        if cfg.pos == "learned":
            s = x.shape[1]
            x = x + params["pos_embed"][:s][None].astype(cfg.dtype)
    else:
        x = batch["frontend_embeds"].astype(cfg.dtype)
    return constrain(x, "batch", "seq_sp", "act_embed")


def _unembed(cfg: ModelConfig, params, x: jnp.ndarray) -> jnp.ndarray:
    # Keep the head weight vocab-sharded (TP) and gather x's sequence dim
    # instead: constraining logits along seq_sp would force GSPMD to fully
    # replicate the (vocab, embed) table in fp32 — measured 3x3.2 GiB/device
    # for deepseek-67b. With vocab@model, CE's logsumexp runs on sharded
    # logits and the tied-embedding gradient reduces to a reduce-scatter.
    x = constrain(x, "batch", "seq", "act_embed")
    if cfg.tie_embeddings and cfg.embed_inputs:
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"].astype(cfg.dtype))
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"].astype(cfg.dtype))
    return constrain(logits, "batch", "seq", "vocab")


def forward(cfg: ModelConfig, params, batch: Dict[str, jnp.ndarray]):
    """Training/prefill forward. batch: {'tokens': (B,S) int32, ...}.

    Returns (logits (B, S_total, vocab) in cfg.dtype, aux_loss scalar).
    """
    x = _embed(cfg, params, batch)

    period = len(cfg.pattern)

    def period_body(carry, period_params):
        x, aux = carry
        for i, slot in enumerate(cfg.pattern):
            f = functools.partial(_slot_forward, cfg, slot)
            if cfg.remat:
                f = jax.checkpoint(f, policy=jax.checkpoint_policies.nothing_saveable)
            x, a = f(period_params[f"slot_{i}"], x)
            aux = aux + a
        return (x, aux), None

    (x, aux), _ = jax.lax.scan(period_body, (x, jnp.zeros((), jnp.float32)), params["blocks"])
    x = _norm(cfg, params["final_norm"], x)
    logits = _unembed(cfg, params, x)
    return logits, aux


# ---------------------------------------------------------------------------
# Decode (serving) path
# ---------------------------------------------------------------------------


class DecodeCache(NamedTuple):
    slots: Dict[str, Any]   # per-slot stacked caches (KVCache | SSMCache)
    step: jnp.ndarray       # tokens generated so far (int32)


def init_decode_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype=jnp.bfloat16) -> DecodeCache:
    slots: Dict[str, Any] = {}
    for i, slot in enumerate(cfg.pattern):
        if slot.mixer == "attn":
            c = init_kv_cache(batch, max_seq, cfg.n_kv_heads, cfg.hd, dtype, quant=cfg.kv_quant)
        elif slot.mixer == "mamba":
            c = init_ssm_cache(batch, cfg.ssm_cfg(), dtype)
        else:
            continue
        # stack over periods
        slots[f"slot_{i}"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (cfg.n_periods,) + a.shape), c
        )
    return DecodeCache(slots=slots, step=jnp.zeros((), jnp.int32))


def abstract_decode_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype=jnp.bfloat16):
    return jax.eval_shape(lambda: init_decode_cache(cfg, batch, max_seq, dtype))


def decode_step(cfg: ModelConfig, params, cache: DecodeCache, tokens: jnp.ndarray):
    """One new token per sequence. tokens: (B, 1) int32.

    The caches were pre-filled to ``cache.step`` positions (for the dry-run
    cells the cache is abstract at its full seq_len). Returns (logits (B, 1,
    vocab), new cache).
    """
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype) if cfg.embed_inputs else tokens
    if cfg.pos == "learned":
        x = x + jax.lax.dynamic_slice_in_dim(params["pos_embed"], cache.step, 1, 0)[None].astype(cfg.dtype)
    x = constrain(x, "batch", "seq", "act_embed")

    # Decode caches ride in the scan CARRY, updated in place with
    # dynamic_update_index: carried buffers alias across loop iterations.
    # Alternatives measured on the qwen1.5-32b decode_32k cell (CPU-backend
    # buffer assignment): xs->ys scan = 61 GiB, fully unrolled layer loop =
    # 147 GiB, carry = best (deepseek-67b decode fits at 7.6 GiB).
    def period_body(carry, operand):
        x, slot_caches = carry
        period_params, idx = operand
        for i, slot in enumerate(cfg.pattern):
            key = f"slot_{i}"
            p = period_params[key]
            if slot.mixer in ("attn", "mamba"):
                c = jax.tree.map(lambda buf: jax.lax.dynamic_index_in_dim(buf, idx, 0, keepdims=False),
                                 slot_caches[key])
                if slot.mixer == "attn":
                    y, nc = attention_decode(p["attn"], _norm(cfg, p["mixer_norm"], x), c, cfg.attn_cfg())
                else:
                    y, nc = ssm_decode(p["ssm"], _norm(cfg, p["mixer_norm"], x), c, cfg.ssm_cfg())
                x = x + y
                slot_caches = dict(slot_caches)
                slot_caches[key] = jax.tree.map(
                    lambda buf, new: jax.lax.dynamic_update_index_in_dim(
                        buf, new.astype(buf.dtype), idx, 0),
                    slot_caches[key], nc)
            if slot.ffn == "dense":
                x = x + mlp_forward(p["mlp"], _norm(cfg, p["ffn_norm"], x), gated=cfg.gated_mlp)
            elif slot.ffn == "moe":
                y, _ = moe_forward(p["moe"], _norm(cfg, p["ffn_norm"], x), cfg.moe_cfg())
                x = x + y
        return (x, slot_caches), None

    idxs = jnp.arange(cfg.n_periods)
    (x, new_slot_caches), _ = jax.lax.scan(period_body, (x, cache.slots), (params["blocks"], idxs))
    x = _norm(cfg, params["final_norm"], x)
    logits = _unembed(cfg, params, x)
    return logits, DecodeCache(slots=new_slot_caches, step=cache.step + 1)


# ---------------------------------------------------------------------------
# Paged decode (serving fast path)
#
# KV lives in per-slot page *pools* shared by every in-flight request and
# addressed through a per-slot-row page table (repro.serve.kvpool owns the
# host-side allocation; repro.kernels.paged_attention does the ragged
# reduction). Unlike DecodeCache there is no per-request (B, S_max) buffer —
# admitting or retiring a request costs zero device reallocation, which is
# what makes continuous batching (repro.serve.scheduler) a pure host-side
# bookkeeping loop over fixed-shape jit calls.
# ---------------------------------------------------------------------------


class PagedState(NamedTuple):
    """Device state for the paged decode path.

    pools:   {'slot_i': (n_periods * n_pages, KV, page, 2*hd)} per attn slot;
             period l's pages are ``l * n_pages + table``
    table:   (B, max_pages) int32 page ids; entry 0 = reserved null page
    lengths: (B,) int32 positions already stored per batch row
    active:  (B,) bool — inactive rows write to the null page and attend
             over 0 positions (their logits are garbage nobody samples)
    """

    pools: Dict[str, jnp.ndarray]
    table: jnp.ndarray
    lengths: jnp.ndarray
    active: jnp.ndarray


def supports_paged(cfg: ModelConfig) -> bool:
    """The paged fast path covers token-in/token-out attention-only stacks.
    SSM/hybrid mixers carry recurrent (not positional) state and int8 KV
    pages are future work, so those fall back to the legacy decode loop."""
    return (cfg.embed_inputs and not cfg.kv_quant
            and all(s.mixer in ("attn", None) for s in cfg.pattern)
            and any(s.mixer == "attn" for s in cfg.pattern))


def init_paged_pools(cfg: ModelConfig, n_pages: int, page_size: int,
                     dtype=jnp.bfloat16) -> Dict[str, jnp.ndarray]:
    """One lane-dense page pool per attention slot, its periods' pools laid
    end to end: ``(n_periods * n_pages, KV, page, 2 * hd)``, period ``l``
    on pages ``[l * n_pages, (l + 1) * n_pages)``. Page 0 of each period is
    its null page (padded table entries; never read because those rows
    report length 0); dead writes of every period go to page 0."""
    pools: Dict[str, jnp.ndarray] = {}
    for i, slot in enumerate(cfg.pattern):
        if slot.mixer == "attn":
            pools[f"slot_{i}"] = jnp.zeros(
                pool_shape(cfg.n_periods * n_pages, page_size,
                           cfg.n_kv_heads, cfg.hd), dtype)
    return pools


def _period_table(cfg: ModelConfig, pool: jnp.ndarray, table: jnp.ndarray,
                  idx) -> jnp.ndarray:
    """``table`` addressing period ``idx``'s pages of a pool laid out by
    :func:`init_paged_pools`."""
    return table + idx * (pool.shape[0] // cfg.n_periods)


def paged_decode_step(cfg: ModelConfig, params, state: PagedState,
                      tokens: jnp.ndarray, *, attn_impl: str = "kernel"):
    """One new token for every active batch row. tokens: (B, 1) int32.

    Returns (logits (B, 1, vocab), ok (B,) bool, new PagedState) — lengths
    advance only on active rows, so a freshly-retired slot can sit idle at
    no cost. Each slot's whole pool rides in the scan carry and every period
    addresses its own pages through ``table + idx * n_pages``: no period's
    pool is sliced out or written back, so a caller that donates the pools
    has them updated in place.

    ``ok`` is the **logit health tap**: per-row all-finite flags computed
    on-device, so the serving engine can detect a poisoned slot (NaN/Inf
    logits) without ever scanning the vocab axis on the host — the same
    in-pass health-stat discipline the guarded train step uses. A row that
    taps False is retired with ``reason="nan"`` instead of sampling garbage.

    ``attn_impl="ref"`` routes attention through the dense
    :func:`repro.kernels.paged_attention.paged_attention_ref` path — the
    engine's per-step graceful degradation when the Pallas launch fails.
    """
    from .attention import attention_paged_decode

    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    if cfg.pos == "learned":
        posv = jnp.clip(state.lengths, 0, cfg.max_position - 1)
        x = x + jnp.take(params["pos_embed"], posv, axis=0)[:, None].astype(cfg.dtype)
    x = constrain(x, "batch", "seq", "act_embed")

    def period_body(carry, operand):
        x, pools = carry
        period_params, idx = operand
        for i, slot in enumerate(cfg.pattern):
            key = f"slot_{i}"
            p = period_params[key]
            if slot.mixer == "attn":
                y, pool = attention_paged_decode(
                    p["attn"], _norm(cfg, p["mixer_norm"], x), pools[key],
                    _period_table(cfg, pools[key], state.table, idx),
                    state.lengths, state.active, cfg.attn_cfg(),
                    use_ref=attn_impl == "ref")
                x = x + y
                pools = {**pools, key: pool}
            if slot.ffn == "dense":
                x = x + mlp_forward(p["mlp"], _norm(cfg, p["ffn_norm"], x), gated=cfg.gated_mlp)
            elif slot.ffn == "moe":
                y, _ = moe_forward(p["moe"], _norm(cfg, p["ffn_norm"], x), cfg.moe_cfg())
                x = x + y
        return (x, pools), None

    idxs = jnp.arange(cfg.n_periods)
    (x, pools), _ = jax.lax.scan(period_body, (x, state.pools), (params["blocks"], idxs))
    x = _norm(cfg, params["final_norm"], x)
    logits = _unembed(cfg, params, x)
    ok = jnp.all(jnp.isfinite(logits.astype(jnp.float32)), axis=(1, 2))
    return logits, ok, PagedState(
        pools=pools, table=state.table,
        lengths=state.lengths + state.active.astype(jnp.int32),
        active=state.active)


def paged_prefill_chunk(cfg: ModelConfig, params, pools: Dict[str, jnp.ndarray],
                        table_row: jnp.ndarray, pos0, n_valid,
                        tokens: jnp.ndarray, *, attn_impl: str = "kernel"):
    """Prefill one chunk of one request's prompt through the paged kernel.

    tokens: (1, C) int32 at absolute positions ``pos0 .. pos0 + C - 1``;
    chunk indices >= ``n_valid`` are padding (K/V routed to the null page).
    ``pos0`` / ``n_valid`` are traced scalars, so every chunk of every
    request reuses one jit executable. Returns
    (logits (1, C, vocab), ok () bool, pools); the caller samples the first
    generated token at chunk index ``n_valid - 1`` of the final chunk, and
    ``ok`` is the logit health tap for exactly that row (padding rows carry
    garbage nobody reads, so only the sampled row's finiteness matters).
    ``attn_impl="ref"`` degrades to the dense reference attention, as in
    :func:`paged_decode_step`.
    """
    from .attention import attention_paged_prefill

    c = tokens.shape[1]
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    if cfg.pos == "learned":
        posv = jnp.clip(pos0 + jnp.arange(c), 0, cfg.max_position - 1)
        x = x + jnp.take(params["pos_embed"], posv, axis=0)[None].astype(cfg.dtype)
    x = constrain(x, "batch", "seq", "act_embed")

    def period_body(carry, operand):
        x, pools = carry
        period_params, idx = operand
        for i, slot in enumerate(cfg.pattern):
            key = f"slot_{i}"
            p = period_params[key]
            if slot.mixer == "attn":
                y, pool = attention_paged_prefill(
                    p["attn"], _norm(cfg, p["mixer_norm"], x), pools[key],
                    _period_table(cfg, pools[key], table_row, idx), pos0, n_valid,
                    cfg.attn_cfg(), use_ref=attn_impl == "ref")
                x = x + y
                pools = {**pools, key: pool}
            if slot.ffn == "dense":
                x = x + mlp_forward(p["mlp"], _norm(cfg, p["ffn_norm"], x), gated=cfg.gated_mlp)
            elif slot.ffn == "moe":
                y, _ = moe_forward(p["moe"], _norm(cfg, p["ffn_norm"], x), cfg.moe_cfg())
                x = x + y
        return (x, pools), None

    idxs = jnp.arange(cfg.n_periods)
    (x, pools), _ = jax.lax.scan(period_body, (x, pools), (params["blocks"], idxs))
    x = _norm(cfg, params["final_norm"], x)
    logits = _unembed(cfg, params, x)
    sampled = jax.lax.dynamic_index_in_dim(logits[0], n_valid - 1, 0,
                                           keepdims=False)
    ok = jnp.all(jnp.isfinite(sampled.astype(jnp.float32)))
    return logits, ok, pools
