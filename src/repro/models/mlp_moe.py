"""Dense MLP blocks (gated SiLU / GELU) and top-k MoE with expert parallelism.

MoE dispatch is gather/scatter-based (no (tokens, experts, capacity) one-hot
dispatch tensor): per expert we build a (capacity,) token-index list from a
cumsum over the routing mask, gather the rows, run the expert FFN batched
over the (sharded) expert dim, and scatter-add back weighted by the gate.
Under the production mesh the expert dim is sharded over 'model' (EP) and the
token rows move through an XLA-inserted all-gather — the collective the
roofline analysis attributes to MoE.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import jax
import jax.numpy as jnp

from ..sharding.logical import constrain
from .common import ParamSpec


def gelu(x):
    return jax.nn.gelu(x, approximate=True)


def mlp_specs(d_model: int, d_ff: int, *, gated: bool, w_init, down_init):
    specs = {
        "w_up": ParamSpec((d_model, d_ff), ("embed", "mlp"), "mlp_up", w_init,
                          fan_in=("embed",), fan_out=("mlp",)),
        "w_down": ParamSpec((d_ff, d_model), ("mlp", "embed"), "mlp_down", down_init,
                            fan_in=("mlp",), fan_out=("embed",)),
    }
    if gated:
        specs["w_gate"] = ParamSpec((d_model, d_ff), ("embed", "mlp"), "mlp_gate", w_init,
                                    fan_in=("embed",), fan_out=("mlp",))
    return specs


def mlp_forward(p, x: jnp.ndarray, *, gated: bool) -> jnp.ndarray:
    y = _mlp_explicit_tp(p, x, gated=gated)
    if y is not None:
        return y
    h = jnp.einsum("...d,df->...f", x, p["w_up"].astype(x.dtype))
    if gated:
        g = jnp.einsum("...d,df->...f", x, p["w_gate"].astype(x.dtype))
        h = jax.nn.silu(g) * h
    else:
        h = gelu(h)
    if h.ndim == 3:
        h = constrain(h, "batch", "seq", "act_mlp")
    y = jnp.einsum("...f,fd->...d", h, p["w_down"].astype(x.dtype))
    if y.ndim == 3:
        # reduce-scatter the TP partial sums straight into the SP layout
        y = constrain(y, "batch", "seq_sp", "act_embed")
    return y


def _mlp_explicit_tp(p, x: jnp.ndarray, *, gated: bool):
    """Explicit Megatron-SP tensor parallelism for the dense MLP.

    GSPMD resolves the TP reduction as a *full fp32 all-reduce* followed by a
    slice (measured on deepseek-67b: 6 x 512 MB fp32 ARs per layer per
    microbatch, 1.2 TB/device/step). This shard_map takes explicit control:
    one bf16 all-gather of the SP-sharded activations in, local matmuls, one
    bf16 reduce-scatter of the partial sums out — 4x fewer ICI bytes (2x
    RS-vs-AR, 2x bf16-vs-fp32). Returns None when the mesh/shape don't allow
    it (falls back to the GSPMD path).
    """
    from ..sharding.logical import current
    from jax.sharding import PartitionSpec as P

    ctx = current()
    if ctx is None or x.ndim != 3 or "model" not in ctx.mesh.axis_names:
        return None
    mesh = ctx.mesh
    tp = mesh.shape["model"]
    b, s, d = x.shape
    f = p["w_up"].shape[1]
    if tp == 1 or s % tp or f % tp:
        return None
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    if b % math.prod(mesh.shape[a] for a in batch_axes):
        return None

    xspec = P(batch_axes, "model", None)          # SP layout between blocks
    wspec_col = P(None, "model")                   # column-parallel up/gate
    wspec_row = P("model", None)                   # row-parallel down
    dtype = x.dtype

    def body(x_l, wu, wd, wg):
        x_full = jax.lax.all_gather(x_l, "model", axis=1, tiled=True)
        h = jnp.einsum("bsd,df->bsf", x_full, wu.astype(dtype))
        if gated:
            h = jax.nn.silu(jnp.einsum("bsd,df->bsf", x_full, wg.astype(dtype))) * h
        else:
            h = gelu(h)
        y_part = jnp.einsum("bsf,fd->bsd", h, wd.astype(dtype)).astype(dtype)
        return jax.lax.psum_scatter(y_part, "model", scatter_dimension=1, tiled=True)

    wg = p.get("w_gate", p["w_up"])
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(xspec, wspec_col, wspec_row, wspec_col),
        out_specs=xspec,
    )(x, p["w_up"], p["w_down"], wg)


# ---------------------------------------------------------------------------
# Mixture of Experts
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_model: int
    d_ff: int
    gated: bool = True
    capacity_factor: float = 1.25
    router_z_coef: float = 1e-3
    aux_coef: float = 1e-2


def moe_specs(cfg: MoEConfig, *, w_init, down_init):
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    specs = {
        "router": ParamSpec((d, e), ("embed", "experts"), "moe_router", w_init),
        "w_up": ParamSpec((e, d, f), ("experts", "embed", "mlp"), "mlp_up", w_init,
                          fan_in=("embed",), fan_out=("mlp",)),
        "w_down": ParamSpec((e, f, d), ("experts", "mlp", "embed"), "mlp_down", down_init,
                            fan_in=("mlp",), fan_out=("embed",)),
    }
    if cfg.gated:
        specs["w_gate"] = ParamSpec((e, d, f), ("experts", "embed", "mlp"), "mlp_gate", w_init,
                                    fan_in=("embed",), fan_out=("mlp",))
    return specs


def _expert_ffn_dense(p, xg, cfg: MoEConfig, dtype):
    """Batched-over-experts FFN; local/unsharded path and shard_map body."""
    h = jnp.einsum("ecd,edf->ecf", xg, p["w_up"].astype(dtype))
    if cfg.gated:
        g = jnp.einsum("ecd,edf->ecf", xg, p["w_gate"].astype(dtype))
        h = jax.nn.silu(g) * h
    else:
        h = gelu(h)
    return jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(dtype))


def _expert_ffn_sharded(p, xg, cfg: MoEConfig, dtype):
    """Expert-parallel FFN over xg: (E, G, C, d) with E on 'model' (EP) and
    the DP-shard dim G on the batch axes.

    Runs inside shard_map so the sharding is *structural*: GSPMD propagation
    through the dispatch gather/scatter loses the expert sharding in the
    backward pass (measured: fp32 (E*C, d_ff) replicated buffers, 4.5 GiB
    each, on jamba). shard_map in_specs also perform the FSDP all-gather of
    the expert weights over 'data'."""
    from ..sharding.logical import current
    from jax.sharding import PartitionSpec as P

    ctx = current()
    e = cfg.n_experts

    def body(xg_l, w):
        el, gl, c, d = xg_l.shape
        y = _expert_ffn_dense(w, xg_l.reshape(el, gl * c, d), cfg, dtype)
        return y.reshape(el, gl, c, d)

    if ctx is None or "model" not in ctx.mesh.axis_names or e % ctx.mesh.shape["model"] != 0:
        return body(xg, p)

    mesh = ctx.mesh
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    g_spec = batch_axes if xg.shape[1] % math.prod(mesh.shape[a] for a in batch_axes) == 0 else None
    xspec = P("model", g_spec, None, None)
    wspec = P("model", None, None)
    weights = {k: p[k] for k in ("w_up", "w_down") + (("w_gate",) if cfg.gated else ())}

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(xspec, {k: wspec for k in weights}),
        out_specs=xspec,
    )(xg, weights)


def _dispatch_group(xf, gates, eidx, e: int, k: int, capacity: int, dtype):
    """Token dispatch for one DP shard. xf: (n, d); gates/eidx: (n, k).

    Returns (xg (E, C, d), token_of (E, C), gate_of (E, C), valid (E, C, 1)).
    """
    n = xf.shape[0]
    flat_e = eidx.reshape(-1)                                   # (n*k,)
    flat_gate = gates.reshape(-1)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - onehot                   # count of same-expert rows before me
    my_pos = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
    keep = my_pos < capacity                                    # dropped beyond capacity

    sentinel = n * k
    dispatch = jnp.full((e, capacity), sentinel, jnp.int32)
    rows = jnp.where(keep, flat_e, e)
    cols = jnp.where(keep, my_pos, 0)
    dispatch = dispatch.at[rows, cols].set(jnp.arange(n * k, dtype=jnp.int32), mode="drop")

    token_of = jnp.where(dispatch == sentinel, 0, dispatch // k)
    valid = (dispatch != sentinel)[..., None]
    xg = jnp.take(xf, token_of.reshape(-1), axis=0).reshape(e, capacity, -1)
    xg = jnp.where(valid, xg, 0).astype(dtype)
    gate_of = jnp.where(dispatch == sentinel, 0.0,
                        jnp.take(flat_gate, jnp.where(dispatch == sentinel, 0, dispatch)))
    return xg, token_of, gate_of, valid


def moe_forward(p, x: jnp.ndarray, cfg: MoEConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, D) -> (out, aux_loss).

    Dispatch is performed *per DP shard* (G groups = product of batch mesh
    axes): capacity then scales with local tokens, so the per-device expert
    buffer is (E/ep, C_local, d_ff) instead of (E/ep, C_global, d_ff) — the
    difference between 0.3 GiB and 4.5 GiB per MoE layer on jamba."""
    from ..sharding.logical import current

    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    ctx = current()
    g = 1
    if ctx is not None:
        g = math.prod(ctx.mesh.shape[a] for a in ("pod", "data") if a in ctx.mesh.axis_names)
        if b % g != 0:
            g = 1
    n_g = b * s // g
    xf = x.reshape(g, n_g, d)

    logits = jnp.einsum("gnd,de->gne", xf.astype(jnp.float32), p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = jax.lax.top_k(probs, k)                       # (G, n, k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)

    # aux losses (load balance + router z), standard Switch/ST-MoE form
    density = jnp.mean(jax.nn.one_hot(eidx[..., 0], e), axis=(0, 1))
    density_proxy = jnp.mean(probs, axis=(0, 1))
    aux = cfg.aux_coef * e * jnp.sum(density * density_proxy)
    zloss = cfg.router_z_coef * jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    aux_loss = aux + zloss

    capacity = int(max(1, round(n_g * k / e * cfg.capacity_factor)))
    if n_g * k <= 16 * e:
        # decode / tiny batches: dropless (capacity = every token could pick
        # this expert) — token drops would make decode diverge from prefill
        capacity = min(n_g, max(capacity, n_g))
    xg, token_of, gate_of, valid = jax.vmap(
        lambda xf_g, g_g, e_g: _dispatch_group(xf_g, g_g, e_g, e, k, capacity, x.dtype)
    )(xf, gates, eidx)                                          # xg: (G, E, C, d)

    xg = jnp.moveaxis(xg, 0, 1)                                 # (E, G, C, d)
    xg = constrain(xg, "experts", "batch", None, None)
    y = _expert_ffn_sharded(p, xg, cfg, x.dtype)
    y = constrain(y, "experts", "batch", None, None)
    y = jnp.moveaxis(y, 1, 0)                                   # (G, E, C, d)

    y = y * gate_of[..., None].astype(y.dtype)
    y = jnp.where(valid, y, 0)

    def combine_group(y_g, token_of_g):
        out = jnp.zeros((n_g, d), y_g.dtype)
        return out.at[token_of_g.reshape(-1)].add(y_g.reshape(-1, d), mode="drop")

    out = jax.vmap(combine_group)(y, token_of)                  # (G, n, d)
    out = out.reshape(b, s, d)
    return constrain(out, "batch", "seq", "act_embed"), aux_loss
