"""Plain float32 decoder forward for the two configuration families.

* ``gpt2`` (GPT-2, Radford et al. 2019, as the paper trains it): learned
  positions, pre-LayerNorm without biases, MLP ``gelu_tanh(x W_up) W_down``.
* ``llama`` (SmolLM-135M's ``config.json``): RMSNorm, rotary positions at
  ``rope_theta`` on the two halves of each head, grouped-query attention
  (query head ``h`` reads kv head ``h // (heads / kv_heads)``), MLP
  ``(silu(x W_gate) * x W_up) W_down``.

Both: causal softmax attention scaled by ``1/sqrt(head_dim)``, a final norm
and the head tied to the token embedding. Every matmul runs at float32
``HIGHEST`` precision unless ``quant`` rounds its operands first.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import dot, exact, norm


def _rope(x, positions, theta):
    """x: (B, S, H, hd); rotate the first half against the second half."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions.astype(jnp.float32)[:, None] * inv          # (S, hd/2)
    sin, cos = jnp.sin(ang)[None, :, None], jnp.cos(ang)[None, :, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(cfg, quant, x, p, positions):
    llama = cfg["family"] == "llama"
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    a = p["attn"]
    h = norm(cfg, x, p["mixer_norm"]["scale"])
    q = dot("bsd,dhk->bshk", h, a["wq"], quant)
    k = dot("bsd,dhk->bshk", h, a["wk"], quant)
    v = dot("bsd,dhk->bshk", h, a["wv"], quant)
    if llama:
        q = _rope(q, positions, cfg["rope_theta"])
        k = _rope(k, positions, cfg["rope_theta"])
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    s = dot("bqhd,bkhd->bhqk", q, k, quant) / jnp.sqrt(float(q.shape[-1]))
    n = x.shape[1]
    s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -jnp.inf)
    o = dot("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v, quant)
    x = x + dot("bshk,hkd->bsd", o, a["wo"], quant)
    m = p["mlp"]
    h = norm(cfg, x, p["ffn_norm"]["scale"])
    up = dot("bsd,df->bsf", h, m["w_up"], quant)
    if llama:
        act = jax.nn.silu(dot("bsd,df->bsf", h, m["w_gate"], quant)) * up
    else:
        act = jax.nn.gelu(up, approximate=True)
    return x + dot("bsf,fd->bsd", act, m["w_down"], quant)


def forward(cfg: dict, params, tokens, quant=exact):
    """tokens: (B, S) int32 -> logits (B, S, vocab) float32."""
    s = tokens.shape[1]
    positions = jnp.arange(s)
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg["position"] == "learned":
        x = x + params["pos_embed"][:s][None]

    @jax.checkpoint
    def body(x, p):
        return _layer(cfg, quant, x, p, positions), None

    x, _ = jax.lax.scan(body, x, params["blocks"]["slot_0"])
    x = norm(cfg, x, params["final_norm"]["scale"])
    return dot("bsd,vd->bsv", x, params["embed"], quant)


def nll_sum(cfg: dict, params, tokens, labels, quant=exact):
    """Summed next-token negative log-likelihood over every position."""
    logits = forward(cfg, params, tokens, quant)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - gold)
