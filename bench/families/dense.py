"""The dense decoder of the ``gpt2`` and ``llama`` families.

* ``gpt2`` (GPT-2, Radford et al. 2019, as the paper trains it): learned
  positions, pre-LayerNorm without biases, MLP ``gelu_tanh(x W_up) W_down``.
* ``llama`` (SmolLM-135M's ``config.json``): RMSNorm, rotary positions at
  ``rope_theta`` on the two halves of each head, grouped-query attention
  (query head ``h`` reads kv head ``h // (heads / kv_heads)``), MLP
  ``(silu(x W_gate) * x W_up) W_down``.

Both: causal softmax attention scaled by ``1/sqrt(head_dim)``, a final norm
and the head tied to the token embedding. Every matmul runs at float32
``HIGHEST`` precision unless ``quant`` rounds its operands first. The
configuration's ``position`` (``rope`` or ``learned``) and ``hidden_act``
(``silu`` gated, else GELU) pick the variant. Parameters use the layer-stacked
layout the configuration files describe: ``blocks.slot_0.<name>`` leaves
carry the layer index first.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import counts, program
from bench.reference.common import dot, exact, norm


def param_shapes(cfg: dict) -> dict:
    """Nested dict of leaf shapes in the configuration's stacked layout."""
    L, D = cfg["num_hidden_layers"], cfg["hidden_size"]
    H, KV, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    F, V = cfg["intermediate_size"], cfg["vocab_size"]
    mlp = {"w_up": (L, D, F), "w_down": (L, F, D)}
    if cfg["hidden_act"] == "silu":
        mlp["w_gate"] = (L, D, F)
    shapes = {
        "embed": (V, D),
        "blocks": {"slot_0": {
            "mixer_norm": {"scale": (L, D)},
            "attn": {"wq": (L, D, H, hd), "wk": (L, D, KV, hd),
                     "wv": (L, D, KV, hd), "wo": (L, H, hd, D)},
            "ffn_norm": {"scale": (L, D)},
            "mlp": mlp,
        }},
        "final_norm": {"scale": (D,)},
    }
    if cfg["position"] == "learned":
        shapes["pos_embed"] = (cfg["max_position_embeddings"], D)
    return shapes


def program_fields(cfg: dict) -> dict:
    want = {
        "n_layers": cfg["num_hidden_layers"], "d_model": cfg["hidden_size"],
        "n_heads": cfg["num_attention_heads"],
        "n_kv_heads": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
        "d_ff": cfg["intermediate_size"], "vocab_size": cfg["vocab_size"],
        "norm": cfg["norm"], "pos": cfg["position"],
        "gated_mlp": cfg["hidden_act"] == "silu",
        "tie_embeddings": cfg["tie_word_embeddings"],
        **program.dtypes(cfg),
    }
    if cfg["position"] == "learned":
        want["max_position"] = cfg["max_position_embeddings"]
    return want


def _rope(x, positions, theta):
    """x: (B, S, H, hd); rotate the first half against the second half."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions.astype(jnp.float32)[:, None] * inv          # (S, hd/2)
    sin, cos = jnp.sin(ang)[None, :, None], jnp.cos(ang)[None, :, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(cfg, quant, x, p, positions):
    rope, gated = cfg["position"] == "rope", cfg["hidden_act"] == "silu"
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    a = p["attn"]
    h = norm(cfg, x, p["mixer_norm"]["scale"])
    q = dot("bsd,dhk->bshk", h, a["wq"], quant)
    k = dot("bsd,dhk->bshk", h, a["wk"], quant)
    v = dot("bsd,dhk->bshk", h, a["wv"], quant)
    if rope:
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
    if gated:
        act = jax.nn.silu(dot("bsd,df->bsf", h, m["w_gate"], quant)) * up
    else:
        act = jax.nn.gelu(up, approximate=True)
    return x + dot("bsf,fd->bsd", act, m["w_down"], quant)


def hidden(cfg: dict, params, tokens, quant=exact):
    """tokens: (B, S) int32 -> the final norm's output (B, S, D) float32."""
    s = tokens.shape[1]
    positions = jnp.arange(s)
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg["position"] == "learned":
        x = x + params["pos_embed"][:s][None]

    @jax.checkpoint
    def body(x, p):
        return _layer(cfg, quant, x, p, positions), None

    x, _ = jax.lax.scan(body, x, params["blocks"]["slot_0"])
    return norm(cfg, x, params["final_norm"]["scale"])


def forward(cfg: dict, params, tokens, quant=exact):
    """tokens: (B, S) int32 -> logits (B, S, vocab) float32."""
    return dot("bsd,vd->bsv", hidden(cfg, params, tokens, quant),
               params["embed"], quant)


def matmul_params(cfg: dict) -> int:
    """Weights that multiply every token once in a forward pass: the
    attention and MLP matrices of every layer and the (tied) LM head. The
    token and position lookups and the norm scales are no matmuls."""
    D, F, L = cfg["hidden_size"], cfg["intermediate_size"], cfg["num_hidden_layers"]
    H, KV, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    attn = D * (H + 2 * KV) * hd + H * hd * D
    mlp = (3 if cfg["hidden_act"] == "silu" else 2) * D * F
    return L * (attn + mlp) + D * cfg["vocab_size"]


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """6 N + 12 L H hd T (PaLM, App. B): forward and backward of the
    matmuls, and of attention's two products over the whole sequence."""
    L, H, hd = (cfg["num_hidden_layers"], cfg["num_attention_heads"],
                cfg["head_dim"])
    return 6.0 * matmul_params(cfg) + 12.0 * L * H * hd * seq


def forward_flops(cfg: dict, contexts) -> float:
    """Forward FLOPs of tokens that attend over ``contexts`` positions each
    (one entry per token): 2 N per token plus 4 L H hd per attended position."""
    L, H, hd = (cfg["num_hidden_layers"], cfg["num_attention_heads"],
                cfg["head_dim"])
    ctx = np.asarray(contexts, np.float64)
    return 2.0 * matmul_params(cfg) * ctx.size + 4.0 * L * H * hd * ctx.sum()


def small(cfg: dict, model) -> dict:
    """``cfg`` at the size of the program's reduced ``ModelConfig``."""
    m = model
    cfg = dict(cfg, num_hidden_layers=m.n_layers, hidden_size=m.d_model,
               num_attention_heads=m.n_heads, num_key_value_heads=m.n_kv_heads,
               head_dim=m.hd, intermediate_size=m.d_ff, vocab_size=m.vocab_size)
    if cfg["position"] == "learned":
        cfg["max_position_embeddings"] = m.max_position
    return cfg


kernels = {"_paged_kernel": counts.paged_attention_window}
counters = ("step", "decode_steps", "decode_rows", "decode_rows_computed",
            "prefill_chunks", "tokens_out")
