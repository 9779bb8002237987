"""Mamba-1 selective-state-space block (falcon-mamba / jamba mixer).

TPU adaptation (DESIGN.md §3): the CUDA selective-scan kernel is replaced by
a *chunked* scan — an outer ``lax.scan`` over sequence chunks carrying the
(B, d_inner, d_state) hidden state, with a parallel ``associative_scan``
inside each chunk. Live memory is O(B * chunk * d_inner * d_state) instead of
O(B * S * d_inner * d_state), which is what lets the 500k-token cell compile.
``repro/kernels/ssm_scan`` provides the Pallas VMEM-resident version of the
inner chunk; this module is its oracle.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from ..sharding.logical import constrain
from .common import ParamSpec, constant_init, normal_init, ones_init, zeros_init


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    d_model: int
    d_inner: int
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 0  # 0 -> ceil(d_model / 16)
    chunk: int = 256

    @property
    def rank(self) -> int:
        return self.dt_rank or -(-self.d_model // 16)


def _a_log_init():
    def init(key, shape, dtype):
        # S4D-real init: A = -(1..d_state) per channel
        d_inner, d_state = shape
        a = jnp.broadcast_to(jnp.arange(1, d_state + 1, dtype=jnp.float32), (d_inner, d_state))
        return jnp.log(a).astype(dtype)

    return init


def _dt_proj_init(rank: int):
    def init(key, shape, dtype):
        std = rank**-0.5
        return (jax.random.uniform(key, shape, minval=-std, maxval=std)).astype(dtype)

    return init


def ssm_specs(cfg: SSMConfig, *, w_init, out_init):
    d, di, n, r = cfg.d_model, cfg.d_inner, cfg.d_state, cfg.rank
    return {
        "in_proj": ParamSpec((d, 2 * di), ("embed", "d_inner"), "ssm_in", w_init,
                             fan_in=("embed",), fan_out=("d_inner",)),
        "conv_w": ParamSpec((di, cfg.d_conv), ("d_inner", "conv_w"), "ssm_conv", normal_init(0.02)),
        "conv_b": ParamSpec((di,), ("d_inner",), "bias", zeros_init()),
        "x_proj": ParamSpec((di, r + 2 * n), ("d_inner", "dt_rank"), "ssm_x", w_init,
                            fan_in=("d_inner",), fan_out=("dt_rank",)),
        "dt_proj": ParamSpec((r, di), ("dt_rank", "d_inner"), "ssm_dt", _dt_proj_init(r),
                             fan_in=("dt_rank",), fan_out=("d_inner",)),
        "dt_bias": ParamSpec((di,), ("d_inner",), "bias", constant_init(math.log(math.e - 1) * 0.01 + 0.0)),
        "a_log": ParamSpec((di, n), ("d_inner", "state"), "ssm_a", _a_log_init()),
        "d_skip": ParamSpec((di,), ("d_inner",), "ssm_d", ones_init()),
        "out_proj": ParamSpec((di, d), ("d_inner", "embed"), "ssm_out", out_init,
                              fan_in=("d_inner",), fan_out=("embed",)),
    }


def _causal_conv(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray, history: jnp.ndarray | None = None):
    """Depthwise causal conv via shifted adds. x: (B, S, di); w: (di, K).

    ``history``: (B, K-1, di) previous inputs (decode); returns new history.
    """
    bsz, s, di = x.shape
    k = w.shape[1]
    if history is None:
        history = jnp.zeros((bsz, k - 1, di), x.dtype)
    xp = jnp.concatenate([history, x], axis=1)  # (B, S+K-1, di)
    out = jnp.zeros_like(x, dtype=jnp.float32)
    for i in range(k):
        out = out + xp[:, i : i + s, :].astype(jnp.float32) * w[:, i].astype(jnp.float32)
    out = out + b.astype(jnp.float32)
    new_hist = xp[:, -(k - 1) :, :] if k > 1 else history
    return out.astype(x.dtype), new_hist


def _scan_chunk(h0: jnp.ndarray, log_decay: jnp.ndarray, inp: jnp.ndarray):
    """Associative scan of h_t = exp(log_decay_t) * h_{t-1} + inp_t over a chunk.

    h0: (B, di, N); log_decay/inp: (B, c, di, N). Returns (h_last, h_all).
    """

    def combine(a, b):
        (la, ua), (lb, ub) = a, b
        return la + lb, jnp.exp(lb) * ua + ub

    ls, us = jax.lax.associative_scan(combine, (log_decay, inp), axis=1)
    h_all = jnp.exp(ls) * h0[:, None] + us  # prefix decay applied to carry-in
    return h_all[:, -1], h_all


def _chunked(t, bsz, n_chunks, chunk, extra_dims):
    return jnp.moveaxis(t.reshape(bsz, n_chunks, chunk, *extra_dims), 1, 0)


def _selective_scan_fwd_inner(x, dt, a, b_t, c_t, d_skip, h0, *, chunk: int):
    bsz, s, di = x.shape
    n = a.shape[-1]
    n_chunks = s // chunk
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    x_ch = _chunked(xf, bsz, n_chunks, chunk, (di,))
    dt_ch = _chunked(dtf, bsz, n_chunks, chunk, (di,))
    b_ch = _chunked(b_t.astype(jnp.float32), bsz, n_chunks, chunk, (n,))
    c_ch = _chunked(c_t.astype(jnp.float32), bsz, n_chunks, chunk, (n,))
    af = a.astype(jnp.float32)

    def body(h, operand):
        x_i, dt_i, b_i, c_i = operand
        log_decay = dt_i[..., None] * af                                  # (B, c, di, N)
        inp = (dt_i * x_i)[..., None] * b_i[:, :, None, :]                # (B, c, di, N)
        h_last, h_all = _scan_chunk(h, log_decay, inp)
        y_i = jnp.einsum("bcdn,bcn->bcd", h_all, c_i)
        return h_last, (y_i, h)                                           # save chunk-boundary h only

    h_final, (y, h_bounds) = jax.lax.scan(body, h0.astype(jnp.float32), (x_ch, dt_ch, b_ch, c_ch))
    y = jnp.moveaxis(y, 0, 1).reshape(bsz, s, di)
    y = y + xf * d_skip.astype(jnp.float32)
    return y.astype(x.dtype), h_final, h_bounds


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def selective_scan(x, dt, a, b_t, c_t, d_skip, h0, chunk: int):
    """x, dt: (B, S, di); a: (di, N); b_t, c_t: (B, S, N); h0: (B, di, N).

    Returns (y: (B, S, di), h_final). Hand-written VJP: differentiating
    through the chunked associative scan makes jax save every scan-tree level
    (9 levels x 8 chunks x ~270 MB for jamba — 19 GB *per layer*). The custom
    backward stores only per-chunk boundary states and replays each chunk,
    using the reverse linear recurrence dh_t = g_t + A_{t+1} (.) dh_{t+1}.
    """
    chunk = _usable_chunk(x.shape[1], chunk)
    y, h_final, _ = _selective_scan_fwd_inner(x, dt, a, b_t, c_t, d_skip, h0, chunk=chunk)
    return y, h_final


def _usable_chunk(s: int, pref: int) -> int:
    """Largest divisor of s that is <= pref."""
    if s <= pref:
        return s
    for c in range(pref, 0, -1):
        if s % c == 0:
            return c
    return 1


def _selective_scan_fwd(x, dt, a, b_t, c_t, d_skip, h0, chunk):
    chunk = _usable_chunk(x.shape[1], chunk)
    y, h_final, h_bounds = _selective_scan_fwd_inner(x, dt, a, b_t, c_t, d_skip, h0, chunk=chunk)
    return (y, h_final), (x, dt, a, b_t, c_t, d_skip, h0, h_bounds)


def _selective_scan_bwd(chunk, res, cts):
    x, dt, a, b_t, c_t, d_skip, h0, h_bounds = res
    dy, dh_final = cts
    bsz, s, di = x.shape
    n = a.shape[-1]
    chunk = _usable_chunk(s, chunk)
    n_chunks = s // chunk
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    af = a.astype(jnp.float32)
    dyf = dy.astype(jnp.float32)

    x_ch = _chunked(xf, bsz, n_chunks, chunk, (di,))
    dt_ch = _chunked(dtf, bsz, n_chunks, chunk, (di,))
    b_ch = _chunked(b_t.astype(jnp.float32), bsz, n_chunks, chunk, (n,))
    c_ch = _chunked(c_t.astype(jnp.float32), bsz, n_chunks, chunk, (n,))
    dy_ch = _chunked(dyf, bsz, n_chunks, chunk, (di,))

    def body(carry, operand):
        dh_next_scaled, da_acc = carry                                    # (B, di, N), (di, N)
        x_i, dt_i, b_i, c_i, dy_i, h_in = operand
        log_decay = dt_i[..., None] * af                                  # (B, c, di, N)
        inp = (dt_i * x_i)[..., None] * b_i[:, :, None, :]
        _, h_all = _scan_chunk(h_in, log_decay, inp)                      # replay forward
        h_prev = jnp.concatenate([h_in[:, None], h_all[:, :-1]], axis=1)  # h_{t-1}

        # reverse recurrence: dh_t = g_t + A_{t+1} (.) dh_{t+1}
        g = dy_i[..., None] * c_i[:, :, None, :]                          # (B, c, di, N)
        g_rev = g[:, ::-1]
        logA_rev = log_decay[:, ::-1]
        # coefficients: tau=0 -> already-scaled carry; tau>=1 -> logA_{c-tau}
        ltilde = jnp.concatenate(
            [jnp.zeros_like(logA_rev[:, :1]), logA_rev[:, : chunk - 1]], axis=1)
        _, dh_rev = _scan_chunk(dh_next_scaled, ltilde, g_rev)
        dh = dh_rev[:, ::-1]                                              # (B, c, di, N)

        # u_t = (dt*x) B ; A_t = exp(dt a)
        du = dh
        dA = dh * h_prev
        dlogA = dA * jnp.exp(log_decay)
        ddtx = jnp.einsum("bcdn,bcn->bcd", du, b_i)
        db_i = jnp.einsum("bcdn,bcd->bcn", du, dt_i * x_i)
        dc_i = jnp.einsum("bcdn,bcd->bcn", h_all, dy_i)
        ddt_i = ddtx * x_i + jnp.einsum("bcdn,dn->bcd", dlogA, af)
        dx_i = ddtx * dt_i
        da_acc = da_acc + jnp.einsum("bcdn,bcd->dn", dlogA, dt_i)

        new_carry = jnp.exp(log_decay[:, 0]) * dh[:, 0]                   # A_0 (.) dh_0
        return (new_carry, da_acc), (dx_i, ddt_i, db_i, dc_i)

    # varying-typed zeros (shard_map vma): union the batch-varying axes from
    # dy with the weight-varying axes from a
    carry0 = (dh_final.astype(jnp.float32), af * 0.0 + dyf.ravel()[0] * 0.0)
    # iterate chunks in reverse
    rev = lambda t: t[::-1]
    (dh0, da), (dx_c, ddt_c, db_c, dc_c) = jax.lax.scan(
        body, carry0,
        (rev(x_ch), rev(dt_ch), rev(b_ch), rev(c_ch), rev(dy_ch), rev(h_bounds)))

    def unchunk(t, extra):
        return jnp.moveaxis(t[::-1], 0, 1).reshape(bsz, s, *extra)

    dx = unchunk(dx_c, (di,)) + dyf * d_skip.astype(jnp.float32)
    ddt = unchunk(ddt_c, (di,))
    db = unchunk(db_c, (n,))
    dc = unchunk(dc_c, (n,))
    dd = jnp.einsum("bsd,bsd->d", dyf, xf)
    return (dx.astype(x.dtype), ddt.astype(dt.dtype), da.astype(a.dtype),
            db.astype(b_t.dtype), dc.astype(c_t.dtype), dd.astype(d_skip.dtype),
            dh0.astype(h0.dtype))


selective_scan.defvjp(_selective_scan_fwd, _selective_scan_bwd)


class SSMCache(NamedTuple):
    conv: jnp.ndarray  # (B, d_conv-1, d_inner)
    h: jnp.ndarray     # (B, d_inner, d_state)


def init_ssm_cache(batch: int, cfg: SSMConfig, dtype=jnp.float32) -> SSMCache:
    return SSMCache(
        conv=jnp.zeros((batch, cfg.d_conv - 1, cfg.d_inner), dtype),
        h=jnp.zeros((batch, cfg.d_inner, cfg.d_state), jnp.float32),
    )


def _ssm_inner(p, x, cfg: SSMConfig, conv_hist, h0):
    """Shared forward core. x: (B, S, D)."""
    xz = jnp.einsum("bsd,de->bse", x, p["in_proj"].astype(x.dtype))
    xb, z = jnp.split(xz, 2, axis=-1)
    xb = constrain(xb, "batch", "seq", "d_inner")
    xb, new_hist = _causal_conv(xb, p["conv_w"], p["conv_b"], conv_hist)
    xb = jax.nn.silu(xb)

    proj = jnp.einsum("bsd,dr->bsr", xb, p["x_proj"].astype(xb.dtype))
    r = cfg.rank
    dt_lr, b_t, c_t = jnp.split(proj, [r, r + cfg.d_state], axis=-1)
    dt = jnp.einsum("bsr,rd->bsd", dt_lr, p["dt_proj"].astype(xb.dtype))
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(p["a_log"].astype(jnp.float32))

    y, h_final = selective_scan(xb, dt, a, b_t, c_t, p["d_skip"], h0, cfg.chunk)
    y = y * jax.nn.silu(z)
    out = jnp.einsum("bsd,de->bse", y, p["out_proj"].astype(x.dtype))
    return constrain(out, "batch", "seq", "act_embed"), new_hist, h_final


def ssm_forward(p, x: jnp.ndarray, cfg: SSMConfig) -> jnp.ndarray:
    y = _ssm_explicit_tp(p, x, cfg)
    if y is not None:
        return y
    bsz = x.shape[0]
    h0 = jnp.zeros((bsz, cfg.d_inner, cfg.d_state), jnp.float32)
    out, _, _ = _ssm_inner(p, x, cfg, None, h0)
    return out


def _ssm_explicit_tp(p, x: jnp.ndarray, cfg: SSMConfig):
    """Explicit Megatron-SP tensor parallelism for the mamba mixer.

    SSM channels are independent across d_inner, so the whole mixer —
    in-proj, conv, selective scan, gate, out-proj — runs channel-sharded
    inside one shard_map: one bf16 all-gather of the SP activations in, one
    small fp32 psum for the x_proj low-rank bottleneck (dt/B/C are shared
    across channels), one bf16 reduce-scatter of the out-proj partial sums.
    Returns None when shapes don't allow it (GSPMD fallback)."""
    import math as _math

    from ..sharding.logical import current
    from jax.sharding import PartitionSpec as P

    ctx = current()
    if ctx is None or "model" not in ctx.mesh.axis_names:
        return None
    mesh = ctx.mesh
    tp = mesh.shape["model"]
    bsz, s, d = x.shape
    di = cfg.d_inner
    if tp == 1 or s % tp or di % tp:
        return None
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    if bsz % _math.prod(mesh.shape[a] for a in batch_axes):
        return None

    di_l = di // tp
    r = cfg.rank
    n = cfg.d_state
    dtype = x.dtype
    xspec = P(batch_axes, "model", None)

    def body(x_l, w):
        x_full = jax.lax.all_gather(x_l, "model", axis=1, tiled=True)      # (B_l, S, D)
        xz = jnp.einsum("bsd,de->bse", x_full, w["in_proj"].astype(dtype)) # (B_l, S, 2*di_l)
        xb, z = jnp.split(xz, 2, axis=-1)
        xb, _ = _causal_conv(xb, w["conv_w"], w["conv_b"], None)
        xb = jax.nn.silu(xb)
        # low-rank dt/B/C bottleneck: partial over local channels -> psum
        proj = jnp.einsum("bsd,dr->bsr", xb.astype(jnp.float32),
                          w["x_proj"].astype(jnp.float32))
        proj = jax.lax.psum(proj, "model")                                 # (B_l, S, r+2N)
        dt_lr, b_t, c_t = jnp.split(proj, [r, r + n], axis=-1)
        dt = jnp.einsum("bsr,rd->bsd", dt_lr.astype(xb.dtype), w["dt_proj"].astype(xb.dtype))
        dt = jax.nn.softplus(dt.astype(jnp.float32) + w["dt_bias"].astype(jnp.float32))
        # vma plumbing: weights entering the custom-vjp scan must carry the
        # full varying axes (their cotangents inherit batch-variation; the
        # pcast-via-zero makes shard_map's transpose insert the 'data' psum)
        vz = (x_full.ravel()[0] * 0.0).astype(jnp.float32)
        a = -jnp.exp(w["a_log"].astype(jnp.float32)) + vz
        dsk = w["d_skip"].astype(jnp.float32) + vz
        h0 = (xb[:, :1, :, None] * 0.0).astype(jnp.float32) * jnp.zeros((1, 1, 1, n))
        h0 = jnp.squeeze(h0, 1)                                            # varying zeros (B_l, di_l, N)
        bt = b_t.astype(xb.dtype) + vz.astype(xb.dtype)   # vma: see `a` above
        ct = c_t.astype(xb.dtype) + vz.astype(xb.dtype)
        y, _ = selective_scan(xb, dt, a, bt, ct, dsk, h0, cfg.chunk)
        y = y * jax.nn.silu(z)
        out_part = jnp.einsum("bsd,de->bse", y, w["out_proj"].astype(dtype)).astype(dtype)
        return jax.lax.psum_scatter(out_part, "model", scatter_dimension=1, tiled=True)

    # weight specs: channel-sharded over 'model' on the d_inner dim; the
    # shard_map entry performs the (bf16) FSDP gather over 'data' where needed
    wspecs = {
        "in_proj": P(None, "model"),        # (d, 2*di): split gives both halves local
        "conv_w": P("model", None),
        "conv_b": P("model"),
        "x_proj": P("model", None),
        "dt_proj": P(None, "model"),
        "dt_bias": P("model"),
        "a_log": P("model", None),
        "d_skip": P("model"),
        "out_proj": P("model", None),
    }
    # in_proj columns: (x | z) halves must each be channel-sharded — the
    # natural layout (d, 2*di) sharded on dim 1 splits into x-half and z-half
    # only if each half is contiguous per shard; reorder columns so shard k
    # holds [x_k | z_k].
    w = dict(p)
    ip = p["in_proj"]
    xw, zw = ip[:, :di], ip[:, di:]
    xw = xw.reshape(d, tp, di_l)
    zw = zw.reshape(d, tp, di_l)
    w["in_proj"] = jnp.concatenate([xw, zw], axis=2).reshape(d, 2 * di)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(xspec, {k: wspecs[k] for k in w}),
        out_specs=xspec,
    )(x, w)


def ssm_decode(p, x: jnp.ndarray, cache: SSMCache, cfg: SSMConfig) -> Tuple[jnp.ndarray, SSMCache]:
    """x: (B, 1, D) — O(1) state-space decode step."""
    out, new_hist, h_final = _ssm_inner(p, x, cfg, cache.conv, cache.h)
    return out, SSMCache(conv=new_hist, h=h_final)
