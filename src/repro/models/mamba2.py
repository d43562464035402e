"""Mamba-2 (SSD) block: projections, causal conv, selective state space.

Prefill/training run the chunked SSD (Pallas kernel on TPU, jnp oracle here);
decode is the O(1) per-token recurrence against a cached (H, P, N) state +
conv tail - the reason ``long_500k`` is feasible for SSM archs at all. A
prefill hands both to decode (``return_state``).

Per head: h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T, y_t = C_t h_t + D x_t
(the skip term takes x before the dt scaling, as Mamba-2 does). The spans
``mamba.conv``, ``mamba.ssd`` and ``mamba.out`` name the block's stages in
the compiled program.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels import ops
from repro.models.config import ModelConfig
from repro.models.layers import init_rmsnorm, apply_rmsnorm, truncated_normal


def init_mamba(key, cfg: ModelConfig):
    d = cfg.d_model
    di = cfg.d_inner
    g, n = cfg.ssm_groups, cfg.ssm_state
    h = cfg.n_ssm_heads
    conv_dim = di + 2 * g * n
    ks = jax.random.split(key, 5)
    return {
        # order: [z (di), x (di), B (g*n), C (g*n), dt (h)]
        "in_proj": truncated_normal(ks[0], (d, 2 * di + 2 * g * n + h),
                                    d ** -0.5),
        "conv_w": truncated_normal(ks[1], (cfg.ssm_conv, conv_dim), 0.2),
        "conv_b": jnp.zeros((conv_dim,), jnp.float32),
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, h)),       # A = -exp(a_log)
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "d_skip": jnp.ones((h,), jnp.float32),
        "norm": init_rmsnorm(di),
        "out_proj": truncated_normal(ks[4], (di, d), di ** -0.5),
    }


def _split_proj(zxbcdt, cfg: ModelConfig):
    di = cfg.d_inner
    gn = cfg.ssm_groups * cfg.ssm_state
    h = cfg.n_ssm_heads
    z = zxbcdt[..., :di]
    xs = zxbcdt[..., di:2 * di]
    B = zxbcdt[..., 2 * di:2 * di + gn]
    C = zxbcdt[..., 2 * di + gn:2 * di + 2 * gn]
    dt = zxbcdt[..., 2 * di + 2 * gn:2 * di + 2 * gn + h]
    return z, xs, B, C, dt


def _causal_conv(u: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                 tail: Optional[jnp.ndarray] = None):
    """Depthwise causal conv. u: (B, S, C); w: (K, C). ``tail``: (B, K-1, C)
    carried state for decode. Returns (y, new_tail)."""
    kk = w.shape[0]
    if tail is None:
        tail = jnp.zeros((u.shape[0], kk - 1, u.shape[2]), u.dtype)
    ext = jnp.concatenate([tail, u], axis=1)                # (B, K-1+S, C)
    y = sum(ext[:, i:i + u.shape[1]] * w[i].astype(u.dtype)
            for i in range(kk))
    y = jax.nn.silu(y + b.astype(u.dtype))
    new_tail = ext[:, -(kk - 1):] if kk > 1 else tail
    return y, new_tail


def _prepare_ssd(xs, B, C, dt, p, cfg: ModelConfig):
    """Shared head-reshape + dt/A handling for prefill and decode: the
    heads' inputs x (B,S,H,P), the SSD's input x * dt, log-decay dt * A,
    and B/C per head."""
    bsz, s, _ = xs.shape
    h, hd = cfg.n_ssm_heads, cfg.ssm_head_dim
    g, n = cfg.ssm_groups, cfg.ssm_state
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))    # (B,S,H)
    a = -jnp.exp(p["a_log"].astype(jnp.float32))                # (H,)
    a_log_dt = dt * a[None, None, :]                            # (B,S,H) <= 0
    x_heads = xs.reshape(bsz, s, h, hd)
    xh = x_heads * dt[..., None].astype(xs.dtype)
    rep = h // g
    Bh = jnp.repeat(B.reshape(bsz, s, g, n), rep, axis=2)
    Ch = jnp.repeat(C.reshape(bsz, s, g, n), rep, axis=2)
    return x_heads, xh, a_log_dt, Bh, Ch


def _skip(y, x_heads, p):
    """Mamba-2's skip term: y + D x, x before the dt scaling."""
    return y + x_heads * p["d_skip"].astype(y.dtype)[None, None, :, None]


def _gated_norm(p, y, z, cfg: ModelConfig):
    y = y.reshape(*z.shape[:2], cfg.d_inner)
    return apply_rmsnorm(p["norm"], y * jax.nn.silu(z), cfg.norm_eps)


def apply_mamba(p, x: jnp.ndarray, cfg: ModelConfig,
                use_pallas: Optional[bool] = None, return_state: bool = False):
    """Full-sequence path. x: (B, S, d). With ``return_state`` also the
    cache decode continues from: ``{"state": (B,H,P,N) f32, "conv": the
    last K-1 conv inputs}``."""
    dtype = x.dtype
    di = cfg.d_inner
    gn = cfg.ssm_groups * cfg.ssm_state
    with obs.span("mamba.conv"):
        zxbcdt = x @ p["in_proj"].astype(dtype)
        z, xs, B, C, dt = _split_proj(zxbcdt, cfg)
        xbc = jnp.concatenate([xs, B, C], axis=-1)
        xbc, tail = _causal_conv(xbc, p["conv_w"], p["conv_b"])
        xs, B, C = xbc[..., :di], xbc[..., di:di + gn], xbc[..., di + gn:]
    with obs.span("mamba.ssd"):
        x_heads, xh, a_log, Bh, Ch = _prepare_ssd(xs, B, C, dt, p, cfg)
        out = ops.ssd(xh, a_log, Bh, Ch, chunk=cfg.ssm_chunk,
                      use_pallas=use_pallas, return_state=return_state)
        y, state = out if return_state else (out, None)
        y = _skip(y, x_heads, p)
    with obs.span("mamba.out"):
        y = _gated_norm(p, y, z, cfg) @ p["out_proj"].astype(dtype)
    if return_state:
        return y, {"state": state, "conv": tail}
    return y


def init_ssm_cache(cfg: ModelConfig, batch: int, dtype=jnp.float32):
    h, hd, n = cfg.n_ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    conv_dim = cfg.d_inner + 2 * cfg.ssm_groups * cfg.ssm_state
    return {
        "state": jnp.zeros((batch, h, hd, n), jnp.float32),
        "conv": jnp.zeros((batch, cfg.ssm_conv - 1, conv_dim), dtype),
    }


def apply_mamba_decode(p, x: jnp.ndarray, cfg: ModelConfig, cache
                       ) -> Tuple[jnp.ndarray, dict]:
    """One-token recurrence. x: (B, 1, d)."""
    dtype = x.dtype
    di = cfg.d_inner
    gn = cfg.ssm_groups * cfg.ssm_state
    zxbcdt = x @ p["in_proj"].astype(dtype)
    z, xs, B, C, dt = _split_proj(zxbcdt, cfg)
    xbc = jnp.concatenate([xs, B, C], axis=-1)
    xbc, new_conv = _causal_conv(xbc, p["conv_w"], p["conv_b"],
                                 tail=cache["conv"])
    xs, B, C = xbc[..., :di], xbc[..., di:di + gn], xbc[..., di + gn:]
    x_heads, xh, a_log, Bh, Ch = _prepare_ssd(xs, B, C, dt, p, cfg)
    # exact one-step recurrence: h' = exp(a) h + x (x) B ; y = h' C
    a = jnp.exp(a_log[:, 0].astype(jnp.float32))[:, :, None, None]
    state = cache["state"]
    upd = jnp.einsum("bhp,bhn->bhpn", xh[:, 0].astype(jnp.float32),
                     Bh[:, 0].astype(jnp.float32))
    state = a * state + upd
    y = jnp.einsum("bhpn,bhn->bhp", state, Ch[:, 0].astype(jnp.float32))
    y = _skip(y.astype(dtype)[:, None], x_heads, p)             # (B,1,H,P)
    with obs.span("mamba.out"):
        y = _gated_norm(p, y, z, cfg) @ p["out_proj"].astype(dtype)
    return y, {"state": state, "conv": new_conv}
