"""GQA attention with RoPE or none (NoPE), KV cache, sliding windows, and
cross-attention. The softmax scale is the config's ``attention_multiplier``
where it sets one, else 1/sqrt(head_dim); ``attn.core`` names the softmax
attention itself in the compiled program.

Training path uses the flash oracle (Pallas kernel on TPU via ops.attention);
decode path writes one token into the cache and attends with a kv-length
mask. The decode attention over a sequence-sharded cache (flash-decoding via
shard_map) lives in repro.distributed.collectives.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels import ops
from repro.models.config import ModelConfig
from repro.models.layers import init_linear, rope, truncated_normal


def init_attention(key, cfg: ModelConfig, d_in: Optional[int] = None):
    d = d_in or cfg.d_model
    hd, hq, hkv = cfg.hd, cfg.n_heads, cfg.n_kv
    ks = jax.random.split(key, 4)
    p = {
        "wq": truncated_normal(ks[0], (d, hq * hd), d ** -0.5),
        "wk": truncated_normal(ks[1], (d, hkv * hd), d ** -0.5),
        "wv": truncated_normal(ks[2], (d, hkv * hd), d ** -0.5),
        "wo": truncated_normal(ks[3], (hq * hd, d), (hq * hd) ** -0.5),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq * hd,), jnp.float32)
        p["bk"] = jnp.zeros((hkv * hd,), jnp.float32)
        p["bv"] = jnp.zeros((hkv * hd,), jnp.float32)
    return p


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                  dtype=jnp.bfloat16):
    hd = cfg.hd
    return {
        "k": jnp.zeros((batch, max_len, cfg.n_kv, hd), dtype),
        "v": jnp.zeros((batch, max_len, cfg.n_kv, hd), dtype),
    }


def _project_qkv(p, x, cfg: ModelConfig, positions, dtype, use_rope=True):
    b, s, _ = x.shape
    hd, hq, hkv = cfg.hd, cfg.n_heads, cfg.n_kv
    q = x @ p["wq"].astype(dtype)
    k = x @ p["wk"].astype(dtype)
    v = x @ p["wv"].astype(dtype)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dtype)
        k = k + p["bk"].astype(dtype)
        v = v + p["bv"].astype(dtype)
    q = q.reshape(b, s, hq, hd)
    k = k.reshape(b, s, hkv, hd)
    v = v.reshape(b, s, hkv, hd)
    if use_rope and cfg.pos == "rope":
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def apply_attention(p, x, cfg: ModelConfig, positions,
                    window: Optional[int] = None, causal: bool = True,
                    use_pallas: Optional[bool] = None, return_kv: bool = False):
    """Full-sequence (training / prefill) attention. x: (B, S, d). With
    ``return_kv`` also ``{"k", "v"}`` (B, S, Hkv, hd), what a prefill
    leaves in the cache."""
    dtype = x.dtype
    q, k, v = _project_qkv(p, x, cfg, positions, dtype)
    with obs.span("attn.core"):
        qh = jnp.moveaxis(q, 2, 1)                # (B, Hq, S, hd)
        kh = jnp.moveaxis(k, 2, 1)
        vh = jnp.moveaxis(v, 2, 1)
        o = ops.attention(qh, kh, vh, causal=causal, window=window,
                          scale=cfg.attention_multiplier,
                          use_pallas=use_pallas)
    b, s = x.shape[:2]
    o = jnp.moveaxis(o, 1, 2).reshape(b, s, cfg.n_heads * cfg.hd)
    o = o @ p["wo"].astype(dtype)
    return (o, {"k": k, "v": v}) if return_kv else o


def apply_attention_decode(p, x, cfg: ModelConfig, cache, write_idx,
                           position, kv_len,
                           use_pallas: Optional[bool] = None
                           ) -> Tuple[jnp.ndarray, dict]:
    """One-token decode. x: (B, 1, d); cache k/v: (B, Smax, Hkv, hd).

    ``write_idx``: cache slot to write (ring buffers: position % Smax);
    ``position``: absolute token position (RoPE);
    ``kv_len``: number of valid cache slots to attend over.

    RoPE keys are stored rotated at their absolute positions, so ring-buffer
    slot order does not matter - relative offsets survive the dot product.
    """
    dtype = x.dtype
    b = x.shape[0]
    positions = jnp.full((b, 1), position, jnp.int32)
    q, k, v = _project_qkv(p, x, cfg, positions, dtype)
    ck = jax.lax.dynamic_update_slice(
        cache["k"], k.astype(cache["k"].dtype), (0, write_idx, 0, 0))
    cv = jax.lax.dynamic_update_slice(
        cache["v"], v.astype(cache["v"].dtype), (0, write_idx, 0, 0))
    qh = jnp.moveaxis(q, 2, 1)                    # (B, Hq, 1, hd)
    kh = jnp.moveaxis(ck, 2, 1).astype(dtype)     # (B, Hkv, Smax, hd)
    vh = jnp.moveaxis(cv, 2, 1).astype(dtype)
    o = masked_decode_attention(qh, kh, vh, kv_len,
                                scale=cfg.attention_multiplier)
    o = jnp.moveaxis(o, 1, 2).reshape(b, 1, cfg.n_heads * cfg.hd)
    return o @ p["wo"].astype(dtype), {"k": ck, "v": cv}


def masked_decode_attention(q, k, v, kv_len, scale=None):
    """Reference decode attention with explicit kv-len mask (fp32 softmax).

    q: (B, Hq, 1, hd); k/v: (B, Hkv, Smax, hd). Replaced per-shard by the
    flash-decoding shard_map in the distributed serve path. ``scale``
    ``None`` is 1/sqrt(hd).
    """
    b, hq, _, hd = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    qf = q.astype(jnp.float32).reshape(b, hkv, group, hd)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    logits = jnp.einsum("bhgd,bhkd->bhgk", qf, kf)
    logits = logits / (hd ** 0.5) if scale is None else logits * scale
    kpos = jnp.arange(k.shape[2])
    mask = kpos[None, :] < kv_len
    logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bhgk,bhkd->bhgd", probs, vf)
    return o.reshape(b, hq, 1, hd).astype(q.dtype)


def apply_cross_attention(p, x, cfg: ModelConfig, memory,
                          use_pallas: Optional[bool] = None) -> jnp.ndarray:
    """Decoder cross-attention: queries from x (B,S,d), keys/values from
    encoder memory (B,Sm,d). No RoPE on cross path (Whisper-style)."""
    dtype = x.dtype
    b, s, _ = x.shape
    sm = memory.shape[1]
    hd, hq, hkv = cfg.hd, cfg.n_heads, cfg.n_kv
    q = (x @ p["wq"].astype(dtype)).reshape(b, s, hq, hd)
    k = (memory @ p["wk"].astype(dtype)).reshape(b, sm, hkv, hd)
    v = (memory @ p["wv"].astype(dtype)).reshape(b, sm, hkv, hd)
    o = ops.attention(jnp.moveaxis(q, 2, 1), jnp.moveaxis(k, 2, 1),
                      jnp.moveaxis(v, 2, 1), causal=False,
                      use_pallas=use_pallas)
    o = jnp.moveaxis(o, 1, 2).reshape(b, s, hq * hd)
    return o @ p["wo"].astype(dtype)
