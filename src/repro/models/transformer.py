"""Decoder-only LM covering the dense / moe / ssm / hybrid / vlm families.

Layers are *scanned* (params stacked on a leading L axis) so HLO size is
layer-count independent - the 94-layer MoE compiles on one CPU core - and
``jax.checkpoint`` around each layer gives per-layer remat.

The ssm family is a patterned stack: ``cfg.layer_pattern`` is one period of
layer kinds ("mamba" | "attention"), each followed by the FFN when
``d_ff``. Its params are stacked by kind (``blocks[kind]``: the kind's
layers in order, period-major); the forward scans over the periods and
applies each period's layers in published order inside one scan step, so
the HLO stays independent of depth. Its caches are stacked alike: KV for
the attention layers, SSM state plus conv tail for the Mamba-2 layers.

An optional ``shard_fn(x, name)`` hook lets the distributed layer constrain
activation shardings without the model importing mesh machinery.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.models import attention as attn_mod
from repro.models import hybrid as hybrid_mod
from repro.models import mamba2 as mamba_mod
from repro.models import moe as moe_mod
from repro.models.config import ModelConfig
from repro.models.layers import (apply_embedding, apply_ffn, apply_rmsnorm,
                                 init_embedding, init_ffn, init_rmsnorm,
                                 truncated_normal)

ShardFn = Callable[[jnp.ndarray, str], jnp.ndarray]
_id_shard: ShardFn = lambda x, name: x


def maybe_remat(body, cfg: ModelConfig):
    """Per-layer remat with the configured policy.

    'full' recomputes everything in backward (min memory, ~2x fwd compute in
    bwd); 'dots' saves matmul outputs (recompute only cheap elementwise -
    the compute-term hillclimb lever); 'none' disables remat."""
    if not cfg.remat or cfg.remat_policy == "none":
        return body
    policy = None
    if cfg.remat_policy == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
    return jax.checkpoint(body, prevent_cse=False, policy=policy)


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------

def _residual(x, y, cfg: ModelConfig):
    """x + residual_multiplier * y (the multiplier in float32)."""
    if cfg.residual_multiplier != 1.0:
        y = (y.astype(jnp.float32) * cfg.residual_multiplier).astype(y.dtype)
    return x + y


def init_block(key, cfg: ModelConfig):
    """One layer of a uniform stack (dense / moe / hybrid / vlm)."""
    ks = jax.random.split(key, 4)
    p = {"ln1": init_rmsnorm(cfg.d_model)}
    if cfg.family == "hybrid":
        p["mix"] = hybrid_mod.init_hybrid(ks[0], cfg)
    else:
        p["attn"] = attn_mod.init_attention(ks[0], cfg)
    p["ln2"] = init_rmsnorm(cfg.d_model)
    if cfg.family == "moe":
        assert cfg.moe_every == 1, "scan requires uniform layer structure"
        p["moe"] = moe_mod.init_moe(ks[1], cfg)
    else:
        p["ffn"] = init_ffn(ks[1], cfg.d_model, cfg.d_ff, cfg.glu)
    return p


def apply_block(p, x, cfg: ModelConfig, positions, is_global,
                shard_fn: ShardFn = _id_shard,
                use_pallas: Optional[bool] = None,
                causal: bool = True):
    """Full-sequence block. Returns (x, aux)."""
    aux = jnp.zeros((), jnp.float32)
    h = apply_rmsnorm(p["ln1"], x, cfg.norm_eps)
    if cfg.family == "hybrid":
        mix = hybrid_mod.apply_hybrid(p["mix"], h, cfg, positions, is_global,
                                      use_pallas=use_pallas)
    else:
        mix = attn_mod.apply_attention(p["attn"], h, cfg, positions,
                                       window=cfg.window, causal=causal,
                                       use_pallas=use_pallas)
    x = shard_fn(_residual(x, mix, cfg), "residual")
    h = apply_rmsnorm(p["ln2"], x, cfg.norm_eps)
    if cfg.family == "moe":
        y, aux = moe_mod.apply_moe(p["moe"], h, cfg)
    else:
        y = apply_ffn(p["ffn"], h, cfg.act, x.dtype)
    return shard_fn(_residual(x, y, cfg), "residual"), aux


def apply_block_decode(p, x, cfg: ModelConfig, cache, cache_index, is_global
                       ) -> Tuple[jnp.ndarray, jnp.ndarray, dict]:
    """One-token decode block. Returns (x, aux, new_cache)."""
    aux = jnp.zeros((), jnp.float32)
    h = apply_rmsnorm(p["ln1"], x, cfg.norm_eps)
    if cfg.family == "hybrid":
        y, nc = hybrid_mod.apply_hybrid_decode(p["mix"], h, cfg, cache,
                                               cache_index, is_global)
    else:
        y, nc = _attention_decode(p["attn"], h, cfg, cache, cache_index)
    x = _residual(x, y, cfg)
    h = apply_rmsnorm(p["ln2"], x, cfg.norm_eps)
    if cfg.family == "moe":
        y, aux = moe_mod.apply_moe(p["moe"], h, cfg)
    else:
        y = apply_ffn(p["ffn"], h, cfg.act, x.dtype)
    return _residual(x, y, cfg), aux, nc


def _attention_decode(p, h, cfg: ModelConfig, cache, cache_index):
    """One token of attention against a (ring) KV cache."""
    smax = cache["k"].shape[1]
    kv_len = jnp.minimum(cache_index + 1, smax)
    return attn_mod.apply_attention_decode(p, h, cfg, cache,
                                           cache_index % smax, cache_index,
                                           kv_len)


# ---------------------------------------------------------------------------
# patterned stack (ssm family)
# ---------------------------------------------------------------------------

def _slots(cfg: ModelConfig):
    """(kind, index within the period's layers of that kind) per layer of
    one period, in published order; and each kind's count per period."""
    counts, slots = {}, []
    for kind in cfg.layer_pattern:
        slots.append((kind, counts.get(kind, 0)))
        counts[kind] = counts.get(kind, 0) + 1
    return slots, counts


def init_layer(key, cfg: ModelConfig, kind: str):
    """One layer of a patterned stack: the kind's mixer, then the FFN."""
    k1, k2 = jax.random.split(key)
    p = {"ln1": init_rmsnorm(cfg.d_model)}
    if kind == "mamba":
        p["ssm"] = mamba_mod.init_mamba(k1, cfg)
    else:
        p["attn"] = attn_mod.init_attention(k1, cfg)
    if cfg.d_ff:
        p["ln2"] = init_rmsnorm(cfg.d_model)
        p["ffn"] = init_ffn(k2, cfg.d_model, cfg.d_ff, cfg.glu)
    return p


def _mlp(p, x, cfg: ModelConfig):
    if not cfg.d_ff:
        return x
    with obs.span("mlp"):
        h = apply_rmsnorm(p["ln2"], x, cfg.norm_eps)
        return _residual(x, apply_ffn(p["ffn"], h, cfg.act, x.dtype), cfg)


def apply_layer(p, x, cfg: ModelConfig, kind: str, positions,
                use_pallas: Optional[bool] = None, return_state: bool = False):
    """Full-sequence layer of a patterned stack. Returns (x, state): with
    ``return_state`` the layer's cache (KV, or SSM state plus conv tail),
    else None."""
    h = apply_rmsnorm(p["ln1"], x, cfg.norm_eps)
    if kind == "mamba":
        out = mamba_mod.apply_mamba(p["ssm"], h, cfg, use_pallas=use_pallas,
                                    return_state=return_state)
    else:
        out = attn_mod.apply_attention(p["attn"], h, cfg, positions,
                                       use_pallas=use_pallas,
                                       return_kv=return_state)
    y, state = out if return_state else (out, None)
    return _mlp(p, _residual(x, y, cfg), cfg), state


def apply_layer_decode(p, x, cfg: ModelConfig, kind: str, cache,
                       cache_index):
    """One-token layer of a patterned stack. Returns (x, new cache)."""
    h = apply_rmsnorm(p["ln1"], x, cfg.norm_eps)
    if kind == "mamba":
        y, nc = mamba_mod.apply_mamba_decode(p["ssm"], h, cfg, cache)
    else:
        y, nc = _attention_decode(p["attn"], h, cfg, cache, cache_index)
    return _mlp(p, _residual(x, y, cfg), cfg), nc


def _by_period(tree, cfg: ModelConfig):
    """Each leaf (kind's layers, ...) as (periods, per period, ...)."""
    n = cfg.n_layers // len(cfg.layer_pattern)
    return jax.tree.map(lambda t: t.reshape(n, -1, *t.shape[1:]), tree)


def _flat(tree):
    return jax.tree.map(lambda t: t.reshape(-1, *t.shape[2:]), tree)


def _over_periods(step, carry, xs, cfg: ModelConfig):
    """``lax.scan`` of ``step`` over the periods, or a Python loop without
    ``scan_layers``; the ys stacked on a leading period axis."""
    if cfg.scan_layers:
        return jax.lax.scan(step, carry, xs)
    ys = []
    for i in range(cfg.n_layers // len(cfg.layer_pattern)):
        carry, y = step(carry, jax.tree.map(lambda t: t[i], xs))
        ys.append(y)
    return carry, jax.tree.map(lambda *ls: jnp.stack(ls), *ys)


def _stack_forward(params, x, cfg: ModelConfig, positions, shard_fn,
                   use_pallas, max_len: Optional[int] = None):
    """The patterned stack over a full sequence. With ``max_len`` (a
    prefill) also each kind's caches, KV padded to ``max_len`` slots."""
    slots, _ = _slots(cfg)
    collect = max_len is not None
    layer = {kind: maybe_remat(functools.partial(
        apply_layer, cfg=cfg, kind=kind, positions=positions,
        use_pallas=use_pallas, return_state=collect), cfg)
        for kind in set(cfg.layer_pattern)}

    def period(xc, pp):
        states = {}
        for kind, j in slots:
            lp = jax.tree.map(lambda t: t[j], pp[kind])
            xc, st = layer[kind](lp, xc)
            xc = shard_fn(xc, "residual")
            if collect:
                with obs.span("prefill.state"):
                    if kind == "attention":
                        pad = max_len - st["k"].shape[1]
                        st = jax.tree.map(lambda t: jnp.pad(
                            t, ((0, 0), (0, pad), (0, 0), (0, 0))), st)
                    states.setdefault(kind, []).append(st)
        return xc, {k: jax.tree.map(lambda *ls: jnp.stack(ls), *v)
                    for k, v in states.items()}

    x, states = _over_periods(period, x, _by_period(params["blocks"], cfg),
                              cfg)
    return x, _flat(states)


# ---------------------------------------------------------------------------
# model
# ---------------------------------------------------------------------------

def _is_global_arr(cfg: ModelConfig) -> jnp.ndarray:
    g = jnp.zeros((cfg.n_layers,), bool)
    for i in cfg.global_layers:
        g = g.at[i].set(True)
    return g


def init_lm(key, cfg: ModelConfig):
    ks = jax.random.split(key, 4)
    if cfg.layer_pattern:
        _, counts = _slots(cfg)
        periods = cfg.n_layers // len(cfg.layer_pattern)
        blocks = {kind: jax.vmap(lambda k, kind=kind: init_layer(
            k, cfg, kind))(jax.random.split(jax.random.fold_in(ks[1], i),
                                            periods * c))
            for i, (kind, c) in enumerate(counts.items())}
    else:
        blocks = jax.vmap(lambda k: init_block(k, cfg))(
            jax.random.split(ks[1], cfg.n_layers))
    params = {
        "embed": init_embedding(ks[0], cfg.vocab, cfg.d_model),
        "blocks": blocks,
        "final_norm": init_rmsnorm(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["head"] = truncated_normal(ks[2], (cfg.d_model, cfg.vocab),
                                          cfg.d_model ** -0.5)
    if cfg.frontend is not None:
        params["frontend_proj"] = truncated_normal(
            ks[3], (cfg.d_model, cfg.d_model), cfg.d_model ** -0.5)
    return params


def _embed(params, tokens, cfg: ModelConfig):
    dtype = jnp.dtype(cfg.dtype)
    x = apply_embedding(params["embed"], tokens, dtype)
    if cfg.embedding_multiplier != 1.0:
        x = (x.astype(jnp.float32) * cfg.embedding_multiplier).astype(dtype)
    return x


def _logits(params, x, cfg: ModelConfig):
    x = apply_rmsnorm(params["final_norm"], x, cfg.norm_eps)
    head = (params["embed"]["table"].T if cfg.tie_embeddings
            else params["head"])
    logits = x @ head.astype(x.dtype)
    if cfg.logits_scaling != 1.0:
        logits = logits / jnp.asarray(cfg.logits_scaling, logits.dtype)
    if cfg.logit_softcap:
        c = cfg.logit_softcap
        logits = c * jnp.tanh(logits.astype(jnp.float32) / c)
    return logits


def forward(params, tokens, cfg: ModelConfig,
            prefix_embeds: Optional[jnp.ndarray] = None,
            shard_fn: ShardFn = _id_shard,
            use_pallas: Optional[bool] = None):
    """Training forward.

    tokens: (B, S) int32. prefix_embeds: (B, P, d) stub frontend output
    (vlm/audio), prepended before the token embeddings.
    Returns (logits (B, S_total, V), aux).
    """
    x = _embed(params, tokens, cfg)
    if prefix_embeds is not None:
        pe = prefix_embeds.astype(x.dtype) @ params["frontend_proj"].astype(
            x.dtype)
        x = jnp.concatenate([pe, x], axis=1)
    x = shard_fn(x, "residual")
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    if cfg.layer_pattern:
        x, _ = _stack_forward(params, x, cfg, positions, shard_fn, use_pallas)
        return _logits(params, x, cfg), jnp.zeros((), jnp.float32)
    x, aux, _ = _uniform_forward(params, x, cfg, positions, shard_fn,
                                 use_pallas)
    return _logits(params, x, cfg), aux


def _uniform_forward(params, x, cfg: ModelConfig, positions, shard_fn,
                     use_pallas, collect_kv: bool = False):
    """The uniform stack over a full sequence; with ``collect_kv`` (a
    prefill of the dense / moe / vlm families) also the stacked KV."""
    is_global = _is_global_arr(cfg)
    dtype = x.dtype

    def body(carry, layer):
        xc, aux = carry
        lp, g = layer
        kv = None
        if collect_kv:
            h = apply_rmsnorm(lp["ln1"], xc, cfg.norm_eps)
            _, k, v = attn_mod._project_qkv(lp["attn"], h, cfg, positions,
                                            dtype)
            kv = {"k": k, "v": v}
        xc, a = apply_block(lp, xc, cfg, positions, g, shard_fn=shard_fn,
                            use_pallas=use_pallas)
        return (xc, aux + a), kv

    body = maybe_remat(body, cfg)
    if cfg.scan_layers or collect_kv:
        (x, aux), kv = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                    (params["blocks"], is_global))
        return x, aux, kv
    aux = jnp.zeros((), jnp.float32)
    for i in range(cfg.n_layers):
        lp = jax.tree.map(lambda t: t[i], params["blocks"])
        (x, aux), _ = body((x, aux), (lp, is_global[i]))
    return x, aux, None


def init_caches(cfg: ModelConfig, batch: int, max_len: int,
                dtype=jnp.bfloat16):
    """Stacked (L, ...) caches for the scan-over-layers decode path; a
    patterned stack's are stacked by kind (``{kind: (layers of kind,
    ...)}``).

    Hybrid models return a per-layer *list* (global layers carry a full
    horizon, windowed layers a ring of ``window`` slots - shapes differ), and
    decode unrolls layers instead of scanning.
    """
    if cfg.family == "hybrid":
        g = set(cfg.global_layers)
        return [hybrid_mod.init_hybrid_cache(cfg, batch, max_len,
                                             is_global=(i in g), dtype=dtype)
                for i in range(cfg.n_layers)]

    def one(kind):
        if kind == "mamba":
            return mamba_mod.init_ssm_cache(cfg, batch, dtype)
        return attn_mod.init_kv_cache(cfg, batch, max_len, dtype)

    def stack(kinds):
        return jax.tree.map(lambda *ls: jnp.stack(ls),
                            *[one(k) for k in kinds])
    if cfg.layer_pattern:
        return {kind: stack([k for k in map(cfg.layer_kind,
                                            range(cfg.n_layers))
                             if k == kind])
                for kind in _slots(cfg)[1]}
    return stack(["attention"] * cfg.n_layers)


def decode_step(params, token, cfg: ModelConfig, caches, cache_index,
                shard_fn: ShardFn = _id_shard):
    """One serving step: token (B, 1) -> (logits (B, 1, V), new caches)."""
    x = shard_fn(_embed(params, token, cfg), "residual")
    if cfg.layer_pattern:
        slots, _ = _slots(cfg)

        def period(xc, layer):
            pp, cc = layer
            new = {kind: [] for kind in cc}
            for kind, j in slots:
                lp = jax.tree.map(lambda t: t[j], pp[kind])
                cache = jax.tree.map(lambda t: t[j], cc[kind])
                xc, nc = apply_layer_decode(lp, xc, cfg, kind, cache,
                                            cache_index)
                xc = shard_fn(xc, "residual")
                new[kind].append(nc)
            return xc, {k: jax.tree.map(lambda *ls: jnp.stack(ls), *v)
                        for k, v in new.items()}

        x, new_caches = _over_periods(
            period, x, (_by_period(params["blocks"], cfg),
                        _by_period(caches, cfg)), cfg)
        return _logits(params, x, cfg), _flat(new_caches)

    is_global = _is_global_arr(cfg)

    def body(carry, layer):
        xc = carry
        lp, cache, g = layer
        xc, _, nc = apply_block_decode(lp, xc, cfg, cache, cache_index, g)
        xc = shard_fn(xc, "residual")
        return xc, nc

    if isinstance(caches, list):            # hybrid: ragged cache shapes
        ncs = []
        for i in range(cfg.n_layers):
            lp = jax.tree.map(lambda t: t[i], params["blocks"])
            x, nc = body(x, (lp, caches[i], is_global[i]))
            ncs.append(nc)
        new_caches = ncs
    elif cfg.scan_layers:
        x, new_caches = jax.lax.scan(body, x,
                                     (params["blocks"], caches, is_global))
    else:
        ncs = []
        for i in range(cfg.n_layers):
            lp = jax.tree.map(lambda t: t[i], params["blocks"])
            cache = jax.tree.map(lambda t: t[i], caches)
            x, nc = body(x, (lp, cache, is_global[i]))
            ncs.append(nc)
        new_caches = jax.tree.map(lambda *ls: jnp.stack(ls), *ncs)
    return _logits(params, x, cfg), new_caches


def prefill(params, tokens, cfg: ModelConfig,
            prefix_embeds: Optional[jnp.ndarray] = None,
            shard_fn: ShardFn = _id_shard,
            use_pallas: Optional[bool] = None,
            max_len: Optional[int] = None):
    """Prefill: the full forward, returning the logits of the last position
    only (B, 1, V), aux, and the caches decode continues from (positions
    0..S-1 filled; decode's first ``cache_index`` is S).

    A patterned stack returns ``{kind: ...}`` caches (``init_caches``'
    layout): KV of ``max(max_len, S)`` slots for its attention layers, SSM
    state and conv tail for its Mamba-2 layers. The dense, moe and vlm
    families return their stacked KV of S slots; hybrid, none.
    """
    x = _embed(params, tokens, cfg)
    if prefix_embeds is not None:
        pe = prefix_embeds.astype(x.dtype) @ params["frontend_proj"].astype(
            x.dtype)
        x = jnp.concatenate([pe, x], axis=1)
    x = shard_fn(x, "residual")
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    if cfg.layer_pattern:
        x, caches = _stack_forward(params, x, cfg, positions, shard_fn,
                                   use_pallas, max_len=max(max_len or s, s))
        aux = jnp.zeros((), jnp.float32)
    else:
        x, aux, caches = _uniform_forward(
            params, x, cfg, positions, shard_fn, use_pallas,
            collect_kv=cfg.family in ("dense", "moe", "vlm"))
    return _logits(params, x[:, -1:], cfg), aux, caches
