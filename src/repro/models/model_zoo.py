"""Unified model API over all families: init / forward / prefill / decode.

Every architecture (dense, moe, ssm, hybrid, vlm, encdec) is driven through
the same four functions; the launcher, trainer, and dry-run never dispatch on
family themselves.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import encdec, transformer
from repro.models.config import ModelConfig


def init(key, cfg: ModelConfig):
    if cfg.family == "encdec":
        return encdec.init_encdec(key, cfg)
    return transformer.init_lm(key, cfg)


def forward(params, batch: dict, cfg: ModelConfig, shard_fn=lambda x, n: x,
            use_pallas: Optional[bool] = None):
    """batch: {'tokens': (B,S)} + optional {'frames'|'patches': (B,P,d)}.
    Returns (logits, aux)."""
    if cfg.family == "encdec":
        return encdec.forward(params, batch["frames"], batch["tokens"], cfg,
                              shard_fn=shard_fn, use_pallas=use_pallas)
    prefix = batch.get("patches")
    return transformer.forward(params, batch["tokens"], cfg,
                               prefix_embeds=prefix, shard_fn=shard_fn,
                               use_pallas=use_pallas)


def init_caches(params, cfg: ModelConfig, batch: int, max_len: int,
                memory: Optional[jnp.ndarray] = None, dtype=jnp.bfloat16):
    if cfg.family == "encdec":
        assert memory is not None, "encdec caches need the encoder memory"
        return encdec.init_decode_caches(params, memory, cfg, batch, max_len,
                                         dtype)
    return transformer.init_caches(cfg, batch, max_len, dtype)


def decode_step(params, token, cfg: ModelConfig, caches, cache_index,
                shard_fn=lambda x, n: x):
    if cfg.family == "encdec":
        return encdec.decode_step(params, token, cfg, caches, cache_index,
                                  shard_fn=shard_fn)
    return transformer.decode_step(params, token, cfg, caches, cache_index,
                                   shard_fn=shard_fn)


def prefill(params, batch: dict, cfg: ModelConfig, shard_fn=lambda x, n: x,
            use_pallas: Optional[bool] = None, max_len: Optional[int] = None):
    """Returns (logits of the last position (B, 1, V), aux, caches);
    ``max_len`` sizes the KV caches a patterned stack returns (see
    :func:`repro.models.transformer.prefill`). encdec returns the encoder
    memory in place of caches."""
    if cfg.family == "encdec":
        memory = encdec.encode(params, batch["frames"], cfg, shard_fn,
                               use_pallas)
        logits = encdec.decode_train(params, batch["tokens"], memory, cfg,
                                     shard_fn, use_pallas)
        return logits[:, -1:], jnp.zeros((), jnp.float32), memory
    prefix = batch.get("patches")
    return transformer.prefill(params, batch["tokens"], cfg,
                               prefix_embeds=prefix, shard_fn=shard_fn,
                               use_pallas=use_pallas, max_len=max_len)


def param_count(cfg: ModelConfig) -> int:
    """Exact parameter count via abstract init (no allocation)."""
    shapes = jax.eval_shape(lambda k: init(k, cfg),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    return int(sum(np.prod(l.shape) for l in jax.tree.leaves(shapes)))


def active_param_count(cfg: ModelConfig) -> int:
    """Params touched per token: total minus non-selected experts."""
    total = param_count(cfg)
    if cfg.family != "moe":
        return total
    de = cfg.d_expert or cfg.d_ff
    per_expert = cfg.d_model * de * (3 if cfg.glu else 2)
    return total - cfg.n_layers * (cfg.n_experts - cfg.top_k) * per_expert
