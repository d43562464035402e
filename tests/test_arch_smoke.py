"""Per-assigned-architecture smoke tests (deliverable f).

Each test instantiates a REDUCED config of the same family (small
layers/width, few experts, tiny vocab) and runs one forward + one train step
on CPU, asserting output shapes and no NaNs. The FULL configs are exercised
via the dry-run (ShapeDtypeStructs, no allocation) - see launch/dryrun.py.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.data.pipeline import DataConfig, make_batch
from repro.launch.train import reduce_config
from repro.models import model_zoo as zoo
from repro.train import train_state as ts
from repro.train.optimizer import AdamWConfig


def _reduced(arch):
    cfg = reduce_config(registry.get_config(arch), layers=2, d_model=64,
                        vocab=128, heads=4)
    return dataclasses.replace(cfg, accum_steps=1, dtype="float32")


@pytest.mark.parametrize("arch", registry.ARCHS)
def test_smoke_forward_and_train_step(arch):
    cfg = _reduced(arch)
    assert cfg.family == registry.get_config(arch).family
    key = jax.random.PRNGKey(0)
    opt = AdamWConfig(lr=1e-3, eight_bit=cfg.opt_8bit, warmup_steps=2,
                      decay_steps=10)
    state = ts.init_state(key, cfg, opt)
    data = DataConfig(vocab=cfg.vocab, global_batch=4, seq_len=16)
    batch = make_batch(cfg, data, 0)
    # forward: shape + finite
    logits, aux = zoo.forward(state["params"], batch, cfg)
    extra = cfg.num_prefix_tokens if cfg.family == "vlm" else 0
    assert logits.shape == (4, 16 + extra, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
    # one train step: loss finite, params move
    step = jax.jit(ts.make_train_step(cfg, opt))
    new_state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    moved = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                         state["params"], new_state["params"])
    assert max(jax.tree.leaves(moved)) > 0


@pytest.mark.parametrize("arch", ["minitron-8b", "mamba2-130m",
                                  "hymba-1.5b", "whisper-small",
                                  "qwen3-moe-235b-a22b",
                                  "granite-4.0-h-micro"])
def test_smoke_decode_step(arch):
    cfg = _reduced(arch)
    key = jax.random.PRNGKey(0)
    params = zoo.init(key, cfg)
    b = 2
    if cfg.family == "encdec":
        mem = jax.random.normal(key, (b, 8, cfg.d_model), jnp.float32)
        caches = zoo.init_caches(params, cfg, b, 24, memory=mem,
                                 dtype=jnp.float32)
    else:
        caches = zoo.init_caches(params, cfg, b, 24, dtype=jnp.float32)
    tok = jnp.zeros((b, 1), jnp.int32)
    logits, new_caches = zoo.decode_step(params, tok, cfg, caches,
                                         jnp.int32(0))
    assert logits.shape == (b, 1, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))


def test_full_configs_match_assignment():
    """The FULL configs carry the exact assigned hyperparameters."""
    spec = {
        "minitron-8b": (32, 4096, 32, 8, 16384, 256000),
        "granite-3-8b": (40, 4096, 32, 8, 12800, 49155),
        "gemma-7b": (28, 3072, 16, 16, 24576, 256000),
        "mistral-large-123b": (88, 12288, 96, 8, 28672, 32768),
        "whisper-small": (12, 768, 12, 12, 3072, 51865),
        "mamba2-130m": (24, 768, 24, 24, 0, 50280),
        "hymba-1.5b": (32, 1600, 25, 5, 5504, 32001),
        "internvl2-1b": (24, 896, 14, 2, 4864, 151655),
        "qwen3-moe-235b-a22b": (94, 4096, 64, 4, 1536, 151936),
        "kimi-k2-1t-a32b": (61, 7168, 64, 8, 2048, 163840),
        "granite-4.0-h-micro": (40, 2048, 32, 8, 8192, 100352),
    }
    for arch, (L, d, h, kv, ff, v) in spec.items():
        cfg = registry.get_config(arch)
        assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv,
                cfg.d_ff, cfg.vocab) == (L, d, h, kv, ff, v), arch
    assert registry.get_config("gemma-7b").head_dim == 256
    assert registry.get_config("qwen3-moe-235b-a22b").n_experts == 128
    assert registry.get_config("qwen3-moe-235b-a22b").top_k == 8
    assert registry.get_config("kimi-k2-1t-a32b").n_experts == 384
    assert registry.get_config("mamba2-130m").ssm_state == 128
    assert registry.get_config("hymba-1.5b").ssm_state == 16


def test_param_counts_in_family_range():
    """Sanity: each arch's parameter count is in its advertised class."""
    expect = {"minitron-8b": (8e9, 11e9), "granite-3-8b": (7e9, 9e9),
              "gemma-7b": (7.5e9, 9.5e9),
              "mistral-large-123b": (118e9, 128e9),
              "whisper-small": (0.2e9, 0.35e9),
              "mamba2-130m": (0.11e9, 0.15e9),
              "hymba-1.5b": (1.3e9, 1.9e9), "internvl2-1b": (0.4e9, 0.6e9),
              "qwen3-moe-235b-a22b": (225e9, 245e9),
              "kimi-k2-1t-a32b": (0.95e12, 1.1e12),
              "granite-4.0-h-micro": (3.1e9, 3.3e9)}
    for arch, (lo, hi) in expect.items():
        n = zoo.param_count(registry.get_config(arch))
        assert lo <= n <= hi, (arch, n)
    # active params for the MoEs: the a22b / a32b designations
    a = zoo.active_param_count(registry.get_config("qwen3-moe-235b-a22b"))
    assert 20e9 <= a <= 24e9
    a = zoo.active_param_count(registry.get_config("kimi-k2-1t-a32b"))
    assert 30e9 <= a <= 34e9


def test_cell_skips_documented():
    defined, skipped = registry.all_cells()
    assert len(defined) == 35
    assert len(skipped) == 9
    assert all(s[1] == "long_500k" for s in skipped)
    # only the sub-quadratic archs run long_500k
    long_archs = {a for a, s in defined if s == "long_500k"}
    assert long_archs == {"mamba2-130m", "hymba-1.5b"}
