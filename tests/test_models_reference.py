"""The patterned Mamba-2 / attention stacks against the plain float32
reference (``repro.models.reference``), on seeded random weights at a small
size: granite-4.0-h-micro's pattern (two periods, d_model 128, NoPE GQA,
the muP multipliers) and mamba2-130m's (Mamba-2 alone, no MLP).

Tolerance: the program runs here in float32 with every product at
HIGHEST, so it differs from the reference only in the order of its sums
(the chunked SSD against the recurrence, flash-style attention against a
plain softmax): 4e-6 (granite) and 8e-6 (mamba2) of the logits' RMS. The
limit, 1e-4 of the RMS, leaves 12x room above that, and sits three orders
of magnitude below what the old skip convention D (x dt) gives (0.19 and
3.7 of the RMS).
"""
import dataclasses
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.models import mamba2
from repro.models import model_zoo as zoo
from repro.models import reference

SEQ, DECODE = 512, 8
TOL = 1e-4
ARCHS = ["granite-4.0-h-micro", "mamba2-130m"]


def small(arch):
    """Two periods of the arch's pattern (4 layers for a one-kind
    pattern) at d_model 128, float32."""
    base = registry.get_config(arch)
    period = len(base.layer_pattern)
    return dataclasses.replace(
        base, n_layers=2 * period if period > 1 else 4, d_model=128,
        d_ff=256 if base.d_ff else 0, vocab=512, n_heads=4,
        n_kv=2 if base.n_kv < base.n_heads else 4, head_dim=32,
        ssm_heads=0, ssm_head_dim=32, ssm_state=16, ssm_chunk=64,
        dtype="float32")


@pytest.fixture(scope="module", params=ARCHS)
def case(request):
    """(cfg, params, tokens, reference logits over prompt and
    continuation). Where init sets constants (conv bias and dt_bias 0, D
    and every norm scale 1), the params vary: D != 0 per head, so the
    skip term is seen."""
    cfg = small(request.param)
    params = zoo.init(jax.random.PRNGKey(0), cfg)

    def vary(path, t):
        name = jax.tree_util.keystr(path)
        if not any(k in name for k in ("conv_b", "dt_bias", "d_skip",
                                        "scale")):
            return t
        u = jax.random.uniform(jax.random.fold_in(jax.random.PRNGKey(7),
                                                  zlib.crc32(name.encode())),
                               t.shape, minval=-0.5, maxval=0.5)
        return t + u
    params = jax.tree_util.tree_map_with_path(vary, params)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, SEQ + DECODE), 0,
                              cfg.vocab)
    want = np.asarray(reference.forward(params, toks, cfg))
    return cfg, params, toks, want


def rel_err(got, want):
    return float(np.max(np.abs(np.asarray(got) - want))
                 / np.sqrt(np.mean(want ** 2)))


def forward(case):
    cfg, params, toks, _ = case
    with jax.default_matmul_precision("highest"):
        return zoo.forward(params, {"tokens": toks}, cfg)[0]


def test_forward_matches_reference(case):
    assert rel_err(forward(case), case[3]) < TOL


def test_prefill_then_decode_matches_reference(case):
    """Prefill's last-position logits, then 8 decode steps through its
    caches (KV for attention layers, SSM state and conv tail for Mamba-2
    layers), against the reference's full forward."""
    cfg, params, toks, want = case
    with jax.default_matmul_precision("highest"):
        logits, _, caches = zoo.prefill(params, {"tokens": toks[:, :SEQ]},
                                        cfg, max_len=SEQ + DECODE)
        assert logits.shape == (2, 1, cfg.vocab)
        rows = [logits]
        step = jax.jit(lambda p, t, c, i: zoo.decode_step(p, t, cfg, c, i))
        for j in range(DECODE):
            lg, caches = step(params, toks[:, SEQ + j:SEQ + j + 1], caches,
                              jnp.int32(SEQ + j))
            rows.append(lg)
    got = np.concatenate([np.asarray(r) for r in rows], axis=1)
    assert rel_err(got, want[:, SEQ - 1:]) < TOL


def test_old_skip_convention_fails_the_reference(case, monkeypatch):
    """The witness of the D skip repair: with D != 0, the skip term taken
    as D (x dt) departs from the reference by far more than TOL."""
    real = mamba2._prepare_ssd

    def x_dt(*args):
        _, xh, a_log, bh, ch = real(*args)
        return xh, xh, a_log, bh, ch
    monkeypatch.setattr(mamba2, "_prepare_ssd", x_dt)
    assert rel_err(forward(case), case[3]) > 100 * TOL


def test_granite_config_is_the_published_one():
    cfg = registry.get_config("granite-4.0-h-micro")
    assert cfg.layer_pattern == ("mamba",) * 5 + ("attention",) \
        + ("mamba",) * 4
    kinds = [cfg.layer_kind(i) for i in range(cfg.n_layers)]
    assert kinds.count("mamba") == 36 and kinds.count("attention") == 4
    assert (cfg.d_model, cfg.vocab, cfg.d_ff, cfg.n_heads, cfg.n_kv,
            cfg.hd) == (2048, 100352, 8192, 32, 8, 64)
    assert (cfg.n_ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
            cfg.ssm_groups, cfg.ssm_expand, cfg.ssm_conv,
            cfg.ssm_chunk) == (64, 64, 128, 1, 2, 4, 256)
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.attention_multiplier, cfg.logits_scaling,
            cfg.norm_eps) == (12, 0.22, 0.015625, 8, 1e-5)
    assert cfg.pos == "none" and cfg.tie_embeddings
    assert not cfg.sub_quadratic and not cfg.attention_free


@pytest.mark.parametrize("arch,count", [
    # 36 x 76,182,976 (Mamba-2 layer + MLP) + 4 x 60,821,504 (attention
    # layer + MLP) + 100352 x 2048 (tied embedding) + 2048 (final norm)
    ("granite-4.0-h-micro", 3_191_396_096),
    ("mamba2-130m", 128_983_488)])
def test_param_count_is_abstract_init(arch, count):
    cfg = registry.get_config(arch)
    assert zoo.param_count(cfg) == cfg.param_count() == count


def test_a_pattern_must_tile_the_layers():
    with pytest.raises(ValueError):
        dataclasses.replace(registry.get_config("granite-4.0-h-micro"),
                            n_layers=12)
