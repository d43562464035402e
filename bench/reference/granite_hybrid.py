"""Plain float32 reference of a Granite-4.0-H stack: Mamba-2 layers
interleaved with NoPE GQA attention, each followed by a SwiGLU MLP; and
the weights it runs, made from a key in the published layout.

Nothing here imports the system under test. The numbers come from the
configuration file (``bench/configs/<config>.json``: the published
``config.json`` keys). The weights are those of the published checkpoint's
state dict, per layer and under its names (``mamba.in_proj.weight`` of
shape (out, in), ``mamba.conv1d.weight`` (C, 1, K), ``shared_mlp.
input_linear.weight`` holding gate then up, ...), random from a key:
:func:`layer_weights` makes layer i alone, in the configuration's dtype, on
the device; the reference makes each layer again as it reaches it and
upcasts it inside a jitted layer function, so it needs no copy of the
model beside the program's. Where the checkpoint's init uses constants,
the values here vary: conv bias, dt_bias (the inverse softplus of dt in
[1e-3, 0.1], log-uniform), A_log = log U[1, 16], D and every RMSNorm scale
U[0.5, 1.5]. The embedding is N(0, 1) over ``embedding_multiplier``.

Equations, per layer (HF ``GraniteMoeHybridDecoderLayer``):
x += r * mixer(rmsnorm(x)); x += r * mlp(rmsnorm(x)), r the residual
multiplier. The Mamba-2 mixer is in_proj -> [z, xBC, dt]; depthwise causal
conv with bias, SiLU; per head the recurrence
h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T, y_t = C_t h_t + D x_t, as a
``lax.scan`` over time (dt = softplus(dt + dt_bias), A = -exp(A_log));
gated RMSNorm of y * silu(z); out_proj. Attention has no position
embedding, softmax scale ``attention_multiplier``, causal, GQA; it runs
over blocks of queries against all keys so the score matrix fits.
Embeddings are scaled by ``embedding_multiplier``, logits divided by
``logits_scaling``, the head tied to the embedding.

``precision`` says how the reference computes: ``"highest"`` (float32 at
XLA's HIGHEST, what the check uses), or ``"fp8"``, one precision below the
configuration's bfloat16 -- a control: every product's operands, and the
residual stream after the embedding and after each layer, scaled per
tensor to e4m3's range and rounded to e4m3's grid (arithmetic, so XLA
cannot fold it away). ``skip="x_dt"`` adds the skip term as D (x dt)
instead of D x -- the other control.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
PRECISIONS = ("highest", "fp8")
SKIPS = ("x", "x_dt")
Q_BLOCK = 256          # queries per block of the attention reference
E4M3_MAX = 448.0

KEYS = ("hidden_size", "rms_norm_eps", "residual_multiplier",
        "embedding_multiplier", "logits_scaling", "attention_multiplier",
        "num_attention_heads", "num_key_value_heads", "mamba_n_heads",
        "mamba_d_head", "mamba_d_state", "mamba_n_groups", "mamba_expand",
        "mamba_d_conv", "shared_intermediate_size", "vocab_size")


def numbers(config: dict) -> tuple:
    """The configuration's numbers the reference reads, hashable."""
    return tuple((k, config[k]) for k in KEYS)


# ------------------------------ the weights ------------------------------

def _normal(key, shape, fan_in):
    return jax.random.normal(key, shape, F32) * fan_in ** -0.5


def _uniform(key, shape, lo, hi):
    return jax.random.uniform(key, shape, F32, lo, hi)


def _mlp_weights(key, c):
    d, f = c["hidden_size"], c["shared_intermediate_size"]
    k1, k2, k3 = jax.random.split(key, 3)
    return {"post_attention_layernorm.weight": _uniform(k1, (d,), 0.5, 1.5),
            "shared_mlp.input_linear.weight": _normal(k2, (2 * f, d), d),
            "shared_mlp.output_linear.weight": _normal(k3, (d, f), f)}


@functools.partial(jax.jit, static_argnames=("kind", "c", "dtype"))
def _make_layer(key, kind, c, dtype):
    c = dict(c)
    d = c["hidden_size"]
    k_norm, k_mix, k_mlp = jax.random.split(key, 3)
    w = {"input_layernorm.weight": _uniform(k_norm, (d,), 0.5, 1.5)}
    if kind == "mamba":
        h, n, g = c["mamba_n_heads"], c["mamba_d_state"], c["mamba_n_groups"]
        di = c["mamba_expand"] * d
        conv, k = di + 2 * g * n, c["mamba_d_conv"]
        ks = jax.random.split(k_mix, 8)
        dt = jnp.exp(_uniform(ks[3], (h,), math.log(1e-3), math.log(0.1)))
        w.update({
            "mamba.in_proj.weight": _normal(ks[0], (di + conv + h, d), d),
            "mamba.conv1d.weight": _normal(ks[1], (conv, 1, k), k),
            "mamba.conv1d.bias": _uniform(ks[2], (conv,), -k ** -0.5,
                                          k ** -0.5),
            "mamba.dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "mamba.A_log": jnp.log(_uniform(ks[4], (h,), 1.0, 16.0)),
            "mamba.D": _uniform(ks[5], (h,), 0.5, 1.5),
            "mamba.norm.weight": _uniform(ks[6], (di,), 0.5, 1.5),
            "mamba.out_proj.weight": _normal(ks[7], (d, di), di)})
    else:
        hq, hkv = c["num_attention_heads"], c["num_key_value_heads"]
        hd = d // hq
        ks = jax.random.split(k_mix, 4)
        w.update({"self_attn.q_proj.weight": _normal(ks[0], (hq * hd, d), d),
                  "self_attn.k_proj.weight": _normal(ks[1], (hkv * hd, d), d),
                  "self_attn.v_proj.weight": _normal(ks[2], (hkv * hd, d), d),
                  "self_attn.o_proj.weight": _normal(ks[3], (d, hq * hd),
                                                     hq * hd)})
    w.update(_mlp_weights(k_mlp, c))
    return {name: t.astype(dtype) for name, t in w.items()}


@functools.partial(jax.jit, static_argnames=("c", "dtype"))
def _make_top(key, c, dtype):
    c = dict(c)
    d = c["hidden_size"]
    k1, k2 = jax.random.split(key)
    # scaled so the embedding multiplier gives the residual stream an RMS
    # of about 1: the layers' updates, not the embedding, set the logits
    table = jax.random.normal(k1, (c["vocab_size"], d), F32) / c[
        "embedding_multiplier"]
    return {"embed_tokens.weight": table.astype(dtype),
            "norm.weight": _uniform(k2, (d,), 0.5, 1.5).astype(dtype)}


def layer_weights(key, i: int, config: dict, dtype=jnp.bfloat16) -> dict:
    """Layer i of ``layer_types``: {state-dict name within the layer:
    array}, in ``dtype`` on the default device."""
    return _make_layer(jax.random.fold_in(jax.random.fold_in(key, 1), i),
                       config["layer_types"][i], numbers(config),
                       jnp.dtype(dtype))


def top_weights(key, config: dict, dtype=jnp.bfloat16) -> dict:
    """``embed_tokens.weight`` (V, d) and the final ``norm.weight``."""
    return _make_top(jax.random.fold_in(key, 0), numbers(config),
                     jnp.dtype(dtype))


# ------------------------------ the reference ------------------------------

def _e4m3(x):
    """x rounded to float8 e4m3fn's grid after a per-tensor scale to its
    range (round to nearest even; subnormals below 2^-6)."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    y = x / scale
    _, e = jnp.frexp(y)                                  # y = m 2^e, m in [.5, 1)
    step = jnp.exp2((jnp.maximum(e, -5) - 4).astype(F32))
    q = jnp.clip(jnp.round(y / step) * step, -E4M3_MAX, E4M3_MAX)
    return q * scale


def _round(x, precision):
    return x if precision == "highest" else _e4m3(x)


def _mm(a, b, precision):
    return jnp.matmul(_round(a, precision), _round(b, precision),
                      precision=lax.Precision.HIGHEST)


def _linear(x, w, precision):
    """x @ W^T for a state-dict weight W of shape (out, in)."""
    return _mm(x, w.T, precision)


def _einsum(spec, a, b, precision):
    return jnp.einsum(spec, _round(a, precision), _round(b, precision),
                      precision=lax.Precision.HIGHEST)


def _rmsnorm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _f32(w):
    return {name: t.astype(F32) for name, t in w.items()}


def _mlp(w, x, c, precision):
    h = _rmsnorm(x, w["post_attention_layernorm.weight"], c["rms_norm_eps"])
    gate, up = jnp.split(_linear(h, w["shared_mlp.input_linear.weight"],
                                 precision), 2, axis=-1)
    y = _linear(jax.nn.silu(gate) * up, w["shared_mlp.output_linear.weight"],
                precision)
    return _round(x + c["residual_multiplier"] * y, precision)


@functools.partial(jax.jit, static_argnames=("c", "precision", "skip"))
def mamba_layer(w, x, c, precision="highest", skip="x"):
    """x (S, d) float32 through one Mamba-2 layer of weights ``w``."""
    c, w = dict(c), _f32(w)
    s = x.shape[0]
    h_, hp, n = c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"]
    g = c["mamba_n_groups"]
    di = c["mamba_expand"] * c["hidden_size"]
    h = _rmsnorm(x, w["input_layernorm.weight"], c["rms_norm_eps"])
    zxbcdt = _linear(h, w["mamba.in_proj.weight"], precision)
    z, xbc, dt = (zxbcdt[:, :di], zxbcdt[:, di:2 * di + 2 * g * n],
                  zxbcdt[:, 2 * di + 2 * g * n:])
    conv_w = w["mamba.conv1d.weight"][:, 0]                  # (C, K)
    k = conv_w.shape[1]
    xp = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(xp[i:i + s] * conv_w[:, i] for i in range(k))
                      + w["mamba.conv1d.bias"])
    xs = xbc[:, :di].reshape(s, h_, hp)
    bm = xbc[:, di:di + g * n].reshape(s, g, n)
    cm = xbc[:, di + g * n:].reshape(s, g, n)
    dt = jax.nn.softplus(dt + w["mamba.dt_bias"])                 # (S, H)
    a = -jnp.exp(w["mamba.A_log"])
    group = jnp.arange(h_) // (h_ // g)

    def step(state, t):
        xt, bt, ct, dtt = t                       # (H,P) (G,N) (G,N) (H,)
        outer = _einsum("hp,hn->hpn", dtt[:, None] * xt, bt[group],
                        precision)
        state = jnp.exp(dtt * a)[:, None, None] * state + outer
        return state, _einsum("hpn,hn->hp", state, ct[group], precision)

    _, y = lax.scan(step, jnp.zeros((h_, hp, n), F32), (xs, bm, cm, dt),
                    unroll=8)
    d_in = xs if skip == "x" else xs * dt[:, :, None]
    y = (y + w["mamba.D"][:, None] * d_in).reshape(s, di) * jax.nn.silu(z)
    y = _rmsnorm(y, w["mamba.norm.weight"], c["rms_norm_eps"])
    x = x + c["residual_multiplier"] * _linear(y, w["mamba.out_proj.weight"],
                                               precision)
    return _mlp(w, x, c, precision)


@functools.partial(jax.jit, static_argnames=("c", "precision"))
def attention_layer(w, x, c, precision="highest"):
    """x (S, d) float32 through one attention layer of weights ``w``."""
    c, w = dict(c), _f32(w)
    s = x.shape[0]
    hq, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c["hidden_size"] // hq
    h = _rmsnorm(x, w["input_layernorm.weight"], c["rms_norm_eps"])
    q = _linear(h, w["self_attn.q_proj.weight"], precision).reshape(
        s, hkv, hq // hkv, hd)
    k = _linear(h, w["self_attn.k_proj.weight"], precision).reshape(
        s, hkv, hd)
    v = _linear(h, w["self_attn.v_proj.weight"], precision).reshape(
        s, hkv, hd)
    nb = -(-s // Q_BLOCK)
    qb = jnp.pad(q, ((0, nb * Q_BLOCK - s), (0, 0), (0, 0), (0, 0))
                 ).reshape(nb, Q_BLOCK, hkv, hq // hkv, hd)
    kpos = jnp.arange(s)

    def block(i):
        sc = _einsum("qhgd,khd->hgqk", qb[i], k, precision)
        sc = sc * c["attention_multiplier"]
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.where(qpos[:, None] >= kpos[None, :], sc, -jnp.inf)
        return _einsum("hgqk,khd->qhgd", jax.nn.softmax(sc, axis=-1), v,
                       precision)

    o = lax.map(block, jnp.arange(nb)).reshape(nb * Q_BLOCK, hq * hd)[:s]
    x = x + c["residual_multiplier"] * _linear(
        o, w["self_attn.o_proj.weight"], precision)
    return _mlp(w, x, c, precision)


@functools.partial(jax.jit, static_argnames=("c", "precision"))
def embed(table, tokens, c, precision="highest"):
    return _round(table[tokens].astype(F32) * dict(c)["embedding_multiplier"],
                  precision)


@functools.partial(jax.jit, static_argnames=("c", "n_last", "precision"))
def last_logits(top, x, c, n_last, precision="highest"):
    c, top = dict(c), _f32(top)
    h = _rmsnorm(x[-n_last:], top["norm.weight"], c["rms_norm_eps"])
    return _linear(h, top["embed_tokens.weight"], precision) / c[
        "logits_scaling"]


def logits(key, tokens, config: dict, n_last: int, dtype=jnp.bfloat16,
           precision: str = "highest", skip: str = "x"):
    """Float32 logits (n_last, V) of the last ``n_last`` positions of one
    sequence ``tokens`` (S,), through every layer of ``layer_types``, on
    the weights that ``key`` makes in ``dtype``."""
    if precision not in PRECISIONS or skip not in SKIPS:
        raise ValueError((precision, skip))
    if (config["position_embedding_type"] != "nope"
            or not config["tie_word_embeddings"]
            or config["hidden_act"] != "silu"):
        raise NotImplementedError("a tied, NoPE, SiLU Granite-4.0-H stack")
    c = numbers(config)
    top = top_weights(key, config, dtype)
    x = embed(top["embed_tokens.weight"], tokens, c, precision=precision)
    for i, kind in enumerate(config["layer_types"]):
        w = layer_weights(key, i, config, dtype)
        if kind == "mamba":
            x = mamba_layer(w, x, c, precision=precision, skip=skip)
        else:
            x = attention_layer(w, x, c, precision=precision)
    return last_logits(top, x, c, n_last, precision=precision)
