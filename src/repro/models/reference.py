"""Plain float32 reference of the patterned Mamba-2 / attention stacks.

The forward pass of ``granite-4.0-h-micro`` (Mamba-2 layers interleaved
with NoPE GQA attention, each followed by a SwiGLU MLP, with the muP
multipliers) and of ``mamba2-130m`` (Mamba-2 layers alone), written from
the published equations in straightforward ``jax.numpy``: float32, every
product at ``jax.default_matmul_precision("highest")``, no kernels, no
cache, no chunking, no batching of layers. It reads the program's
parameter tree (``blocks[kind]``: that kind's layers in order) and nothing
else of the program.

Per Mamba-2 head, over time t:

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,   y_t = C_t h_t + D x_t

as a ``lax.scan`` over time, with dt = softplus(dt_raw + dt_bias),
A = -exp(A_log), the depthwise causal conv (with bias) and SiLU before it,
and the gated RMSNorm y * silu(z) after it. Attention is plain softmax over
the causal mask with the config's scale. Departures: none known.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _mamba(p, x, cfg):
    """One Mamba-2 mixer over the whole sequence. x: (B, S, d)."""
    bsz, s, _ = x.shape
    di, g, n = cfg.d_inner, cfg.ssm_groups, cfg.ssm_state
    h, hp = cfg.n_ssm_heads, cfg.ssm_head_dim
    zxbcdt = x @ p["in_proj"]
    z, xbc, dt = (zxbcdt[..., :di], zxbcdt[..., di:2 * di + 2 * g * n],
                  zxbcdt[..., 2 * di + 2 * g * n:])
    k = p["conv_w"].shape[0]
    xp = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(xp[:, i:i + s] * p["conv_w"][i] for i in range(k))
    xbc = jax.nn.silu(conv + p["conv_b"])
    xs = xbc[..., :di].reshape(bsz, s, h, hp)
    head_group = jnp.arange(h) // (h // g)
    bm = xbc[..., di:di + g * n].reshape(bsz, s, g, n)[:, :, head_group]
    cm = xbc[..., di + g * n:].reshape(bsz, s, g, n)[:, :, head_group]
    dt = jax.nn.softplus(dt + p["dt_bias"])                      # (B,S,H)
    a = -jnp.exp(p["a_log"])                                      # (H,)

    def step(state, t):
        xt, bt, ct, dtt = t                     # (B,H,P) (B,H,N) (B,H,N) (B,H)
        state = (jnp.exp(dtt * a)[..., None, None] * state
                 + (dtt[..., None] * xt)[..., None] * bt[:, :, None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, ct)

    tmajor = [jnp.moveaxis(t, 1, 0) for t in (xs, bm, cm, dt)]
    _, y = jax.lax.scan(step, jnp.zeros((bsz, h, hp, n), F32), tmajor)
    y = jnp.moveaxis(y, 0, 1) + p["d_skip"][:, None] * xs
    y = y.reshape(bsz, s, di) * jax.nn.silu(z)
    return _rmsnorm(y, p["norm"]["scale"], cfg.norm_eps) @ p["out_proj"]


def _attention(p, x, cfg):
    """Causal GQA softmax attention with no position embedding."""
    if cfg.pos != "none":
        raise NotImplementedError("the reference covers NoPE attention")
    bsz, s, _ = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv, cfg.hd
    q = (x @ p["wq"]).reshape(bsz, s, hkv, hq // hkv, hd)
    k = (x @ p["wk"]).reshape(bsz, s, hkv, hd)
    v = (x @ p["wv"]).reshape(bsz, s, hkv, hd)
    scale = cfg.attention_multiplier or hd ** -0.5
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", q, k) * scale
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v).reshape(bsz, s, hq * hd)
    return o @ p["wo"]


def forward(params, tokens, cfg):
    """Logits (B, S, V) in float32 of a patterned stack (``layer_pattern``)
    for tokens (B, S)."""
    params = jax.tree.map(lambda t: jnp.asarray(t, F32), params)
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["table"][tokens] * cfg.embedding_multiplier
        seen = {}
        for i in range(cfg.n_layers):
            kind = cfg.layer_pattern[i % len(cfg.layer_pattern)]
            j = seen[kind] = seen.get(kind, -1) + 1
            p = jax.tree.map(lambda t: t[j], params["blocks"][kind])
            h = _rmsnorm(x, p["ln1"]["scale"], cfg.norm_eps)
            y = (_mamba(p["ssm"], h, cfg) if kind == "mamba"
                 else _attention(p["attn"], h, cfg))
            x = x + cfg.residual_multiplier * y
            if cfg.d_ff:
                h = _rmsnorm(x, p["ln2"]["scale"], cfg.norm_eps)
                f = p["ffn"]
                y = (jax.nn.silu(h @ f["w_gate"]) * (h @ f["w_in"])) @ f["w_out"]
                x = x + cfg.residual_multiplier * y
        x = _rmsnorm(x, params["final_norm"]["scale"], cfg.norm_eps)
        head = (params["embed"]["table"].T if cfg.tie_embeddings
                else params["head"])
        return (x @ head) / cfg.logits_scaling
