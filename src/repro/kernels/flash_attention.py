"""Pallas streaming-softmax attention (prefill + decode).

Attention's inner loop is the paper's dependent-reduction pattern at scale:
the online-softmax running triple (m, l, acc) is a serial chain across KV
blocks - a hazard per block - while everything inside a block is parallel.
Block sizes come from :func:`repro.core.codesign.plan_attention`: bigger
``block_k`` means fewer serial rescales (fewer hazards) at higher VMEM cost,
the exact eq.-2 trade-off.

Layout: q (B, Hq, Sq, D), k/v (B, Hkv, Sk, D), GQA via Hq = g * Hkv.
Grid (B, Hq, Sq/bq, Sk/bk), KV innermost (sequential) so the fp32 running
state lives in VMEM scratch across KV steps.

Supports causal masking with an absolute ``q_offset`` (decode: Sk - Sq),
sliding windows, and KV-length masking for padded caches.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.codesign import LANE, plan_attention

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 scale: float, causal: bool, q_offset: int, kv_len: int,
                 window: Optional[int], block_q: int, block_k: int,
                 nk: int):
    kk = pl.program_id(3)

    @pl.when(kk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                  # (bq, D)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bk, D)
    v = v_ref[0, 0].astype(jnp.float32)                  # (bk, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    i = pl.program_id(2)
    qpos = (i * block_q + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            + q_offset)
    kpos = (kk * block_k
            + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1))
    mask = kpos < kv_len
    if causal:
        mask &= qpos >= kpos
    if window is not None:
        mask &= (qpos - kpos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[:, :1]                                # (bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                               # (bq, bk)
    alpha = jnp.exp(m_prev - m_new)                      # (bq, 1)
    l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(kk == nk - 1)
    def _flush():
        l = l_ref[:, :1]
        safe = jnp.where(l > 0, l, 1.0)
        o_ref[0, 0] = (acc_ref[...] / safe).astype(o_ref.dtype)


def attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
              causal: bool = True, scale: Optional[float] = None,
              q_offset: int = 0, window: Optional[int] = None,
              kv_len: Optional[int] = None,
              block_q: Optional[int] = None, block_k: Optional[int] = None,
              interpret: Optional[bool] = None) -> jnp.ndarray:
    """Flash attention; see module docstring for layout. Returns q-shaped.
    ``interpret`` ``None`` takes the device's mode
    (:func:`repro.kernels.ops.interpret_mode`)."""
    if 0 in q.shape or 0 in k.shape or 0 in v.shape:
        # zero-dim operands cannot tile a Pallas grid (rule KL004): an
        # empty batch/head/query/feature axis makes the output empty, and
        # an empty KV axis leaves every denominator at the kernel's
        # safe-divide zero - jnp zeros of q's shape is exact either way
        return jnp.zeros(q.shape, q.dtype)
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    group = hq // hkv
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    kv_len = kv_len if kv_len is not None else sk
    plan = plan_attention(sq, sk, d)
    bq = block_q or min(plan.block_q, max(8, sq))
    bk = block_k or min(plan.block_k, max(LANE, sk))
    bq = max(8, min(bq, -(-sq // 8) * 8))
    pq, pk_ = (-(-sq // bq) * bq, -(-sk // bk) * bk)
    if pq != sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq - sq), (0, 0)))
    if pk_ != sk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk_ - sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk_ - sk), (0, 0)))
    nk = pk_ // bk
    if interpret is None:
        from repro.kernels.ops import interpret_mode    # ops imports this
        interpret = interpret_mode()
    out = pl.pallas_call(
        functools.partial(_attn_kernel, scale=scale, causal=causal,
                          q_offset=q_offset, kv_len=kv_len, window=window,
                          block_q=bq, block_k=bk, nk=nk),
        grid=(b, hq, pq // bq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, i, kk: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h, i, kk: (b_, h // group, kk, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h, i, kk: (b_, h // group, kk, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b_, h, i, kk: (b_, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, pq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, LANE), jnp.float32),   # running max m
            pltpu.VMEM((bq, LANE), jnp.float32),   # running denom l
            pltpu.VMEM((bq, d), jnp.float32),      # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :sq]
