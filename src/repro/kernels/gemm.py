"""Pallas TPU GEMM - the MXU realization of the paper's DOT4 idea.

The paper reconfigures 4 multipliers + 3 adders into a fused multiply-reduce
(DOT4). The MXU *is* that structure scaled to a 128x128 systolic array; this
kernel expresses C = A B as MXU-tile FMAs with a per-precision VMEM
accumulator (fp32 for float32/bfloat16 operands, fp64 for float64), and
takes its tiling from :func:`repro.core.codesign.plan_gemm` - block shapes
are the pipeline-depth analogue (HBM->VMEM grid pipelining; see DESIGN.md
section 2).

Grid: (M/bm, N/bn, K/bk) with the K dimension innermost ('arbitrary'
semantics - sequential), so the accumulator scratch carries across K steps.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.codesign import GemmPlan, plan_gemm


def _gemm_kernel(a_ref, b_ref, o_ref, acc_ref, *, nk: int, acc_dtype):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=acc_dtype,
                            precision=mxu_precision(a_ref.dtype))

    @pl.when(k == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def mxu_precision(dtype) -> lax.Precision:
    """MXU precision for operands of ``dtype``: float32 (and wider)
    operands multiply at full precision, as the BLAS routines promise;
    bfloat16 operands are exact in the MXU's single pass."""
    return lax.Precision.HIGHEST if jnp.dtype(dtype).itemsize >= 4 \
        else lax.Precision.DEFAULT


def accumulator_dtype(dtype) -> jnp.dtype:
    """Per-precision accumulator width (the paper's per-pipeline depths):
    float64 operands accumulate in float64, everything narrower (float32,
    bfloat16) in float32."""
    return jnp.dtype(jnp.float64) if jnp.dtype(dtype) == jnp.float64 \
        else jnp.dtype(jnp.float32)


def vmem_limit(block_bytes: int, scratch_bytes: int = 0) -> int:
    """Scoped-VMEM limit for a kernel whose pipelined in/out blocks total
    ``block_bytes`` (one buffer each) and whose scratch is
    ``scratch_bytes``.

    Mosaic double-buffers every pipelined block, and its default scoped
    limit (16 MiB on v5e) is below the footprint of the planner's large
    tiles, which it then refuses to compile. The kernel body's own
    temporaries (the dot result, an epilogue, the substitution loop's
    values) come on top, so ask for twice the buffered footprint. What
    bounds a kernel is the chip's VMEM, which the planners' budget keeps
    the buffered footprint under.
    """
    return max(2 * (2 * block_bytes + scratch_bytes), 16 * 2 ** 20)


def gemm(a: jnp.ndarray, b: jnp.ndarray, plan: Optional[GemmPlan] = None,
         out_dtype=None, interpret: Optional[bool] = None) -> jnp.ndarray:
    """C = A @ B via the Pallas MXU kernel.

    Shapes are padded up to block multiples (model-chosen blocks are MXU
    aligned); padding contributes zeros to the accumulation.
    ``interpret`` ``None`` takes the device's mode
    (:func:`repro.kernels.ops.interpret_mode`).
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    out_dtype = out_dtype or a.dtype
    if plan is None:
        plan = plan_gemm(m, n, k, dtype_bytes=a.dtype.itemsize)
    bm, bn, bk = plan.bm, plan.bn, plan.bk
    pm, pn, pk = (-(-d // blk) * blk for d, blk in ((m, bm), (n, bn), (k, bk)))
    a_p = jnp.pad(a, ((0, pm - m), (0, pk - k))) if (pm, pk) != (m, k) else a
    b_p = jnp.pad(b, ((0, pk - k), (0, pn - n))) if (pk, pn) != (k, n) else b
    nk = pk // bk
    acc_dtype = accumulator_dtype(a.dtype)
    if interpret is None:
        from repro.kernels.ops import interpret_mode    # ops imports this
        interpret = interpret_mode()
    block_bytes = (bm * bk + bk * bn) * a.dtype.itemsize \
        + bm * bn * jnp.dtype(out_dtype).itemsize
    out = pl.pallas_call(
        functools.partial(_gemm_kernel, nk=nk, acc_dtype=acc_dtype),
        grid=(pm // bm, pn // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((pm, pn), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit(
                block_bytes, bm * bn * acc_dtype.itemsize)),
        interpret=interpret,
    )(a_p, b_p)
    return out[:m, :n]
