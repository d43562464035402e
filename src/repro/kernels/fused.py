"""Streaming Pallas kernel chains - FBLAS-style epilogue/stage fusion.

FBLAS (1907.07929) composes BLAS stages as streams so intermediates never
round-trip through off-chip memory; the paper's PE wins rest on the same
locality (keep the fused multiply-reduce pipeline fed from local storage).
This module is that idea on the Pallas path:

``gemm_bias_act``
    C = act(A B + bias) in one kernel: the epilogue runs on the VMEM
    accumulator block at flush time, so C is written to HBM exactly once
    (the staged path writes A B, then re-reads and re-writes it).

``trsm_gemm``
    The blocked factorizations' trailing pair as one kernel: the panel
    solve X = L11^{-1} AP lands in a VMEM scratch and the trailing GEMM
    row-blocks consume it from there - X reaches HBM only as an output,
    never as a GEMM input. ``form="lu"`` computes C - B X (getrf),
    ``form="syrk"`` computes C - X^T X (potrf).

Whether fusing pays is priced by
:func:`repro.core.codesign.plan_fused_chain` and decided by
:func:`repro.tune.dispatch.resolve` under the ``"gemm+epilogue"`` /
``"trsm+gemm"`` ops; fused launches are annotated with the modeled
``hbm_bytes_saved`` via :func:`fused_span` so traces show the streaming
win. Differential oracle: ``tests/test_fusion.py``.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs as _obs
from repro.core.codesign import GemmPlan, plan_fused_chain, plan_gemm
from repro.kernels.gemm import accumulator_dtype, mxu_precision, vmem_limit

EPILOGUES = ("none", "relu", "gelu")


def apply_epilogue(x, epilogue: str, bias=None):
    """The one shared epilogue definition: bias add (broadcast over rows),
    then the activation. Used inside the fused kernel, by the staged
    kernel chain, and by the jnp reference path, so all three agree up to
    accumulation order."""
    if epilogue not in EPILOGUES:
        raise ValueError(f"unknown epilogue {epilogue!r}; "
                         f"expected one of {EPILOGUES}")
    if bias is not None:
        x = x + bias
    if epilogue == "relu":
        x = jnp.maximum(x, jnp.zeros_like(x))
    elif epilogue == "gelu":
        x = jax.nn.gelu(x, approximate=True)
    return x


def fused_span(name: str, chain, **attrs):
    """An obs span for one fused launch, carrying the chain plan's saved
    HBM bytes (the quantity the streaming composition exists to delete)."""
    return _obs.span("fused." + name, cat="fused",
                     hbm_bytes_saved=chain.hbm_bytes_saved,
                     fused_hbm_bytes=chain.fused_hbm_bytes,
                     unfused_hbm_bytes=chain.unfused_hbm_bytes, **attrs)


# ------------------------------ gemm + epilogue ------------------------------

def _gemm_epilogue_kernel(*refs, nk: int, acc_dtype, epilogue: str,
                          has_bias: bool):
    if has_bias:
        a_ref, b_ref, bias_ref, o_ref, acc_ref = refs
    else:
        a_ref, b_ref, o_ref, acc_ref = refs
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=acc_dtype,
                            precision=mxu_precision(a_ref.dtype))

    @pl.when(k == nk - 1)
    def _flush():
        acc = acc_ref[...]
        bias = bias_ref[...].astype(acc_dtype) if has_bias else None
        o_ref[...] = apply_epilogue(acc, epilogue, bias).astype(o_ref.dtype)


def gemm_bias_act(a: jnp.ndarray, b: jnp.ndarray,
                  bias: Optional[jnp.ndarray] = None,
                  epilogue: str = "none", plan: Optional[GemmPlan] = None,
                  out_dtype=None,
                  interpret: Optional[bool] = None) -> jnp.ndarray:
    """C = act(A @ B + bias) in one Pallas launch.

    Same grid/tiling contract as :func:`repro.kernels.gemm.gemm` (the
    epilogue costs no extra HBM traffic beyond the optional bias stream);
    ``bias`` is a length-n vector broadcast over rows, applied in the
    accumulator dtype at flush time.
    """
    if epilogue not in EPILOGUES:
        raise ValueError(f"unknown epilogue {epilogue!r}; "
                         f"expected one of {EPILOGUES}")
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
        from repro.kernels.ops import interpret_mode    # ops imports gemm
        interpret = interpret_mode()
    has_bias = bias is not None
    block_bytes = (bm * bk + bk * bn) * a.dtype.itemsize \
        + bm * bn * jnp.dtype(out_dtype).itemsize
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
    ]
    operands = [a_p, b_p]
    if has_bias:
        bias_p = jnp.pad(jnp.asarray(bias).reshape(1, -1),
                         ((0, 0), (0, pn - n)))
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)))
        operands.append(bias_p)
        block_bytes += bn * bias_p.dtype.itemsize
    out = pl.pallas_call(
        functools.partial(_gemm_epilogue_kernel, nk=nk, acc_dtype=acc_dtype,
                          epilogue=epilogue, has_bias=has_bias),
        grid=(pm // bm, pn // bn, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((pm, pn), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit(
                block_bytes, bm * bn * acc_dtype.itemsize)),
        interpret=interpret,
    )(*operands)
    return out[:m, :n]


# -------------------------------- trsm -> gemm -------------------------------

def _trsm_gemm_kernel(*refs, form: str, unit_diag: bool, pnb: int,
                      bm: int, acc_dtype):
    if form == "lu":
        l_ref, ap_ref, bl_ref, c_ref, x_ref, o_ref, xs_ref = refs
    else:
        l_ref, ap_ref, c_ref, x_ref, o_ref, xs_ref = refs
    i = pl.program_id(0)
    prec = mxu_precision(c_ref.dtype)

    @pl.when(i == 0)
    def _solve():
        # forward substitution on values (not refs): row r of X depends on
        # rows < r, extracted with one-hot reductions so the loop carries a
        # dense (pnb, n) accumulator - the serial divider chain of
        # level3._trsm_unblocked, run once in VMEM at accumulator width.
        # Every value stays 2-D: Mosaic lowers no 1-D dot.
        l = l_ref[...].astype(acc_dtype)
        ap = ap_ref[...].astype(acc_dtype)
        ii = lax.broadcasted_iota(jnp.int32, l.shape, 0)
        jj = lax.broadcasted_iota(jnp.int32, l.shape, 1)
        strict = jnp.where(jj < ii, l, jnp.zeros_like(l))
        rows = lax.broadcasted_iota(jnp.int32, ap.shape, 0)

        def body(r, x):
            row_mask = (rows == r)                      # one-hot row of AP
            rhs = jnp.sum(jnp.where(row_mask, ap, jnp.zeros_like(ap)),
                          axis=0, keepdims=True)        # (1, pn)
            lrow = jnp.sum(jnp.where(ii == r, strict, jnp.zeros_like(strict)),
                           axis=0, keepdims=True)       # (1, pnb)
            s = rhs - jnp.dot(lrow, x, preferred_element_type=acc_dtype,
                              precision=prec)
            if not unit_diag:
                dk = jnp.sum(jnp.where((ii == r) & (jj == r), l,
                                       jnp.zeros_like(l)),
                             axis=(0, 1), keepdims=True)  # (1, 1)
                s = s / dk
            return x + row_mask.astype(acc_dtype) * s

        x = lax.fori_loop(0, pnb, body, jnp.zeros(ap.shape, acc_dtype))
        xs_ref[...] = x
        x_ref[...] = x.astype(x_ref.dtype)

    if form == "lu":
        upd = jnp.dot(bl_ref[...].astype(acc_dtype), xs_ref[...],
                      preferred_element_type=acc_dtype, precision=prec)
    else:
        # this step's column block of X, read from the scratch ref
        col0 = pl.multiple_of(i * bm, bm)
        xi = xs_ref[:, pl.ds(col0, bm)]
        upd = lax.dot_general(xi, xs_ref[...], (((0,), (0,)), ((), ())),
                              preferred_element_type=acc_dtype,
                              precision=prec)
    o_ref[...] = (c_ref[...].astype(acc_dtype) - upd).astype(o_ref.dtype)


def trsm_gemm(l11: jnp.ndarray, a_panel: jnp.ndarray,
              b_left: Optional[jnp.ndarray], c: jnp.ndarray,
              form: str = "lu", unit_diag: bool = False,
              row_block: Optional[int] = None,
              interpret: Optional[bool] = None
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused X = L11^{-1} AP then C -= (B X | X^T X), one Pallas launch.

    Parameters
    ----------
    l11 : (nb, nb) lower-triangular panel diagonal.
    a_panel : (nb, n) right-hand sides AP.
    b_left : (m, nb) left GEMM operand for ``form="lu"``; ``None`` (and
        m == n) for ``form="syrk"``, which reuses X as both operands.
    c : (m, n) trailing block to update.
    row_block : row-block height of the GEMM stage (the chain plan's
        ``block``); the solve itself is not tiled - X stays resident.

    Returns
    -------
    (x, c_out) : the panel solve (nb, n) and the updated trailing block -
    the two arrays the blocked driver writes back.

    Notes
    -----
    The grid is 1-D over C's row blocks with ``arbitrary`` semantics: step
    0 runs the substitution scan into a VMEM scratch, every step reads X
    from that scratch, so X never transits HBM between the stages.
    Padding: nb rows pad with an identity diagonal (solved rows of the
    padding are zero), n columns pad with zeros.
    """
    if form not in ("lu", "syrk"):
        raise ValueError(f"unknown trsm+gemm form {form!r}; "
                         f"expected 'lu' or 'syrk'")
    nb = l11.shape[0]
    n = a_panel.shape[1]
    m = c.shape[0]
    assert a_panel.shape[0] == nb and c.shape[1] == n
    if form == "syrk":
        assert b_left is None and m == n, (m, n)
    else:
        assert b_left is not None and b_left.shape == (m, nb)
    dtype = c.dtype
    acc_dtype = accumulator_dtype(dtype)
    if interpret is None:
        from repro.kernels.ops import interpret_mode    # ops imports gemm
        interpret = interpret_mode()
    pnb = -(-nb // 8) * 8
    pn = -(-n // 128) * 128
    if form == "syrk":
        # row blocks must tile the padded (pn, pn) output
        bm = row_block if row_block and pn % row_block == 0 else 128
        pm = pn
    else:
        bm = min(row_block or 128, -(-m // 8) * 8)
        pm = -(-m // bm) * bm
    l_p = jnp.pad(l11, ((0, pnb - nb), (0, pnb - nb)))
    if pnb != nb:
        # unit diagonal on the padding keeps the padded solve rows zero
        # (and the division NaN-free)
        l_p = l_p + jnp.diag((jnp.arange(pnb) >= nb).astype(dtype))
    ap_p = jnp.pad(a_panel, ((0, pnb - nb), (0, pn - n)))
    c_p = jnp.pad(c, ((0, pm - m), (0, pn - n)))
    in_specs = [
        pl.BlockSpec((pnb, pnb), lambda i: (0, 0)),
        pl.BlockSpec((pnb, pn), lambda i: (0, 0)),
    ]
    operands = [l_p, ap_p]
    if form == "lu":
        in_specs.append(pl.BlockSpec((bm, pnb), lambda i: (i, 0)))
        operands.append(jnp.pad(b_left, ((0, pm - m), (0, pnb - nb))))
    in_specs.append(pl.BlockSpec((bm, pn), lambda i: (i, 0)))
    operands.append(c_p)
    db = jnp.dtype(dtype).itemsize
    # L11, AP, the X output and the C in/out row blocks (+ B's for "lu")
    block_bytes = (pnb * pnb + 2 * pnb * pn + 2 * bm * pn
                   + (bm * pnb if form == "lu" else 0)) * db
    x_out, c_out = pl.pallas_call(
        functools.partial(_trsm_gemm_kernel, form=form, unit_diag=unit_diag,
                          pnb=pnb, bm=bm, acc_dtype=acc_dtype),
        grid=(pm // bm,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((pnb, pn), lambda i: (0, 0)),
            pl.BlockSpec((bm, pn), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((pnb, pn), dtype),
            jax.ShapeDtypeStruct((pm, pn), dtype),
        ],
        scratch_shapes=[pltpu.VMEM((pnb, pn), acc_dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_limit(
                block_bytes, pnb * pn * acc_dtype.itemsize)),
        interpret=interpret,
    )(*operands)
    return x_out[:nb, :n], c_out[:m, :n]
