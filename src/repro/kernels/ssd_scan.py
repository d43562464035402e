"""Pallas Mamba-2 SSD chunked scan.

The state-space recurrence h_t = a_t h_{t-1} + x_t (x) B_t is the paper's
serial hazard chain in its purest form: every step depends on the last. The
SSD (state-space duality) chunking is exactly the paper's remedy applied at
algorithm level - convert most of the chain into parallel within-chunk work
(a masked-decay "attention" matrix on the MXU) and keep only one serial
dependence per chunk. Chunk size from :func:`repro.core.codesign.plan_ssd`
balances the c^2 within-chunk term against the seq/c serial chain - the
busy/non-busy split of eq. 1.

Layout (pre-arranged by ops.ssd): x (B, H, L, P), a_log (B, H, L),
B/C (B, H, L, N). Grid (B, H, L/c), chunk dim sequential; fp32 (P, N) state
carried in VMEM scratch across chunks, and written out after the last chunk
when the caller asks for it (a prefill hands it to decode).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.codesign import plan_ssd


def _ssd_kernel(x_ref, a_ref, b_ref, c_ref, y_ref, *rest, chunk: int,
                nc: int):
    *final_ref, state_ref = rest
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    al = a_ref[0, 0].astype(jnp.float32)                 # (1, c) row
    x = x_ref[0, 0].astype(jnp.float32)                  # (c, P)
    Bm = b_ref[0, 0].astype(jnp.float32)                 # (c, N)
    Cm = c_ref[0, 0].astype(jnp.float32)                 # (c, N)
    t_io = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_io = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lower = t_io >= s_io
    zero = jnp.zeros((chunk, chunk), jnp.float32)
    # cumulative log-decay as a column (over t) and as a row (over s),
    # from masked reductions of the broadcast row: Mosaic lowers neither
    # 1-D values nor a cumsum
    a_ts = jnp.broadcast_to(al, (chunk, chunk))          # [t, s] = a_s
    cum = jnp.sum(jnp.where(lower, a_ts, zero), axis=1, keepdims=True)
    a_col = jnp.sum(jnp.where(t_io == s_io, a_ts, zero), axis=1,
                    keepdims=True)                       # (c, 1)
    cum_row = jnp.sum(jnp.where(t_io <= s_io, a_col, zero), axis=0,
                      keepdims=True)                     # (1, c)
    total = jnp.sum(al, axis=1, keepdims=True)           # (1, 1)
    seg = jnp.exp(cum)                                   # decay since entry
    # mask before exp (upper-triangle diffs are positive -> overflow)
    diff = cum - cum_row
    Lmat = jnp.exp(jnp.where(lower, diff, -jnp.inf))
    # within-chunk (parallel, MXU): masked-decay attention
    scores = lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32) * Lmat
    y = lax.dot(scores, x, preferred_element_type=jnp.float32)   # (c, P)
    # cross-chunk (the one serial hazard): contribution of carried state
    state = state_ref[...]                               # (P, N)
    y = y + lax.dot_general(Cm * seg, state,
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    dout = jnp.exp(total - cum)                          # (c, 1)
    state_ref[...] = (jnp.exp(total) * state
                      + lax.dot_general(x, Bm * dout,
                                        (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32))
    y_ref[0, 0] = y.astype(y_ref.dtype)

    if final_ref:
        @pl.when(i == nc - 1)
        def _final():
            final_ref[0][0, 0] = state_ref[...]


def ssd_scan(x: jnp.ndarray, a_log: jnp.ndarray, B: jnp.ndarray,
             C: jnp.ndarray, chunk: int | None = None,
             interpret: Optional[bool] = None, return_state: bool = False):
    """Chunked SSD over (B, H, L, ...) layout; returns y (B, H, L, P), and
    with ``return_state`` also the final state (B, H, P, N) in float32.

    ``a_log`` streams as one (1, chunk) row per grid step, so on a TPU the
    chunk is a multiple of 128 or spans the whole (padded) sequence.
    ``interpret`` ``None`` takes the device's mode
    (:func:`repro.kernels.ops.interpret_mode`).
    """
    if 0 in x.shape or 0 in a_log.shape or 0 in B.shape or 0 in C.shape:
        # zero-dim operands cannot tile a Pallas grid (rule KL004): empty
        # batch/head/length/feature axes make y empty, and an empty state
        # axis N zeroes every contribution - jnp zeros of x's shape is
        # the exact answer either way
        y = jnp.zeros(x.shape, x.dtype)
        if return_state:
            return y, jnp.zeros(x.shape[:2] + (x.shape[-1], B.shape[-1]),
                                jnp.float32)
        return y
    bsz, h, L, p = x.shape
    n = B.shape[-1]
    if chunk is None:
        chunk = plan_ssd(L, h, p, n).chunk
    chunk = min(chunk, max(L, 8))
    pad = (-L) % chunk
    if pad:  # a_log pads with 0 (decay 1): state passes through untouched
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
        a_log = jnp.pad(a_log, ((0, 0), (0, 0), (0, pad)))
        B = jnp.pad(B, ((0, 0), (0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, 0), (0, pad), (0, 0)))
    nc = (L + pad) // chunk
    if interpret is None:
        from repro.kernels.ops import interpret_mode    # ops imports this
        interpret = interpret_mode()
    a_rows = a_log[:, :, None, :]                        # (B, H, 1, L)
    out_specs = [pl.BlockSpec((1, 1, chunk, p),
                              lambda b_, h_, i: (b_, h_, i, 0))]
    out_shape = [jax.ShapeDtypeStruct((bsz, h, L + pad, p), x.dtype)]
    if return_state:    # one (P, N) block per (batch, head), resident
        out_specs.append(pl.BlockSpec((1, 1, p, n),
                                      lambda b_, h_, i: (b_, h_, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((bsz, h, p, n), jnp.float32))
    out = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk, nc=nc),
        grid=(bsz, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda b_, h_, i: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda b_, h_, i: (b_, h_, 0, i)),
            pl.BlockSpec((1, 1, chunk, n), lambda b_, h_, i: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, chunk, n), lambda b_, h_, i: (b_, h_, i, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, a_rows, B, C)
    if return_state:
        return out[0][:, :, :L], out[1]
    return out[0][:, :, :L]
