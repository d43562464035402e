"""Pallas level-1 fused multiply-reduce - the paper's ddot, codesigned.

This is the kernel where the paper's analysis is most literal. A dot product
is n independent multiplies feeding a reduction whose *schedule* decides the
adder-pipe hazards (section 4.1, fig. 5). On the TPU VPU, a single running
sum exposes the FP-add latency on every element; U parallel partial
accumulators fill the latency window exactly like U pipeline slots
(DESIGN.md section 2, row 1).

The kernel keeps a (U, 128) fp32 accumulator tile in VMEM; each grid step
streams a (U, 128)-shaped chunk of x*y into it elementwise (one VPU FMA per
lane - 128*U independent chains). The final combine (sum over the tile) is
the paper's small post-loop reduction tree. U comes from
``codesign.optimal_accumulators`` - eq. 3 applied to the VPU.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.codesign import LANE, optimal_accumulators


def _dotp_kernel(x_ref, y_ref, o_ref, acc_ref, *, nsteps: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += (x_ref[...].astype(jnp.float32)
                     * y_ref[...].astype(jnp.float32))

    @pl.when(i == nsteps - 1)
    def _flush():
        o_ref[...] = acc_ref[...]


def dotp(x: jnp.ndarray, y: jnp.ndarray, accumulators: Optional[int] = None,
         interpret: Optional[bool] = None) -> jnp.ndarray:
    """<x, y> with a U-accumulator streaming schedule; returns fp32 scalar.
    ``interpret`` ``None`` takes the device's mode
    (:func:`repro.kernels.ops.interpret_mode`)."""
    assert x.shape == y.shape and x.ndim == 1
    n = x.shape[0]
    u = accumulators or optimal_accumulators(n)
    width = u * LANE
    pad = (-n) % width
    if pad:
        x = jnp.pad(x, (0, pad))
        y = jnp.pad(y, (0, pad))
    nsteps = (n + pad) // width
    if interpret is None:
        from repro.kernels.ops import interpret_mode    # ops imports this
        interpret = interpret_mode()
    xs = x.reshape(nsteps, u, LANE)
    ys = y.reshape(nsteps, u, LANE)
    partials = pl.pallas_call(
        functools.partial(_dotp_kernel, nsteps=nsteps),
        grid=(nsteps,),
        in_specs=[pl.BlockSpec((1, u, LANE), lambda i: (i, 0, 0)),
                  pl.BlockSpec((1, u, LANE), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((1, u, LANE), lambda i: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, u, LANE), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, u, LANE), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(xs, ys)
    # the paper's final combine tree over the U*LANE partials
    return jnp.sum(partials)
