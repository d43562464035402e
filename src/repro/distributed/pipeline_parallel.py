"""Pipeline parallelism: collective-permute microbatch schedule over a
"stage" mesh axis via shard_map.

The assigned 40-cell dry-run mesh is DP x TP per the task; PP is provided as
a first-class framework feature with its own tests/example (DESIGN.md
section 5): a GPipe-style fill-drain schedule in which stage s computes
microbatch t - s while activations hop stages through collective-permute
(the 1F1B ordering falls out of the skewed schedule; with forward-only
steady state each stage is busy every tick after fill).

``stage_fn(params_local, x)`` is the per-stage computation; params are
stacked on a leading stage dim and sharded P("stage", ...).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_forward(stage_fn: Callable, mesh: Mesh,
                     stage_axis: str = "stage"):
    """Builds run(params_stacked, x_micro) -> y_micro.

    params_stacked: pytree with leading dim = n_stages (sharded over
    ``stage_axis``). x_micro: (M, B, ...) microbatches, replicated.
    Returns (M, B, ...) outputs of the last stage, broadcast to all stages.
    """
    n_stages = mesh.shape[stage_axis]
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def inner(params, x):
        params = jax.tree.map(lambda t: t[0], params)    # local stage params
        sid = lax.axis_index(stage_axis)
        m = x.shape[0]
        ticks = m + n_stages - 1
        buf = jnp.zeros_like(x)                          # output collector
        carry = jnp.zeros_like(x[0])                     # inter-stage wire

        def tick(t, acc):
            carry, buf = acc
            # stage 0 injects microbatch t; others take the permuted wire
            inject = x[jnp.minimum(t, m - 1)]
            xin = jnp.where(sid == 0, inject, carry)
            yout = stage_fn(params, xin)
            # last stage records its result for microbatch t - (S-1)
            slot = t - (n_stages - 1)
            ok = (sid == n_stages - 1) & (slot >= 0)
            buf = lax.cond(
                ok,
                lambda b: lax.dynamic_update_index_in_dim(
                    b, yout, jnp.maximum(slot, 0), 0),
                lambda b: b, buf)
            carry = lax.ppermute(yout, stage_axis, perm)
            return (carry, buf)

        _, buf = lax.fori_loop(0, ticks, tick, (carry, buf))
        # broadcast the last stage's collected outputs to every stage
        last = n_stages - 1
        buf = lax.psum(jnp.where(sid == last, buf, jnp.zeros_like(buf)),
                       stage_axis)
        return buf

    # P(stage_axis) is a pytree *prefix*: applies to every params leaf
    return jax.shard_map(inner, mesh=mesh, in_specs=(P(stage_axis), P()),
                         out_specs=P(), check_vma=False)


def stack_stage_params(per_stage_params):
    """[stage0_params, stage1_params, ...] -> stacked pytree (S, ...)."""
    return jax.tree.map(lambda *ls: jnp.stack(ls), *per_stage_params)
