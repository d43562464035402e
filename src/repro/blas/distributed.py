"""Sharded multi-device BLAS: SUMMA ``pdgemm`` and ``pdtrsm`` via shard_map.

The paper's thesis - match the DAG's parallel operations to the platform's
compute/memory structure - applied across the device boundary. A 2D
``("x", "y")`` mesh turns the GEMM K reduction into the same picture as the
paper's adder pipeline: ``px * py`` parallel accumulators (one partial C
per device) fed by a serial panel stream, where the "latch overhead" is now
an inter-chip hop instead of a pipeline register.

Layout (SUMMA):

* A ``(m, k)`` is sharded ``P("x", "y")`` - rows over ``x``, the K
  dimension over ``y`` (each device column owns one coarse k-panel of A);
* B ``(k, n)`` is sharded ``P("x", "y")`` - the K dimension over ``x``,
  columns over ``y``;
* C ``(m, n)`` comes out ``P("x", "y")``, no reduction needed.

Each of the ``px * py`` steps broadcasts one fine k-panel of A along the
``y`` ring and the matching panel of B along the ``x`` ring
(:func:`repro.distributed.collectives.ring_bcast` -
``lax.ppermute``-pipelined, one panel per hop), then runs the local
``(m/px, k_f) @ (k_f, n/py)`` update through the *existing* policy
dispatcher - ``reference`` is plain jnp, ``model``/``tuned`` the Pallas MXU
kernel at the config :func:`repro.tune.dispatch.resolve` picks for op
``"pdgemm"`` (registry key carries the mesh component).
:func:`repro.core.codesign.plan_pdgemm` prices the whole schedule.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.distributed.collectives import (CollectiveRecord, emit_record,
                                           ring_bcast)

MESH_AXES = ("x", "y")


def make_blas_mesh(px: int, py: int) -> Mesh:
    """A (px, py) ``("x", "y")`` mesh over the first ``px * py`` devices."""
    import numpy as np
    devs = np.asarray(jax.devices()[: px * py]).reshape(px, py)
    return Mesh(devs, MESH_AXES)


def mesh_key(mesh: Mesh) -> str:
    """Registry mesh component for a BLAS mesh (e.g. ``"x2y4"``)."""
    return "".join(f"{a}{mesh.shape[a]}" for a in mesh.axis_names)


def _mesh_xy(mesh: Mesh):
    if tuple(mesh.axis_names) != MESH_AXES:
        raise ValueError(
            f"distributed BLAS needs a ('x', 'y') mesh; got axes "
            f"{tuple(mesh.axis_names)}")
    return mesh.shape["x"], mesh.shape["y"]


def _pad2(a: jnp.ndarray, r0: int, r1: int) -> jnp.ndarray:
    """Zero-pad a 2-D array so dims are multiples of (r0, r1)."""
    p0 = (-a.shape[0]) % r0
    p1 = (-a.shape[1]) % r1
    if p0 == 0 and p1 == 0:
        return a
    return jnp.pad(a, ((0, p0), (0, p1)))


def _local_update(ap, bp, res, interpret: Optional[bool]):
    """One SUMMA panel update on the resolved path (jnp or Pallas) - the
    exact executor every other policy-dispatched GEMM uses."""
    from repro.tune.dispatch import _gemm_exec      # lazy: avoid cycle
    return _gemm_exec(ap, bp, res, interpret)


def pdgemm(a: jnp.ndarray, b: jnp.ndarray, mesh: Mesh,
           c: Optional[jnp.ndarray] = None, alpha=1.0, beta=0.0,
           policy: Optional[str] = None, use_kernel: Optional[bool] = None,
           interpret: Optional[bool] = None, registry=None) -> jnp.ndarray:
    """C <- alpha * A B + beta * C, SUMMA-sharded over a ("x", "y") mesh.

    Parameters
    ----------
    a, b : jnp.ndarray
        Global operands, shapes ``(m, k)`` and ``(k, n)``. Any float dtype
        the single-device :func:`repro.blas.level3.gemm` accepts
        (float32/float64; bfloat16 storage). Internally zero-padded so m,
        n, k divide the mesh tiling; the pad never leaks into the output.
    mesh : jax.sharding.Mesh
        A ``("x", "y")`` mesh (see :func:`make_blas_mesh`). ``(1, 1)``
        degenerates to the single-device kernel path with zero hops.
    c : jnp.ndarray, optional
        ``(m, n)`` accumuland for the ``beta`` epilogue (applied on the
        host layout, outside shard_map, like every repro.blas epilogue).
    policy : {"reference", "model", "tuned"}, optional
        Per-step local updates run plain jnp (``reference``) or the Pallas
        MXU kernel at the config ``resolve("pdgemm", (m, n, k), ...,
        mesh=(px, py))`` picks - ``tuned`` reads the mesh-keyed registry
        entry and cold-starts to ``model``. ``use_kernel`` stays the
        deprecated boolean alias.

    Returns
    -------
    jnp.ndarray
        The global ``(m, n)`` product (sharded ``P("x", "y")`` on exit).

    Notes
    -----
    Differential oracle: ``tests/test_distributed_blas.py`` checks every
    mesh in {(1,1), (2,2), (4,2)} x policy against single-device ``gemm``
    under the shared ``dtype_tolerances``.
    """
    from repro.tune import dispatch as _tune
    px, py = _mesh_xy(mesh)
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    steps = px * py
    res = _tune.resolve("pdgemm", (m, n, k), a.dtype, policy=policy,
                        use_kernel=use_kernel, registry=registry,
                        mesh=(px, py))
    # pad so rows/cols tile the mesh and K splits into px*py equal fine
    # panels (k <= steps * kf, so K always pads to exactly steps * kf)
    kf = -(-max(k, 1) // steps)
    a_p = _pad2(a, px, steps * kf)
    b_p = _pad2(b, steps * kf, py)
    # declare the whole schedule for the static analyzer: the geometry
    # here is exactly what plan_pdgemm prices, so spmd_lint can re-derive
    # the collective term and diff it against the traced ppermutes (CC003)
    emit_record(CollectiveRecord(
        kind="pdgemm", size=steps,
        info={"m": m, "n": n, "k": k, "px": px, "py": py, "kf": kf,
              "itemsize": jnp.dtype(a.dtype).itemsize,
              "dtype": jnp.dtype(a.dtype).name}))
    inner = functools.partial(_summa_inner, px=px, py=py, kf=kf, res=res,
                              interpret=interpret)
    f = jax.shard_map(inner, mesh=mesh,
                      in_specs=(P("x", "y"), P("x", "y")),
                      out_specs=P("x", "y"), check_vma=False)
    out = f(a_p, b_p)[:m, :n]
    out = alpha * out
    if c is not None:
        out = out + beta * c
    return out


def _summa_inner(a, b, *, px: int, py: int, kf: int, res,
                 interpret: Optional[bool]):
    """Per-device SUMMA body: a (m/px, k/py) A shard holding coarse k-panel
    ``j``; b (k/px, n/py) B shard holding coarse k-panel ``i``. Fine panel
    ``g`` lives at A coarse ``g // px`` offset ``(g % px) * kf`` and B
    coarse ``g // py`` offset ``(g % py) * kf``."""
    acc = jnp.zeros((a.shape[0], b.shape[1]), a.dtype)
    for g in range(px * py):
        a_own, a_off = g // px, (g % px) * kf
        b_own, b_off = g // py, (g % py) * kf
        ap = ring_bcast(a[:, a_off:a_off + kf], "y", py, a_own)
        bp = ring_bcast(b[b_off:b_off + kf, :], "x", px, b_own)
        acc = acc + _local_update(ap, bp, res, interpret)
    return acc


def pdtrsm(a: jnp.ndarray, b: jnp.ndarray, mesh: Mesh, lower: bool = True,
           unit_diag: bool = False, left: bool = True,
           block: Optional[int] = None, policy: Optional[str] = None,
           use_kernel: Optional[bool] = None, interpret: Optional[bool] = None,
           registry=None) -> jnp.ndarray:
    """Solve op(T) X = B with the right-hand sides sharded over the mesh.

    The substitution chain down T's diagonal is the serial hazard the paper
    cannot parallelize; the RHS columns are the embarrassingly parallel
    axis. So T ``(n, n)`` is replicated and B's columns are sharded over
    the flattened ``("x", "y")`` mesh: every device runs the *blocked*
    single-device :func:`repro.blas.level3.trsm` (policy-dispatched, so
    its off-diagonal GEMMs ride the Pallas path) on its column slab.

    Parameters
    ----------
    a : (n, n) triangular matrix; b : (n, nrhs) RHS (1-D b is treated as
    one column). ``left=False`` solves X op(T) = B by the usual transpose
    identity. ``block``/``policy`` are forwarded to the local trsm.

    Returns
    -------
    jnp.ndarray
        X with B's shape.

    Notes
    -----
    Oracle: ``tests/test_distributed_blas.py`` vs single-device ``trsm``.
    """
    if not left:
        return pdtrsm(a.T, b.T, mesh, lower=not lower, unit_diag=unit_diag,
                      left=True, block=block, policy=policy,
                      use_kernel=use_kernel, interpret=interpret,
                      registry=registry).T
    from repro.blas.level3 import trsm
    px, py = _mesh_xy(mesh)
    ndev = px * py
    vec = b.ndim == 1
    rhs = b[:, None] if vec else b
    nrhs = rhs.shape[1]
    rhs_p = _pad2(rhs, 1, ndev)                     # zero cols solve to zero

    def inner(t, r):
        return trsm(t, r, lower=lower, unit_diag=unit_diag, left=True,
                    block=block, policy=policy, use_kernel=use_kernel,
                    interpret=interpret, registry=registry)

    f = jax.shard_map(inner, mesh=mesh,
                      in_specs=(P(None, None), P(None, ("x", "y"))),
                      out_specs=P(None, ("x", "y")), check_vma=False)
    x = f(a, rhs_p)[:, :nrhs]
    return x[:, 0] if vec else x
