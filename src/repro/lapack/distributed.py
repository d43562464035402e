"""Mesh-parallel batched LAPACK: shard the batch axis, reuse the blocked
drivers per shard.

The batched workload (many independent factorizations) has no cross-item
dependence at all, so the mesh mapping is pure data parallelism: the batch
axis is sharded over every mesh axis, and each device runs the *same*
vmapped blocked driver (:mod:`repro.lapack.batched`) on its slab - panel
hazard chains in lockstep locally, trailing updates on the policy-dispatched
Pallas GEMM path, zero collectives. This is the scaling layer between the
single-device batched drivers (PR 1) and the SUMMA kernels of
:mod:`repro.blas.distributed`: factor on the mesh, solve on the mesh, and
the per-shard kernel configs still resolve through ``repro.tune``.

Batches that do not divide the device count are padded with identity
matrices (SPD, invertible - safe for every factorization kind) and the pad
is sliced off the result, so any batch size runs on any mesh.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.distributed.collectives import CollectiveRecord, emit_record
from repro.lapack import batched as _batched
from repro.lapack.batched import FactorizationResult, _resolve_block
from repro.tune.policy import resolve_policy


def _mesh_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(mesh.axis_names)


def _ndev(mesh: Mesh) -> int:
    n = 1
    for a in mesh.axis_names:
        n *= mesh.shape[a]
    return n


def _pad_batch(a: jnp.ndarray, ndev: int) -> Tuple[jnp.ndarray, int]:
    """Pad the (B, m, n) batch to a device-count multiple with identities."""
    b = a.shape[0]
    pad = (-b) % ndev
    # declare the pad for spmd_lint's SH002 discipline check: identity
    # filler (factorizable), minimal, and device-count divisible
    emit_record(CollectiveRecord(
        kind="pad_batch", size=ndev,
        info={"batch": b, "pad": pad, "identity": True}))
    if pad == 0:
        return a, b
    eye = jnp.broadcast_to(jnp.eye(a.shape[1], a.shape[2], dtype=a.dtype),
                           (pad, a.shape[1], a.shape[2]))
    return jnp.concatenate([a, eye], axis=0), b


def _shard_batched(mesh: Mesh, fn, a: jnp.ndarray, n_out: int):
    """Run ``fn`` (local batch -> tuple of per-item arrays) on the
    batch-sharded ``a``; returns the tuple with the pad still attached."""
    axes = _mesh_axes(mesh)
    spec = P(axes)                              # batch axis only; rest open
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec,),
                         out_specs=tuple(spec for _ in range(n_out)),
                         check_vma=False)(a)


def batched_potrf(a: jnp.ndarray, mesh: Mesh, block: Optional[int] = None,
                  policy: Optional[str] = None,
                  registry=None) -> FactorizationResult:
    """Cholesky of a (B, n, n) SPD batch, batch-sharded over ``mesh``.

    Parameters
    ----------
    a : (B, n, n) SPD batch (float32/float64).
    mesh : any jax Mesh; the batch is sharded over all its axes flattened.
    block, policy : forwarded to the per-shard
        :func:`repro.lapack.batched.batched_potrf` - the trailing updates
        of every local factorization resolve their kernel configs through
        ``repro.tune`` exactly as on one device.

    Returns
    -------
    FactorizationResult
        Same pytree as the single-device driver (kind "potrf"); factors
        hold L per batch item.

    Notes
    -----
    Oracle: ``tests/test_distributed_blas.py`` - bitwise-comparable to
    single-device ``batched_potrf`` under ``dtype_tolerances`` on every
    mesh in {(1,1), (2,2), (4,2)}.
    """
    assert a.ndim == 3 and a.shape[1] == a.shape[2], a.shape
    pol = resolve_policy(policy)
    nb = _resolve_block(a.shape[1], block, "potrf", a.dtype)
    a_p, b0 = _pad_batch(a, _ndev(mesh))

    def local(x):
        return (_batched.batched_potrf(x, block=nb, policy=pol,
                                       registry=registry).factors,)

    (factors,) = _shard_batched(mesh, local, a_p, 1)
    return FactorizationResult(factors[:b0], None, None, "potrf", nb)


def batched_getrf(a: jnp.ndarray, mesh: Mesh, block: Optional[int] = None,
                  policy: Optional[str] = None,
                  registry=None) -> FactorizationResult:
    """LU with partial pivoting of a (B, m, n) batch, batch-sharded.

    Shape/dtype/policy contract matches
    :func:`repro.lapack.batched.batched_getrf`; pivots come back (B, k)
    int32 in LAPACK ipiv convention. Oracle:
    ``tests/test_distributed_blas.py``.
    """
    assert a.ndim == 3, a.shape
    pol = resolve_policy(policy)
    nb = _resolve_block(min(a.shape[1], a.shape[2]), block, "getrf", a.dtype)
    a_p, b0 = _pad_batch(a, _ndev(mesh))

    def local(x):
        r = _batched.batched_getrf(x, block=nb, policy=pol,
                                   registry=registry)
        return r.factors, r.pivots

    factors, piv = _shard_batched(mesh, local, a_p, 2)
    return FactorizationResult(factors[:b0], piv[:b0], None, "getrf", nb)


def batched_geqrf(a: jnp.ndarray, mesh: Mesh, block: Optional[int] = None,
                  policy: Optional[str] = None,
                  registry=None) -> FactorizationResult:
    """Householder QR of a (B, m, n) batch, batch-sharded.

    Contract matches :func:`repro.lapack.batched.batched_geqrf` (packed
    R/V factors + tau). Oracle: ``tests/test_distributed_blas.py``.
    """
    assert a.ndim == 3, a.shape
    pol = resolve_policy(policy)
    nb = _resolve_block(min(a.shape[1], a.shape[2]), block, "geqrf", a.dtype)
    a_p, b0 = _pad_batch(a, _ndev(mesh))

    def local(x):
        r = _batched.batched_geqrf(x, block=nb, policy=pol,
                                   registry=registry)
        return r.factors, r.tau

    factors, tau = _shard_batched(mesh, local, a_p, 2)
    return FactorizationResult(factors[:b0], None, tau[:b0], "geqrf", nb)


def batched_solve(res: FactorizationResult, b: jnp.ndarray, mesh: Mesh,
                  policy: Optional[str] = None,
                  registry=None) -> jnp.ndarray:
    """Solve A_i x_i = b_i for a batch-sharded FactorizationResult.

    ``res`` is a result of any driver in this module (or the single-device
    ones - the pytrees are identical); ``b`` is (B, n) or (B, n, k). The
    factors, pivot/tau metadata, and RHS are sharded on the batch axis and
    every device runs :func:`repro.lapack.batched.batched_solve` on its
    slab, so the triangular solves thread the same policy as the
    factorization did. Identity-padded batch items solve against a zero
    RHS and are sliced off.

    Oracle: ``tests/test_distributed_blas.py`` (factor + solve round-trip
    vs the single-device path under ``dtype_tolerances``).
    """
    pol = resolve_policy(policy)
    ndev = _ndev(mesh)
    axes = _mesh_axes(mesh)
    b0 = res.factors.shape[0]
    pad = (-b0) % ndev
    vec = b.ndim == 2
    rhs = b[:, :, None] if vec else b
    emit_record(CollectiveRecord(
        kind="pad_batch", size=ndev,
        info={"batch": b0, "pad": pad, "identity": True}))
    if pad:
        m_f, n_f = res.factors.shape[1], res.factors.shape[2]
        eye = jnp.broadcast_to(
            jnp.eye(m_f, n_f, dtype=res.factors.dtype), (pad, m_f, n_f))
        factors = jnp.concatenate([res.factors, eye], axis=0)
        rhs = jnp.concatenate(
            [rhs, jnp.zeros((pad,) + rhs.shape[1:], rhs.dtype)], axis=0)
    else:
        factors = res.factors

    def _pad_meta(x, fill):
        if x is None or pad == 0:
            return x
        return jnp.concatenate(
            [x, jnp.broadcast_to(fill, (pad,) + x.shape[1:])], axis=0)

    piv = _pad_meta(res.pivots,
                    jnp.arange(res.pivots.shape[1], dtype=res.pivots.dtype)
                    if res.pivots is not None else None)
    tau = _pad_meta(res.tau, jnp.zeros((), res.factors.dtype)
                    if res.tau is not None else None)

    spec = P(axes)
    operands = [factors, rhs]
    in_specs = [spec, spec]
    if piv is not None:
        operands.append(piv)
        in_specs.append(spec)
    if tau is not None:
        operands.append(tau)
        in_specs.append(spec)

    def local(f, r, *meta):
        lp = meta[0] if piv is not None else None
        lt = meta[0] if (tau is not None and piv is None) else None
        lres = FactorizationResult(f, lp, lt, res.kind, res.block)
        return _batched.batched_solve(lres, r, policy=pol,
                                      registry=registry)

    x = jax.shard_map(local, mesh=mesh, in_specs=tuple(in_specs),
                      out_specs=spec, check_vma=False)(*operands)
    x = x[:b0]
    return x[:, :, 0] if vec else x
