"""Batched blocked LAPACK drivers (vmap over the leading axis).

The ROADMAP's batched-workload scenario: many independent small/medium
factorizations (mixture-of-experts solves, per-head whitening, ensemble
Kalman updates) executed as ONE blocked computation. ``vmap`` lifts the
blocked right-looking routines of :mod:`repro.lapack` - whose trailing
updates all dispatch through :func:`repro.blas.level3.gemm` - so a batch
of trailing updates lowers onto batched GEMM on the Pallas hot path, and
the panel hazard chains of the whole batch run in lockstep instead of
serially.

All entry points share one result type, :class:`FactorizationResult`, a
registered pytree (jit/vmap/scan-transparent) tagged with the static
factorization kind, so downstream code (``batched_solve``,
``reconstruct``) dispatches without re-inspecting shapes.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.blas.level3 import trsm as _trsm
from repro.lapack import cholesky, lu, qr
from repro.lapack.cholesky import default_block
from repro.tune.policy import resolve_policy


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["factors", "pivots", "tau"],
                   meta_fields=["kind", "block"])
@dataclasses.dataclass(frozen=True)
class FactorizationResult:
    """One batched factorization in LAPACK packed layout.

    factors: (B, m, n) packed factor(s) - L (potrf), L\\U (getrf), or the
             Householder-packed R/V (geqrf).
    pivots:  (B, k) int32 ipiv (getrf only, else None).
    tau:     (B, k) reflector scales (geqrf only, else None).
    kind:    static tag: "potrf" | "getrf" | "geqrf".
    block:   panel width the factorization actually used.
    """

    factors: jnp.ndarray
    pivots: Optional[jnp.ndarray]
    tau: Optional[jnp.ndarray]
    kind: str
    block: int

    @property
    def batch(self) -> int:
        return self.factors.shape[0]


def _resolve_block(kmax: int, block: Optional[int], kind: str,
                   dtype=None) -> int:
    return default_block(kmax, kind, dtype) if block is None else int(block)


def batched_potrf(a: jnp.ndarray, block: Optional[int] = None,
                  policy: Optional[str] = None,
                  use_kernel: Optional[bool] = None,
                  interpret: Optional[bool] = None,
                  registry=None) -> FactorizationResult:
    """Cholesky of a (B, n, n) SPD batch; factors holds L (lower).

    float32/float64 (NaNs per non-SPD item, LAPACK-style). ``policy``
    threads to every trailing update via :mod:`repro.tune.dispatch`
    (``use_kernel`` deprecated alias); ``block=None`` takes the
    ``plan_factorization`` model pick. Oracle:
    ``tests/test_lapack_batched.py`` (round-trip + kernel-path-identical);
    mesh-parallel form: :func:`repro.lapack.distributed.batched_potrf`.
    """
    assert a.ndim == 3 and a.shape[1] == a.shape[2], a.shape
    pol = resolve_policy(policy, use_kernel)
    nb = _resolve_block(a.shape[1], block, "potrf", a.dtype)
    f = jax.vmap(lambda x: cholesky.potrf(x, block=nb, policy=pol,
                                          interpret=interpret,
                                          registry=registry))
    return FactorizationResult(f(a), None, None, "potrf", nb)


def batched_getrf(a: jnp.ndarray, block: Optional[int] = None,
                  policy: Optional[str] = None,
                  use_kernel: Optional[bool] = None,
                  interpret: Optional[bool] = None,
                  registry=None) -> FactorizationResult:
    """LU with partial pivoting of a (B, m, n) batch.

    Returns packed L\\U factors + (B, min(m, n)) int32 ipiv. Same
    policy/block contract as :func:`batched_potrf`. Oracle:
    ``tests/test_lapack_batched.py`` (incl. non-square and
    ill-conditioned); mesh-parallel form:
    :func:`repro.lapack.distributed.batched_getrf`.
    """
    assert a.ndim == 3, a.shape
    pol = resolve_policy(policy, use_kernel)
    nb = _resolve_block(min(a.shape[1], a.shape[2]), block, "getrf", a.dtype)
    f = jax.vmap(lambda x: lu.getrf(x, block=nb, policy=pol,
                                    interpret=interpret, registry=registry))
    packed, piv = f(a)
    return FactorizationResult(packed, piv, None, "getrf", nb)


def batched_geqrf(a: jnp.ndarray, block: Optional[int] = None,
                  policy: Optional[str] = None,
                  use_kernel: Optional[bool] = None,
                  interpret: Optional[bool] = None,
                  registry=None) -> FactorizationResult:
    """Householder QR of a (B, m, n) batch (packed R/V + tau per item).

    Same policy/block contract as :func:`batched_potrf`. Oracle:
    ``tests/test_lapack_batched.py``; mesh-parallel form:
    :func:`repro.lapack.distributed.batched_geqrf`.
    """
    assert a.ndim == 3, a.shape
    pol = resolve_policy(policy, use_kernel)
    nb = _resolve_block(min(a.shape[1], a.shape[2]), block, "geqrf", a.dtype)
    f = jax.vmap(lambda x: qr.geqrf(x, block=nb, policy=pol,
                                    interpret=interpret, registry=registry))
    packed, tau = f(a)
    return FactorizationResult(packed, None, tau, "geqrf", nb)


def batched_solve(res: FactorizationResult, b: jnp.ndarray,
                  policy: Optional[str] = None,
                  use_kernel: Optional[bool] = None,
                  interpret: Optional[bool] = None,
                  registry=None) -> jnp.ndarray:
    """Solve A_i x_i = b_i for every batch item from a FactorizationResult.

    b: (B, n) or (B, n, k). potrf solves the SPD system L L^T x = b; getrf
    the pivoted L U x = P b; geqrf the least-squares system via
    R^{-1} Q^T b (m >= n). ``policy`` threads to every triangular solve
    (``use_kernel`` deprecated alias). Oracle:
    ``tests/test_lapack_batched.py`` (solve residuals per kind);
    mesh-parallel form: :func:`repro.lapack.distributed.batched_solve`.
    """
    vec = b.ndim == 2
    rhs = b[:, :, None] if vec else b
    pol = resolve_policy(policy, use_kernel)

    def trsm(t, r, **kw):
        return _trsm(t, r, left=True, policy=pol, interpret=interpret,
                     registry=registry, **kw)

    if res.kind == "potrf":
        def solve1(l, r):
            y = trsm(l, r, lower=True, unit_diag=False)
            return trsm(l.T, y, lower=False, unit_diag=False)
        x = jax.vmap(solve1)(res.factors, rhs)
    elif res.kind == "getrf":
        m, n = res.factors.shape[1:]
        if m != n:
            raise ValueError(
                f"batched_solve(getrf) needs square factors; got "
                f"{res.factors.shape} (use geqrf for least squares)")

        def solve1(packed, piv, r):
            r = lu.apply_ipiv(r, piv)
            y = trsm(packed, r, lower=True, unit_diag=True)
            return trsm(packed, y, lower=False, unit_diag=False)
        x = jax.vmap(solve1)(res.factors, res.pivots, rhs)
    elif res.kind == "geqrf":
        m, n = res.factors.shape[1:]
        if m < n:
            raise ValueError(
                f"batched_solve(geqrf) is a least-squares solve and needs "
                f"m >= n; got factors of shape {res.factors.shape}")

        def solve1(packed, tau, r):
            q = qr.q_from_geqrf(packed, tau)
            qtb = q.T @ r
            rr = jnp.triu(packed)[:n, :n]
            return trsm(rr, qtb[:n], lower=False, unit_diag=False)
        x = jax.vmap(solve1)(res.factors, res.tau, rhs)
    else:
        raise ValueError(f"unknown factorization kind: {res.kind!r}")
    return x[:, :, 0] if vec else x


def reconstruct(res: FactorizationResult) -> jnp.ndarray:
    """Rebuild the (B, m, n) input batch from its factors (testing oracle)."""
    if res.kind == "potrf":
        return jax.vmap(lambda l: l @ l.T)(res.factors)
    if res.kind == "getrf":
        return jax.vmap(lu.lu_reconstruct)(res.factors, res.pivots)
    if res.kind == "geqrf":
        def rec1(packed, tau):
            q = qr.q_from_geqrf(packed, tau)
            return q @ jnp.triu(packed)
        return jax.vmap(rec1)(res.factors, res.tau)
    raise ValueError(f"unknown factorization kind: {res.kind!r}")
