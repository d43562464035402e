"""GESV-style dense solvers built on the factorizations.

Both drivers thread the tuner policy (``reference`` | ``model`` |
``tuned``) through every factorization
and triangular solve, so the whole solve resolves its kernel configs via
:mod:`repro.tune.dispatch`.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro import obs as _obs
from repro.blas.level3 import trsm
from repro.lapack.lu import apply_ipiv, getrf
from repro.lapack.qr import geqrf, q_from_geqrf


def gesv(a: jnp.ndarray, b: jnp.ndarray, block: Optional[int] = None,
         policy: Optional[str] = None, registry=None) -> jnp.ndarray:
    """Solve A X = B via LU with partial pivoting (LAPACK DGESV).

    Parameters
    ----------
    a : (n, n) matrix (float32/float64); b : (n,) or (n, k) RHS.
    block : forwarded to :func:`repro.lapack.lu.getrf`.
    policy : {"reference", "model", "tuned"}, optional
        Threaded through the factorization and both triangular solves,
        so the whole solve resolves its kernel configs through
        :mod:`repro.tune.dispatch`.

    Returns
    -------
    X with b's shape.

    Notes
    -----
    Oracle: ``tests/test_lapack.py`` (vs ``np.linalg.solve``).
    """
    from repro.tune.policy import resolve_policy
    pol = resolve_policy(policy)
    packed, piv = getrf(a, block=block, policy=pol, registry=registry)
    rhs = b if b.ndim == 2 else b[:, None]
    with _obs.span("gesv.getrs", cat="solve"):
        rhs = apply_ipiv(rhs, piv)
        y = trsm(packed, rhs, lower=True, unit_diag=True, left=True,
                 policy=pol, registry=registry)
        x = trsm(packed, y, lower=False, unit_diag=False, left=True,
                 policy=pol, registry=registry)
    return x if b.ndim == 2 else x[:, 0]


def lstsq_qr(a: jnp.ndarray, b: jnp.ndarray, block: Optional[int] = None,
             policy: Optional[str] = None, registry=None) -> jnp.ndarray:
    """Least-squares min ||A x - b|| via QR: x = R^{-1} Q^T b.

    Parameters
    ----------
    a : (m, n) matrix with m >= n, full column rank (float32/float64);
        b : (m,) or (m, k) RHS.
    block, policy : forwarded to :func:`repro.lapack.qr.geqrf` and the
        final TRSM - same policy semantics as :func:`gesv`.

    Returns
    -------
    x, shape (n,) or (n, k).

    Notes
    -----
    Oracle: ``tests/test_lapack.py`` (vs ``np.linalg.lstsq`` on
    overdetermined systems).
    """
    from repro.tune.policy import resolve_policy
    pol = resolve_policy(policy)
    m, n = a.shape
    packed, tau = geqrf(a, block=block, policy=pol, registry=registry)
    q = q_from_geqrf(packed, tau)
    rhs = b if b.ndim == 2 else b[:, None]
    qtb = q.T @ rhs
    r = jnp.triu(packed)[:n, :n]
    x = trsm(r, qtb[:n], lower=False, unit_diag=False, left=True,
             policy=pol, registry=registry)
    return x if b.ndim == 2 else x[:, 0]
