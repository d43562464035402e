"""GETRF - LU with partial pivoting, unblocked and blocked, in JAX.

Section-4.2 workload #2: the column-scaling divisions are the serial divider
stream ("the occurrence of division ... is similar to the square root/divider
in the QR factorization"); the trailing update is DGEMM.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp
from jax import lax

from repro import obs as _obs
from repro.lapack.cholesky import default_block


def _getf2(p: jnp.ndarray
           ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """LAPACK's dgetf2 on ``p`` alone: unblocked LU with partial pivoting.

    Each step picks the pivot by argmax, swaps rows, scales the column
    and applies the rank-1 update to the columns on its right. Returns
    ``(packed, piv, perm)``: the packed L\\U, the 0-based ipiv of
    :func:`getrf_unblocked`, and the rows' permutation (row i of
    ``packed`` comes from row ``perm[i]`` of ``p``), which the blocked
    driver applies to the columns outside the panel.
    """
    m, w = p.shape
    rows = jnp.arange(m)
    cols = jnp.arange(w)

    def body(k, carry):
        A, piv, perm = carry
        col = jnp.where(rows >= k, jnp.abs(A[:, k]), -jnp.inf)
        # pin to int32 (LAPACK ipiv width): under JAX_ENABLE_X64 argmax
        # yields int64, and scattering that into the int32 piv buffer is
        # a dtype-mismatch error in future JAX (analysis rule DF family)
        q = jnp.argmax(col).astype(jnp.int32)
        with _obs.span("getrf.swap", cat="swap"):
            piv = piv.at[k].set(q)
            rk, rq = A[k], A[q]
            A = A.at[k].set(rq).at[q].set(rk)
            pk, pq = perm[k], perm[q]
            perm = perm.at[k].set(pq).at[q].set(pk)
        pivval = A[k, k]
        safe = jnp.where(jnp.abs(pivval) > 0, pivval, 1.0)
        l = jnp.where(rows > k, A[:, k] / safe, 0.0)
        A = A.at[:, k].set(jnp.where(rows > k, l, A[:, k]))
        urow = jnp.where(cols > k, A[k], 0.0)
        A = A - jnp.outer(l, urow)
        return A, piv, perm

    kmax = min(m, w)
    return lax.fori_loop(0, kmax, body,
                         (p, jnp.zeros((kmax,), jnp.int32),
                          jnp.arange(m, dtype=jnp.int32)))


def getrf_unblocked(a: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Unblocked LU with partial pivoting of one matrix.

    Parameters
    ----------
    a : (n, m) matrix (float32/float64); square or rectangular.

    Returns
    -------
    (packed, piv)
        ``packed``: L (unit lower, below diagonal) and U (on/above) in
        one array; ``piv``: (min(n, m),) int32 - ``piv[k]`` is the row
        swapped into k (LAPACK ipiv, 0-based).

    Notes
    -----
    Oracle: ``tests/test_lapack.py`` (vs ``scipy.linalg.lu_factor``).
    """
    return _getf2(a)[:2]


def getrf(a: jnp.ndarray, block: Optional[int] = None,
          policy: Optional[str] = None, registry=None,
          fuse: Optional[bool] = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Blocked right-looking LU with partial pivoting (LAPACK DGETRF).

    Each panel of ``block`` columns is factored as an (m - j0) x nb array
    of its own (LAPACK's dgetf2: pivot, swap, scale, rank-1 update within
    the panel). Its row interchanges then reach the columns left and
    right of the panel once, as one gather and scatter of at most 2 nb
    rows (LAPACK's dlaswp), before the trailing TRSM + GEMM. No step of
    the panel loop touches the rest of the matrix.

    Parameters
    ----------
    a : (m, n) matrix (float32/float64).
    block : panel width NB; ``None`` takes
        ``plan_factorization(kind="getrf")``'s model pick at a's dtype.
    registry : tuned-config registry forwarded to every trailing update
        (``None`` = the process default).
    policy : {"reference", "model", "tuned"}, optional
        Trailing updates (TRSM for U12, GEMM for A22) dispatch through
        :mod:`repro.blas.level3`, resolved by :mod:`repro.tune.dispatch`:
        ``"model"`` reaches the Pallas MXU kernel, ``"tuned"`` the
        registry config.
    fuse : stream each trailing TRSM->GEMM pair through the fused
        ``trsm+gemm`` kernel? ``None`` defers to
        :func:`repro.core.codesign.plan_fused_chain` under the kernel
        policies; ``False`` forces the staged path (bitwise the
        historical trailing update), ``True`` forces fusion whenever the
        policy reaches the kernel at all.

    Returns
    -------
    (packed, piv)
        Same packed L\\U + 0-based ipiv contract as
        :func:`getrf_unblocked`, piv length min(m, n).

    Notes
    -----
    Oracle: ``tests/test_lapack.py`` and
    ``tests/test_lapack_batched.py`` (reconstruction round-trip,
    non-square and ill-conditioned cases); per-policy agreement in
    ``tests/test_tune.py``; fused-vs-staged agreement in
    ``tests/test_fusion.py``.
    """
    from repro.tune import dispatch as _tune
    from repro.tune.policy import resolve_policy
    pol = resolve_policy(policy)
    n, nc = a.shape
    kmax = min(n, nc)
    if block is None:
        block = default_block(kmax, "getrf", a.dtype)
    if kmax <= block:
        return getrf_unblocked(a)
    pivs = []
    for j0 in range(0, kmax, block):
        nb = min(block, kmax - j0)
        with _obs.span("getrf.panel", cat="panel", j0=j0, nb=nb,
                       flops=(n - j0) * nb * nb):
            # dgetf2 on the (n - j0) x nb panel alone ...
            pf, piv, perm = _getf2(a[j0:, j0:j0 + nb])
            with _obs.span("getrf.swap", cat="swap"):
                # ... then dlaswp: the panel's interchanges on the other
                # columns, once. Only the panel's first nb local rows and
                # its pivot rows can move, so 2 nb rows are gathered and
                # scattered (a repeated index writes the same row twice)
                idx = jnp.concatenate([jnp.arange(nb, dtype=jnp.int32), piv])
                a = a.at[j0 + idx].set(a[j0 + perm[idx]])
            a = a.at[j0:, j0:j0 + nb].set(pf)
        pivs.append(piv + j0)
        if j0 + nb < nc:
            mr, ncr = n - j0 - nb, nc - j0 - nb     # trailing block dims
            with _obs.span("getrf.trailing", cat="trailing", j0=j0, nb=nb,
                           flops=nb * nb * ncr + 2 * mr * ncr * nb):
                # U12 = L11^{-1} A12 ; A22 -= L21 U12: the trsm+gemm
                # chain streams U12 through VMEM when its plan says
                # fusing wins; otherwise the staged TRSM + GEMM pair runs
                # exactly as before
                l11 = a[j0:j0 + nb, j0:j0 + nb]
                u12, c_out = _tune.dispatch(
                    "trsm+gemm", l11, a[j0:j0 + nb, j0 + nb:],
                    a[j0 + nb:, j0:j0 + nb], a[j0 + nb:, j0 + nb:],
                    form="lu", unit_diag=True, fuse=fuse, policy=pol,
                    registry=registry)
                a = a.at[j0:j0 + nb, j0 + nb:].set(u12)
                a = a.at[j0 + nb:, j0 + nb:].set(c_out)
    return a, jnp.concatenate(pivs)


def apply_ipiv(b: jnp.ndarray, piv: jnp.ndarray) -> jnp.ndarray:
    """Apply the pivot sequence (forward) to rows of b: b <- P b.

    b : (n,) or (n, k); piv : int32 ipiv from :func:`getrf`. Returns b
    with its shape. Inverse operation inside :func:`lu_reconstruct`.
    """
    def body(k, x):
        p = piv[k]
        rk, rp = x[k], x[p]
        return x.at[k].set(rp).at[p].set(rk)
    return lax.fori_loop(0, piv.shape[0], body, b)


def lu_reconstruct(packed: jnp.ndarray, piv: jnp.ndarray) -> jnp.ndarray:
    """P^T L U from a packed :func:`getrf` result - the testing oracle:
    the return value should equal the original input matrix (square
    packed layout)."""
    n = packed.shape[0]
    l = jnp.tril(packed, -1) + jnp.eye(n, dtype=packed.dtype)
    u = jnp.triu(packed)
    lu = l @ u
    # invert the pivot sequence (apply swaps in reverse)
    def body(i, x):
        k = piv.shape[0] - 1 - i
        p = piv[k]
        rk, rp = x[k], x[p]
        return x.at[k].set(rp).at[p].set(rk)
    return lax.fori_loop(0, piv.shape[0], body, lu)
