"""POTRF - Cholesky factorization (lower), unblocked and blocked.

Blocked right-looking form: POTRF(diag) + TRSM(panel) + SYRK(trailing).
Every trailing flop dispatches through :mod:`repro.blas.level3`, whose
kernel configs resolve via :mod:`repro.tune.dispatch`: ``policy="model"``
(the deprecated ``use_kernel=True``) lowers the SYRK/GEMM hot path onto
the Pallas MXU kernel (interpret mode on CPU); ``"tuned"`` uses the
registry's measured config. The default panel width comes from
:func:`repro.core.codesign.plan_factorization` - the same roofline +
pipeline-depth model that tiles the GEMM itself. Public front-end:
:func:`repro.linalg.cholesky`.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
from jax import lax

from repro import obs as _obs


def default_block(n: int, kind: str, dtype=None) -> int:
    """Model-picked panel width NB for a size-n factorization.

    ``dtype`` (optional) makes the plan dtype-aware: the roofline terms
    price operand bytes at that dtype's width (float32 when omitted).
    """
    from repro.core.codesign import plan_factorization
    return plan_factorization(n, kind=kind, dtype=dtype).block


def potrf_unblocked(a: jnp.ndarray) -> jnp.ndarray:
    """Lower-triangular Cholesky of one SPD matrix, unblocked.

    Parameters
    ----------
    a : (n, n) SPD matrix (float32/float64). Non-SPD input produces NaNs,
        LAPACK-style - no error is raised.

    Returns
    -------
    (n, n) lower-triangular L with A = L L^T.

    Notes
    -----
    The serial sqrt-then-div chain per column is the paper's dpotrf
    hazard profile. Oracle: ``tests/test_lapack.py`` (vs
    ``np.linalg.cholesky``).
    """
    n = a.shape[0]
    rows = jnp.arange(n)

    def body(k, A):
        d = jnp.sqrt(A[k, k])
        col = jnp.where(rows > k, A[:, k] / d, 0.0)
        A = A.at[k, k].set(d)
        A = A.at[:, k].set(jnp.where(rows > k, col, A[:, k]))
        # trailing rank-1 update on the lower triangle
        upd = jnp.outer(col, col)
        mask = (rows[:, None] > k) & (rows[None, :] > k)
        return A - jnp.where(mask, upd, 0.0)

    A = lax.fori_loop(0, n, body, a)
    return jnp.tril(A)


def potrf(a: jnp.ndarray, block: Optional[int] = None,
          policy: Optional[str] = None, use_kernel: Optional[bool] = None,
          interpret: Optional[bool] = None, registry=None,
          fuse: Optional[bool] = None) -> jnp.ndarray:
    """Blocked right-looking POTRF: panel = hazards, trailing = GEMM.

    Parameters
    ----------
    a : (n, n) SPD matrix (float32/float64; NaNs on non-SPD input,
        LAPACK-style).
    block : panel width NB; ``None`` takes
        :func:`repro.core.codesign.plan_factorization`'s model pick at
        a's dtype.
    policy : {"reference", "model", "tuned"}, optional
        Every trailing update (panel TRSM + trailing GEMM) dispatches
        through :mod:`repro.blas.level3`, so the kernel policies put all
        trailing flops on the Pallas MXU path; ``use_kernel`` is the
        deprecated alias (True == "model").
    registry : tuned-config registry forwarded to every trailing update
        (``None`` = the process default).
    fuse : stream each trailing TRSM->SYRK pair through the fused
        ``trsm+gemm`` kernel (:mod:`repro.kernels.fused`)? ``None``
        (default) defers to :func:`repro.core.codesign.plan_fused_chain`
        under the kernel policies; ``False`` forces the staged path
        (bitwise the historical trailing update), ``True`` forces fusion
        whenever the policy reaches the kernel at all.

    Returns
    -------
    (n, n) lower-triangular L with A = L L^T.

    Notes
    -----
    Oracle: ``tests/test_lapack.py`` (round-trip vs
    ``np.linalg.cholesky``); kernel-path agreement in
    ``tests/test_lapack_batched.py`` and ``tests/test_tune.py``;
    fused-vs-staged agreement in ``tests/test_fusion.py``.
    """
    from repro.tune import dispatch as _tune
    from repro.tune.policy import resolve_policy
    pol = resolve_policy(policy, use_kernel)
    n = a.shape[0]
    if block is None:
        block = default_block(n, "potrf", a.dtype)
    if n <= block:
        return potrf_unblocked(a)
    for j0 in range(0, n, block):
        nb = min(block, n - j0)
        with _obs.span("potrf.panel", cat="panel", j0=j0, nb=nb,
                       flops=nb ** 3 // 3):
            a = a.at[j0:j0 + nb, j0:j0 + nb].set(
                potrf_unblocked(a[j0:j0 + nb, j0:j0 + nb]))
        if j0 + nb < n:
            r = n - j0 - nb                 # trailing-block side length
            with _obs.span("potrf.trailing", cat="trailing", j0=j0, nb=nb,
                           flops=nb * nb * r + 2 * r * r * nb):
                l11 = a[j0:j0 + nb, j0:j0 + nb]
                # X = L11^{-1} A21^T then A22 -= X^T X (L21 = X^T): the
                # trsm+gemm chain keeps X resident in VMEM when its plan
                # says streaming wins; otherwise it runs the staged
                # TRSM + SYRK-shaped GEMM exactly as before
                x, c_out = _tune.dispatch(
                    "trsm+gemm", l11, a[j0 + nb:, j0:j0 + nb].T, None,
                    a[j0 + nb:, j0 + nb:], form="syrk", unit_diag=False,
                    fuse=fuse, policy=pol, interpret=interpret,
                    registry=registry)
                a = a.at[j0 + nb:, j0:j0 + nb].set(x.T)
                a = a.at[j0 + nb:, j0 + nb:].set(c_out)
    return jnp.tril(a)
