"""Level-2 BLAS in JAX.

Cores under un-prefixed names; ``dgemv``/``dger``/``dtrsv`` are
deprecation shims forwarding through :mod:`repro.linalg`. ``gemv`` shares
the BLAS-3 policy mechanism: its matvec core resolves through
:mod:`repro.tune.dispatch` (``reference`` = plain jnp; ``model`` /
``tuned`` route op(A) x through the Pallas GEMM kernel as an (m, n) x
(n, 1) product), so Level-2 configs live in the same registry.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
from jax import lax

from repro.blas._deprecated import warn_once


def gemv(a: jnp.ndarray, x: jnp.ndarray, beta=0.0, y=None,
         alpha=1.0, trans: bool = False, policy: Optional[str] = None,
         use_kernel: Optional[bool] = None, interpret: Optional[bool] = None,
         registry=None) -> jnp.ndarray:
    """y <- alpha*op(A) x + beta*y (BLAS GEMV core).

    Parameters
    ----------
    a : (m, n) matrix; x : (n,) vector ((m,) when ``trans``). Any float
        dtype (float32/float64; bfloat16 storage).
    trans : bool
        op(A) = A^T when True (BLAS TRANS flag).
    y : (m,) accumuland for the ``beta`` epilogue, optional.
    policy : {"reference", "model", "tuned"}, optional
        ``reference`` is plain jnp; ``model``/``tuned`` run op(A) x
        through the Pallas GEMM kernel as an (m, n) x (n, 1) product, so
        Level-2 configs share the gemm registry entries. ``use_kernel``
        is the deprecated boolean alias (True == "model").

    Returns
    -------
    jnp.ndarray, shape (m,) ((n,) when ``trans``).

    Notes
    -----
    Public front-end: :func:`repro.linalg.gemv` (context-scoped). Oracle:
    ``tests/test_differential_blas.py`` (vs NumPy matvec over a
    shape x dtype x trans grid); per-policy agreement in
    ``tests/test_tune.py``.
    """
    from repro.tune import dispatch as _tune
    ax = _tune.dispatch("gemv", a, x, trans=trans, policy=policy,
                        use_kernel=use_kernel, interpret=interpret,
                        registry=registry)
    out = alpha * ax
    if y is not None:
        out = out + beta * y
    return out


def ger(alpha, x: jnp.ndarray, y: jnp.ndarray, a: jnp.ndarray) -> jnp.ndarray:
    """A <- alpha * x y^T + A (BLAS GER rank-1 update).

    Parameters
    ----------
    x : (m,); y : (n,); a : (m, n), all the same float dtype.

    Returns
    -------
    (m, n) updated matrix. Pure jnp (no policy - the update is a single
    fused outer product). Oracle: ``tests/test_differential_blas.py``.
    """
    return a + alpha * jnp.outer(x, y)


def trsv(a: jnp.ndarray, b: jnp.ndarray, lower: bool = True,
         unit_diag: bool = False) -> jnp.ndarray:
    """Solve op(T) x = b for triangular T via a row-sequential scan.

    The sequential dependence (x_i needs all earlier x_j) is the paper's
    divider-pipe hazard chain: one divide per row, each waiting on the
    previous row's substitution.

    Parameters
    ----------
    a : (n, n) triangular matrix (only the referenced triangle is read);
        b : (n,) or (n, k) RHS. Any float dtype.
    lower : solve the lower (True) or upper (False) triangle.
    unit_diag : assume unit diagonal (LAPACK DIAG="U"); diagonal entries
        are never read when True.

    Returns
    -------
    x with b's shape. Pure jnp scan - no policy; the blocked,
    policy-dispatched form is :func:`repro.blas.level3.trsm`.

    Notes
    -----
    Oracle: ``tests/test_differential_blas.py`` (vs
    ``scipy.linalg.solve_triangular``).
    """
    n = a.shape[0]
    order = jnp.arange(n) if lower else jnp.arange(n - 1, -1, -1)
    diag = jnp.diagonal(a)
    strict = a - jnp.diag(diag)

    def body(x, i):
        s = b[i] - strict[i] @ x
        xi = s if unit_diag else s / diag[i]
        return x.at[i].set(xi), None

    x0 = jnp.zeros_like(b)
    x, _ = lax.scan(body, x0, order)
    return x


# -------------------------- deprecated d-prefixed shims ----------------------

def dgemv(a, x, beta=0.0, y=None, alpha=1.0, trans: bool = False,
          policy: Optional[str] = None, use_kernel: Optional[bool] = None,
          interpret: Optional[bool] = None, registry=None,
          use_pallas: Optional[bool] = None):
    """Deprecated alias of :func:`repro.linalg.gemv` (old kwargs mapped to
    a per-call context). Warning + bitwise-identity oracle:
    ``tests/test_linalg_deprecation.py``."""
    warn_once("dgemv", "gemv")
    from repro import linalg
    from repro.linalg.context import compat_context
    return linalg.gemv(a, x, y=y, alpha=alpha, beta=beta, trans=trans,
                       context=compat_context(policy, use_kernel, interpret,
                                              registry, use_pallas))


def dger(alpha, x, y, a):
    """Deprecated alias of :func:`repro.linalg.ger`."""
    warn_once("dger", "ger")
    from repro import linalg
    from repro.linalg.context import compat_context
    return linalg.ger(alpha, x, y, a, context=compat_context())


def dtrsv(a, b, lower: bool = True, unit_diag: bool = False):
    """Deprecated alias of :func:`repro.linalg.trsv`."""
    warn_once("dtrsv", "trsv")
    from repro import linalg
    from repro.linalg.context import compat_context
    return linalg.trsv(a, b, lower=lower, unit_diag=unit_diag,
                       context=compat_context())
