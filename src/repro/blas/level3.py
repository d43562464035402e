"""Level-3 BLAS in JAX, policy-dispatched onto the Pallas GEMM hot spot.

``gemm`` is the routine the whole paper orbits (every LAPACK trailing
update lowers to it). Every kernel-shaped core here resolves through
:mod:`repro.tune.dispatch`: ``policy="reference"`` is plain jnp,
``"model"`` the Pallas MXU kernel at the :func:`repro.core.codesign`
tiling, ``"tuned"`` the measured registry config (cold start == model).
``syrk`` and ``trsm`` thread the same policy through their internal
GEMMs, so a blocked factorization dispatches *every* trailing flop onto
the one hot path.

These are the numeric cores; the public, context-scoped front-end is
:mod:`repro.linalg`. The old d-prefixed names (``dgemm``/``dsyrk``/
``dtrsm``) are deprecation shims forwarding there, and
``use_kernel=True/False`` remains a deprecated alias for
``policy="model"`` / ``"reference"``.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
from jax import lax

from repro.blas._deprecated import warn_once


def gemm(a: jnp.ndarray, b: jnp.ndarray, c: Optional[jnp.ndarray] = None,
         alpha=1.0, beta=0.0, transa: bool = False, transb: bool = False,
         policy: Optional[str] = None, use_kernel: Optional[bool] = None,
         interpret: Optional[bool] = None, registry=None) -> jnp.ndarray:
    """C <- alpha * op(A) op(B) + beta * C (BLAS GEMM core).

    Parameters
    ----------
    a, b : matrices with op(A) (m, k) and op(B) (k, n); ``transa`` /
        ``transb`` are the BLAS transpose flags. Any float dtype
        (float32/float64; bfloat16 storage, fp32 accumulation in the
        kernel - float64 operands accumulate in float64).
    c : (m, n) accumuland for the ``beta`` epilogue, optional.
    policy : {"reference", "model", "tuned"}, optional
        ``reference`` = plain jnp (the oracle path); ``model`` = Pallas
        MXU kernel at the :func:`repro.core.codesign.plan_gemm` tiling;
        ``tuned`` = the registry's measured config, cold-starting to
        ``model``. ``use_kernel`` is the deprecated alias
        (True == "model", False == "reference").
    interpret : run Pallas in interpret mode; ``None`` takes the device's
        mode (:func:`repro.kernels.ops.interpret_mode`).

    Returns
    -------
    jnp.ndarray, shape (m, n).

    Notes
    -----
    This is the hot path the whole stack funnels into - every LAPACK
    trailing update and the distributed SUMMA panels execute here.
    Public front-end: :func:`repro.linalg.gemm` (context-scoped, mesh
    routing). Oracle: ``tests/test_differential_blas.py`` (shape x dtype
    x transpose grid vs NumPy); per-policy agreement in
    ``tests/test_tune.py``.
    """
    from repro.tune import dispatch as _tune
    op_a = a.T if transa else a
    op_b = b.T if transb else b
    ab = _tune.dispatch("gemm", op_a, op_b, policy=policy,
                        use_kernel=use_kernel, interpret=interpret,
                        registry=registry)
    out = alpha * ab
    if c is not None:
        out = out + beta * c
    return out


def gemm_bias_act(a: jnp.ndarray, b: jnp.ndarray,
                  bias: Optional[jnp.ndarray] = None, epilogue: str = "none",
                  policy: Optional[str] = None,
                  use_kernel: Optional[bool] = None,
                  interpret: Optional[bool] = None,
                  registry=None) -> jnp.ndarray:
    """C = act(A @ B + bias) - the fused-epilogue GEMM core.

    Parameters
    ----------
    a, b : (m, k) and (k, n) matrices (any supported float dtype).
    bias : length-n vector broadcast over rows, optional.
    epilogue : one of :data:`repro.kernels.fused.EPILOGUES`
        (``"none"`` / ``"relu"`` / ``"gelu"``).
    policy : {"reference", "model", "tuned"}, optional
        ``reference`` applies the epilogue to plain ``a @ b``; the kernel
        policies resolve the ``"gemm+epilogue"`` chain through
        :func:`repro.tune.dispatch.resolve`, which streams the epilogue
        inside the Pallas GEMM when
        :func:`repro.core.codesign.plan_fused_chain` says fusing wins
        (else the staged kernel + epilogue pass).

    Notes
    -----
    Public front-end: :func:`repro.linalg.gemm_bias_act`. Differential
    oracle: ``tests/test_fusion.py``.
    """
    from repro.tune import dispatch as _tune
    return _tune.dispatch("gemm+epilogue", a, b, bias=bias,
                          epilogue=epilogue, policy=policy,
                          use_kernel=use_kernel, interpret=interpret,
                          registry=registry)


def syrk(a: jnp.ndarray, c: Optional[jnp.ndarray] = None, alpha=1.0,
         beta=0.0, lower: bool = True, trans: bool = False,
         policy: Optional[str] = None, use_kernel: Optional[bool] = None,
         interpret: Optional[bool] = None, registry=None) -> jnp.ndarray:
    """C <- alpha op(A) op(A)^T + beta C (BLAS SYRK core), symmetric output.

    Parameters
    ----------
    a : (n, k) matrix ((k, n) when ``trans``); any float dtype.
    trans : BLAS TRANS flag - False computes A A^T, True A^T A.
    lower : which triangle of C is authoritative; the other is mirrored.
    c : (n, n) accumuland, optional.
    policy : {"reference", "model", "tuned"}, optional
        The product runs through the same ``gemm`` kernel path (SYRK
        shares the gemm registry entries), so SYRK reaches Pallas under
        the kernel policies; ``use_kernel`` deprecated alias as in
        :func:`gemm`.

    Returns
    -------
    (n, n) symmetric matrix.

    Notes
    -----
    Public front-end: :func:`repro.linalg.syrk`. Oracle:
    ``tests/test_differential_blas.py``; per-policy agreement in
    ``tests/test_tune.py``.
    """
    from repro.tune import dispatch as _tune
    full = alpha * _tune.dispatch("syrk", a, trans=trans, policy=policy,
                                  use_kernel=use_kernel, interpret=interpret,
                                  registry=registry)
    if c is not None:
        full = full + beta * c
    return mirror_triangle(full, lower)


def mirror_triangle(full: jnp.ndarray, lower: bool) -> jnp.ndarray:
    """SYRK epilogue: keep the authoritative triangle of ``full`` and
    mirror it across the diagonal (shared by the local and SUMMA paths)."""
    n = full.shape[0]
    i, j = jnp.mgrid[0:n, 0:n]
    mask = (i >= j) if lower else (i <= j)
    return jnp.where(mask, full, full.T)


def trsm(a: jnp.ndarray, b: jnp.ndarray, lower: bool = True,
         unit_diag: bool = False, left: bool = True,
         block: Optional[int] = None, policy: Optional[str] = None,
         use_kernel: Optional[bool] = None, interpret: Optional[bool] = None,
         registry=None) -> jnp.ndarray:
    """Solve op(T) X = B (left=True) or X op(T) = B, T triangular, blocked.

    Diagonal blocks use the sequential substitution scan (the serial
    divider chain); off-diagonal updates are GEMMs - the paper's
    panel/trailing structure in miniature - and follow the policy onto the
    Pallas path.

    Parameters
    ----------
    a : (n, n) triangular matrix; b : (n, k) or (n,) RHS ((m, n) layouts
        transposed internally when ``left=False``). Any float dtype.
    lower, unit_diag : LAPACK UPLO/DIAG flags.
    left : solve op(T) X = B (True) or X op(T) = B (False).
    block : diagonal-block width; ``None`` resolves it through
        :func:`repro.tune.dispatch.resolve` (64 under ``reference`` - the
        historical default - else the ``plan_trsm`` model or the
        registry's measured width).
    policy : {"reference", "model", "tuned"}, optional
        Applies to the off-diagonal GEMM updates (the substitution scan
        itself has no kernel form); ``use_kernel`` deprecated alias.

    Returns
    -------
    X with b's shape.

    Notes
    -----
    Public front-end: :func:`repro.linalg.trsm` (context-scoped, pdtrsm
    under a mesh). Oracle: ``tests/test_differential_blas.py`` (vs
    ``scipy.linalg.solve_triangular`` over lower/upper x unit/non-unit);
    per-policy agreement in ``tests/test_tune.py``.
    """
    if not left:
        # X T = B  <=>  T^T X^T = B^T
        return trsm(a.T, b.T, lower=not lower, unit_diag=unit_diag,
                    left=True, block=block, policy=policy,
                    use_kernel=use_kernel, interpret=interpret,
                    registry=registry).T
    n = a.shape[0]
    if block is None:
        from repro.tune import dispatch as _tune
        nrhs = b.shape[1] if b.ndim == 2 else 1
        res = _tune.resolve("trsm", (n, nrhs), a.dtype, policy=policy,
                            use_kernel=use_kernel, registry=registry)
        pol, block = res.policy, res.block
    else:
        from repro.tune.policy import resolve_policy
        pol = resolve_policy(policy, use_kernel)
    if n <= block:
        return _trsm_unblocked(a, b, lower=lower, unit_diag=unit_diag)
    blocks = list(range(0, n, block))
    x = jnp.zeros_like(b)
    order = blocks if lower else blocks[::-1]
    for i0 in order:
        i1 = min(i0 + block, n)
        rhs = b[i0:i1]
        if lower and i0 > 0:
            rhs = rhs - gemm(a[i0:i1, :i0], x[:i0], policy=pol,
                             interpret=interpret, registry=registry)
        elif not lower and i1 < n:
            rhs = rhs - gemm(a[i0:i1, i1:], x[i1:], policy=pol,
                             interpret=interpret, registry=registry)
        xi = _trsm_unblocked(a[i0:i1, i0:i1], rhs, lower=lower,
                             unit_diag=unit_diag)
        x = x.at[i0:i1].set(xi)
    return x


def _trsm_unblocked(a: jnp.ndarray, b: jnp.ndarray, lower: bool,
                    unit_diag: bool) -> jnp.ndarray:
    n = a.shape[0]
    order = jnp.arange(n) if lower else jnp.arange(n - 1, -1, -1)
    diag = jnp.diagonal(a)
    strict = a - jnp.diag(diag)

    def body(x, i):
        s = b[i] - strict[i] @ x
        xi = s if unit_diag else s / diag[i]
        return x.at[i].set(xi), None

    x, _ = lax.scan(body, jnp.zeros_like(b), order)
    return x


# -------------------------- deprecated d-prefixed shims ----------------------

def dgemm(a, b, c=None, alpha=1.0, beta=0.0, transa: bool = False,
          transb: bool = False, policy: Optional[str] = None,
          use_kernel: Optional[bool] = None, interpret: Optional[bool] = None,
          registry=None, use_pallas: Optional[bool] = None):
    """Deprecated alias of :func:`repro.linalg.gemm` (old kwargs mapped to
    a local, per-call context). Warning + bitwise-identity oracle:
    ``tests/test_linalg_deprecation.py``."""
    warn_once("dgemm", "gemm")
    from repro import linalg
    from repro.linalg.context import compat_context
    return linalg.gemm(a, b, c=c, alpha=alpha, beta=beta, transa=transa,
                       transb=transb,
                       context=compat_context(policy, use_kernel, interpret,
                                              registry, use_pallas))


def dsyrk(a, c=None, alpha=1.0, beta=0.0, lower: bool = True,
          trans: bool = False, policy: Optional[str] = None,
          use_kernel: Optional[bool] = None, interpret: Optional[bool] = None,
          registry=None, use_pallas: Optional[bool] = None):
    """Deprecated alias of :func:`repro.linalg.syrk`."""
    warn_once("dsyrk", "syrk")
    from repro import linalg
    from repro.linalg.context import compat_context
    return linalg.syrk(a, c=c, alpha=alpha, beta=beta, lower=lower,
                       trans=trans,
                       context=compat_context(policy, use_kernel, interpret,
                                              registry, use_pallas))


def dtrsm(a, b, lower: bool = True, unit_diag: bool = False,
          left: bool = True, block: Optional[int] = None,
          policy: Optional[str] = None, use_kernel: Optional[bool] = None,
          interpret: Optional[bool] = None, registry=None,
          use_pallas: Optional[bool] = None):
    """Deprecated alias of :func:`repro.linalg.trsm`."""
    warn_once("dtrsm", "trsm")
    from repro import linalg
    from repro.linalg.context import compat_context
    return linalg.trsm(a, b, lower=lower, unit_diag=unit_diag, left=left,
                       block=block,
                       context=compat_context(policy, use_kernel, interpret,
                                              registry, use_pallas))
