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
:mod:`repro.linalg`, which resolves its context into their ``policy`` and
``registry`` arguments. The Pallas kernels take interpret mode from the
device.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

import jax.numpy as jnp
from jax import lax

from repro import obs as _obs


def gemm(a: jnp.ndarray, b: jnp.ndarray, c: Optional[jnp.ndarray] = None,
         alpha=1.0, beta=0.0, transa: bool = False, transb: bool = False,
         policy: Optional[str] = None, registry=None) -> jnp.ndarray:
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
        ``model``.

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
                        registry=registry)
    out = alpha * ab
    if c is not None:
        out = out + beta * c
    return out


def gemm_bias_act(a: jnp.ndarray, b: jnp.ndarray,
                  bias: Optional[jnp.ndarray] = None, epilogue: str = "none",
                  policy: Optional[str] = None,
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
                          registry=registry)


def syrk(a: jnp.ndarray, c: Optional[jnp.ndarray] = None, alpha=1.0,
         beta=0.0, lower: bool = True, trans: bool = False,
         policy: Optional[str] = None, registry=None) -> jnp.ndarray:
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
        the kernel policies.

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
         registry=None) -> jnp.ndarray:
    """Solve op(T) X = B (left=True) or X op(T) = B, T triangular, blocked.

    With more than one block, every diagonal block is inverted at once
    (one batched recursive halving, LAPACK's recursive dtrtri: one
    reciprocal per diagonal element, then log2(block) levels of batched
    products), and each block is then solved by one product with its
    inverse. Off-diagonal updates are GEMMs - the paper's panel/trailing
    structure in miniature - and follow the policy onto the Pallas path.
    A single block (``n <= block``) runs the row-by-row substitution scan.

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
        Applies to the off-diagonal GEMM updates (the diagonal blocks'
        inversion and products, and the substitution scan, have no kernel
        form).

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
                    registry=registry).T
    n = a.shape[0]
    if block is None:
        from repro.tune import dispatch as _tune
        nrhs = b.shape[1] if b.ndim == 2 else 1
        res = _tune.resolve("trsm", (n, nrhs), a.dtype, policy=policy,
                            registry=registry)
        pol, block = res.policy, res.block
    else:
        from repro.tune.policy import resolve_policy
        pol = resolve_policy(policy)
    if n <= block:
        return _trsm_unblocked(a, b, lower=lower, unit_diag=unit_diag)
    inv = _inverted_diagonal_blocks(a, block, lower, unit_diag)
    blocks = list(range(0, n, block))
    x = jnp.zeros_like(b)
    order = blocks if lower else blocks[::-1]
    for i0 in order:
        i1 = min(i0 + block, n)
        rhs = b[i0:i1]
        if lower and i0 > 0:
            rhs = rhs - gemm(a[i0:i1, :i0], x[:i0], policy=pol,
                             registry=registry)
        elif not lower and i1 < n:
            rhs = rhs - gemm(a[i0:i1, i1:], x[i1:], policy=pol,
                             registry=registry)
        x = x.at[i0:i1].set(inv[i0 // block, :i1 - i0, :i1 - i0] @ rhs)
    return x


def _inverted_diagonal_blocks(a: jnp.ndarray, block: int, lower: bool,
                              unit_diag: bool) -> jnp.ndarray:
    """Inverses of all diagonal ``block`` x ``block`` blocks of triangular
    ``a``, as one (nblk, p, p) stack, p = block rounded up to a power of 2.

    Block k's inverse is ``[k, :w, :w]`` (w its width); a last partial
    block and the rows and columns from w to p are padded with the
    identity, whose inverse they keep. Recursive halving as in LAPACK's
    dtrtri, over the whole stack at once: with X the inverse of the
    s x s diagonal sub-blocks and E the off-diagonal s x s quadrants of
    the 2s x 2s ones, the 2s x 2s inverse is X - X E X (for lower T the
    quadrant -D^-1 C A^-1 of inv([[A, 0], [C, D]])), since (X E)^2 = 0.
    Only the strict triangle named by ``lower`` and, unless
    ``unit_diag``, the diagonal are read.
    """
    p = 1 << (block - 1).bit_length()
    t = jnp.stack([_pad_identity(a[i:i + block, i:i + block], p)
                   for i in range(0, a.shape[0], block)])
    _obs.inc("trsm.inverted_blocks", t.shape[0])
    eye = jnp.eye(p, dtype=t.dtype)
    x = jnp.broadcast_to(eye, t.shape) if unit_diag else \
        eye / jnp.diagonal(t, axis1=-2, axis2=-1)[..., None, :]
    i, j = np.indices((p, p))
    s = 1
    while s < p:
        quadrant = (i // s > j // s) if lower else (i // s < j // s)
        e = jnp.where(quadrant & (i // (2 * s) == j // (2 * s)), t, 0)
        x = x - x @ (e @ x)
        s *= 2
    return x


def _pad_identity(t: jnp.ndarray, size: int) -> jnp.ndarray:
    """Pad square ``t`` to ``size`` x ``size``, with the identity on the
    new part of the diagonal."""
    w = t.shape[0]
    if w == size:
        return t
    return jnp.pad(t, (0, size - w)) + jnp.diag(
        (jnp.arange(size) >= w).astype(t.dtype))


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
