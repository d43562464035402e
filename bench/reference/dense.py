"""Plain dense references and the accuracy arithmetic that decides `correct`.

Nothing here imports the system under test. The residual and ratio
functions are HPL's and LAPACK's test arithmetic, run in float64 on the
host. The factorizations are textbook blocked right-looking algorithms in
`jax.numpy`; `precision` selects how their matrix products are computed:

- ``"highest"``: float32 products (XLA's HIGHEST; six bfloat16 passes on a
  TPU). This is what the configurations state.
- ``"bf16x3"``: each float32 operand split into a bfloat16 head and tail,
  and the three leading products summed in float32 -- the three-pass
  precision a later change might be tempted to use. It is the control:
  written out explicitly, so it computes the same on a CPU as on a TPU.
  The head is cut from the bits (not rounded by a conversion), so that
  XLA cannot fold the split away as a float32 -> bfloat16 -> float32
  round trip.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.scipy.linalg import solve_triangular

PRECISIONS = ("highest", "bf16x3")

F32_UNIT_ROUNDOFF = float(np.finfo(np.float32).eps) / 2


def matmul(a, b, precision: str = "highest"):
    """a @ b in float32 at the named precision."""
    if precision == "highest":
        return jnp.matmul(a, b, precision=lax.Precision.HIGHEST)
    if precision == "bf16x3":
        def split(x):
            bits = lax.bitcast_convert_type(x, jnp.uint32)
            hi = lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                          jnp.float32)
            return hi.astype(jnp.bfloat16), (x - hi).astype(jnp.bfloat16)

        def dot(x, y):
            return jnp.matmul(x, y, preferred_element_type=jnp.float32)
        ah, al = split(a)
        bh, bl = split(b)
        return dot(ah, bh) + (dot(ah, bl) + dot(al, bh))
    raise ValueError(f"unknown precision {precision!r}")


def cholesky(a, nb: int = 256, precision: str = "highest"):
    """Lower Cholesky factor of an SPD matrix, blocked right-looking."""
    n = a.shape[0]
    for k in range(0, n, nb):
        e = min(k + nb, n)
        d = jnp.linalg.cholesky(a[k:e, k:e])
        a = a.at[k:e, k:e].set(d)
        if e < n:
            l21 = solve_triangular(d, a[e:, k:e].T, lower=True).T
            a = a.at[e:, k:e].set(l21)
            a = a.at[e:, e:].add(-matmul(l21, l21.T, precision))
    return jnp.tril(a)


def lu_solve(a, b, nb: int = 256, precision: str = "highest"):
    """x with A x = b: blocked LU with partial pivoting, then two
    triangular solves."""
    n = a.shape[0]
    perm = jnp.arange(n)
    for k in range(0, n, nb):
        e = min(k + nb, n)
        panel, _, p = lax.linalg.lu(a[k:, k:e])
        a = a.at[k:, :].set(a[k:, :][p])
        perm = perm.at[k:].set(perm[k:][p])
        a = a.at[k:, k:e].set(panel)
        if e < n:
            l11 = jnp.tril(panel[:e - k], -1) + jnp.eye(e - k, dtype=a.dtype)
            u12 = solve_triangular(l11, a[k:e, e:], lower=True,
                                   unit_diagonal=True)
            a = a.at[k:e, e:].set(u12)
            a = a.at[e:, e:].add(-matmul(a[e:, k:e], u12, precision))
    y = solve_triangular(a, b[perm], lower=True, unit_diagonal=True)
    return solve_triangular(a, y, lower=False)


# ------------------------- accuracy arithmetic ------------------------------

def hpl_residual(a, x, b) -> float:
    """HPL's scaled residual ||Ax-b||_inf / (eps (||A||_inf ||x||_inf +
    ||b||_inf) n), in float64 on the host, eps the float32 unit roundoff."""
    a64 = np.asarray(a, np.float64)
    x64 = np.asarray(x, np.float64)
    b64 = np.asarray(b, np.float64)
    r = np.max(np.abs(a64 @ x64 - b64))
    norm_a = np.max(np.sum(np.abs(a64), axis=1))
    return float(r / (F32_UNIT_ROUNDOFF * (norm_a * np.max(np.abs(x64))
                                           + np.max(np.abs(b64)))
                      * a64.shape[0]))


def lapack_ratio(product, a) -> float:
    """||product - A||_1 / (n ||A||_1 eps), float64 on the host, eps the
    float32 machine epsilon (LAPACK's xPOT01 ratio)."""
    a64 = np.asarray(a, np.float64)
    diff = np.asarray(product, np.float64) - a64

    def one(m):
        return float(np.max(np.sum(np.abs(m), axis=0)))
    return one(diff) / (a64.shape[0] * one(a64)
                        * float(np.finfo(np.float32).eps))


def cholesky_ratio(l, a) -> float:
    """LAPACK's ratio for a Cholesky factor: ||L L^T - A||_1 / (n ||A||_1
    eps), the product taken in float64 on the host."""
    l64 = np.tril(np.asarray(l, np.float64))
    return lapack_ratio(l64 @ l64.T, a)
