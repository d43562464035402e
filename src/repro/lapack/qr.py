"""GEQRF - Householder QR, unblocked and blocked (compact-WY), in JAX.

The paper's section-4.2 workload: the panel path carries the serial
sqrt (column norm) -> div (vector scale) hazard chain; the trailing update is
pure DGEMM. The blocked form makes that split explicit - panel = hazards,
trailing = throughput - which is why the adder/multiplier depths from
section 4.1 carry over and only sqrt/div need their own analysis.

All routines are jittable (static shapes, masked updates inside fori_loop).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro import obs as _obs
from repro.blas.level3 import gemm
from repro.lapack.cholesky import default_block


def _house_column(a: jnp.ndarray, k: int | jnp.ndarray,
                  row0: int | jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Householder vector for column ``k`` of ``a``, rows >= row0.

    Returns (v, tau) with v[row0..] the reflector (v[row0] = 1), zeros above.
    H = I - tau v v^T maps the column to (-sign(x0) ||x||) e_row0.
    """
    m = a.shape[0]
    rows = jnp.arange(m)
    mask = rows >= row0
    x = jnp.where(mask, a[:, k], 0.0)
    normx = jnp.sqrt(jnp.sum(x * x))
    x0 = a[row0, k]
    sign = jnp.where(x0 >= 0, 1.0, -1.0).astype(a.dtype)
    alpha = x0 + sign * normx                       # v0 before normalization
    safe = jnp.abs(alpha) > jnp.finfo(a.dtype).tiny
    alpha = jnp.where(safe, alpha, 1.0)
    v = jnp.where(rows > row0, x / alpha, 0.0)
    v = jnp.where(rows == row0, 1.0, v)
    v = jnp.where(mask, v, 0.0)
    vtv = jnp.sum(v * v)
    tau = jnp.where(safe & (normx > 0), 2.0 / vtv, 0.0).astype(a.dtype)
    return v, tau


def geqrf_unblocked(a: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Unblocked Householder QR in LAPACK packed layout.

    Parameters
    ----------
    a : (m, n) matrix (float32/float64), any aspect ratio.

    Returns
    -------
    (packed, tau)
        ``packed``: R on/above the diagonal, reflector tails below;
        ``tau``: (min(m, n),) reflector scales.

    Notes
    -----
    Oracle: ``tests/test_lapack.py`` (Q/R round-trip vs
    ``np.linalg.qr``).
    """
    m, n = a.shape
    kmax = min(m, n)

    def body(k, carry):
        A, tau = carry
        v, tk = _house_column(A, k, k)
        # apply H = I - tau v v^T to columns >= k only (earlier columns hold
        # stored reflector tails which H must not touch)
        w = tk * (v @ A)                             # (n,)
        w = jnp.where(jnp.arange(n) >= k, w, 0.0)
        A = A - jnp.outer(v, w)
        # store the reflector tail below the diagonal of column k
        col = jnp.where(jnp.arange(m) > k, v, A[:, k])
        A = A.at[:, k].set(col)
        return A, tau.at[k].set(tk)

    A, tau = lax.fori_loop(0, kmax, body, (a, jnp.zeros((kmax,), a.dtype)))
    return A, tau


def _larft(v: jnp.ndarray, tau: jnp.ndarray) -> jnp.ndarray:
    """Forward compact-WY T factor: Q = I - V T V^T (T upper triangular)."""
    nb = tau.shape[0]

    def body(k, t):
        # T[:k, k] = -tau_k * T[:k, :k] @ (V^T v_k);  T[k, k] = tau_k
        col = (v.T @ v[:, k])                        # (nb,)
        col = jnp.where(jnp.arange(nb) < k, col, 0.0)
        tcol = -tau[k] * (t @ col)
        tcol = jnp.where(jnp.arange(nb) < k, tcol, 0.0)
        tcol = tcol.at[k].set(tau[k])
        return t.at[:, k].set(tcol)

    return lax.fori_loop(0, nb, body, jnp.zeros((nb, nb), v.dtype))


def geqrf(a: jnp.ndarray, block: Optional[int] = None,
          policy: Optional[str] = None,
          registry=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Blocked Householder QR, compact WY (LAPACK DGEQRF).

    Python loop over static panel boundaries -> still a single jittable
    computation.

    Parameters
    ----------
    a : (m, n) matrix (float32/float64).
    block : panel width NB; ``None`` takes
        ``plan_factorization(kind="geqrf")``'s model pick at a's dtype.
    registry : tuned-config registry forwarded to every trailing update
        (``None`` = the process default).
    policy : {"reference", "model", "tuned"}, optional
        The trailing compact-WY triple product is three GEMMs dispatched
        through :func:`repro.blas.level3.gemm`, resolved by
        :mod:`repro.tune.dispatch` (``"model"`` is the Pallas MXU kernel,
        ``"tuned"`` the registry config).

    Returns
    -------
    (packed, tau)
        Same LAPACK packed contract as :func:`geqrf_unblocked`.

    Notes
    -----
    Oracle: ``tests/test_lapack.py`` and ``tests/test_lapack_batched.py``
    (round-trip incl. tall and ill-conditioned inputs); per-policy
    agreement in ``tests/test_tune.py``.
    """
    from repro.tune.policy import resolve_policy
    pol = resolve_policy(policy)
    m, n = a.shape
    kmax = min(m, n)
    if block is None:
        block = default_block(kmax, "geqrf", a.dtype)
    if kmax <= block:
        return geqrf_unblocked(a)
    taus = []
    for j0 in range(0, kmax, block):
        nb = min(block, kmax - j0)
        # panel factorization (unblocked on the full height, masked rows)
        panel = a[:, j0:j0 + nb]

        def pbody(k, carry):
            P, tau = carry
            v, tk = _house_column(P, k, j0 + k)
            w = tk * (v @ P)
            w = jnp.where(jnp.arange(nb) >= k, w, 0.0)
            P = P - jnp.outer(v, w)
            col = jnp.where(jnp.arange(m) > j0 + k, v, P[:, k])
            P = P.at[:, k].set(col)
            return P, tau.at[k].set(tk)

        with _obs.span("geqrf.panel", cat="panel", j0=j0, nb=nb,
                       flops=2 * (m - j0) * nb * nb):
            panel, tau = lax.fori_loop(0, nb, pbody,
                                       (panel, jnp.zeros((nb,), a.dtype)))
        a = a.at[:, j0:j0 + nb].set(panel)
        taus.append(tau)
        # trailing update: C <- (I - V T V^T)^T C = C - V T^T (V^T C)
        if j0 + nb < n:
            rest = n - j0 - nb              # trailing columns
            with _obs.span("geqrf.trailing", cat="trailing", j0=j0, nb=nb,
                           flops=4 * m * nb * rest + 2 * nb * nb * rest):
                rows = jnp.arange(m)
                V = jnp.where(rows[:, None] > (j0 + jnp.arange(nb))[None, :],
                              panel, 0.0)
                V = jnp.where(rows[:, None] == (j0 + jnp.arange(nb))[None, :],
                              1.0, V)
                T = _larft(V, tau)
                C = a[:, j0 + nb:]
                W = gemm(V, C, transa=True, policy=pol,
                         registry=registry)           # (nb, rest)   GEMM
                W = T.T @ W                           # small (nb x nb) GEMM
                a = a.at[:, j0 + nb:].set(
                    C - gemm(V, W, policy=pol,
                             registry=registry))      # GEMM
    return a, jnp.concatenate(taus)


def q_from_geqrf(packed: jnp.ndarray, tau: jnp.ndarray) -> jnp.ndarray:
    """Accumulate the full (m, m) orthogonal Q from a packed
    :func:`geqrf` result (LAPACK DORGQR, applied in reverse reflector
    order). Oracle: ``tests/test_lapack.py`` (orthogonality +
    round-trip)."""
    m = packed.shape[0]
    kmax = tau.shape[0]
    rows = jnp.arange(m)

    def body(i, q):
        k = kmax - 1 - i                              # apply in reverse
        v = jnp.where(rows > k, packed[:, k], 0.0)
        v = v.at[k].set(1.0)
        v = jnp.where(rows >= k, v, 0.0)
        w = tau[k] * (v @ q)
        return q - jnp.outer(v, w)

    return lax.fori_loop(0, kmax, body, jnp.eye(m, dtype=packed.dtype))


def qr(a: jnp.ndarray, block: Optional[int] = None,
       policy: Optional[str] = None,
       registry=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Convenience thin-QR: returns (Q (m, min(m,n)), R (min(m,n), n))
    from :func:`geqrf` + :func:`q_from_geqrf`; same block/policy contract
    as :func:`geqrf`."""
    packed, tau = geqrf(a, block=block, policy=policy, registry=registry)
    q = q_from_geqrf(packed, tau)
    r = jnp.triu(packed)[: min(a.shape), :]
    return q[:, : min(a.shape)], r
