"""LAPACK substrate: QR / LU / Cholesky / solvers (+ hypothesis)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import lapack


def _rand(rng, m, n):
    return jnp.asarray(rng.normal(size=(m, n)).astype(np.float32))


@pytest.mark.parametrize("block", [8, 999])
@pytest.mark.parametrize("m,n", [(32, 32), (48, 32), (33, 20)])
def test_qr_reconstruction(rng, m, n, block):
    a = _rand(rng, m, n)
    q, r = lapack.qr.qr(a, block=block)
    np.testing.assert_allclose(np.asarray(q @ r), np.asarray(a), atol=5e-4)
    np.testing.assert_allclose(np.asarray(q.T @ q), np.eye(n), atol=5e-4)
    # R upper triangular
    assert float(jnp.max(jnp.abs(jnp.tril(r, -1)))) < 1e-5


def test_qr_matches_numpy_abs(rng):
    a = _rand(rng, 24, 24)
    _, r = lapack.qr.qr(a)
    r_np = np.linalg.qr(np.asarray(a))[1]
    # QR unique up to column signs
    np.testing.assert_allclose(np.abs(np.asarray(r)), np.abs(r_np),
                               atol=5e-4)


@pytest.mark.parametrize("block", [8, 999])
def test_lu_reconstruction(rng, block):
    a = _rand(rng, 40, 40)
    packed, piv = lapack.getrf(a, block=block)
    np.testing.assert_allclose(np.asarray(lapack.lu_reconstruct(packed, piv)),
                               np.asarray(a), atol=5e-4)
    # partial pivoting: |L| <= 1
    l = np.tril(np.asarray(packed), -1)
    assert np.max(np.abs(l)) <= 1.0 + 1e-5


def test_lu_blocked_equals_unblocked(rng):
    a = _rand(rng, 36, 36)
    p1, v1 = lapack.getrf(a, block=8)
    p2, v2 = lapack.getrf_unblocked(a)
    np.testing.assert_allclose(np.asarray(p1), np.asarray(p2), atol=3e-4)
    assert bool(jnp.all(v1 == v2))


def _getrf_whole_matrix_panels(a, block):
    """Oracle: the blocked LU whose panel loop carries the whole matrix,
    swapping full rows and masking the rank-1 update to the panel at
    every step (the driver before dgetf2 ran on the panel alone)."""
    from jax import lax
    from repro.tune import dispatch as _tune
    n, nc = a.shape
    kmax = min(n, nc)
    if kmax <= block:
        return lapack.getrf_unblocked(a)
    pivs, rows = [], jnp.arange(n)
    for j0 in range(0, kmax, block):
        nb = min(block, kmax - j0)

        def pbody(kk, carry, j0=j0, nb=nb):
            A, piv = carry
            k = j0 + kk
            col = jnp.where(rows >= k, jnp.abs(A[:, k]), -jnp.inf)
            p = jnp.argmax(col).astype(jnp.int32)
            piv = piv.at[kk].set(p)
            rk, rp = A[k], A[p]
            A = A.at[k].set(rp).at[p].set(rk)
            pivval = A[k, k]
            safe = jnp.where(jnp.abs(pivval) > 0, pivval, 1.0)
            l = jnp.where(rows > k, A[:, k] / safe, 0.0)
            A = A.at[:, k].set(jnp.where(rows > k, l, A[:, k]))
            cols = jnp.arange(nc)
            urow = jnp.where((cols > k) & (cols < j0 + nb), A[k], 0.0)
            return A - jnp.outer(l, urow), piv

        a, piv = lax.fori_loop(0, nb, pbody, (a, jnp.zeros((nb,), jnp.int32)))
        pivs.append(piv)
        if j0 + nb < nc:
            u12, c_out = _tune.dispatch(
                "trsm+gemm", a[j0:j0 + nb, j0:j0 + nb],
                a[j0:j0 + nb, j0 + nb:], a[j0 + nb:, j0:j0 + nb],
                a[j0 + nb:, j0 + nb:], form="lu", unit_diag=True,
                policy="reference")
            a = a.at[j0:j0 + nb, j0 + nb:].set(u12)
            a = a.at[j0 + nb:, j0 + nb:].set(c_out)
    return a, jnp.concatenate(pivs)


@pytest.mark.parametrize("m,n,block,batched", [
    (64, 64, 16, False),      # square
    (96, 40, 16, False),      # tall
    (40, 96, 16, False),      # wide
    (70, 70, 16, False),      # n not a multiple of the block
    (24, 24, 64, False),      # a single panel
    (48, 48, 16, True),       # vmap through batched_getrf
])
def test_lu_panel_alone_matches_whole_matrix_panels(rng, m, n, block,
                                                    batched):
    """dgetf2 on the panel plus one dlaswp per panel does the whole-matrix
    panel loop's arithmetic element for element: same pivots, same
    packed factors, bit for bit."""
    if batched:
        a = jnp.asarray(rng.normal(size=(3, m, n)).astype(np.float32))
        res = jax.jit(lambda x: lapack.batched_getrf(
            x, block=block, policy="reference"))(a)
        got = res.factors, res.pivots
        want = jax.jit(jax.vmap(
            lambda x: _getrf_whole_matrix_panels(x, block)))(a)
    else:
        a = _rand(rng, m, n)
        got = jax.jit(lambda x: lapack.getrf(x, block=block,
                                             policy="reference"))(a)
        want = jax.jit(lambda x: _getrf_whole_matrix_panels(x, block))(a)
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))


@pytest.mark.parametrize("block", [8, 999])
def test_cholesky(rng, block):
    a = _rand(rng, 32, 32)
    s = a @ a.T + 32 * jnp.eye(32)
    c = lapack.potrf(s, block=block)
    np.testing.assert_allclose(np.asarray(c @ c.T), np.asarray(s), rtol=1e-4,
                               atol=5e-3)
    np.testing.assert_allclose(np.asarray(c), np.linalg.cholesky(np.asarray(s)),
                               rtol=2e-3, atol=5e-3)


def test_gesv(rng):
    a = _rand(rng, 32, 32) + 8 * jnp.eye(32)
    b = _rand(rng, 32, 3)
    x = lapack.gesv(a, b, block=8)
    np.testing.assert_allclose(np.asarray(a @ x), np.asarray(b), atol=2e-3)


def _trsm_row_substitution(a, b, lower, unit_diag, block=64):
    """Oracle: the blocked TRSM that solves each diagonal block by
    substitution, one dependent row a step (the driver before the
    diagonal blocks were inverted)."""
    from jax import lax
    n = a.shape[0]

    def substitute(t, rhs):
        order = jnp.arange(t.shape[0])
        diag = jnp.diagonal(t)
        strict = t - jnp.diag(diag)

        def body(x, i):
            s = rhs[i] - strict[i] @ x
            return x.at[i].set(s if unit_diag else s / diag[i]), None

        return lax.scan(body, jnp.zeros_like(rhs),
                        order if lower else order[::-1])[0]

    blocks = list(range(0, n, block))
    x = jnp.zeros_like(b)
    for i0 in blocks if lower else blocks[::-1]:
        i1 = min(i0 + block, n)
        rhs = b[i0:i1]
        if lower and i0 > 0:
            rhs = rhs - a[i0:i1, :i0] @ x[:i0]
        elif not lower and i1 < n:
            rhs = rhs - a[i0:i1, i1:] @ x[i1:]
        x = x.at[i0:i1].set(substitute(a[i0:i1, i0:i1], rhs))
    return x


def _hpl_residual(a, x, b):
    """HPL's scaled residual ||A x - b|| / (eps (||A|| ||x|| + ||b||) n),
    infinity norms, in float64."""
    a, x, b = (np.asarray(v, np.float64) for v in (a, x, b))
    eps = np.finfo(np.float32).eps
    return np.abs(a @ x - b).max() / (
        eps * (np.abs(a).sum(1).max() * np.abs(x).max() + np.abs(b).max())
        * a.shape[0])


def test_gesv_inverted_blocks_residual_near_substitution(rng):
    """getrs with inverted diagonal blocks stays within 2x the HPL scaled
    residual of row-by-row substitution on the same LU (n = 1024, an
    N(0, 1) system, so U's diagonal blocks are not dominant)."""
    a = _rand(rng, 1024, 1024)
    b = _rand(rng, 1024, 1)
    x = jax.jit(lambda a, b: lapack.gesv(a, b, block=128))(a, b)

    def by_substitution(a, b):
        packed, piv = lapack.getrf(a, block=128)
        y = _trsm_row_substitution(packed, lapack.lu.apply_ipiv(b, piv),
                                   lower=True, unit_diag=True)
        return _trsm_row_substitution(packed, y, lower=False,
                                      unit_diag=False)

    oracle = _hpl_residual(a, jax.jit(by_substitution)(a, b), b)
    assert _hpl_residual(a, x, b) <= 2 * oracle


def test_lstsq_qr(rng):
    a = _rand(rng, 50, 20)
    b = jnp.asarray(rng.normal(size=50).astype(np.float32))
    x = lapack.lstsq_qr(a, b)
    ref = np.linalg.lstsq(np.asarray(a), np.asarray(b), rcond=None)[0]
    np.testing.assert_allclose(np.asarray(x), ref, atol=2e-3)


def test_jit_compatible(rng):
    a = _rand(rng, 24, 24)
    f = jax.jit(lambda m: lapack.getrf(m, block=8))
    packed, piv = f(a)
    np.testing.assert_allclose(np.asarray(lapack.lu_reconstruct(packed, piv)),
                               np.asarray(a), atol=3e-4)
    g = jax.jit(lambda m: lapack.qr.geqrf(m, block=8))
    pk, tau = g(a)
    q = lapack.q_from_geqrf(pk, tau)
    r = jnp.triu(pk)
    np.testing.assert_allclose(np.asarray(q @ r), np.asarray(a), atol=5e-4)


@given(seed=st.integers(0, 2 ** 31 - 1), n=st.integers(4, 48))
@settings(max_examples=15, deadline=None)
def test_property_lu_solves(seed, n):
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.normal(size=(n, n)).astype(np.float32)) \
        + n * jnp.eye(n)
    b = jnp.asarray(rng.normal(size=n).astype(np.float32))
    x = lapack.gesv(a, b, block=16)
    resid = float(jnp.max(jnp.abs(a @ x - b)))
    assert resid < 1e-2 * n


@given(seed=st.integers(0, 2 ** 31 - 1), m=st.integers(6, 40),
       n=st.integers(4, 30))
@settings(max_examples=15, deadline=None)
def test_property_qr_orthogonality(seed, m, n):
    if m < n:
        m, n = n, m
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.normal(size=(m, n)).astype(np.float32))
    q, r = lapack.qr.qr(a, block=16)
    err = float(jnp.max(jnp.abs(q.T @ q - jnp.eye(n))))
    assert err < 3e-3
