"""Round-trip property tests for the blocked + batched LAPACK layer.

A == L L^T (potrf), P A == L U (getrf), A == Q R + Q orthonormal (geqrf),
for both the blocked single-matrix paths and the vmap-batched drivers, on
well-conditioned, ill-conditioned, and non-square inputs - and the blocked
paths must produce identical factors whether trailing updates run through
``a @ b`` or the Pallas kernel (policy="model", interpret mode).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import lapack
from repro.core.codesign import plan_factorization


def _batch(rng, b, m, n):
    return jnp.asarray(rng.normal(size=(b, m, n)).astype(np.float32))


def _spd_batch(rng, b, n, ridge=None):
    a = rng.normal(size=(b, n, n)).astype(np.float32)
    s = a @ np.swapaxes(a, 1, 2) + (ridge or n) * np.eye(n, dtype=np.float32)
    return jnp.asarray(s)


# --------------------------- blocked round trips ----------------------------

@pytest.mark.parametrize("block", [8, 16, None])
def test_blocked_potrf_roundtrip(rng, block):
    s = _spd_batch(rng, 1, 48)[0]
    l = lapack.potrf(s, block=block)
    np.testing.assert_allclose(np.asarray(l @ l.T), np.asarray(s),
                               rtol=1e-4, atol=5e-3)
    assert float(jnp.max(jnp.abs(jnp.triu(l, 1)))) == 0.0


@pytest.mark.parametrize("m,n", [(48, 48), (56, 40), (40, 56)])
def test_blocked_getrf_roundtrip(rng, m, n):
    a = _batch(rng, 1, m, n)[0]
    packed, piv = lapack.getrf(a, block=16)
    if m == n:
        np.testing.assert_allclose(
            np.asarray(lapack.lu_reconstruct(packed, piv)), np.asarray(a),
            atol=5e-4)
    # partial pivoting keeps multipliers bounded regardless of shape
    assert float(jnp.max(jnp.abs(jnp.tril(packed, -1)))) <= 1.0 + 1e-5


@pytest.mark.parametrize("m,n", [(48, 48), (64, 40), (33, 20)])
def test_blocked_geqrf_roundtrip(rng, m, n):
    a = _batch(rng, 1, m, n)[0]
    q, r = lapack.qr.qr(a, block=16)
    np.testing.assert_allclose(np.asarray(q @ r), np.asarray(a), atol=5e-4)
    np.testing.assert_allclose(np.asarray(q.T @ q), np.eye(min(m, n)),
                               atol=5e-4)


# --------------------- kernel path == reference path ------------------------

def test_potrf_kernel_path_identical(rng):
    s = _spd_batch(rng, 1, 48)[0]
    ref = lapack.potrf(s, block=16, policy="reference")
    ker = lapack.potrf(s, block=16, policy="model")
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref), atol=1e-5)


def test_getrf_kernel_path_identical(rng):
    a = _batch(rng, 1, 48, 48)[0]
    ref, piv_ref = lapack.getrf(a, block=16, policy="reference")
    ker, piv_ker = lapack.getrf(a, block=16, policy="model")
    assert bool(jnp.all(piv_ref == piv_ker))
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref), atol=1e-5)


def test_geqrf_kernel_path_identical(rng):
    a = _batch(rng, 1, 48, 32)[0]
    ref, tau_ref = lapack.geqrf(a, block=16, policy="reference")
    ker, tau_ker = lapack.geqrf(a, block=16, policy="model")
    np.testing.assert_allclose(np.asarray(tau_ker), np.asarray(tau_ref),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref), atol=1e-5)


# ------------------------- batched == unbatched -----------------------------

def test_batched_potrf_matches_unbatched(rng):
    s = _spd_batch(rng, 6, 32)
    res = lapack.batched_potrf(s, block=8)
    for i in range(s.shape[0]):
        one = lapack.potrf(s[i], block=8)
        np.testing.assert_allclose(np.asarray(res.factors[i]),
                                   np.asarray(one), atol=1e-5)
    np.testing.assert_allclose(np.asarray(lapack.reconstruct(res)),
                               np.asarray(s), rtol=1e-4, atol=5e-3)


def test_batched_getrf_matches_unbatched(rng):
    a = _batch(rng, 6, 32, 32)
    res = lapack.batched_getrf(a, block=8)
    for i in range(a.shape[0]):
        packed, piv = lapack.getrf(a[i], block=8)
        assert bool(jnp.all(res.pivots[i] == piv))
        np.testing.assert_allclose(np.asarray(res.factors[i]),
                                   np.asarray(packed), atol=1e-5)
    np.testing.assert_allclose(np.asarray(lapack.reconstruct(res)),
                               np.asarray(a), atol=5e-4)


@pytest.mark.parametrize("m,n", [(32, 32), (40, 24)])
def test_batched_geqrf_matches_unbatched(rng, m, n):
    a = _batch(rng, 5, m, n)
    res = lapack.batched_geqrf(a, block=8)
    for i in range(a.shape[0]):
        packed, tau = lapack.geqrf(a[i], block=8)
        np.testing.assert_allclose(np.asarray(res.factors[i]),
                                   np.asarray(packed), atol=1e-5)
        np.testing.assert_allclose(np.asarray(res.tau[i]), np.asarray(tau),
                                   atol=1e-5)
    np.testing.assert_allclose(np.asarray(lapack.reconstruct(res)),
                               np.asarray(a), atol=5e-4)


def test_batched_kernel_path_matches(rng):
    """vmap composes with the Pallas interpret-mode trailing updates."""
    s = _spd_batch(rng, 3, 32)
    ref = lapack.batched_potrf(s, block=16, policy="reference")
    ker = lapack.batched_potrf(s, block=16, policy="model")
    np.testing.assert_allclose(np.asarray(ker.factors),
                               np.asarray(ref.factors), atol=1e-5)


# ------------------------------ batched solve -------------------------------

def test_batched_solve_all_kinds(rng):
    B, n = 4, 32
    a = _batch(rng, B, n, n) + 8 * jnp.eye(n)
    s = _spd_batch(rng, B, n)
    b = jnp.asarray(rng.normal(size=(B, n)).astype(np.float32))

    x = lapack.batched_solve(lapack.batched_getrf(a, block=8), b)
    resid = jnp.einsum("bij,bj->bi", a, x) - b
    assert float(jnp.max(jnp.abs(resid))) < 2e-3

    x = lapack.batched_solve(lapack.batched_potrf(s, block=8), b)
    resid = jnp.einsum("bij,bj->bi", s, x) - b
    assert float(jnp.max(jnp.abs(resid))) < 2e-3

    # least squares: tall systems, compare against numpy per item
    at = _batch(rng, B, 48, 20)
    bt = jnp.asarray(rng.normal(size=(B, 48)).astype(np.float32))
    x = lapack.batched_solve(lapack.batched_geqrf(at, block=8), bt)
    for i in range(B):
        ref = np.linalg.lstsq(np.asarray(at[i]), np.asarray(bt[i]),
                              rcond=None)[0]
        np.testing.assert_allclose(np.asarray(x[i]), ref, atol=2e-3)


def test_batched_solve_matrix_rhs(rng):
    B, n, k = 3, 24, 5
    a = _batch(rng, B, n, n) + 8 * jnp.eye(n)
    b = jnp.asarray(rng.normal(size=(B, n, k)).astype(np.float32))
    x = lapack.batched_solve(lapack.batched_getrf(a, block=8), b)
    resid = a @ x - b
    assert float(jnp.max(jnp.abs(resid))) < 2e-3


# --------------------------- edge cases & pytree ----------------------------

def test_potrf_ill_conditioned_stays_finite(rng):
    """Condition number ~1e6: factor must stay finite and reconstruct to a
    relative accuracy ~ cond * eps."""
    n = 24
    q, _ = np.linalg.qr(rng.normal(size=(n, n)))
    d = np.logspace(0, -6, n)
    s = jnp.asarray((q @ np.diag(d) @ q.T).astype(np.float32))
    s = (s + s.T) / 2 + 1e-6 * jnp.eye(n)
    l = lapack.potrf(s, block=8)
    assert bool(jnp.all(jnp.isfinite(l)))
    np.testing.assert_allclose(np.asarray(l @ l.T), np.asarray(s),
                               atol=1e-4)


def test_getrf_singular_column_no_nan(rng):
    """A zero column hits the safe-pivot path, never produces NaN."""
    a = np.asarray(_batch(rng, 1, 16, 16)[0]).copy()
    a[:, 3] = 0.0
    packed, piv = lapack.getrf(jnp.asarray(a), block=8)
    assert bool(jnp.all(jnp.isfinite(packed)))


def test_batched_solve_wide_geqrf_rejected(rng):
    """m < n is underdetermined: clear error, not a shape blowup."""
    a = _batch(rng, 2, 8, 12)
    res = lapack.batched_geqrf(a, block=4)
    with pytest.raises(ValueError, match="m >= n"):
        lapack.batched_solve(res, jnp.asarray(np.ones((2, 8), np.float32)))
    rl = lapack.batched_getrf(_batch(rng, 2, 12, 8), block=4)
    with pytest.raises(ValueError, match="square"):
        lapack.batched_solve(rl, jnp.asarray(np.ones((2, 12), np.float32)))


def test_geqrf_wide_matrix(rng):
    """m < n: kmax = m reflectors, R is m x n trapezoidal."""
    a = _batch(rng, 1, 20, 33)[0]
    packed, tau = lapack.geqrf(a, block=8)
    assert tau.shape == (20,)
    q = lapack.q_from_geqrf(packed, tau)
    r = jnp.triu(packed)
    np.testing.assert_allclose(np.asarray(q @ r), np.asarray(a), atol=5e-4)


def test_factorization_result_is_pytree(rng):
    a = _batch(rng, 2, 16, 16)
    res = lapack.batched_getrf(a, block=8)
    leaves = jax.tree_util.tree_leaves(res)
    assert len(leaves) == 2  # factors + pivots; static kind/block in aux
    rebuilt = jax.tree_util.tree_map(lambda x: x, res)
    assert rebuilt.kind == "getrf" and rebuilt.block == 8
    # jit through the pytree API end to end
    f = jax.jit(lambda m, b: lapack.batched_solve(
        lapack.batched_getrf(m, block=8), b))
    b = jnp.asarray(np.ones((2, 16), np.float32))
    x = f(a, b)
    assert x.shape == (2, 16)


def test_plan_factorization_defaults_are_sane():
    """The codesign model must return usable NB everywhere on the grid the
    benchmarks sweep, and collapse to unblocked for panel-sized problems."""
    for kind in ("potrf", "getrf", "geqrf"):
        for n in (4, 16, 64, 256, 2048):
            p = plan_factorization(n, kind=kind)
            assert 1 <= p.block <= max(n, 8)
            assert p.modeled_time > 0
            assert 0.0 <= p.panel_fraction <= 1.0
        small = plan_factorization(16, kind=kind)
        assert small.block == 16  # single panel -> unblocked path
    with pytest.raises(ValueError):
        plan_factorization(64, kind="svd")


# ------------------ dtype-generic repro.linalg front-end --------------------
# Round-trips of the batched drivers through the new context-scoped API in
# every in-process dtype (float64 runs in tests/test_linalg.py's x64
# subprocess grid); tolerances from the shared dtype_tolerances helper.

from conftest import LINALG_DTYPES  # noqa: F401  (shared dtype grid)

from repro import linalg


@pytest.mark.parametrize("dtype", LINALG_DTYPES)
def test_linalg_batched_cholesky_roundtrip_dtypes(rng, assert_close, dtype):
    s = _spd_batch(rng, 4, 16).astype(dtype)
    res = linalg.batched_cholesky(s, block=8)
    assert res.factors.dtype == jnp.dtype(dtype)
    assert_close(jnp.einsum("bij,bkj->bik", res.factors, res.factors),
                 np.asarray(s.astype(jnp.float32), np.float64), scale=16.0)


@pytest.mark.parametrize("dtype", LINALG_DTYPES)
def test_linalg_batched_lu_roundtrip_dtypes(rng, assert_close, dtype):
    a = _batch(rng, 4, 16, 16).astype(dtype)
    res = linalg.batched_lu(a, block=8)
    assert_close(lapack.reconstruct(res),
                 np.asarray(a.astype(jnp.float32), np.float64), scale=16.0)


@pytest.mark.parametrize("dtype", LINALG_DTYPES)
def test_linalg_batched_qr_roundtrip_dtypes(rng, assert_close, dtype):
    a = _batch(rng, 3, 20, 12).astype(dtype)
    res = linalg.batched_qr(a, block=8)
    assert res.kind == "geqrf" and res.tau.dtype == jnp.dtype(dtype)
    assert_close(lapack.reconstruct(res),
                 np.asarray(a.astype(jnp.float32), np.float64), scale=16.0)


@pytest.mark.parametrize("pol", ["reference", "model", "tuned"])
def test_linalg_batched_solve_policy_grid(rng, pol):
    B, n = 3, 16
    a = _batch(rng, B, n, n) + 8 * jnp.eye(n)
    b = jnp.asarray(rng.normal(size=(B, n)).astype(np.float32))
    with linalg.use(policy=pol):
        x = linalg.batched_solve(linalg.batched_lu(a, block=8), b)
    resid = jnp.einsum("bij,bj->bi", a, x) - b
    assert float(jnp.max(jnp.abs(resid))) < 2e-3
