"""The plain references against NumPy."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import dense


@pytest.fixture(scope="module")
def mats():
    rng = np.random.default_rng(5)
    n = 96
    g = rng.standard_normal((n, n)).astype(np.float32)
    return g, (g @ g.T / n + np.eye(n)).astype(np.float32), \
        rng.standard_normal(n).astype(np.float32)


@pytest.mark.parametrize("precision", dense.PRECISIONS)
def test_cholesky_and_lu_solve_match_numpy(mats, precision):
    g, spd, b = mats
    tol = 1e-5 if precision == "highest" else 1e-3
    l = jax.jit(lambda a: dense.cholesky(a, nb=32, precision=precision))(spd)
    np.testing.assert_allclose(l, np.linalg.cholesky(spd.astype(np.float64)),
                               rtol=0, atol=tol)
    x = jax.jit(lambda a, v: dense.lu_solve(a, v, nb=32,
                                            precision=precision))(g, b)
    want = np.linalg.solve(g.astype(np.float64), b)
    assert np.max(np.abs(x - want)) / np.max(np.abs(want)) < 100 * tol


def test_three_pass_products_lose_about_sixteen_bits(mats):
    g, _, _ = mats
    exact = g.astype(np.float64) @ g.astype(np.float64)
    scale = np.abs(g).astype(np.float64) @ np.abs(g).astype(np.float64)

    def err(p):
        got = np.asarray(dense.matmul(jnp.asarray(g), jnp.asarray(g), p),
                         np.float64)
        return np.max(np.abs(got - exact) / scale)
    assert err("highest") < 2 ** -20
    assert 2 ** -20 < err("bf16x3") < 2 ** -12


def test_accuracy_arithmetic():
    a = np.array([[2.0, 0.0], [0.0, 4.0]])
    x = np.array([0.5, 0.25 + 1e-6])
    b = np.array([1.0, 1.0])
    u = np.finfo(np.float32).eps / 2
    assert dense.hpl_residual(a, x, b) == pytest.approx(
        4e-6 / (u * (4 * 0.5 + 1) * 2))
    l = np.array([[1.0, 0.0], [0.5, 1.0]])
    spd = l @ l.T
    assert dense.cholesky_ratio(l, spd) == 0.0
    spd2 = spd.copy()
    spd2[0, 0] += 1e-5
    assert dense.cholesky_ratio(l, spd2) == pytest.approx(
        1e-5 / (2 * 1.75 * float(np.finfo(np.float32).eps)), rel=1e-6)

