"""BLAS substrate numerics (+ hypothesis properties)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from hypothesis.extra import numpy as hnp

from repro import blas

F32 = st.floats(-10, 10, width=32)


def _vec(n=st.integers(2, 200)):
    return n.flatmap(lambda k: hnp.arrays(np.float32, (k,), elements=F32))


@given(_vec())
@settings(max_examples=40, deadline=None)
def test_property_ddot_schedules_agree(x):
    y = np.roll(x, 1)
    ref = float(np.dot(x.astype(np.float64), y.astype(np.float64)))
    scale = max(float(np.sum(np.abs(x * y))), 1.0)
    for s in ("tree", "sequential", "strided"):
        got = float(blas.dot(jnp.asarray(x), jnp.asarray(y), schedule=s))
        assert abs(got - ref) / scale < 1e-4, s


@given(_vec())
@settings(max_examples=30, deadline=None)
def test_property_nrm2_overflow_safe(x):
    got = float(blas.nrm2(jnp.asarray(x)))
    ref = float(np.linalg.norm(x.astype(np.float64)))
    assert got == pytest.approx(ref, rel=1e-4, abs=1e-5)
    # the scaled form survives values near fp32 max
    big = jnp.asarray(x) * 1e30
    assert np.isfinite(float(blas.nrm2(big))) or float(jnp.max(jnp.abs(big))) == np.inf


def test_gemv_gemm(rng):
    a = jnp.asarray(rng.normal(size=(24, 36)).astype(np.float32))
    x = jnp.asarray(rng.normal(size=36).astype(np.float32))
    np.testing.assert_allclose(np.asarray(blas.gemv(a, x)),
                               np.asarray(a) @ np.asarray(x), rtol=2e-4,
                               atol=1e-4)
    b = jnp.asarray(rng.normal(size=(36, 12)).astype(np.float32))
    np.testing.assert_allclose(np.asarray(blas.gemm(a, b)),
                               np.asarray(a) @ np.asarray(b), rtol=2e-4,
                               atol=1e-4)


def test_gemm_alpha_beta(rng):
    a = jnp.asarray(rng.normal(size=(8, 8)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(8, 8)).astype(np.float32))
    c = jnp.asarray(rng.normal(size=(8, 8)).astype(np.float32))
    out = blas.gemm(a, b, c=c, alpha=2.0, beta=-1.0)
    ref = 2.0 * np.asarray(a) @ np.asarray(b) - np.asarray(c)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=1e-4)


def test_trsv_trsm(rng):
    n = 40
    a = jnp.asarray(rng.normal(size=(n, n)).astype(np.float32))
    t = jnp.tril(a) + 4 * jnp.eye(n)
    b = jnp.asarray(rng.normal(size=n).astype(np.float32))
    x = blas.trsv(t, b, lower=True)
    np.testing.assert_allclose(np.asarray(t @ x), np.asarray(b), atol=1e-4)
    bm = jnp.asarray(rng.normal(size=(n, 7)).astype(np.float32))
    for lower in (True, False):
        tt = t if lower else t.T
        xm = blas.trsm(tt, bm, lower=lower, block=16)
        np.testing.assert_allclose(np.asarray(tt @ xm), np.asarray(bm),
                                   atol=2e-4)


def test_trsm_right_side(rng):
    n, m = 24, 10
    t = jnp.tril(jnp.asarray(rng.normal(size=(n, n)).astype(np.float32))) \
        + 4 * jnp.eye(n)
    b = jnp.asarray(rng.normal(size=(m, n)).astype(np.float32))
    x = blas.trsm(t, b, lower=True, left=False, block=8)
    np.testing.assert_allclose(np.asarray(x @ t), np.asarray(b), atol=2e-4)


def test_syrk(rng):
    a = jnp.asarray(rng.normal(size=(12, 20)).astype(np.float32))
    c = blas.syrk(a)
    np.testing.assert_allclose(np.asarray(c), np.asarray(a @ a.T), rtol=2e-4,
                               atol=1e-4)


def test_ddot_kernel_dispatch(rng):
    x = jnp.asarray(rng.normal(size=2000).astype(np.float32))
    y = jnp.asarray(rng.normal(size=2000).astype(np.float32))
    from repro.kernels import ops
    got = float(ops.dotp(x, y, use_pallas=True, interpret=True))
    assert got == pytest.approx(float(np.dot(np.asarray(x), np.asarray(y))),
                                rel=1e-4)
