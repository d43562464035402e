"""Differential tests: every BLAS routine vs a NumPy/SciPy oracle.

Levels 1-3 over a parametrized shape x dtype x transpose grid; all
comparisons go through the shared dtype-keyed tolerance helper in
conftest.py (oracle computed in float64). This is the testing convention
ROADMAP.md prescribes for every new numeric routine.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.linalg

from repro import blas
from repro.blas import level3
from repro.obs import counters

DTYPES = [np.float32, jnp.bfloat16]
SHAPES_MM = [(8, 8, 8), (24, 36, 12), (17, 5, 29), (1, 64, 1)]
VEC_NS = [1, 7, 64, 1000]


def _mk(rng, shape, dtype):
    return jnp.asarray(rng.normal(size=shape).astype(np.float32)).astype(dtype)


def _f64(x):
    return np.asarray(x.astype(jnp.float32)).astype(np.float64)


# ------------------------------- level 1 ------------------------------------

@pytest.mark.parametrize("n", VEC_NS)
@pytest.mark.parametrize("schedule", ["tree", "sequential", "strided"])
def test_ddot_vs_numpy(rng, assert_close, n, schedule):
    x = _mk(rng, n, np.float32)
    y = _mk(rng, n, np.float32)
    got = blas.dot(x, y, schedule=schedule, accumulators=8)
    assert_close(got, np.dot(_f64(x), _f64(y)), scale=max(1.0, n / 64))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("n", VEC_NS)
def test_daxpy_dscal_vs_numpy(rng, assert_close, n, dtype):
    x, y = _mk(rng, n, dtype), _mk(rng, n, dtype)
    assert_close(blas.axpy(2.5, x, y), 2.5 * _f64(x) + _f64(y))
    assert_close(blas.scal(-0.5, x), -0.5 * _f64(x))


@pytest.mark.parametrize("n", VEC_NS)
def test_dnrm2_dasum_idamax_vs_numpy(rng, assert_close, n):
    x = _mk(rng, n, np.float32)
    assert_close(blas.nrm2(x), np.linalg.norm(_f64(x)))
    assert_close(blas.level1.asum(x), np.abs(_f64(x)).sum(),
                 scale=max(1.0, n / 64))
    assert int(blas.iamax(x)) == int(np.argmax(np.abs(_f64(x))))


def test_drot_vs_oracle(rng, assert_close):
    x, y = _mk(rng, 33, np.float32), _mk(rng, 33, np.float32)
    c, s = np.cos(0.3), np.sin(0.3)
    gx, gy = blas.level1.rot(x, y, c, s)
    assert_close(gx, c * _f64(x) + s * _f64(y))
    assert_close(gy, c * _f64(y) - s * _f64(x))


# ------------------------------- level 2 ------------------------------------

@pytest.mark.parametrize("trans", [False, True])
@pytest.mark.parametrize("m,n", [(8, 8), (24, 36), (17, 5), (1, 64)])
def test_dgemv_vs_numpy(rng, assert_close, m, n, trans):
    a = _mk(rng, (m, n), np.float32)
    x = _mk(rng, m if trans else n, np.float32)
    y = _mk(rng, n if trans else m, np.float32)
    ref = (_f64(a).T if trans else _f64(a)) @ _f64(x)
    assert_close(blas.gemv(a, x, trans=trans), ref)
    got = blas.gemv(a, x, trans=trans, alpha=1.5, beta=-2.0, y=y)
    assert_close(got, 1.5 * ref - 2.0 * _f64(y))


def test_dger_vs_numpy(rng, assert_close):
    x, y = _mk(rng, 13, np.float32), _mk(rng, 21, np.float32)
    a = _mk(rng, (13, 21), np.float32)
    assert_close(blas.ger(0.75, x, y, a),
                 _f64(a) + 0.75 * np.outer(_f64(x), _f64(y)))


@pytest.mark.parametrize("unit_diag", [False, True])
@pytest.mark.parametrize("lower", [False, True])
@pytest.mark.parametrize("n", [5, 32, 65])
def test_dtrsv_vs_scipy(rng, assert_close, n, lower, unit_diag):
    a = _mk(rng, (n, n), np.float32)
    t = (jnp.tril(a) if lower else jnp.triu(a)) + 4 * jnp.eye(n)
    b = _mk(rng, n, np.float32)
    got = blas.trsv(t, b, lower=lower, unit_diag=unit_diag)
    ref = scipy.linalg.solve_triangular(
        _f64(t), _f64(b), lower=lower, unit_diagonal=unit_diag)
    assert_close(got, ref, scale=4.0)


# ------------------------------- level 3 ------------------------------------

@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("ta,tb", [(False, False), (True, False),
                                   (False, True), (True, True)])
@pytest.mark.parametrize("m,n,k", SHAPES_MM)
def test_dgemm_transpose_grid_vs_numpy(rng, assert_close, m, n, k, ta, tb,
                                       dtype):
    a = _mk(rng, (k, m) if ta else (m, k), dtype)
    b = _mk(rng, (n, k) if tb else (k, n), dtype)
    opa, opb = (a.T if ta else a), (b.T if tb else b)
    ref = (_f64(a).T if ta else _f64(a)) @ (_f64(b).T if tb else _f64(b))
    assert_close(blas.gemm(opa, opb), ref, scale=max(1.0, k / 16))


@pytest.mark.parametrize("m,n,k", [(24, 36, 12), (17, 5, 29)])
def test_dgemm_alpha_beta_vs_numpy(rng, assert_close, m, n, k):
    a, b = _mk(rng, (m, k), np.float32), _mk(rng, (k, n), np.float32)
    c = _mk(rng, (m, n), np.float32)
    got = blas.gemm(a, b, c=c, alpha=-1.5, beta=0.5)
    assert_close(got, -1.5 * _f64(a) @ _f64(b) + 0.5 * _f64(c))


@pytest.mark.parametrize("m,n,k", SHAPES_MM)
def test_dgemm_kernel_path_vs_numpy(rng, assert_close, m, n, k):
    """policy="model" (Pallas, interpret on CPU) against the same oracle."""
    a, b = _mk(rng, (m, k), np.float32), _mk(rng, (k, n), np.float32)
    got = blas.gemm(a, b, policy="model")
    assert_close(got, _f64(a) @ _f64(b), scale=max(1.0, k / 16))


@pytest.mark.parametrize("lower", [False, True])
def test_dsyrk_vs_numpy(rng, assert_close, lower):
    a = _mk(rng, (12, 20), np.float32)
    ref = _f64(a) @ _f64(a).T
    assert_close(blas.syrk(a, lower=lower), ref)
    c = _mk(rng, (12, 12), np.float32)
    got = blas.syrk(a, c=c, alpha=2.0, beta=-1.0, lower=lower,
                    policy="model")
    # BLAS semantics: only the selected triangle of C is referenced
    tri = np.tril if lower else np.triu
    assert_close(tri(np.asarray(got)), tri(2.0 * ref - _f64(c)))


@pytest.mark.parametrize("policy", ["reference", "model"])
@pytest.mark.parametrize("left", [True, False])
@pytest.mark.parametrize("lower", [True, False])
@pytest.mark.parametrize("n,nrhs,block", [(24, 7, 8), (40, 3, 999)])
def test_dtrsm_grid_vs_scipy(rng, assert_close, n, nrhs, block, lower, left,
                             policy):
    a = _mk(rng, (n, n), np.float32)
    t = (jnp.tril(a) if lower else jnp.triu(a)) + 4 * jnp.eye(n)
    b = _mk(rng, (n, nrhs) if left else (nrhs, n), np.float32)
    got = blas.trsm(t, b, lower=lower, left=left, block=block,
                    policy=policy)
    if left:
        ref = scipy.linalg.solve_triangular(_f64(t), _f64(b), lower=lower)
    else:  # X T = B
        ref = scipy.linalg.solve_triangular(_f64(t).T, _f64(b).T,
                                            lower=not lower).T
    assert_close(got, ref, scale=4.0)


@pytest.mark.parametrize("left", [True, False])
@pytest.mark.parametrize("lower,unit_diag", [(True, True), (False, False),
                                             (True, False), (False, True)])
@pytest.mark.parametrize("n,nrhs", [(200, 5), (256, 1), (256, 37)])
def test_trsm_inverted_blocks_vs_scipy(rng, assert_close, n, nrhs, lower,
                                       unit_diag, left):
    """The blocked path (n > block 64, a partial last block at n = 200)
    on triangles of scipy's LU of an N(0, 1) matrix: L and U as getrs
    solves them (unit lower, non-dominant upper), and their transposes for
    the other two (UPLO, DIAG) pairs. The whole packed array is passed, so
    only the named triangle and, unless unit, the diagonal may be read."""
    lu = scipy.linalg.lu_factor(rng.normal(size=(n, n)))[0]
    packed = (lu if lower == unit_diag else lu.T).astype(np.float32)
    t = (np.tril(packed) if lower else np.triu(packed)).astype(np.float64)
    if unit_diag:
        np.fill_diagonal(t, 1.0)
    b = _mk(rng, (n, nrhs) if left else (nrhs, n), np.float32)
    got = level3.trsm(jnp.asarray(packed), b, lower=lower,
                      unit_diag=unit_diag, left=left, block=64)
    if left:
        ref = scipy.linalg.solve_triangular(t, _f64(b), lower=lower)
    else:  # X T = B
        ref = scipy.linalg.solve_triangular(t.T, _f64(b).T,
                                            lower=not lower).T
    assert_close(got, ref, scale=4.0)


def test_trsm_blocked_path_has_no_loop(rng):
    """n = 512 at block 64: the eight diagonal blocks are inverted as one
    stack, so the compiled solve holds no while loop."""
    t = jnp.asarray(np.tril(rng.normal(size=(512, 512))) + 16 * np.eye(512),
                    jnp.float32)
    b = _mk(rng, (512, 3), np.float32)
    before = counters.value("trsm.inverted_blocks")
    hlo = jax.jit(lambda t, b: level3.trsm(t, b, block=64)).lower(
        t, b).compile().as_text()
    assert counters.value("trsm.inverted_blocks") - before == 8
    assert "while" not in hlo


# ------------------ dtype-generic repro.linalg front-end --------------------
# Float64 legs need JAX_ENABLE_X64 and run in tests/test_linalg.py's
# subprocess grid; the in-process grid covers every dtype the default
# config supports.

from conftest import LINALG_DTYPES

from repro import linalg


@pytest.mark.parametrize("dtype", LINALG_DTYPES)
@pytest.mark.parametrize("ta,tb", [(False, False), (True, True)])
@pytest.mark.parametrize("m,n,k", SHAPES_MM)
def test_linalg_gemm_dtype_grid(rng, assert_close, m, n, k, ta, tb, dtype):
    a = _mk(rng, (k, m) if ta else (m, k), dtype)
    b = _mk(rng, (n, k) if tb else (k, n), dtype)
    got = linalg.gemm(a, b, transa=ta, transb=tb)
    assert got.dtype == jnp.dtype(dtype)
    ref = (_f64(a).T if ta else _f64(a)) @ (_f64(b).T if tb else _f64(b))
    assert_close(got, ref, scale=max(1.0, k / 16))


@pytest.mark.parametrize("dtype", LINALG_DTYPES)
@pytest.mark.parametrize("trans", [False, True])
def test_linalg_gemv_dtype_grid(rng, assert_close, trans, dtype):
    a = _mk(rng, (24, 36), dtype)
    x = _mk(rng, 24 if trans else 36, dtype)
    got = linalg.gemv(a, x, trans=trans)
    assert_close(got, (_f64(a).T if trans else _f64(a)) @ _f64(x),
                 scale=2.0)


@pytest.mark.parametrize("dtype", LINALG_DTYPES)
@pytest.mark.parametrize("lower", [False, True])
def test_linalg_trsm_dtype_grid(rng, assert_close, lower, dtype):
    n = 24
    a = _mk(rng, (n, n), dtype)
    t = (jnp.tril(a) if lower else jnp.triu(a)) + 4 * jnp.eye(n, dtype=dtype)
    b = _mk(rng, (n, 5), dtype)
    got = linalg.trsm(t, b, lower=lower, block=8)
    ref = scipy.linalg.solve_triangular(_f64(t), _f64(b), lower=lower)
    assert_close(got, ref, scale=8.0)


@pytest.mark.parametrize("dtype", LINALG_DTYPES)
def test_linalg_level1_dtype_grid(rng, assert_close, dtype):
    x, y = _mk(rng, 129, dtype), _mk(rng, 129, dtype)
    assert_close(linalg.dot(x, y), np.dot(_f64(x), _f64(y)), scale=4.0)
    assert_close(linalg.axpy(0.5, x, y), 0.5 * _f64(x) + _f64(y))
    s = _mk(rng, (8, 12), dtype)
    assert_close(linalg.syrk(s), _f64(s) @ _f64(s).T, scale=2.0)


@pytest.mark.parametrize("pol", ["reference", "model", "tuned"])
def test_linalg_policy_context_equals_kwarg_path(rng, pol):
    """linalg under use(policy=...) must be bitwise the numeric core
    called with the same policy= argument."""
    a, b = _mk(rng, (24, 12), np.float32), _mk(rng, (12, 18), np.float32)
    with linalg.use(policy=pol):
        new = linalg.gemm(a, b)
    old = blas.gemm(a, b, policy=pol)
    assert np.array_equal(np.asarray(new), np.asarray(old))
