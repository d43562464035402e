"""Differential + context-semantics tests for the repro.linalg front-end.

Acceptance grid (ISSUE 4): the *same* ``repro.linalg`` call under
{reference, model, tuned} x {no mesh, (2, 2) mesh} x {float32, float64}
must agree with the NumPy/SciPy oracle within the shared
``dtype_tolerances``. The mesh legs need 8 forced host devices and the
float64 legs need ``JAX_ENABLE_X64`` - both are process-level switches -
so that grid runs in one subprocess (pattern of
``tests/test_distributed_blas.py``); everything else (policy x
{float32, bfloat16} grids, batched delegation, ExecutionContext
semantics, registry-path contexts, accumulation dtype) runs in-process.
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest
import scipy.linalg

from repro import linalg
from repro.linalg.context import UNSET

from conftest import LINALG_DTYPES as DTYPES  # shared in-process dtype grid

POLICIES = ["reference", "model", "tuned"]


def _mk(rng, shape, dtype=np.float32):
    return jnp.asarray(rng.normal(size=shape).astype(np.float32)).astype(dtype)


def _f64(x):
    return np.asarray(jnp.asarray(x).astype(jnp.float32)).astype(np.float64)


# --------------------- policy x dtype differential grid ---------------------

@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("pol", POLICIES)
@pytest.mark.parametrize("m,n,k", [(24, 36, 12), (17, 5, 29)])
def test_gemm_policy_dtype_grid(rng, assert_close, m, n, k, pol, dtype):
    a, b = _mk(rng, (m, k), dtype), _mk(rng, (k, n), dtype)
    with linalg.use(policy=pol):
        got = linalg.gemm(a, b)
    assert got.dtype == jnp.dtype(dtype)
    assert_close(got, _f64(a) @ _f64(b), scale=max(1.0, k / 16))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("pol", POLICIES)
def test_gemv_syrk_policy_dtype_grid(rng, assert_close, pol, dtype):
    a, x = _mk(rng, (17, 9), dtype), _mk(rng, 9, dtype)
    with linalg.use(policy=pol):
        got_v = linalg.gemv(a, x)
        got_s = linalg.syrk(a)
    assert_close(got_v, _f64(a) @ _f64(x))
    assert_close(got_s, _f64(a) @ _f64(a).T, scale=2.0)


@pytest.mark.parametrize("pol", POLICIES)
@pytest.mark.parametrize("lower", [True, False])
def test_trsm_policy_grid(rng, assert_close, pol, lower):
    n = 40
    a = _mk(rng, (n, n))
    t = (jnp.tril(a) if lower else jnp.triu(a)) + 4 * jnp.eye(n)
    b = _mk(rng, (n, 3))
    with linalg.use(policy=pol):
        got = linalg.trsm(t, b, lower=lower)
    ref = scipy.linalg.solve_triangular(_f64(t), _f64(b), lower=lower)
    assert_close(got, ref, scale=4.0)


@pytest.mark.parametrize("dtype", DTYPES)
def test_level1_vs_numpy(rng, assert_close, dtype):
    x, y = _mk(rng, 65, dtype), _mk(rng, 65, dtype)
    assert_close(linalg.dot(x, y, schedule="strided"),
                 np.dot(_f64(x), _f64(y)), scale=4.0)
    assert_close(linalg.axpy(2.5, x, y), 2.5 * _f64(x) + _f64(y))
    assert_close(linalg.scal(-0.5, x), -0.5 * _f64(x))
    assert_close(linalg.nrm2(x), np.linalg.norm(_f64(x)), scale=2.0)
    assert_close(linalg.asum(x), np.abs(_f64(x)).sum(), scale=2.0)
    assert int(linalg.iamax(x)) == int(np.argmax(np.abs(_f64(x))))
    gx, gy = linalg.rot(x, y, np.cos(0.3), np.sin(0.3))
    assert_close(gx, np.cos(0.3) * _f64(x) + np.sin(0.3) * _f64(y))
    u, v, a = _mk(rng, 9, dtype), _mk(rng, 7, dtype), _mk(rng, (9, 7), dtype)
    assert_close(linalg.ger(0.75, u, v, a),
                 _f64(a) + 0.75 * np.outer(_f64(u), _f64(v)), scale=2.0)
    b2 = _mk(rng, 9, dtype)
    t = jnp.tril(_mk(rng, (9, 9), dtype)) + 4 * jnp.eye(9, dtype=dtype)
    assert_close(linalg.trsv(t, b2),
                 scipy.linalg.solve_triangular(_f64(t), _f64(b2), lower=True),
                 scale=4.0)


@pytest.mark.parametrize("pol", POLICIES)
def test_lapack_routines_policy_grid(rng, assert_close, pol):
    n = 32
    a = _mk(rng, (n, n)) + 8 * jnp.eye(n)
    s = a @ a.T + n * jnp.eye(n)
    b = _mk(rng, (n, 2))
    with linalg.use(policy=pol):
        l = linalg.cholesky(s, block=8)
        assert_close(l @ l.T, _f64(s), scale=16.0)
        packed, piv = linalg.lu(a, block=8)
        from repro.lapack.lu import lu_reconstruct
        assert_close(lu_reconstruct(packed, piv), _f64(a), scale=16.0)
        q, r = linalg.qr(a, block=8)
        assert_close(q @ r, _f64(a), scale=16.0)
        assert_close(q.T @ q, np.eye(n), scale=16.0)
        x = linalg.solve(a, b, block=8)
        assert_close(x, np.linalg.solve(_f64(a), _f64(b)), scale=16.0)
    tall = _mk(rng, (48, 20))
    bt = _mk(rng, 48)
    with linalg.use(policy=pol):
        xl = linalg.lstsq(tall, bt, block=8)
    ref = np.linalg.lstsq(_f64(tall), _f64(bt), rcond=None)[0]
    assert_close(xl, ref, scale=32.0)


# --------------------------- batched delegation -----------------------------

def test_gemm_3d_batches_via_vmap(rng, assert_close):
    a = _mk(rng, (4, 12, 8))
    b = _mk(rng, (4, 8, 10))
    with linalg.use(policy="model"):
        got = linalg.gemm(a, b)
    assert_close(got, np.einsum("bij,bjk->bik", _f64(a), _f64(b)))


def test_lapack_3d_delegates_to_batched(rng, assert_close):
    g = _mk(rng, (5, 16, 16))
    spd = g @ jnp.swapaxes(g, 1, 2) + 16 * jnp.eye(16)
    l3 = linalg.cholesky(spd, block=8)
    res = linalg.batched_cholesky(spd, block=8)
    assert res.kind == "potrf"
    np.testing.assert_array_equal(np.asarray(l3), np.asarray(res.factors))
    b = _mk(rng, (5, 16))
    x = linalg.batched_solve(res, b)
    resid = jnp.einsum("bij,bj->bi", spd, x) - b
    assert float(jnp.max(jnp.abs(resid))) < 2e-3
    x2 = linalg.solve(g + 8 * jnp.eye(16), b, block=8)
    for i in range(5):
        assert_close(x2[i], np.linalg.solve(_f64(g[i]) + 8 * np.eye(16),
                                            _f64(b[i])), scale=16.0)
    packed, piv = linalg.lu(g, block=8)
    assert packed.shape == (5, 16, 16) and piv.shape == (5, 16)
    q, r = linalg.qr(g, block=8)
    assert_close(jnp.einsum("bij,bjk->bik", q, r), _f64(g), scale=16.0)
    tall = _mk(rng, (4, 24, 10))
    bt = _mk(rng, (4, 24))
    xb = linalg.lstsq(tall, bt, block=8)
    assert xb.shape == (4, 10)
    for i in range(4):
        ref = np.linalg.lstsq(_f64(tall[i]), _f64(bt[i]), rcond=None)[0]
        assert_close(xb[i], ref, scale=32.0)


# ------------------------- ExecutionContext semantics -----------------------

def test_context_layering_and_overrides():
    assert linalg.get_context().policy is None          # library default
    linalg.set_context(policy="model")
    assert linalg.get_context().policy == "model"
    with linalg.use(policy="tuned"):
        assert linalg.get_context().policy == "tuned"
        with linalg.use(interpret=True):                # inherits policy
            assert linalg.get_context().policy == "tuned"
        ctx = linalg.ExecutionContext(policy="reference")
        from repro.linalg.context import current
        assert current(ctx).policy == "reference"       # per-call override
        assert current(dict(policy="model")).policy == "model"
    assert linalg.get_context().policy == "model"       # use() popped
    linalg.reset_context()
    assert linalg.get_context().policy is None


def test_context_validation():
    with pytest.raises(ValueError, match="unknown policy"):
        linalg.ExecutionContext(policy="warp-speed")
    with pytest.raises(ValueError, match="px, py"):
        linalg.ExecutionContext(mesh=(2, 2, 2))
    with pytest.raises(TypeError):
        linalg.use(linalg.ExecutionContext(), policy="model").__enter__()


def test_context_describe_is_jsonable():
    import json
    ctx = linalg.ExecutionContext(policy="tuned", mesh=(2, 2),
                                  accum_dtype=jnp.float32,
                                  registry="/tmp/reg.json")
    d = ctx.describe()
    assert d == {"policy": "tuned", "mesh": [2, 2],
                 "registry": "/tmp/reg.json", "accum_dtype": "float32",
                 "interpret": True, "machine": "tpu-like", "obs": None}
    json.dumps(d)
    # defaults resolve to the process default policy
    assert linalg.get_context().describe()["policy"] == "reference"


def test_default_context_interpret_comes_from_the_device(monkeypatch):
    """The root context leaves interpret mode to the device: True on the
    CPU backend, False on a TPU, and describe() reports the resolved
    value. An explicit interpret still wins."""
    from repro.kernels import ops
    from repro.linalg.context import resolved_interpret
    ctx = linalg.get_context()
    assert ctx.interpret is None                         # not pinned
    assert resolved_interpret(ctx) is True               # CPU backend
    assert ctx.describe()["interpret"] is True
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    assert resolved_interpret(ctx) is False
    assert ctx.describe()["interpret"] is False
    with linalg.use(interpret=True):
        assert linalg.get_context().describe()["interpret"] is True


def test_compile_cache_dir_is_fixed(monkeypatch):
    """The compile cache lives where JAX_COMPILATION_CACHE_DIR says, else
    at one path inside the checkout - the same on every call."""
    from repro.runtime import compile_cache as cc
    monkeypatch.setenv(cc.ENV, "/some/shared/cache")
    assert cc.compile_cache_dir() == "/some/shared/cache"
    monkeypatch.delenv(cc.ENV)
    first = cc.compile_cache_dir()
    assert first == cc.compile_cache_dir()
    root = Path(__file__).resolve().parents[1]
    assert Path(first) == root / ".jax_cache"


def test_context_registry_path_reaches_dispatch(rng, assert_close, tmp_path):
    """A path-string registry in the context must feed tuned resolution -
    for BLAS and for LAPACK trailing updates (the threaded registry)."""
    from repro.tune.registry import Registry
    path = str(tmp_path / "ctx_registry.json")
    reg = Registry(path=path)
    reg.record("gemm", (24, 18, 12), jnp.float32, "cpu",
               {"bm": 256, "bn": 128, "bk": 128})
    reg.save()
    a, b = _mk(rng, (24, 12)), _mk(rng, (12, 18))
    with linalg.use(policy="tuned", registry=path):
        got = linalg.gemm(a, b)
        from repro.linalg.context import resolved_registry
        r = resolved_registry(linalg.get_context())
        assert r is resolved_registry(linalg.get_context())  # cached
        from repro.tune import dispatch
        res = dispatch.resolve("gemm", (24, 18, 12), jnp.float32,
                               policy="tuned", registry=r, backend="cpu")
        assert res.source == "registry" and res.gemm_plan.bm == 256
    assert_close(got, _f64(a) @ _f64(b))


def test_accum_dtype_upcasts_computation(rng):
    """bf16 storage + f32 accumulation must beat pure-bf16 accumulation
    on a long sequential reduction, while keeping bf16 storage."""
    n = 4096
    x = _mk(rng, n, jnp.bfloat16)
    y = _mk(rng, n, jnp.bfloat16)
    want = np.dot(_f64(x), _f64(y))
    plain = linalg.dot(x, y, schedule="sequential")
    with linalg.use(accum_dtype=jnp.float32):
        mixed = linalg.dot(x, y, schedule="sequential")
    assert plain.dtype == jnp.bfloat16 and mixed.dtype == jnp.bfloat16
    err_plain = abs(float(plain) - want)
    err_mixed = abs(float(mixed) - want)
    assert err_mixed <= err_plain + 1e-6


def test_mixed_dtype_accumuland_promotes(rng, assert_close):
    """A wider c/y accumuland must survive the epilogue (no silent
    downcast): default-context results stay bitwise the core path, which
    promotes like plain jnp."""
    a = _mk(rng, (8, 6), jnp.bfloat16)
    b = _mk(rng, (6, 5), jnp.bfloat16)
    c = _mk(rng, (8, 5), np.float32)
    got = linalg.gemm(a, b, c=c, beta=1.0)
    assert got.dtype == jnp.float32
    from repro.blas import level3
    want = level3.gemm(a, b, c=c, beta=1.0)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    x = _mk(rng, 6, jnp.bfloat16)
    y = _mk(rng, 8, np.float32)
    got_v = linalg.gemv(a, x, y=y, beta=1.0)
    assert got_v.dtype == jnp.float32


def test_dtype_arg_casts_storage(rng, assert_close):
    a, b = _mk(rng, (12, 8)), _mk(rng, (8, 10))
    got = linalg.gemm(a, b, dtype=jnp.bfloat16)
    assert got.dtype == jnp.bfloat16
    assert_close(got, _f64(a) @ _f64(b))
    l = linalg.cholesky(jnp.eye(8) * 4.0, dtype=jnp.bfloat16)
    assert l.dtype == jnp.bfloat16


# ------------------ the full acceptance grid (subprocess) -------------------

_ENV = dict(os.environ,
            XLA_FLAGS="--xla_force_host_platform_device_count=8",
            JAX_ENABLE_X64="1",
            PYTHONPATH="src")

_PRELUDE = """
import sys
sys.path.insert(0, "tests")
from conftest import dtype_tolerances
import numpy as np
import jax, jax.numpy as jnp
import scipy.linalg
from repro import linalg

def close(got, want, scale=1.0, msg=""):
    rtol, atol = dtype_tolerances(np.asarray(got).dtype, scale)
    np.testing.assert_allclose(np.asarray(got).astype(np.float64),
                               np.asarray(want).astype(np.float64),
                               rtol=rtol, atol=atol, err_msg=msg)
"""


def test_linalg_grid_policy_mesh_dtype():
    """The same repro.linalg calls over the full acceptance grid:
    {reference, model, tuned} x {no mesh, (2, 2)} x {float32, float64}."""
    code = _PRELUDE + textwrap.dedent("""
    rng = np.random.default_rng(0)
    for dtype in (np.float32, np.float64):
        a = jnp.asarray(rng.normal(size=(24, 20)).astype(dtype))
        b = jnp.asarray(rng.normal(size=(20, 16)).astype(dtype))
        want_mm = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
        t = np.tril(rng.normal(size=(24, 24))).astype(dtype) \\
            + 4.0 * np.eye(24, dtype=dtype)
        rhs = rng.normal(size=(24, 6)).astype(dtype)
        want_tr = scipy.linalg.solve_triangular(
            np.asarray(t, np.float64), np.asarray(rhs, np.float64),
            lower=True)
        g = rng.normal(size=(5, 12, 12)).astype(dtype)
        spd = g @ np.swapaxes(g, 1, 2) + 12 * np.eye(12, dtype=dtype)
        want_l = np.stack([np.linalg.cholesky(np.asarray(m, np.float64))
                           for m in spd])
        brhs = rng.normal(size=(5, 12)).astype(dtype)
        t, rhs, spd, brhs = map(jnp.asarray, (t, rhs, spd, brhs))
        for mesh in (None, (2, 2)):
            for pol in ("reference", "model", "tuned"):
                tag = f"dtype={np.dtype(dtype).name} mesh={mesh} policy={pol}"
                with linalg.use(policy=pol, mesh=mesh):
                    got = linalg.gemm(a, b)
                    assert got.dtype == jnp.dtype(dtype), (tag, got.dtype)
                    close(got, want_mm, scale=8.0, msg="gemm " + tag)
                    close(linalg.trsm(t, rhs, lower=True), want_tr,
                          scale=16.0, msg="trsm " + tag)
                    res = linalg.batched_cholesky(spd, block=8)
                    close(res.factors, want_l, scale=64.0,
                          msg="cholesky " + tag)
                    x = linalg.batched_solve(res, brhs)
                    close(jnp.einsum("bij,bj->bi", jnp.asarray(spd), x),
                          brhs, scale=256.0, msg="solve " + tag)
        # d-prefixed shim == repro.linalg, bitwise, per dtype
        import warnings
        from repro import blas
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            old = blas.dgemm(a, b)
        assert np.array_equal(np.asarray(old), np.asarray(linalg.gemm(a, b)))
    print("linalg acceptance grid OK")
    """)
    r = subprocess.run([sys.executable, "-c", code], env=_ENV,
                       capture_output=True, text=True, timeout=900,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "linalg acceptance grid OK" in r.stdout
