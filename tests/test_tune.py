"""Differential + persistence tests for the repro.tune subsystem.

Per-policy differential tests (ROADMAP convention): every dispatcher op
must agree across ``reference`` / ``model`` / ``tuned`` within the shared
``dtype_tolerances`` (Pallas in interpret mode on CPU). Registry coverage:
round-trip (write -> reload -> same config), corrupt/missing-file
fallback, LRU eviction, and the deprecated ``use_kernel`` alias mapping.
"""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest
import scipy.linalg

from repro import blas, lapack
from repro.tune import dispatch, policy, search
from repro.tune.registry import KernelConfig, Registry, make_key, shape_bucket

POLICIES = ["reference", "model", "tuned"]


def _mk(rng, shape, dtype=np.float32):
    return jnp.asarray(rng.normal(size=shape).astype(np.float32)).astype(dtype)


def _f64(x):
    return np.asarray(x.astype(jnp.float32)).astype(np.float64)


@pytest.fixture
def tmp_registry(tmp_path):
    return Registry(path=str(tmp_path / "registry.json"))


# --------------------- per-policy differential tests ------------------------

@pytest.mark.parametrize("pol", POLICIES)
@pytest.mark.parametrize("m,n,k", [(8, 8, 8), (24, 36, 12), (17, 5, 29)])
def test_dgemm_policies_vs_numpy(rng, assert_close, m, n, k, pol):
    a, b = _mk(rng, (m, k)), _mk(rng, (k, n))
    got = blas.dgemm(a, b, policy=pol, interpret=True)
    assert_close(got, _f64(a) @ _f64(b), scale=max(1.0, k / 16))


@pytest.mark.parametrize("pol", POLICIES)
@pytest.mark.parametrize("ta,tb", [(False, False), (True, False),
                                   (False, True), (True, True)])
def test_dgemm_transpose_flags(rng, assert_close, ta, tb, pol):
    a = _mk(rng, (12, 24) if ta else (24, 12))
    b = _mk(rng, (18, 12) if tb else (12, 18))
    got = blas.dgemm(a, b, transa=ta, transb=tb, policy=pol, interpret=True)
    ref = (_f64(a).T if ta else _f64(a)) @ (_f64(b).T if tb else _f64(b))
    assert_close(got, ref)


@pytest.mark.parametrize("pol", POLICIES)
@pytest.mark.parametrize("trans", [False, True])
def test_dsyrk_policies_reach_gemm_path(rng, assert_close, trans, pol):
    a = _mk(rng, (12, 20))
    op_a = _f64(a).T if trans else _f64(a)
    got = blas.dsyrk(a, trans=trans, policy=pol, interpret=True)
    assert_close(got, op_a @ op_a.T)


@pytest.mark.parametrize("pol", POLICIES)
@pytest.mark.parametrize("trans", [False, True])
def test_dgemv_policies_vs_numpy(rng, assert_close, trans, pol):
    a, x = _mk(rng, (17, 9)), _mk(rng, 17 if trans else 9)
    got = blas.dgemv(a, x, trans=trans, policy=pol, interpret=True)
    assert_close(got, (_f64(a).T if trans else _f64(a)) @ _f64(x))


@pytest.mark.parametrize("pol", POLICIES)
@pytest.mark.parametrize("lower", [True, False])
def test_dtrsm_policies_vs_scipy(rng, assert_close, lower, pol):
    n = 40
    a = _mk(rng, (n, n))
    t = (jnp.tril(a) if lower else jnp.triu(a)) + 4 * jnp.eye(n)
    b = _mk(rng, (n, 3))
    got = blas.dtrsm(t, b, lower=lower, policy=pol, interpret=True)
    ref = scipy.linalg.solve_triangular(_f64(t), _f64(b), lower=lower)
    assert_close(got, ref, scale=4.0)


@pytest.mark.parametrize("pol", POLICIES)
def test_potrf_policies_agree(rng, assert_close, pol):
    a = _mk(rng, (48, 48))
    s = a @ a.T + 48 * jnp.eye(48)
    got = lapack.potrf(s, block=16, policy=pol, interpret=True)
    want = np.linalg.cholesky(_f64(s))
    assert_close(got, want, scale=8.0)


@pytest.mark.parametrize("pol", POLICIES)
def test_gesv_policies_agree(rng, assert_close, pol):
    a = _mk(rng, (32, 32)) + 8 * jnp.eye(32)
    b = _mk(rng, (32, 2))
    got = lapack.gesv(a, b, block=8, policy=pol, interpret=True)
    assert_close(got, np.linalg.solve(_f64(a), _f64(b)), scale=8.0)


def test_cold_start_tuned_identical_to_use_kernel_path(rng, tmp_path):
    """Acceptance: with no registry file, the tuned policy must produce
    bitwise the numerics of the PR-1 use_kernel=True path."""
    empty = Registry(path=str(tmp_path / "never-written.json"))
    a, b = _mk(rng, (24, 12)), _mk(rng, (12, 18))
    old = blas.dgemm(a, b, use_kernel=True, interpret=True)
    new = blas.dgemm(a, b, policy="tuned", registry=empty, interpret=True)
    assert np.array_equal(np.asarray(old), np.asarray(new))
    s = a @ a.T + 24 * jnp.eye(24)
    old_l = lapack.potrf(s, block=8, use_kernel=True, interpret=True)
    import repro.tune.registry as reg_mod
    reg_mod.set_default_registry(empty)
    try:
        new_l = lapack.potrf(s, block=8, policy="tuned", interpret=True)
    finally:
        reg_mod.set_default_registry(None)
    assert np.array_equal(np.asarray(old_l), np.asarray(new_l))


# ----------------------------- policy resolution ----------------------------

@pytest.mark.parametrize("op,shape", [("gemm", (64, 64, 64)),
                                      ("trsm", (64, 8)),
                                      ("trsm+gemm", (64, 64, 16)),
                                      ("pdgemm", (64, 64, 64))])
@pytest.mark.parametrize("pol", ["model", "tuned"])
def test_float64_on_tpu_resolves_to_xla(op, shape, pol):
    """Mosaic compiles no float64 kernel, so on a TPU the kernel policies
    hand float64 to XLA and say why; float32 still reaches the kernel."""
    mesh = (2, 2) if op == "pdgemm" else None
    res = dispatch.resolve(op, shape, jnp.float64, policy=pol,
                           backend="tpu", mesh=mesh)
    assert (res.source, res.use_pallas, res.fused) == \
        ("xla-float64", False, False)
    assert res.describe()["source"] == "xla-float64"
    f32 = dispatch.resolve(op, shape, jnp.float32, policy=pol,
                           backend="tpu", mesh=mesh)
    assert f32.use_pallas and f32.source != "xla-float64"
    cpu = dispatch.resolve(op, shape, jnp.float64, policy=pol,
                           backend="cpu", mesh=mesh)
    assert cpu.use_pallas                    # interpret mode handles f64


def test_use_kernel_alias_mapping():
    assert policy.resolve_policy("tuned", use_kernel=False) == "tuned"
    assert policy.resolve_policy(None, use_kernel=True) == "model"
    assert policy.resolve_policy(None, use_kernel=False) == "reference"
    assert policy.resolve_policy(None, None) == "reference"
    with pytest.raises(ValueError, match="unknown policy"):
        policy.resolve_policy("fastest")


def test_default_policy_env(monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_POLICY", "model")
    assert policy.default_policy() == "model"
    monkeypatch.setenv("REPRO_TUNE_POLICY", "warp-speed")
    with pytest.raises(ValueError):
        policy.default_policy()


def test_resolve_sources(tmp_registry):
    r = dispatch.resolve("gemm", (32, 32, 32), jnp.float32,
                         policy="reference", registry=tmp_registry)
    assert (r.source, r.use_pallas) == ("reference", False)
    r = dispatch.resolve("gemm", (32, 32, 32), jnp.float32, policy="model",
                         registry=tmp_registry)
    assert r.source == "model" and r.gemm_plan is not None
    r = dispatch.resolve("gemm", (32, 32, 32), jnp.float32, policy="tuned",
                         registry=tmp_registry)
    assert r.source == "fallback-model"       # cold start
    tmp_registry.record("gemm", (32, 32, 32), jnp.float32, "cpu",
                        {"bm": 256, "bn": 128, "bk": 128})
    r = dispatch.resolve("gemm", (32, 32, 32), jnp.float32, policy="tuned",
                         registry=tmp_registry, backend="cpu")
    assert r.source == "registry" and r.gemm_plan.bm == 256
    assert r.describe()["config"] == {"bm": 256, "bn": 128, "bk": 128}
    with pytest.raises(ValueError, match="unknown op"):
        dispatch.resolve("axpy", (8,), jnp.float32)


def test_gemv_tuned_shares_gemm_registry_entries(rng, assert_close,
                                                 tmp_registry):
    """gemv executes as an (m, 1, n) GEMM, so its tuned lookups must hit
    gemm entries recorded under that execution shape."""
    tmp_registry.record("gemm", (24, 1, 12), jnp.float32, "cpu",
                        {"bm": 256, "bn": 128, "bk": 128})
    r = dispatch.resolve("gemv", (24, 12), jnp.float32, policy="tuned",
                         registry=tmp_registry, backend="cpu")
    assert r.source == "registry" and r.gemm_plan.bm == 256
    a, x = _mk(rng, (24, 12)), _mk(rng, 12)
    got = blas.dgemv(a, x, policy="tuned", registry=tmp_registry,
                     interpret=True)
    assert_close(got, _f64(a) @ _f64(x))


def test_registry_lru_order_survives_save_load(tmp_path):
    """Recency, not key order, must round-trip through the file."""
    reg = Registry(path=str(tmp_path / "r.json"))
    reg.record("gemm", (8, 8, 8), jnp.float32, "cpu", {"bm": 1, "bn": 1, "bk": 1})
    reg.record("gemm", (16, 16, 16), jnp.float32, "cpu", {"bm": 2, "bn": 2, "bk": 2})
    # touch the alphabetically-later key so it is most recently used
    reg.lookup("gemm", (8, 8, 8), jnp.float32, "cpu")
    path = reg.save()
    reloaded = Registry(path=path, capacity=2)
    reloaded.record("gemm", (32, 32, 32), jnp.float32, "cpu",
                    {"bm": 3, "bn": 3, "bk": 3})
    # (16,16,16) was LRU at save time -> it is the one evicted
    assert reloaded.lookup("gemm", (16, 16, 16), jnp.float32, "cpu") is None
    assert reloaded.lookup("gemm", (8, 8, 8), jnp.float32, "cpu") is not None


def test_trsm_reference_keeps_historical_block():
    r = dispatch.resolve("trsm", (256, 8), jnp.float32, policy="reference")
    assert r.block == 64


# ------------------------------ registry ------------------------------------

def test_registry_round_trip(tmp_registry):
    cfg = tmp_registry.record("gemm", (100, 60, 30), jnp.float32, "cpu",
                              {"bm": 128, "bn": 256, "bk": 128},
                              measured_s=1e-3)
    path = tmp_registry.save()
    reloaded = Registry(path=path)
    got = reloaded.lookup("gemm", (100, 60, 30), jnp.float32, "cpu")
    assert got == cfg
    # bucket neighbors share the entry; different buckets miss
    assert reloaded.lookup("gemm", (65, 36, 20), jnp.float32, "cpu") == cfg
    assert reloaded.lookup("gemm", (300, 60, 30), jnp.float32, "cpu") is None
    assert reloaded.lookup("gemm", (100, 60, 30), jnp.bfloat16, "cpu") is None


def test_registry_missing_file_is_cold_start(tmp_path):
    reg = Registry(path=str(tmp_path / "nope" / "registry.json"))
    assert reg.lookup("gemm", (8, 8, 8), jnp.float32, "cpu") is None
    assert "cold start" in reg.load_error


@pytest.mark.parametrize("blob", ["{not json", '{"version": 99, "entries": {}}',
                                  '[1, 2, 3]',
                                  '{"version": 1, "entries": {"k": {"op": "gemm"}}}'])
def test_registry_corrupt_file_falls_back(tmp_path, blob):
    p = tmp_path / "registry.json"
    p.write_text(blob)
    reg = Registry(path=str(p))
    assert reg.lookup("gemm", (8, 8, 8), jnp.float32, "cpu") is None
    assert reg.load_error is not None
    # and dispatch still resolves (fallback to the model plan)
    r = dispatch.resolve("gemm", (8, 8, 8), jnp.float32, policy="tuned",
                         registry=reg)
    assert r.source == "fallback-model" and r.gemm_plan is not None


def test_registry_lru_eviction(tmp_path):
    reg = Registry(path=str(tmp_path / "r.json"), capacity=2)
    reg.record("gemm", (8, 8, 8), jnp.float32, "cpu", {"bm": 1, "bn": 1, "bk": 1})
    reg.record("gemm", (16, 16, 16), jnp.float32, "cpu", {"bm": 2, "bn": 2, "bk": 2})
    # touch the first so the second becomes least recently used
    assert reg.lookup("gemm", (8, 8, 8), jnp.float32, "cpu") is not None
    reg.record("gemm", (32, 32, 32), jnp.float32, "cpu", {"bm": 3, "bn": 3, "bk": 3})
    assert len(reg) == 2
    assert reg.lookup("gemm", (16, 16, 16), jnp.float32, "cpu") is None
    assert reg.lookup("gemm", (8, 8, 8), jnp.float32, "cpu") is not None


def test_shape_bucket_and_key():
    assert shape_bucket((100, 60, 30)) == (128, 64, 32)
    assert shape_bucket((1, 128)) == (1, 128)
    key = make_key("gemm", (100, 60, 30), jnp.float32, "cpu")
    assert key == "gemm|128x64x32|float32|cpu"


def test_registry_file_format_is_documented_schema(tmp_registry):
    tmp_registry.record("trsm", (64, 8), jnp.float32, "cpu", {"block": 32})
    path = tmp_registry.save()
    blob = json.load(open(path))
    assert blob["version"] == 1
    entry = blob["entries"]["trsm|64x8|float32|cpu"]
    assert entry["op"] == "trsm" and entry["params"] == {"block": 32}
    assert KernelConfig.from_json(entry).params["block"] == 32


# ------------------------------- search -------------------------------------

def test_gemm_candidates_seeded_by_model():
    from repro.core.codesign import plan_gemm
    cands = search.gemm_candidates(256, 256, 256, dtype_bytes=4,
                                   max_candidates=4)
    assert 1 <= len(cands) <= 4
    seed = plan_gemm(256, 256, 256, dtype_bytes=4)
    assert any((c.bm, c.bn, c.bk) == (seed.bm, seed.bn, seed.bk)
               for c in cands)
    for c in cands:
        assert search.model_score(c, 256, 256, 256, 4) > 0


def test_tune_gemm_writes_registry_and_dispatch_uses_it(rng, assert_close,
                                                        tmp_registry):
    res = search.tune_gemm(16, 16, 16, registry=tmp_registry, top_k=2, reps=1)
    assert res.best.op == "gemm" and res.best.measured_s > 0
    assert len(res.measured) >= 1
    import jax
    hit = tmp_registry.lookup("gemm", (16, 16, 16), jnp.float32,
                              jax.default_backend())
    assert hit == res.best
    r = dispatch.resolve("gemm", (16, 16, 16), jnp.float32, policy="tuned",
                         registry=tmp_registry)
    assert r.source == "registry"
    # numerics through the tuned config still match the oracle
    a, b = _mk(rng, (16, 16)), _mk(rng, (16, 16))
    got = blas.dgemm(a, b, policy="tuned", registry=tmp_registry,
                     interpret=True)
    assert_close(got, _f64(a) @ _f64(b))


def test_tune_trsm_writes_registry(tmp_registry):
    res = search.tune_trsm(32, 4, registry=tmp_registry, reps=1,
                           blocks=(16, 32))
    assert res.best.op == "trsm" and "block" in res.best.params
    import jax
    hit = tmp_registry.lookup("trsm", (32, 4), jnp.float32,
                              jax.default_backend())
    assert hit == res.best


def test_seed_registry_from_model_per_dtype(tmp_registry):
    """Model-seeded entries give non-swept dtypes real registry hits."""
    n = search.seed_registry_from_model(
        tmp_registry, gemm_shapes=[(64, 64, 64)], trsm_shapes=[(64, 8)],
        dtypes=(jnp.float32, jnp.float64, jnp.bfloat16), backend="cpu")
    assert n == 6 and len(tmp_registry) == 6
    for dt in (jnp.float32, jnp.float64, jnp.bfloat16):
        hit = tmp_registry.lookup("gemm", (64, 64, 64), dt, "cpu")
        assert hit is not None and hit.source == "model"
        r = dispatch.resolve("gemm", (64, 64, 64), dt, policy="tuned",
                             registry=tmp_registry, backend="cpu")
        assert r.source == "registry"
        rt = dispatch.resolve("trsm", (64, 8), dt, policy="tuned",
                              registry=tmp_registry, backend="cpu")
        assert rt.source == "registry" and rt.block >= 1
    # a later measured sweep overwrites the seeded entry in place
    import jax
    if jax.default_backend() == "cpu":
        res = search.tune_gemm(64, 64, 64, registry=tmp_registry, top_k=1,
                               reps=1)
        hit = tmp_registry.lookup("gemm", (64, 64, 64), jnp.float32, "cpu")
        assert hit.source == "sweep" and hit == res.best


def test_planners_accept_dtype_directly():
    from repro.core import codesign
    p32 = codesign.plan_gemm(256, 256, 256, dtype=jnp.float32)
    pb = codesign.plan_gemm(256, 256, 256, dtype_bytes=4)
    assert (p32.bm, p32.bn, p32.bk) == (pb.bm, pb.bn, pb.bk)
    p64 = codesign.plan_gemm(2048, 2048, 2048, dtype=jnp.float64)
    assert p64.vmem_bytes <= codesign.VMEM_BYTES
    t = codesign.plan_trsm(128, 8, dtype=jnp.float64)
    assert t.block >= 1
    f = codesign.plan_factorization(256, kind="potrf", dtype=jnp.float64)
    assert f.block >= 1
