"""Differential tests for the sharded BLAS/LAPACK layer.

ROADMAP convention: every distributed routine is oracle-tested against its
single-device counterpart under the shared ``dtype_tolerances``, over mesh
shapes {(1,1), (2,2), (4,2)} x policy {reference, model, tuned}. Mesh
bodies run in a subprocess with ``--xla_force_host_platform_device_count=8``
(the main pytest process must keep 1 device - see conftest); the
registry/persistence tests are pure CPU and run in-process.
"""
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import pytest

from repro.tune import dispatch
from repro.tune.registry import Registry, make_key

_ENV = dict(os.environ,
            XLA_FLAGS="--xla_force_host_platform_device_count=8",
            PYTHONPATH="src")

_PRELUDE = """
import sys, os
sys.path.insert(0, "tests")
from conftest import dtype_tolerances
import jax, jax.numpy as jnp, numpy as np
from repro.blas import distributed as dblas, level3
MESHES = [(1, 1), (2, 2), (4, 2)]
POLICIES = ["reference", "model", "tuned"]

def close(got, want, scale=1.0, msg=""):
    rtol, atol = dtype_tolerances(np.asarray(got).dtype, scale)
    np.testing.assert_allclose(np.asarray(got).astype(np.float64),
                               np.asarray(want).astype(np.float64),
                               rtol=rtol, atol=atol, err_msg=msg)
"""


def _run(body: str):
    code = _PRELUDE + textwrap.dedent(body)
    r = subprocess.run([sys.executable, "-c", code], env=_ENV,
                       capture_output=True, text=True, timeout=900,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_pdgemm_matches_dgemm_over_meshes_and_policies():
    _run("""
    rng = np.random.default_rng(0)
    # divisible and ragged (padding-path) shapes
    for (m, n, k) in [(32, 32, 32), (24, 20, 36)]:
        a = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
        b = jnp.asarray(rng.normal(size=(k, n)).astype(np.float32))
        want = np.asarray(level3.gemm(a, b, policy="reference"))
        for px, py in MESHES:
            mesh = dblas.make_blas_mesh(px, py)
            for pol in POLICIES:
                got = dblas.pdgemm(a, b, mesh, policy=pol)
                assert got.shape == (m, n)
                close(got, want, scale=4.0,
                      msg=f"mesh=({px},{py}) policy={pol} mnk={m},{n},{k}")
    print("pdgemm differential OK")
    """)


def test_pdgemm_epilogue_and_dispatch_route():
    _run("""
    from repro.tune import dispatch as td
    rng = np.random.default_rng(1)
    a = jnp.asarray(rng.normal(size=(16, 24)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(24, 16)).astype(np.float32))
    c = jnp.asarray(rng.normal(size=(16, 16)).astype(np.float32))
    mesh = dblas.make_blas_mesh(2, 2)
    want = np.asarray(level3.gemm(a, b, c=c, alpha=0.5, beta=-2.0,
                                  policy="reference"))
    got = dblas.pdgemm(a, b, mesh, c=c, alpha=0.5, beta=-2.0,
                       policy="reference")
    close(got, want, scale=4.0)
    # the unified dispatcher routes op="pdgemm" here too
    got2 = td.dispatch("pdgemm", a, b, mesh=mesh, policy="reference")
    close(got2, np.asarray(a @ b), scale=4.0)
    print("pdgemm epilogue OK")
    """)


def test_pdtrsm_matches_dtrsm():
    _run("""
    rng = np.random.default_rng(2)
    n, nrhs = 48, 10                       # nrhs ragged vs every mesh
    t = np.tril(rng.normal(size=(n, n))).astype(np.float32) \\
        + 4.0 * np.eye(n, dtype=np.float32)
    t = jnp.asarray(t)
    b = jnp.asarray(rng.normal(size=(n, nrhs)).astype(np.float32))
    for lower in (True, False):
        tt = t if lower else t.T
        want = np.asarray(level3.trsm(tt, b, lower=lower,
                                      policy="reference"))
        for px, py in MESHES:
            mesh = dblas.make_blas_mesh(px, py)
            for pol in POLICIES:
                got = dblas.pdtrsm(tt, b, mesh, lower=lower, policy=pol)
                close(got, want, scale=8.0,
                      msg=f"mesh=({px},{py}) lower={lower} policy={pol}")
    # right-side solve and 1-D rhs
    mesh = dblas.make_blas_mesh(4, 2)
    want = np.asarray(level3.trsm(t, b.T, lower=True, left=False,
                                  policy="reference"))
    close(dblas.pdtrsm(t, b.T, mesh, lower=True, left=False,
                       policy="reference"), want, scale=8.0)
    v = b[:, 0]
    close(dblas.pdtrsm(t, v, mesh, policy="reference"),
          np.asarray(level3.trsm(t, v[:, None], policy="reference"))[:, 0],
          scale=8.0)
    print("pdtrsm differential OK")
    """)


def test_mesh_batched_factorizations_match_single_device():
    _run("""
    from repro.lapack import batched, distributed as dlap
    rng = np.random.default_rng(3)
    B, n = 6, 24                           # B=6 ragged vs 4 and 8 devices
    g = rng.normal(size=(B, n, n)).astype(np.float32)
    spd = g @ np.swapaxes(g, 1, 2) + n * np.eye(n, dtype=np.float32)
    spd, g = jnp.asarray(spd), jnp.asarray(g)
    rhs = jnp.asarray(rng.normal(size=(B, n)).astype(np.float32))
    for px, py in MESHES:
        mesh = dblas.make_blas_mesh(px, py)
        for pol in POLICIES:
            r0 = batched.batched_potrf(spd, policy=pol)
            r1 = dlap.batched_potrf(spd, mesh, policy=pol)
            assert (r1.kind, r1.block) == (r0.kind, r0.block)
            close(r1.factors, np.asarray(r0.factors), scale=4.0,
                  msg=f"potrf mesh=({px},{py}) policy={pol}")
            r0g = batched.batched_getrf(g, policy=pol)
            r1g = dlap.batched_getrf(g, mesh, policy=pol)
            close(r1g.factors, np.asarray(r0g.factors), scale=4.0,
                  msg=f"getrf mesh=({px},{py}) policy={pol}")
            assert np.array_equal(np.asarray(r0g.pivots),
                                  np.asarray(r1g.pivots))
            x0 = batched.batched_solve(r0g, rhs, policy=pol)
            x1 = dlap.batched_solve(r1g, rhs, mesh, policy=pol)
            close(x1, np.asarray(x0), scale=16.0,
                  msg=f"solve mesh=({px},{py}) policy={pol}")
    # geqrf + SPD solve round-trip on the largest mesh, reference policy
    mesh = dblas.make_blas_mesh(4, 2)
    rq0 = batched.batched_geqrf(g, policy="reference")
    rq1 = dlap.batched_geqrf(g, mesh, policy="reference")
    close(rq1.factors, np.asarray(rq0.factors), scale=8.0)
    close(rq1.tau, np.asarray(rq0.tau), scale=8.0)
    rp = dlap.batched_potrf(spd, mesh, policy="reference")
    xs = dlap.batched_solve(rp, rhs, mesh, policy="reference")
    close(jnp.einsum("bij,bj->bi", spd, xs), np.asarray(rhs), scale=64.0)
    print("mesh batched LAPACK differential OK")
    """)


# ------------------------- in-process (1 device) ---------------------------

def test_registry_mesh_key_roundtrip(tmp_path):
    reg = Registry(path=str(tmp_path / "registry.json"))
    reg.record("pdgemm", (128, 128, 64), jnp.float32, "cpu",
               {"bm": 128, "bn": 128, "bk": 128}, source="sweep",
               measured_s=1e-3, mesh="x2y4")
    # same op/shape, no mesh component: a distinct single-device entry
    reg.record("gemm", (128, 128, 64), jnp.float32, "cpu",
               {"bm": 256, "bn": 128, "bk": 128})
    path = reg.save()
    reloaded = Registry(path=path)
    hit = reloaded.lookup("pdgemm", (128, 128, 64), jnp.float32, "cpu",
                          mesh="x2y4")
    assert hit is not None and hit.params["bm"] == 128
    assert reloaded.lookup("pdgemm", (128, 128, 64), jnp.float32, "cpu",
                           mesh="x4y2") is None, "mesh shapes must not alias"
    assert reloaded.lookup("pdgemm", (128, 128, 64), jnp.float32,
                           "cpu") is None, "mesh entry must not leak meshless"
    single = reloaded.lookup("gemm", (128, 128, 64), jnp.float32, "cpu")
    assert single is not None and single.params["bm"] == 256
    assert make_key("pdgemm", (128, 128, 64), jnp.float32, "cpu",
                    "x2y4") == "pdgemm|128x128x64|float32|cpu|x2y4"


def test_pdgemm_resolution_sources(tmp_path):
    reg = Registry(path=str(tmp_path / "registry.json"))
    # cold start: tuned falls back to the model plan
    res = dispatch.resolve("pdgemm", (64, 64, 64), jnp.float32,
                           policy="tuned", registry=reg, backend="cpu",
                           mesh=(2, 2))
    assert res.source == "fallback-model" and res.use_pallas
    assert res.mesh == "x2y2" and res.describe()["mesh"] == "x2y2"
    model = dispatch.resolve("pdgemm", (64, 64, 64), jnp.float32,
                             policy="model", backend="cpu", mesh=(2, 2))
    assert res.gemm_plan == model.gemm_plan, "cold-start tuned != model plan"
    # a recorded mesh entry takes over
    reg.record("pdgemm", (64, 64, 64), jnp.float32, "cpu",
               {"bm": 128, "bn": 128, "bk": 128}, mesh="x2y2")
    res2 = dispatch.resolve("pdgemm", (64, 64, 64), jnp.float32,
                            policy="tuned", registry=reg, backend="cpu",
                            mesh=(2, 2))
    assert res2.source == "registry"
    # reference never touches the kernel; mesh is required for pdgemm
    ref = dispatch.resolve("pdgemm", (64, 64, 64), jnp.float32,
                           policy="reference", mesh=(2, 2))
    assert not ref.use_pallas
    with pytest.raises(ValueError):
        dispatch.resolve("pdgemm", (64, 64, 64), jnp.float32,
                         policy="model")


def test_plan_pdgemm_collective_term():
    from repro.core.codesign import plan_pdgemm
    n = 4096                                    # large enough to amortize
    p11 = plan_pdgemm(n, n, n, 1, 1)            # per-step pipeline fill
    p22 = plan_pdgemm(n, n, n, 2, 2)
    p42 = plan_pdgemm(n, n, n, 4, 2)
    assert p11.collective_bytes == 0 and p11.collective_s == 0.0
    assert p22.collective_bytes > 0
    # more devices -> smaller local compute term, more on-wire traffic
    assert p42.compute_s < p11.compute_s
    assert p42.collective_bytes > p22.collective_bytes
    assert p22.steps == 4 and p42.steps == 8
    assert p22.modeled_time == max(p22.compute_s, p22.collective_s)
    # tiny problems never amortize the per-step fill (fig.-2 saturation):
    # the model must expose that, not hide it
    small = plan_pdgemm(128, 128, 128, 4, 2)
    assert small.compute_s > plan_pdgemm(128, 128, 128, 1, 1).compute_s
