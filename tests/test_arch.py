"""repro.arch: machine specs, registry, serialization, and the end-to-end
machine -> planner -> tuner-key -> context flow.

Conventions covered (ROADMAP): persistence gets round-trip + corrupt-file +
missing-file tests; the default machine must keep every planner output
bit-identical to the pre-arch module constants (also guarded by
scripts/check_golden_plans.py in CI); a non-default machine must change
planner/tuner decisions end-to-end through ``linalg.use(machine=...)``.
"""
import dataclasses
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro import arch, linalg, tune
from repro.arch import (FPUSpec, MachineSpec, MemorySpec, PEGeometry,
                        PowerAreaSpec)
from repro.core import codesign as cd
from repro.tune.registry import Registry, make_key


@pytest.fixture(autouse=True)
def _clean_machine_state():
    yield
    arch.set_default_machine(None)
    linalg.reset_context()


def _toy_spec(name="toy", **over):
    kw = dict(
        name=name,
        fpu=FPUSpec(depths={"mul": 3, "add": 2, "div": 9, "sqrt": 11},
                    t_p={"mul": 50.0, "add": 30.0, "div": 150.0,
                         "sqrt": 180.0},
                    t_o=0.8,
                    gamma={"mul": 0.4, "add": 0.4, "div": 0.7, "sqrt": 0.9}),
        memory=MemorySpec(hbm_bw=1e11, vmem_bytes=1 << 20, ici_bw=1e10),
        pe=PEGeometry(mxu=16, sublane=2, lane=16, vreg_budget=16,
                      peak_flops=1e12),
        power_area=PowerAreaSpec(
            pj_per_flop={"mul": 1.0, "add": 0.5, "div": 5.0, "sqrt": 6.0},
            pj_per_byte_hbm=20.0, static_w=2.0, area_mm2=10.0),
    )
    kw.update(over)
    return MachineSpec(**kw)


# ------------------------------ spec basics ---------------------------------

def test_tpu_like_matches_legacy_constants():
    """The default machine IS the historical constant set, field by field -
    the bit-identity contract of the refactor."""
    m = arch.get("tpu-like")
    assert m.pe.peak_flops == 197e12 == cd.PEAK_BF16_FLOPS
    assert m.memory.hbm_bw == 819e9 == cd.HBM_BW
    assert m.memory.ici_bw == 50e9 == cd.ICI_BW
    assert m.memory.vmem_bytes == 96 * 2 ** 20 == cd.VMEM_BYTES
    assert m.pe.mxu == 128 == cd.MXU
    assert m.pe.sublane == 8 == cd.SUBLANE
    assert m.pe.lane == 128 == cd.LANE
    assert m.fpu.add_latency == 6 == cd.VPU_ADD_LATENCY
    assert m.pe.vreg_budget == 64 == cd.VREG_BUDGET
    assert m.fpu.acc_overhead == 0.75 == cd.ACC_OVERHEAD
    assert m.memory.pipeline_fill_s == 2e-6 == cd.PIPELINE_FILL_S
    assert m.pe.mxu_clock == cd.MXU_CLOCK
    assert m.pe.vpu_flops == cd.VPU_FLOPS
    assert m.dtype_bytes() == 2          # native bfloat16


def test_spec_is_frozen_and_validated():
    m = _toy_spec()
    with pytest.raises(dataclasses.FrozenInstanceError):
        m.name = "other"
    with pytest.raises(ValueError):
        FPUSpec(depths={"mul": 1}, t_p={"mul": 1.0}, t_o=1.0,
                gamma={"mul": 0.5})              # missing op classes
    with pytest.raises(ValueError):
        _toy_spec(memory=MemorySpec(hbm_bw=-1.0, vmem_bytes=1, ici_bw=1.0))
    with pytest.raises(ValueError):
        _toy_spec(native_dtype="notadtype")
    with pytest.raises(ValueError):
        FPUSpec(depths={"mul": 0, "add": 2, "div": 9, "sqrt": 11},
                t_p={"mul": 1.0, "add": 1.0, "div": 1.0, "sqrt": 1.0},
                t_o=1.0,
                gamma={"mul": .5, "add": .5, "div": .5, "sqrt": .5})


def test_fpu_feeds_pipeline_model():
    """FPUSpec.tpi / p_opt are eq. 2 / eq. 3 at the spec's constants."""
    from repro.core import pipeline_model as pm
    fpu = arch.get("paper-pe").fpu
    got = float(fpu.tpi("div", 8, n_i=1e5, n_h=1e4))
    want = float(pm.tpi(8, n_i=1e5, n_h=1e4, gamma=fpu.gamma["div"],
                        t_p=fpu.t_p["div"], t_o=fpu.t_o))
    assert got == want
    popt = fpu.p_opt("div", n_i=1e5, n_h=1e4)
    assert popt == pytest.approx(
        float(np.sqrt(1e5 * fpu.t_p["div"] / (fpu.gamma["div"] * 1e4
                                              * fpu.t_o))), rel=1e-5)
    # hazard-free pipes: unbounded optimum (the multiplier's flat curve)
    assert np.isinf(fpu.p_opt("mul", n_i=1e5, n_h=0))
    pp = fpu.pipe_params("sqrt", 100, 99)
    assert pp.t_p == fpu.t_p["sqrt"] and pp.gamma == fpu.gamma["sqrt"]


def test_power_area_reproduces_paper_ratio_bands():
    """paper-pe vs tpu-like lands in the paper's comparison bands:
    1.1-1.5x in Gflops/W, 1.9-2.1x in Gflops/mm^2."""
    pe_ = arch.get("paper-pe")
    tpu = arch.get("tpu-like")
    gw = pe_.peak_gflops_per_w() / tpu.peak_gflops_per_w()
    mm = pe_.peak_gflops_per_mm2() / tpu.peak_gflops_per_mm2()
    assert 1.1 <= gw <= 1.5
    assert 1.9 <= mm <= 2.1


def test_watts_model_terms():
    m = _toy_spec()
    base = m.watts(0.0)
    assert base == m.power_area.static_w
    # FMA mix: (1.0 + 0.5)/2 pJ/flop -> 100 Gflops = 0.075 W dynamic
    assert m.watts(100.0) == pytest.approx(2.0 + 100.0 * 0.75e-3)
    assert m.watts(100.0, hbm_bytes_per_s=1e9) == pytest.approx(
        2.0 + 100.0 * 0.75e-3 + 1e9 * 20.0 * 1e-12)
    assert m.gflops_per_mm2(50.0) == pytest.approx(5.0)


def test_bench_metrics_fields():
    row = arch.bench_metrics(123.0)
    assert row["machine"] == "tpu-like"
    assert row["gflops"] == 123.0
    assert row["gflops_per_w"] > 0 and row["gflops_per_mm2"] > 0
    row2 = arch.bench_metrics(123.0, machine="paper-pe")
    assert row2["machine"] == "paper-pe"
    assert row2["gflops_per_w"] != row["gflops_per_w"]


# --------------------- JSON round-trip / corrupt / unknown ------------------

def test_json_roundtrip_in_memory():
    for name in arch.names():
        m = arch.get(name)
        blob = json.loads(json.dumps(m.to_json()))
        assert MachineSpec.from_json(blob) == m


def test_json_roundtrip_file(tmp_path):
    p = os.path.join(tmp_path, "machine.json")
    m = _toy_spec()
    m.save(p)
    assert MachineSpec.load(p) == m


def test_corrupt_file_raises_value_error(tmp_path):
    p = os.path.join(tmp_path, "bad.json")
    with open(p, "w") as f:
        f.write("{not json")
    with pytest.raises(ValueError):
        MachineSpec.load(p)
    # parseable JSON, wrong schema
    with open(p, "w") as f:
        json.dump({"schema": 999, "name": "x"}, f)
    with pytest.raises(ValueError):
        MachineSpec.load(p)
    # right schema, missing section
    blob = _toy_spec().to_json()
    del blob["fpu"]
    with open(p, "w") as f:
        json.dump(blob, f)
    with pytest.raises(ValueError):
        MachineSpec.load(p)
    # right schema, malformed field inside a section
    blob = _toy_spec().to_json()
    blob["memory"]["hbm_bw"] = -5.0
    with pytest.raises(ValueError):
        MachineSpec.from_json(blob)


def test_missing_file_raises_oserror(tmp_path):
    with pytest.raises(OSError):
        MachineSpec.load(os.path.join(tmp_path, "nope.json"))


def test_unknown_name_lists_registered():
    with pytest.raises(ValueError) as e:
        arch.get("not-a-machine")
    msg = str(e.value)
    assert "not-a-machine" in msg and "tpu-like" in msg


def test_register_and_overwrite():
    m = _toy_spec(name="test-register-machine")
    try:
        arch.register(m)
        assert arch.get("test-register-machine") == m
        with pytest.raises(ValueError):
            arch.register(_toy_spec(name="test-register-machine"))
        m2 = _toy_spec(name="test-register-machine",
                       native_dtype="float64")
        arch.register(m2, overwrite=True)
        assert arch.get("test-register-machine") == m2
        with pytest.raises(TypeError):
            arch.register("not-a-spec")
    finally:
        arch.registry._REGISTRY.pop("test-register-machine", None)


# --------------------------- ambient machine scope --------------------------

def test_machine_scope_nesting_and_default():
    assert arch.current_machine().name == "tpu-like"
    with arch.machine_scope("paper-pe"):
        assert arch.current_machine().name == "paper-pe"
        with arch.machine_scope("cpu-host"):
            assert arch.current_machine().name == "cpu-host"
        assert arch.current_machine().name == "paper-pe"
    assert arch.current_machine().name == "tpu-like"
    arch.set_default_machine("cpu-host")
    assert arch.current_machine().name == "cpu-host"
    with arch.machine_scope("paper-pe"):
        assert arch.current_machine().name == "paper-pe"
        with arch.machine_scope(None):      # None = back to process default
            assert arch.current_machine().name == "cpu-host"
    arch.set_default_machine(None)
    assert arch.current_machine().name == "tpu-like"


# ------------------- planners are machine-parameterized ---------------------

def test_shared_dtype_default_unified():
    """Satellite: one shared dtype-width default for every planner, derived
    from the machine's native dtype (no more 2-vs-4 split)."""
    tpu = arch.get("tpu-like")
    assert cd.resolve_dtype_bytes(machine=tpu) == 2          # bfloat16
    assert cd.resolve_dtype_bytes(machine=arch.get("paper-pe")) == 8
    assert cd.resolve_dtype_bytes(machine=arch.get("cpu-host")) == 4
    assert cd.resolve_dtype_bytes(dtype=jnp.float64, machine=tpu) == 8
    assert cd.resolve_dtype_bytes(dtype_bytes=4, machine=tpu) == 4
    # bare planner calls all agree with the explicit native width now
    g = cd.plan_gemm(512, 512, 512)
    assert (g.bm, g.bn, g.bk) == \
        (lambda p: (p.bm, p.bn, p.bk))(cd.plan_gemm(512, 512, 512,
                                                    dtype_bytes=2))
    t = cd.plan_trsm(512, 8)
    assert t.block == cd.plan_trsm(512, 8, dtype_bytes=2).block
    f = cd.plan_factorization(512)
    assert f.block == cd.plan_factorization(512, dtype_bytes=2).block


def test_planners_change_with_machine():
    big = (2048, 2048, 2048)
    p_tpu = cd.plan_gemm(*big, dtype_bytes=4)
    p_pe = cd.plan_gemm(*big, dtype_bytes=4, machine=arch.get("paper-pe"))
    # paper-pe: 32-wide systolic edge, 4 MiB scratch -> smaller tiles
    assert (p_pe.bm, p_pe.bn, p_pe.bk) != (p_tpu.bm, p_tpu.bn, p_tpu.bk)
    assert p_pe.bm % 32 == 0 and p_pe.vmem_bytes <= 4 * 2 ** 20
    assert p_tpu.ridge != p_pe.ridge
    # factorization panel widths respond to the machine's chain depths
    f_tpu = cd.plan_factorization(2048, kind="potrf", dtype_bytes=8)
    f_pe = cd.plan_factorization(2048, kind="potrf", dtype_bytes=8,
                                 machine=arch.get("paper-pe"))
    assert f_pe.modeled_time != f_tpu.modeled_time
    # ambient scoping reaches planners with no kwargs at all
    with arch.machine_scope("paper-pe"):
        assert cd.plan_gemm(*big, dtype_bytes=4) == p_pe


def test_pdgemm_plan_uses_machine_ici():
    p_tpu = cd.plan_pdgemm(4096, 4096, 4096, 2, 2, dtype_bytes=4)
    p_pe = cd.plan_pdgemm(4096, 4096, 4096, 2, 2, dtype_bytes=4,
                          machine=arch.get("paper-pe"))
    assert p_pe.collective_bytes == p_tpu.collective_bytes   # same wire bytes
    assert p_pe.collective_s > p_tpu.collective_s            # slower links


# ------------------- tuner keys / resolve / end-to-end ----------------------

def test_registry_machine_key_component(tmp_path):
    reg = Registry(path=os.path.join(tmp_path, "r.json"))
    reg.record("gemm", (64, 64, 64), jnp.float32, "cpu",
               {"bm": 128, "bn": 128, "bk": 128})
    reg.record("gemm", (64, 64, 64), jnp.float32, "cpu",
               {"bm": 32, "bn": 32, "bk": 32}, machine="paper-pe")
    # namespaces are disjoint
    assert reg.lookup("gemm", (64, 64, 64), jnp.float32,
                      "cpu").params["bm"] == 128
    assert reg.lookup("gemm", (64, 64, 64), jnp.float32, "cpu",
                      machine="paper-pe").params["bm"] == 32
    # key format: default omits the component (old files resolve unchanged)
    assert make_key("gemm", (64, 64, 64), jnp.float32, "cpu") == \
        "gemm|64x64x64|float32|cpu"
    assert make_key("gemm", (64, 64, 64), jnp.float32, "cpu",
                    machine="paper-pe") == \
        "gemm|64x64x64|float32|cpu|m:paper-pe"
    assert make_key("pdgemm", (64, 64, 64), jnp.float32, "cpu",
                    mesh="x2y2", machine="paper-pe") == \
        "pdgemm|64x64x64|float32|cpu|x2y2|m:paper-pe"
    # round-trips through the file with the machine component intact
    path = reg.save()
    reloaded = Registry(path=path)
    assert reloaded.lookup("gemm", (64, 64, 64), jnp.float32, "cpu",
                           machine="paper-pe").params["bm"] == 32


def test_resolve_scopes_registry_by_machine(tmp_path):
    reg = Registry(path=os.path.join(tmp_path, "r.json"))
    import jax
    backend = jax.default_backend()
    reg.record("gemm", (64, 64, 64), jnp.float32, backend,
               {"bm": 256, "bn": 256, "bk": 256})
    # default machine: hit
    r = tune.resolve("gemm", (64, 64, 64), jnp.float32, policy="tuned",
                     registry=reg)
    assert r.source == "registry" and r.machine == "tpu-like"
    # non-default machine: its namespace is empty -> model fallback
    r_pe = tune.resolve("gemm", (64, 64, 64), jnp.float32, policy="tuned",
                        registry=reg, machine=arch.get("paper-pe"))
    assert r_pe.source == "fallback-model" and r_pe.machine == "paper-pe"
    # tune a machine-scoped entry and hit it
    from repro.tune import search
    search.seed_registry_from_model(reg, gemm_shapes=[(64, 64, 64)],
                                    backend=backend,
                                    machine=arch.get("paper-pe"))
    r_pe2 = tune.resolve("gemm", (64, 64, 64), jnp.float32, policy="tuned",
                         registry=reg, machine=arch.get("paper-pe"))
    assert r_pe2.source == "registry"


def test_linalg_use_machine_end_to_end():
    """Acceptance: linalg.use(machine=...) changes planner/tuner decisions
    end-to-end, while the default context resolves exactly as before."""
    shape = (2048, 2048, 2048)
    r_default = tune.resolve("gemm", shape, jnp.float32, policy="model")
    with linalg.use(machine=arch.get("paper-pe")):
        # context machine only binds inside routine bodies; emulate the
        # routine's scope entry the way _machine_scoped does
        ctx = linalg.get_context()
        from repro.linalg.context import resolved_machine
        with arch.machine_scope(resolved_machine(ctx)):
            r_pe = tune.resolve("gemm", shape, jnp.float32, policy="model")
    assert r_default.machine == "tpu-like" and r_pe.machine == "paper-pe"
    cfg_d = (r_default.gemm_plan.bm, r_default.gemm_plan.bn,
             r_default.gemm_plan.bk)
    cfg_p = (r_pe.gemm_plan.bm, r_pe.gemm_plan.bn, r_pe.gemm_plan.bk)
    assert cfg_d != cfg_p
    # and the default context is untouched afterwards
    r_after = tune.resolve("gemm", shape, jnp.float32, policy="model")
    assert r_after == r_default


def test_linalg_machine_context_numerics_and_describe(rng=None):
    """Execution under a non-default machine keeps numerics (same kernel,
    different tiling) and the context describes its machine."""
    rng = np.random.default_rng(7)
    a = jnp.asarray(rng.normal(size=(96, 96)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(96, 96)).astype(np.float32))
    want = np.asarray(a, dtype=np.float64) @ np.asarray(b, dtype=np.float64)
    got_default = linalg.gemm(a, b, context=dict(policy="model"))
    with linalg.use(policy="model", machine="paper-pe") as ctx:
        assert ctx.describe()["machine"] == "paper-pe"
        got_pe = linalg.gemm(a, b)
    np.testing.assert_allclose(np.asarray(got_pe), want, rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(got_default), want, rtol=2e-4,
                               atol=2e-4)
    assert linalg.get_context().describe()["machine"] == "tpu-like"


def test_machine_name_string_in_context_validated():
    with pytest.raises(ValueError):
        linalg.ExecutionContext(machine="definitely-not-registered")
    with pytest.raises(ValueError):
        linalg.ExecutionContext(machine=123)


def test_cholesky_trailing_updates_see_context_machine(tmp_path):
    """The machine scope wraps the whole routine body: the trailing-update
    GEMMs inside a blocked factorization resolve under ctx.machine (probed
    via the machine-scoped registry namespace)."""
    import jax
    reg = Registry(path=os.path.join(tmp_path, "r.json"))
    rng = np.random.default_rng(3)
    m = rng.normal(size=(48, 48)).astype(np.float32)
    spd = jnp.asarray(m @ m.T + 48 * np.eye(48, dtype=np.float32))
    with linalg.use(policy="tuned", registry=reg, machine="paper-pe"):
        l = linalg.cholesky(spd, block=16)
    np.testing.assert_allclose(np.asarray(l @ l.T), np.asarray(spd),
                               rtol=2e-4, atol=2e-4)
    # the trailing updates resolved under paper-pe: verify by resolving the
    # same trailing shape in both namespaces against a seeded registry
    backend = jax.default_backend()
    from repro.tune import search
    search.seed_registry_from_model(reg, gemm_shapes=[(32, 16, 16)],
                                    backend=backend,
                                    machine=arch.get("paper-pe"))
    r = tune.resolve("gemm", (32, 16, 16), jnp.float32, policy="tuned",
                     registry=reg, machine=arch.get("paper-pe"))
    assert r.source == "registry"
    r_def = tune.resolve("gemm", (32, 16, 16), jnp.float32, policy="tuned",
                         registry=reg)
    assert r_def.source == "fallback-model"
