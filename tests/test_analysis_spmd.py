"""Tests for the SPMD static-analysis layer (rules CC/SH/BY).

Two tiers, mirroring the repo's distributed-test convention: synthetic
jaxpr-like objects exercise every rule's detector in-process (the main
pytest process keeps 1 device - see conftest), and one subprocess with
8 forced host devices runs the real distributed sweep plus real-mesh
seeded violations. Every new rule ID is proven *live* - a seeded
violation fires it - and the clean sweep is proven silent.
"""
import json
import os
import subprocess
import sys
import textwrap
import warnings
from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

from repro import analysis
from repro.analysis import bypass_lint, spmd_lint
from repro.distributed.collectives import (CollectiveRecord,
                                           record_collectives)


# --------------------------- synthetic jaxpr kit ----------------------------

def _var(shape, dtype="float32"):
    return SimpleNamespace(aval=SimpleNamespace(shape=tuple(shape),
                                                dtype=jnp.dtype(dtype)))


def _eqn(prim, params=None, invars=(), outvars=()):
    return SimpleNamespace(primitive=SimpleNamespace(name=prim),
                           params=dict(params or {}),
                           invars=list(invars), outvars=list(outvars))


def _jaxpr(*eqns):
    return SimpleNamespace(eqns=list(eqns))


def _mesh(**axes):
    return SimpleNamespace(shape=dict(axes))


def _shard_map_eqn(body, mesh, in_names=(), out_names=(), invars=(),
                   outvars=()):
    return _eqn("shard_map",
                params={"mesh": mesh, "in_names": tuple(in_names),
                        "out_names": tuple(out_names),
                        "jaxpr": SimpleNamespace(jaxpr=body)},
                invars=invars, outvars=outvars)


def _ppermute(perm, axis="y", shape=(4, 4)):
    return _eqn("ppermute", params={"axis_name": (axis,),
                                    "perm": tuple(perm)},
                invars=[_var(shape)], outvars=[_var(shape)])


def _ring_perm(size):
    return [((d - 1) % size, d) for d in range(size)]


def _rules_of(findings):
    return {f.rule for f in findings}


# ------------------------------- CC001 --------------------------------------

def test_cc001_clean_ring_is_silent():
    f = spmd_lint.lint_ppermute_eqn(_ppermute(_ring_perm(4)), {"y": 4})
    assert f == []


def test_cc001_self_send_fires():
    f = spmd_lint.lint_ppermute_eqn(
        _ppermute([(0, 0), (1, 1)]), {"y": 2})
    assert _rules_of(f) == {"CC001"} and "self-send" in f[0].message


def test_cc001_duplicate_endpoint_fires():
    f = spmd_lint.lint_ppermute_eqn(
        _ppermute([(0, 1), (0, 2), (1, 0)]), {"y": 3})
    assert _rules_of(f) == {"CC001"} and "bijection" in f[0].message


def test_cc001_partial_coverage_fires():
    f = spmd_lint.lint_ppermute_eqn(
        _ppermute([(0, 1), (1, 0)]), {"y": 4})
    assert _rules_of(f) == {"CC001"} and "2 of 4" in f[0].message


def test_cc001_multi_cycle_fires():
    # bijective and covering, but two disjoint 2-cycles - half the ring
    # never sees the src panel
    f = spmd_lint.lint_ppermute_eqn(
        _ppermute([(0, 1), (1, 0), (2, 3), (3, 2)]), {"y": 4})
    assert _rules_of(f) == {"CC001"} and "cycles" in f[0].message


def test_cc001_fires_through_the_walker_with_shard_map_axis_env():
    # the axis size comes from the *enclosing* shard_map's mesh
    body = _jaxpr(_ppermute([(0, 1), (1, 0)], axis="y"))
    top = _jaxpr(_shard_map_eqn(body, _mesh(x=2, y=4)))
    f = spmd_lint.lint_collective_jaxpr(top)
    assert "CC001" in _rules_of(f)


# ------------------------------- SH001 --------------------------------------

def test_sh001_clean_spec_is_silent():
    eq = _shard_map_eqn(_jaxpr(), _mesh(x=2, y=2),
                        in_names=({0: ("x",), 1: ("y",)},),
                        invars=[_var((4, 6))])
    assert spmd_lint.lint_shard_map_eqn(eq) == []


def test_sh001_non_divisible_dim_fires():
    eq = _shard_map_eqn(_jaxpr(), _mesh(x=2, y=2),
                        in_names=({0: ("x",)},), invars=[_var((3, 4))])
    f = spmd_lint.lint_shard_map_eqn(eq)
    assert _rules_of(f) == {"SH001"} and "not divisible" in f[0].message


def test_sh001_spec_beyond_rank_fires():
    eq = _shard_map_eqn(_jaxpr(), _mesh(x=2, y=2),
                        in_names=({2: ("x",)},), invars=[_var((4, 4))])
    f = spmd_lint.lint_shard_map_eqn(eq)
    assert _rules_of(f) == {"SH001"} and "rank-2" in f[0].message


def test_sh001_unknown_mesh_axis_fires_on_out_spec():
    eq = _shard_map_eqn(_jaxpr(), _mesh(x=2, y=2),
                        out_names=({0: ("z",)},), outvars=[_var((4, 4))])
    f = spmd_lint.lint_shard_map_eqn(eq)
    assert _rules_of(f) == {"SH001"} and "absent from the mesh" in \
        f[0].message


# ------------------------------- SH003 --------------------------------------

def test_sh003_all_gather_inside_shard_map_warns():
    gather = _eqn("all_gather", params={"axis_name": ("y",)},
                  invars=[_var((4, 4))], outvars=[_var((8, 4))])
    top = _jaxpr(_shard_map_eqn(_jaxpr(gather), _mesh(x=2, y=2)))
    f = spmd_lint.lint_collective_jaxpr(top)
    assert _rules_of(f) == {"SH003"} and f[0].severity == "warn"


def test_sh003_all_gather_outside_shard_map_is_silent():
    gather = _eqn("all_gather", params={"axis_name": ("y",)},
                  invars=[_var((4, 4))], outvars=[_var((8, 4))])
    assert spmd_lint.lint_collective_jaxpr(_jaxpr(gather)) == []


# ------------------------------- CC002 --------------------------------------

def _ring_record(size=4, hops=None, axis="y", per_hop=64):
    hops = size - 1 if hops is None else hops
    return CollectiveRecord(kind="ring_bcast", axis=axis, size=size,
                            src=0, hops=hops, per_hop_bytes=per_hop,
                            wire_bytes=per_hop * hops)


def test_cc002_recorded_hops_must_be_size_minus_one():
    f = spmd_lint.lint_collective_records(_jaxpr(), [_ring_record(hops=2)])
    assert "CC002" in _rules_of(f)
    assert any("size - 1 = 3" in x.message for x in f)


def test_cc002_jaxpr_census_must_match_records():
    # schedule declares 3 hops on "y"; the trace only contains 2
    jx = _jaxpr(_ppermute(_ring_perm(4)), _ppermute(_ring_perm(4)))
    f = spmd_lint.lint_collective_records(jx, [_ring_record(size=4)])
    assert any(x.rule == "CC002" and "traced 2" in x.message for x in f)


def test_cc002_counter_delta_must_match_records():
    jx = _jaxpr(*[_ppermute(_ring_perm(4)) for _ in range(3)])
    f = spmd_lint.lint_collective_records(
        jx, [_ring_record(size=4)], counter_delta={"collective.hops": 5})
    assert any(x.rule == "CC002" and "counter" in x.message for x in f)


def test_cc002_consistent_schedule_is_silent():
    jx = _jaxpr(*[_ppermute(_ring_perm(4), shape=(4, 4))
                  for _ in range(3)])
    f = spmd_lint.lint_collective_records(
        jx, [_ring_record(size=4, per_hop=64)],
        counter_delta={"collective.hops": 3, "collective.bytes": 192})
    assert f == []


# ------------------------------- CC003 --------------------------------------

def test_cc003_counter_byte_drift_fires():
    jx = _jaxpr(_ppermute(_ring_perm(2), shape=(4, 4)))   # 64 B on wire
    f = spmd_lint.lint_collective_records(
        jx, [_ring_record(size=2, hops=1, per_hop=64)],
        counter_delta={"collective.hops": 1, "collective.bytes": 128})
    assert any(x.rule == "CC003" and "counter" in x.message for x in f)


def test_cc003_plan_pdgemm_drift_fires():
    # a declared pdgemm schedule with an empty trace: the plan's
    # collective term (7168 B for this geometry) has nothing to match
    sched = CollectiveRecord(kind="pdgemm", size=4,
                             info={"m": 48, "n": 64, "k": 32, "px": 2,
                                   "py": 2, "kf": 8, "itemsize": 4,
                                   "dtype": "float32"})
    f = spmd_lint.lint_collective_records(_jaxpr(), [sched])
    assert any(x.rule == "CC003" and "plan_pdgemm" in x.message for x in f)


# ------------------------------- SH002 --------------------------------------

def _pad_record(batch, pad, ndev, identity=True):
    return CollectiveRecord(kind="pad_batch", size=ndev,
                            info={"batch": batch, "pad": pad,
                                  "identity": identity})


def test_sh002_clean_pad_is_silent():
    f = spmd_lint.lint_collective_records(
        _jaxpr(), [_pad_record(2, 6, 8), _pad_record(8, 0, 8)])
    assert f == []


def test_sh002_non_multiple_pad_fires():
    f = spmd_lint.lint_collective_records(_jaxpr(), [_pad_record(3, 2, 4)])
    assert _rules_of(f) == {"SH002"} and "not a" in f[0].message


def test_sh002_non_minimal_pad_fires():
    f = spmd_lint.lint_collective_records(_jaxpr(), [_pad_record(3, 5, 4)])
    assert _rules_of(f) == {"SH002"} and "not minimal" in f[0].message


def test_sh002_non_identity_filler_fires():
    f = spmd_lint.lint_collective_records(
        _jaxpr(), [_pad_record(3, 1, 4, identity=False)])
    assert _rules_of(f) == {"SH002"} and "identity" in f[0].message


# --------------------- real traces on the 1-device host ---------------------

def test_record_collectives_captures_pdgemm_schedule_on_1x1():
    # a (1,1) mesh works on the single-device pytest host: zero hops, but
    # the pdgemm schedule record and the degenerate ring records appear
    import jax
    from repro.blas import distributed as dblas
    a = jnp.ones((8, 8), jnp.float32)
    mesh = dblas.make_blas_mesh(1, 1)
    with record_collectives() as rec:
        jax.make_jaxpr(lambda x, y: dblas.pdgemm(x, y, mesh))(a, a)
    kinds = [r.kind for r in rec]
    assert "pdgemm" in kinds and "ring_bcast" in kinds
    assert all(r.hops == 0 for r in rec if r.kind == "ring_bcast")


def test_check_runs_spmd_rules_on_1x1_mesh_leg():
    from repro import linalg
    a = np.random.default_rng(0).standard_normal((8, 8)).astype(np.float32)
    with linalg.use(mesh=(1, 1)):
        rep = analysis.check(linalg.gemm, a, a, retrace=False, drift=False)
    assert rep.ok, rep.summary()


# ----------------------- 8-device subprocess sweep --------------------------

_ENV = dict(os.environ,
            XLA_FLAGS="--xla_force_host_platform_device_count=8",
            PYTHONPATH="src")

_PRELUDE = """
import jax, jax.numpy as jnp, numpy as np
from functools import partial
from jax.sharding import PartitionSpec as P
from repro import analysis
from repro.analysis import report, spmd_lint
from repro.blas import distributed as dblas
"""


def _run(body: str):
    code = _PRELUDE + textwrap.dedent(body)
    r = subprocess.run([sys.executable, "-c", code], env=_ENV,
                       capture_output=True, text=True, timeout=900,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_distributed_sweep_is_clean_and_seeded_violations_fire():
    out = _run("""
    # 1) the clean sweep: direct pdgemm/pdtrsm over every acceptance mesh
    rep = report.check_distributed(dtypes=("float32",))
    assert rep.ok, rep.summary()
    cases = [c for c in rep.cases if "skipped" not in c]
    meshes = {tuple(c["mesh"]) for c in cases}
    names = {c["routine"] for c in cases}
    assert meshes == {(1, 1), (2, 2), (4, 2)}, meshes
    assert names == {"pdgemm", "pdtrsm"}, names
    assert len(cases) == 18, len(cases)     # 3 meshes x 2 routines x 3 pol
    print("clean sweep OK:", len(cases), "cases")

    # ... and the via-context mesh legs of the linalg surface
    rep = analysis.check_surface(routines=["gemm", "batched_cholesky"],
                                 dtypes=("float32",),
                                 meshes=report.SURFACE_MESHES)
    assert rep.ok, rep.summary()
    print("surface mesh legs OK:", len(rep.cases), "cases")

    # 2) seeded CC001 on a real mesh: a two-2-cycle ppermute perm
    mesh = dblas.make_blas_mesh(4, 2)
    def two_cycles(x):
        perm = [(0, 1), (1, 0), (2, 3), (3, 2)]
        return jax.lax.ppermute(x, "x", perm)
    bad = jax.shard_map(two_cycles, mesh=mesh, in_specs=P("x", None),
                        out_specs=P("x", None), check_vma=False)
    closed = jax.make_jaxpr(bad)(jnp.ones((8, 4), jnp.float32))
    f = spmd_lint.lint_collective_jaxpr(closed)
    assert {x.rule for x in f} == {"CC001"}, f
    print("seeded CC001 OK")

    # 3) seeded CC002/CC003 on a real trace: records from a real pdgemm
    # paired against a doctored jaxpr (fewer hops / fewer bytes)
    from repro.distributed.collectives import record_collectives
    a = jnp.ones((16, 16), jnp.float32)
    mesh = dblas.make_blas_mesh(2, 2)
    with record_collectives() as rec:
        closed = jax.make_jaxpr(lambda p, q: dblas.pdgemm(p, q, mesh))(a, a)
    f = spmd_lint.lint_collective_records(closed, rec)
    assert not f, f                          # truthful pairing is silent
    empty = jax.make_jaxpr(lambda x: x + 1)(a)
    f = spmd_lint.lint_collective_records(empty, rec)
    got = {x.rule for x in f}
    assert "CC002" in got and "CC003" in got, f
    print("seeded CC002/CC003 OK")
    """)
    assert "clean sweep OK" in out
    assert "seeded CC001 OK" in out
    assert "seeded CC002/CC003 OK" in out


# ------------------------------- BY001 --------------------------------------

def _raw_entry(name="raw"):
    def build():
        a = jnp.ones((4, 4), jnp.float32)

        def fn(x):
            return x @ x
        return fn, (a,), {}
    return [(name, build)]


def test_by001_fires_on_raw_contraction():
    rep = bypass_lint.lint_bypass(entries=_raw_entry(), allowlist=None)
    assert not rep.ok
    assert [f.rule for f in rep.findings] == ["BY001"]
    assert "dot_general" in rep.findings[0].message


def test_by001_dispatched_path_is_silent():
    # the same contraction through the dispatcher's executor is exempt
    def build():
        from repro.tune import dispatch
        a = jnp.ones((8, 8), jnp.float32)
        res = dispatch.resolve("gemm", (8, 8, 8), a.dtype,
                               policy="reference")

        def fn(x):
            return dispatch._gemm_exec(x, x, res, True)
        return fn, (a,), {}
    rep = bypass_lint.lint_bypass(entries=[("via-dispatch", build)],
                                  allowlist=None)
    assert rep.ok and not rep.findings and not rep.suppressed


def test_by001_allowlist_round_trip(tmp_path):
    rep = bypass_lint.lint_bypass(entries=_raw_entry(), allowlist=None)
    site = rep.findings[0].location
    path = tmp_path / "by.json"
    path.write_text(json.dumps({
        "schema_version": 1, "rule": "BY001",
        "sites": [{"site": site, "reason": "test exemption"}]}))
    rep2 = bypass_lint.lint_bypass(entries=_raw_entry(),
                                   allowlist=str(path))
    assert rep2.ok and not rep2.findings
    assert len(rep2.suppressed) == 1
    assert rep2.suppressed[0].suppressed_by == f"allowlist:{path}"


def test_by001_corrupt_allowlist_warns_once_and_refires(tmp_path):
    path = tmp_path / "corrupt.json"
    path.write_text("{not json")
    with pytest.warns(RuntimeWarning, match="corrupt"):
        rep = bypass_lint.lint_bypass(entries=_raw_entry(),
                                      allowlist=str(path))
    assert not rep.ok and rep.findings          # re-fires, never hides
    with warnings.catch_warnings():
        warnings.simplefilter("error")          # second load: no warning
        assert bypass_lint.load_bypass_allowlist(str(path)) == {}


def test_by001_missing_allowlist_is_silently_empty(tmp_path):
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        got = bypass_lint.load_bypass_allowlist(str(tmp_path / "no.json"))
    assert got == {}


def test_by001_wrong_rule_allowlist_warns(tmp_path):
    path = tmp_path / "wrong.json"
    path.write_text(json.dumps({"schema_version": 1, "rule": "CM001",
                                "sites": []}))
    with pytest.warns(RuntimeWarning, match="corrupt"):
        assert bypass_lint.load_bypass_allowlist(str(path)) == {}


def test_by001_committed_allowlist_covers_every_current_bypass():
    # the acceptance contract: the committed burn-down file enumerates
    # every bypass reachable from models/kernels/serving today (count >
    # 0), so CI fails exactly on *new* sites. Slow-ish (traces every
    # entry point) but pure tracing - no execution.
    assert os.path.exists(bypass_lint.DEFAULT_ALLOWLIST_PATH)
    committed = bypass_lint.load_bypass_allowlist()
    assert len(committed) > 0
    rep = bypass_lint.lint_bypass()
    broken = [c for c in rep.cases if "error" in c]
    assert not broken, broken                    # every entry must trace
    assert rep.ok, "new bypass site(s):\n" + rep.summary()
    assert len(rep.suppressed) == len(committed)


# --------------------------- vocabulary plumbing ----------------------------

def test_spmd_rules_reachable_from_check_surface_defaults():
    # SURFACE_MESHES is the frozen acceptance set; the default surface
    # sweep must expand the legacy mesh knob onto it
    from repro.analysis import report
    assert report.SURFACE_MESHES == ((1, 1), (2, 2), (4, 2))
    assert tuple(report.DISTRIBUTED_ROUTINES) == ("pdgemm", "pdtrsm")


def test_allow_scope_suppresses_spmd_rule():
    with analysis.allow("SH002"):
        rep_findings, = [spmd_lint.lint_collective_records(
            _jaxpr(), [_pad_record(3, 2, 4)])]
        from repro.analysis.rules import apply_suppression
        active, suppressed = apply_suppression(rep_findings)
    assert not active and len(suppressed) == 1
    assert suppressed[0].suppressed_by == "allow()"
