"""analysis.check / check_surface: trace, lint, and report.

``check(fn, *args)`` is the one entry point: it traces ``fn`` with
``jax.make_jaxpr`` (no execution) under ``jax.enable_x64``
- the canonical lint mode, where silent f64 promotion (DF001) and 64-bit
index dtypes (KL003) are *representable* instead of being clamped away -
records every dispatcher :class:`~repro.tune.dispatch.Resolution` the
trace produced, and runs the three rule families over the result:

* kernel-launch lint over the traced ``pallas_call`` eqns and recorded
  plans (:mod:`repro.analysis.kernel_lint`),
* dtype-flow lint over the jaxpr (:mod:`repro.analysis.jaxpr_lint`),
* cost-model drift: the routine's ``_routine`` span annotation
  (``flops``/``bytes``) against jaxpr-derived counts, plus a double-trace
  retrace-stability probe (CM003),
* SPMD lint over the same trace (:mod:`repro.analysis.spmd_lint`):
  ppermute ring discipline, shard_map spec/shape consistency, and the
  recorded :class:`~repro.distributed.collectives.CollectiveRecord`
  schedule cross-checked against the jaxpr hop/byte census, the ``obs``
  collective counters, and ``plan_pdgemm``'s collective term.

``check_surface()`` sweeps every public ``repro.linalg`` routine over the
acceptance grid (policies x dtypes x {no mesh, meshes}) with canonical
small operands and merges the per-case reports - the distributed leg now
covers ``SURFACE_MESHES`` plus direct ``pdgemm``/``pdtrsm`` entry points;
it is the engine behind ``scripts/check_static_analysis.py``. See
``docs/static_analysis.md``.
"""
from __future__ import annotations

import dataclasses
import json
import math
import re
from typing import Callable, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import arch as _arch
from repro.analysis import kernel_lint, rules, spmd_lint
from repro.analysis.jaxpr_lint import iter_eqns, lint_dtype_flow
from repro.analysis.rules import (Allowlist, Finding, apply_suppression,
                                  drift_tolerance, load_allowlist,
                                  make_finding)
from repro.core import jaxpr_census

SCHEMA_VERSION = rules.SCHEMA_VERSION


@dataclasses.dataclass
class AnalysisReport:
    """Lint results for one target (routine or surface sweep).

    ``cases`` records what was actually checked - one dict per traced
    (policy, dtype, mesh) leg, including skips - so a report that found
    nothing is distinguishable from a report that checked nothing.
    """

    target: str
    cases: List[Dict]
    findings: List[Finding]
    suppressed: List[Finding]
    schema_version: int = SCHEMA_VERSION

    @property
    def errors(self) -> List[Finding]:
        return [f for f in self.findings if f.severity == rules.ERROR]

    @property
    def warnings(self) -> List[Finding]:
        return [f for f in self.findings if f.severity == rules.WARN]

    @property
    def ok(self) -> bool:
        """No unsuppressed errors (warnings do not fail the gate)."""
        return not self.errors

    def to_json(self) -> Dict:
        return {"schema_version": self.schema_version, "target": self.target,
                "cases": self.cases,
                "findings": [f.to_json() for f in self.findings],
                "suppressed": [f.to_json() for f in self.suppressed]}

    def save(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_json(), f, indent=2, sort_keys=True)
        return path

    def summary(self) -> str:
        n_e, n_w = len(self.errors), len(self.warnings)
        head = (f"analysis[{self.target}]: {len(self.cases)} case(s), "
                f"{n_e} error(s), {n_w} warning(s), "
                f"{len(self.suppressed)} suppressed")
        lines = [head]
        for f in self.findings:
            lines.append(f"  {f.severity.upper():5s} {f.rule} "
                         f"[{f.routine or '-'}] {f.message}")
        for f in self.suppressed:
            lines.append(f"  allow {f.rule} [{f.routine or '-'}] "
                         f"(via {f.suppressed_by})")
        return "\n".join(lines)


def merge_reports(reports: Sequence[AnalysisReport],
                  target: str) -> AnalysisReport:
    cases: List[Dict] = []
    findings: List[Finding] = []
    suppressed: List[Finding] = []
    for r in reports:
        cases.extend(r.cases)
        findings.extend(r.findings)
        suppressed.extend(r.suppressed)
    return AnalysisReport(target, cases, findings, suppressed)


# ------------------------------ tracing helpers -----------------------------

def _x64():
    return jax.enable_x64(True)


def _leaves(args, kw):
    return jax.tree_util.tree_leaves((args, kw))


def _has_zero_dim(args, kw) -> bool:
    return any(0 in tuple(getattr(a, "shape", ()))
               for a in _leaves(args, kw))


_ADDR = re.compile(r"0x[0-9a-fA-F]+")


def _normalize_jaxpr_str(closed) -> str:
    """Jaxpr text with memory addresses scrubbed: two traces of a stable
    function compare equal even where params repr closure objects."""
    return _ADDR.sub("0x", str(closed.jaxpr))


def _trace(fn: Callable, args, kw):
    """(closed_jaxpr, resolutions, collective_records, counter_delta)
    under the canonical lint mode. The collective records and the
    ``collective.*`` counter movement come from the *same* trace as the
    jaxpr, so spmd_lint can diff declared schedule against traced
    reality."""
    from repro.distributed.collectives import record_collectives
    from repro.obs import counters as _counters
    from repro.tune import dispatch
    before = _counters.snapshot()
    with _x64():
        with dispatch.record_resolutions() as rec, \
                record_collectives() as coll:
            closed = jax.make_jaxpr(lambda *a: fn(*a, **kw))(*args)
    return closed, list(rec), list(coll), _counters.delta(before)


# --------------------------- cost-model drift (CM) --------------------------

def _getrf_flops(m, n):
    k = min(m, n)
    return m * n * k - (m + n) * k * k // 2 + k ** 3 // 3


def _geqrf_flops(m, n):
    k = min(m, n)
    return 2 * m * n * k - k * k * (m + n) + 2 * k ** 3 // 3


def _opaque_lapack_flops(closed) -> float:
    """Analytic flops of LAPACK primitives jaxpr_census treats as opaque
    (it counts elementwise/dot volumes; `cholesky`, `triangular_solve`,
    ... are single eqns to it). Leading-order coefficients, the same
    accounting the span annotations use."""
    total = 0.0
    for eqn, _ in iter_eqns(closed.jaxpr):
        name = eqn.primitive.name
        avals = [v.aval for v in eqn.invars if hasattr(v, "aval")]
        if not avals or not hasattr(avals[0], "shape"):
            continue
        s = avals[0].shape
        batch = float(np.prod(s[:-2])) if len(s) > 2 else 1.0
        if name == "cholesky" and len(s) >= 2:
            total += batch * s[-1] ** 3 / 3
        elif name == "lu" and len(s) >= 2:
            total += batch * _getrf_flops(s[-2], s[-1])
        elif name == "geqrf" and len(s) >= 2:
            total += batch * _geqrf_flops(s[-2], s[-1])
        elif name == "householder_product" and len(s) >= 2:
            k = min(s[-2], s[-1])
            total += batch * (4 * s[-2] * s[-1] * k - 2 * (s[-2] + s[-1])
                              * k * k + 4 * k ** 3 / 3) / 2
        elif name == "triangular_solve" and len(avals) >= 2:
            b = avals[1].shape
            nrhs = b[-1] if len(b) >= 2 else 1
            total += batch * s[-1] ** 2 * nrhs
    return total


def _boundary_bytes(closed) -> int:
    total = 0
    for aval in list(closed.in_avals) + list(closed.out_avals):
        shape = getattr(aval, "shape", None)
        dtype = getattr(aval, "dtype", None)
        if shape is None or dtype is None:
            continue
        n = 1
        for d in shape:
            n *= int(d)
        total += n * jnp.dtype(dtype).itemsize
    return total


def _rel_drift(annotated: float, derived: float) -> float:
    if annotated == derived:
        return 0.0
    return abs(annotated - derived) / max(abs(annotated), abs(derived), 1.0)


def _drift_findings(fn: Callable, args, kw, info: Callable,
                    closed, routine: Optional[str],
                    case: Optional[Mapping]) -> List[Finding]:
    """CM001/CM002: span annotation vs jaxpr-derived counts.

    The census runs on the *reference-policy* trace (plain jnp: the
    census cannot see inside pallas_call bodies), which is fair game -
    the annotation claims to price the mathematical routine, not one
    kernelization of it."""
    findings: List[Finding] = []
    try:
        ann = info(*args, **kw)
        ann_flops = float(ann["flops"])
        ann_bytes = float(ann["bytes"])
    except Exception as exc:
        findings.append(make_finding(
            "CM001", f"span annotation info fn failed: {exc!r}",
            routine=routine, case=case))
        return findings
    from repro import linalg
    with linalg.use(policy="reference"), _x64():
        cen = jaxpr_census.census_of(lambda *a: fn(*a, **kw), *args,
                                     name=routine or "fn")
        ref_closed = jax.make_jaxpr(lambda *a: fn(*a, **kw))(*args)
    derived_flops = cen.flops + _opaque_lapack_flops(ref_closed)
    tol_f = drift_tolerance(rules.DRIFT_FLOPS_TOL, routine)
    drift_f = _rel_drift(ann_flops, derived_flops)
    if drift_f > tol_f:
        findings.append(make_finding(
            "CM001", f"flops annotation {ann_flops:.4g} vs census "
            f"{derived_flops:.4g}: drift {drift_f:.2f} > declared "
            f"tolerance {tol_f:.2f}", routine=routine, case=case))
    derived_bytes = _boundary_bytes(closed)
    tol_b = drift_tolerance(rules.DRIFT_BYTES_TOL, routine)
    drift_b = _rel_drift(ann_bytes, derived_bytes)
    if drift_b > tol_b:
        findings.append(make_finding(
            "CM002", f"bytes annotation {ann_bytes:.4g} vs traced "
            f"boundary {derived_bytes:.4g}: drift {drift_b:.2f} > "
            f"declared tolerance {tol_b:.2f}", routine=routine, case=case))
    return findings


# --------------------------------- check ------------------------------------

def check(fn: Callable, *args, routine: Optional[str] = None,
          info: Optional[Callable] = None, machine=None,
          allowlist: Optional[Allowlist] = None, accum_dtype=None,
          drift: bool = True, retrace: bool = True,
          case: Optional[Mapping] = None, **kw) -> AnalysisReport:
    """Statically verify one callable against the full rule vocabulary.

    ``fn`` is traced, never executed. ``routine``/``info`` default to the
    ``_analysis_op``/``_analysis_info`` attributes the ``_routine``
    decorator attaches to every public linalg routine (so
    ``check(linalg.gemm, a, b)`` just works); ``info=None`` skips the
    drift rules. ``machine`` defaults to the ambient
    :func:`repro.arch.current_machine`. ``allowlist`` (see
    :func:`repro.analysis.rules.load_allowlist`) and any active
    :func:`repro.analysis.allow` scopes move matching findings into
    ``report.suppressed`` instead of deleting them.
    """
    routine = routine or getattr(fn, "_analysis_op", None) \
        or getattr(fn, "__name__", None)
    info = info if info is not None else getattr(fn, "_analysis_info", None)
    mach = _arch.resolve_machine(machine)
    zero_dim = _has_zero_dim(args, kw)
    findings: List[Finding] = []
    cases: List[Dict] = [dict(case or {}, routine=routine,
                              zero_dim=zero_dim)]
    try:
        closed, resolutions, coll_records, counter_delta = \
            _trace(fn, args, kw)
    except Exception as exc:
        if zero_dim:
            # the PR 8 bug class: an empty operand crashed the kernel
            # path at trace time instead of routing to the jnp fallback
            findings.append(make_finding(
                "KL004", f"trace crashed on zero-dim operands: "
                f"{type(exc).__name__}: {exc}", routine=routine, case=case))
            active, suppressed = apply_suppression(findings, allowlist)
            return AnalysisReport(routine or "fn", cases, active, suppressed)
        raise
    findings.extend(kernel_lint.lint_kernel_launches(
        closed, mach, routine=routine, zero_dim_inputs=zero_dim))
    findings.extend(kernel_lint.lint_resolutions(
        resolutions, mach, routine=routine))
    findings.extend(lint_dtype_flow(closed, routine=routine,
                                    accum_dtype=accum_dtype))
    findings.extend(spmd_lint.lint_spmd(closed, coll_records,
                                        counter_delta=counter_delta,
                                        routine=routine))
    if retrace:
        closed2, _, _, _ = _trace(fn, args, kw)
        if _normalize_jaxpr_str(closed) != _normalize_jaxpr_str(closed2):
            findings.append(make_finding(
                "CM003", "two same-shape traces produced different "
                "jaxprs (unstable jit cache key - every call retraces)",
                routine=routine, case=case))
    if drift and info is not None and not zero_dim:
        findings.extend(_drift_findings(fn, args, kw, info, closed,
                                        routine, case))
    if case is not None:
        findings = [dataclasses.replace(f, case=dict(case))
                    if f.case is None else f for f in findings]
    active, suppressed = apply_suppression(findings, allowlist)
    return AnalysisReport(routine or "fn", cases, active, suppressed)


def check_routine(name: str, *args, **kw) -> AnalysisReport:
    """``check`` a public routine by its ``repro.linalg`` name."""
    from repro import linalg
    return check(getattr(linalg, name), *args, **kw)


# ----------------------------- surface sweep --------------------------------

# canonical operand sizes: big enough that blocked drivers take their
# real panel/trailing structure and leading-order flop terms dominate,
# small enough that a full sweep stays trace-only cheap
_N, _M, _K, _VEC, _BATCH = 64, 48, 32, 4096, 2


def _rng():
    return np.random.default_rng(0)


def _mat(r, *shape):
    return r.standard_normal(shape).astype(np.float32)


def _spd(r, n):
    g = _mat(r, n, n)
    return (g @ g.T + n * np.eye(n, dtype=np.float32)).astype(np.float32)


def _surface_args(name: str) -> Optional[Tuple[tuple, dict]]:
    """Canonical (args, kwargs) for one linalg routine, float32 base."""
    r = _rng()
    n, m, k, v, bt = _N, _M, _K, _VEC, _BATCH
    if name == "gemm":
        return (_mat(r, m, k), _mat(r, k, n)), {}
    if name == "gemm_bias_act":
        return (_mat(r, m, k), _mat(r, k, n)), {"bias": _mat(r, n),
                                                "epilogue": "relu"}
    if name == "syrk":
        return (_mat(r, m, k),), {}
    if name == "trsm":
        t = np.tril(_mat(r, n, n)) + n * np.eye(n, dtype=np.float32)
        return (t.astype(np.float32), _mat(r, n, k)), {}
    if name == "gemv":
        return (_mat(r, m, k), _mat(r, k)), {}
    if name == "ger":
        return (1.5, _mat(r, m), _mat(r, k), _mat(r, m, k)), {}
    if name == "trsv":
        t = np.tril(_mat(r, n, n)) + n * np.eye(n, dtype=np.float32)
        return (t.astype(np.float32), _mat(r, n)), {}
    if name in ("axpy", "scal"):
        return ((1.5, _mat(r, v), _mat(r, v)) if name == "axpy"
                else (1.5, _mat(r, v))), {}
    if name in ("dot", "nrm2", "asum", "iamax"):
        return ((_mat(r, v), _mat(r, v)) if name == "dot"
                else (_mat(r, v),)), {}
    if name == "rot":
        return (_mat(r, v), _mat(r, v), 0.8, 0.6), {}
    if name == "cholesky":
        return (_spd(r, n),), {}
    if name in ("lu", "qr"):
        return (_mat(r, n, n),), {}
    if name == "solve":
        return (_spd(r, n), _mat(r, n, 4)), {}
    if name == "lstsq":
        return (_mat(r, n, k), _mat(r, n)), {}
    if name == "batched_cholesky":
        return (np.stack([_spd(r, k) for _ in range(bt)]),), {}
    if name in ("batched_lu", "batched_qr"):
        return (np.stack([_mat(r, k, k) for _ in range(bt)]),), {}
    if name == "batched_solve":
        from repro.lapack.batched import FactorizationResult
        factors = np.stack([_spd(r, k) for _ in range(bt)])
        res = FactorizationResult(factors=jnp.asarray(factors), pivots=None,
                                  tau=None, kind="potrf", block=16)
        return (res, _mat(r, bt, k)), {}
    return None                         # context machinery etc: not callable


def _cast_args(args, kw, dtype):
    def cast(x):
        if hasattr(x, "dtype") and jnp.dtype(x.dtype).kind == "f":
            return jnp.asarray(x).astype(dtype)
        return x
    return jax.tree_util.tree_map(cast, args), \
        jax.tree_util.tree_map(cast, kw)


SURFACE_POLICIES = ("reference", "model", "tuned")
SURFACE_DTYPES = ("float32", "bfloat16", "float64")
SURFACE_MESH = (2, 2)
# the acceptance meshes of tests/test_distributed_blas.py: degenerate,
# square, and rectangular - the shapes that exercise distinct SUMMA
# schedules (0, 8, and 32 hops per pdgemm)
SURFACE_MESHES = ((1, 1), (2, 2), (4, 2))
# distributed entry points checked directly (not via the linalg context):
# name -> callable(mesh, policy) applied to canonical operands
DISTRIBUTED_ROUTINES = ("pdgemm", "pdtrsm")


def _distributed_args(name: str) -> Tuple[tuple, dict]:
    """Canonical float32 operands for one direct distributed entry."""
    r = _rng()
    if name == "pdgemm":
        return (_mat(r, _M, _K), _mat(r, _K, _N)), {}
    if name == "pdtrsm":
        t = np.tril(_mat(r, _N, _N)) + _N * np.eye(_N, dtype=np.float32)
        return (t.astype(np.float32), _mat(r, _N, _K)), {}
    raise KeyError(name)


def check_distributed(meshes: Sequence[Tuple[int, int]] = SURFACE_MESHES,
                      policies: Sequence[str] = SURFACE_POLICIES,
                      dtypes: Sequence[str] = SURFACE_DTYPES,
                      allowlist: Optional[Allowlist] = None,
                      machine=None, progress: Optional[Callable] = None
                      ) -> AnalysisReport:
    """Sweep the direct ``pdgemm``/``pdtrsm`` entry points.

    Unlike the ``linalg.use(mesh=...)`` legs of :func:`check_surface`
    (which route the *single-device* surface through the context), this
    calls :mod:`repro.blas.distributed` directly with a real Mesh, so the
    SUMMA schedule - ppermute rings, recorded hops/bytes, plan_pdgemm's
    collective term - is on the traced path for the CC/SH rules. Meshes
    needing more devices than the backend has record skipped cases.
    """
    import functools
    from repro.blas import distributed as _dist
    reports: List[AnalysisReport] = []
    for mesh in meshes:
        px, py = int(mesh[0]), int(mesh[1])
        ndev = px * py
        mesh_ok = len(jax.devices()) >= ndev
        mesh_obj = _dist.make_blas_mesh(px, py) if mesh_ok else None
        for name in DISTRIBUTED_ROUTINES:
            base = _distributed_args(name)
            fn = getattr(_dist, name)
            for dtype in dtypes:
                with _x64():
                    args, kw = _cast_args(*base, jnp.dtype(dtype))
                for policy in policies:
                    case = {"routine": name, "policy": policy,
                            "dtype": dtype, "mesh": [px, py],
                            "entry": "direct"}
                    if not mesh_ok:
                        reports.append(AnalysisReport(
                            name, [dict(case,
                                        skipped=f"needs {ndev} devices")],
                            [], []))
                        continue
                    if progress is not None:
                        progress(case)
                    call = functools.partial(fn, mesh=mesh_obj,
                                             policy=policy)
                    reports.append(check(
                        call, *args, routine=name, machine=machine,
                        allowlist=allowlist, drift=False, retrace=False,
                        case=case, **kw))
    return merge_reports(reports, target="distributed-surface")


def surface_routines() -> List[str]:
    """The checkable (callable, arg-synthesizable) slice of linalg.__all__."""
    from repro import linalg
    return [n for n in linalg.__all__ if _surface_args(n) is not None]


def check_surface(routines: Optional[Sequence[str]] = None,
                  policies: Sequence[str] = SURFACE_POLICIES,
                  dtypes: Sequence[str] = SURFACE_DTYPES,
                  mesh: Optional[Tuple[int, int]] = SURFACE_MESH,
                  allowlist: Optional[Allowlist] = None,
                  machine=None, progress: Optional[Callable] = None,
                  meshes: Optional[Sequence[Tuple[int, int]]] = None,
                  base_leg: bool = True,
                  distributed: Optional[bool] = None) -> AnalysisReport:
    """Sweep the public surface over the acceptance grid and merge.

    Grid: routines x policies x dtypes x {no mesh, meshes}, plus (for a
    full default sweep) the direct distributed entry points of
    :func:`check_distributed`. ``mesh`` is the legacy single-mesh knob:
    left at its default it expands to ``SURFACE_MESHES``; set explicitly
    it pins exactly that mesh (``None`` = no mesh legs). ``meshes``
    overrides both. A mesh leg needs ``px * py`` devices and records a
    skipped case when the backend has fewer
    (``scripts/check_static_analysis.py`` re-execs itself with forced
    host devices so CI never skips it). ``base_leg=False`` drops the
    no-mesh legs (the SPMD-only sweep); ``distributed`` defaults to True
    exactly for unrestricted default-grid sweeps. Drift and retrace
    probes run on the no-mesh legs only: annotations are
    mesh-independent, and the census does not descend into shard_map.
    """
    from repro import linalg
    names = list(routines) if routines is not None else surface_routines()
    if meshes is None:
        if mesh is None:
            meshes = ()
        elif tuple(mesh) == SURFACE_MESH:
            meshes = SURFACE_MESHES
        else:
            meshes = (tuple(mesh),)
    meshes = tuple(tuple(m) for m in meshes)
    if distributed is None:
        distributed = routines is None and bool(meshes)
    reports: List[AnalysisReport] = []
    for name in names:
        base = _surface_args(name)
        if base is None:
            raise KeyError(f"no canonical surface args for {name!r}")
        fn = getattr(linalg, name)
        for dtype in dtypes:
            with _x64():
                args, kw = _cast_args(*base, jnp.dtype(dtype))
            for policy in policies:
                legs = ([None] if base_leg else []) + list(meshes)
                for leg in legs:
                    case = {"routine": name, "policy": policy,
                            "dtype": dtype,
                            "mesh": None if leg is None else list(leg)}
                    if leg is not None and \
                            len(jax.devices()) < int(np.prod(leg)):
                        reports.append(AnalysisReport(
                            name, [dict(case, skipped="needs "
                                        f"{int(np.prod(leg))} devices")],
                            [], []))
                        continue
                    if progress is not None:
                        progress(case)
                    with linalg.use(policy=policy, mesh=leg):
                        reports.append(check(
                            fn, *args, machine=machine, allowlist=allowlist,
                            drift=(leg is None and policy == "reference"
                                   ), retrace=leg is None, case=case, **kw))
    if distributed and meshes:
        reports.append(check_distributed(
            meshes=meshes, policies=policies, dtypes=dtypes,
            allowlist=allowlist, machine=machine, progress=progress))
    return merge_reports(reports, target="linalg-surface")
