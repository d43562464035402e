"""Unified BLAS/LAPACK kernel-config resolution and execution.

``resolve`` is the single place a (op, shape, dtype, backend, policy)
tuple becomes an executable config; ``dispatch`` executes it. Every BLAS-3
and blocked-LAPACK call in the repo funnels through here - the old
``use_kernel`` booleans survive only as deprecated aliases that
:func:`repro.tune.policy.resolve_policy` folds into a policy.

Resolution table (``source`` records which row fired):

    policy      registry hit        registry miss / no file / corrupt
    ---------   -----------------   ---------------------------------
    reference   (never consulted)   plain jnp
    model       (never consulted)   plan_gemm / plan_trsm config
    tuned       stored config       model config  (source="fallback-model")

On a TPU, float64 under ``model``/``tuned`` resolves to XLA instead
(``source="xla-float64"``): Mosaic compiles no float64 kernel.

The miss column is why a cold-start ``tuned`` run is numerically identical
to ``model``: both execute the same kernel with the same plan.
"""
from __future__ import annotations

import contextlib
import dataclasses
from contextvars import ContextVar
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import arch as _arch
from repro import obs as _obs
from repro.arch import MachineSpec
from repro.core.codesign import (FusedChainPlan, GemmPlan, plan_from_blocks,
                                 plan_fused_chain, plan_gemm, plan_pdgemm,
                                 plan_trsm)
from repro.obs import counters as _counters
from repro.tune.policy import resolve_policy, uses_kernel
from repro.tune.registry import Registry, default_registry


OPS = ("gemm", "gemv", "trsm", "syrk", "pdgemm", "gemm+epilogue",
       "trsm+gemm")
FUSED_OPS = ("gemm+epilogue", "trsm+gemm")

# Resolution provenance for the dispatcher-bypass lint (BY001,
# repro.analysis.bypass_lint): every contraction traced from a source
# file under one of these prefixes reached ``resolve()``/``dispatch()``
# by construction - the BLAS/LAPACK drivers and the kernels this module
# launches are the *governed* set. A raw dot_general/conv whose source
# frame lies anywhere else (models/, launch/, the hand-rolled attention
# and SSD kernels) bypassed the dispatcher and must be on the committed
# burn-down allowlist. Frozen by scripts/check_api_surface.py.
DISPATCHED_MODULES = (
    "repro/blas/", "repro/lapack/", "repro/linalg/", "repro/tune/",
    "repro/core/",
    "repro/kernels/ops.py", "repro/kernels/ref.py", "repro/kernels/gemm.py",
    "repro/kernels/fused.py", "repro/kernels/dotp.py",
)


@dataclasses.dataclass(frozen=True)
class Resolution:
    """The resolved execution recipe for one call."""

    op: str
    policy: str                   # "reference" | "model" | "tuned"
    source: str                   # "reference" | "model" | "registry" |
                                  # "fallback-model" | "xla-float64"
    use_pallas: bool
    gemm_plan: Optional[GemmPlan] = None
    block: Optional[int] = None   # trsm diagonal width
    mesh: Optional[str] = None    # registry mesh component (pdgemm)
    machine: Optional[str] = None   # machine the call resolved under
    fused: bool = False           # run the streaming fused kernel?
    chain: Optional[FusedChainPlan] = None   # fused-vs-staged pricing

    def describe(self) -> dict:
        """JSON-able summary - benchmarks attach this to every record so
        trajectories are comparable across PRs."""
        d = {"op": self.op, "policy": self.policy, "source": self.source,
             "use_pallas": self.use_pallas, "machine": self.machine}
        if self.gemm_plan is not None:
            d["config"] = {"bm": self.gemm_plan.bm, "bn": self.gemm_plan.bn,
                           "bk": self.gemm_plan.bk}
        if self.block is not None:
            d.setdefault("config", {})["block"] = self.block
        if self.mesh is not None:
            d["mesh"] = self.mesh
        if self.op in FUSED_OPS:
            d["fused"] = self.fused
            if self.chain is not None:
                d["hbm_bytes_saved"] = self.chain.hbm_bytes_saved
        return d


# scoped Resolution capture for the static analyzer: resolve() runs in
# Python at trace time, so every plan a jax.make_jaxpr trace produces can
# be recorded without executing anything (repro.analysis.kernel_lint
# checks the recorded plans against the ambient machine budget)
_RECORD: "ContextVar[Optional[List[Resolution]]]" = ContextVar(
    "dispatch_resolution_record", default=None)


@contextlib.contextmanager
def record_resolutions():
    """Collect every Resolution produced inside the scope (trace-safe)."""
    rec: List[Resolution] = []
    token = _RECORD.set(rec)
    try:
        yield rec
    finally:
        _RECORD.reset(token)


def _observed(res: "Resolution") -> "Resolution":
    """Resolution accounting: counters always, a provenance event when a
    trace is capturing (``obs.event("tune.resolve", ...)`` carrying
    :meth:`Resolution.describe` - the registry-hit / model-seeded /
    reference provenance every traced call records)."""
    rec = _RECORD.get()
    if rec is not None:
        rec.append(res)
    _counters.inc("dispatch.resolve")
    if res.policy == "tuned":
        _counters.inc("dispatch.registry_hit" if res.source == "registry"
                      else "dispatch.registry_miss")
    if _obs.enabled():
        _obs.event("tune.resolve", cat="resolve", **res.describe())
    return res


def resolve(op: str, shape: Tuple[int, ...], dtype,
            policy: Optional[str] = None, use_kernel: Optional[bool] = None,
            registry: Optional[Registry] = None,
            backend: Optional[str] = None,
            mesh: Optional[Tuple[int, int]] = None,
            machine: Optional[MachineSpec] = None,
            epilogue: str = "none", form: str = "lu",
            has_bias: bool = True) -> Resolution:
    """Resolve one call's config. shape is (m, n, k) for gemm/syrk/pdgemm
    (pdgemm: the *global* problem), (m, n) for gemv, (n, nrhs) for trsm.
    ``mesh`` is the (px, py) device mesh for pdgemm; its registry entries
    live under the mesh-suffixed key ``pdgemm|bucket|dtype|backend|pxXpyY``.
    ``machine`` parameterizes every planner and (for non-default machines)
    suffixes the registry key; ``None`` resolves the ambient
    :func:`repro.arch.current_machine` - which is what
    ``repro.linalg.use(machine=...)`` scopes for its routines.

    The fused chain ops take shape (m, n, k): ``"gemm+epilogue"`` is the
    GEMM problem (``epilogue``/``has_bias`` price the second stage),
    ``"trsm+gemm"`` the trailing update C[m, n] fed by a width-k panel
    solve (``form`` = "lu" | "syrk"). Their ``fused`` flag comes from
    :func:`repro.core.codesign.plan_fused_chain` under the kernel
    policies (a tuned registry hit stores the measured winner, still
    vetoed when the streamed kernel no longer fits the ambient machine's
    VMEM).
    """
    if op not in OPS:
        raise ValueError(f"unknown op {op!r}; expected one of {OPS}")
    if op == "pdgemm" and mesh is None:
        raise ValueError("pdgemm resolution needs mesh=(px, py)")
    mach = _arch.resolve_machine(machine)
    mach_str = _arch.machine_key_component(mach)
    mesh_str = f"x{mesh[0]}y{mesh[1]}" if (op == "pdgemm" and mesh) else None
    pol = resolve_policy(policy, use_kernel)
    if not uses_kernel(pol):
        if op == "trsm":
            # the reference path still needs a diagonal width; 64 is the
            # historical (pre-tuner) default
            return _observed(Resolution(op, pol, "reference", False, block=64,
                              machine=mach.name))
        return _observed(Resolution(op, pol, "reference", False, mesh=mesh_str,
                          machine=mach.name))
    dtype = jnp.dtype(dtype)
    backend = backend or jax.default_backend()
    if dtype == jnp.float64 and backend == "tpu":
        # Mosaic has no float64, so no Pallas kernel compiles for it: the
        # kernel policies hand float64 to XLA, and the provenance says so
        return _observed(Resolution(op, pol, "xla-float64", False,
                                    block=64 if op == "trsm" else None,
                                    mesh=mesh_str, machine=mach.name))
    cfg = None
    source = "model"
    if pol == "tuned":
        reg = registry if registry is not None else default_registry()
        # syrk and gemv execute as GEMMs, so they share the gemm registry
        # entries (gemv under its execution shape (m, 1, n))
        lookup_op, lookup_shape = op, shape
        if op == "syrk":
            lookup_op = "gemm"
        elif op == "gemv":
            lookup_op, lookup_shape = "gemm", (shape[0], 1, shape[1])
        cfg = reg.lookup(lookup_op, lookup_shape, dtype, backend,
                         mesh=mesh_str, machine=mach_str)
        source = "registry" if cfg is not None else "fallback-model"
    if op == "pdgemm":
        # the stored/planned config tiles the per-step *local* update
        # (m/px, k_fine) @ (k_fine, n/py) - see codesign.plan_pdgemm
        m, n, k = shape
        px, py = mesh
        pplan = plan_pdgemm(m, n, k, px, py, dtype_bytes=dtype.itemsize,
                            machine=mach)
        if cfg is not None:
            local = plan_from_blocks(
                -(-max(m, 1) // px), -(-max(n, 1) // py), pplan.k_fine,
                cfg.params["bm"], cfg.params["bn"], cfg.params["bk"],
                dtype_bytes=dtype.itemsize, machine=mach)
        else:
            local = pplan.local
        return _observed(Resolution(op, pol, source, True, gemm_plan=local,
                          mesh=mesh_str, machine=mach.name))
    if op in FUSED_OPS:
        m, n, k = shape
        chain = plan_fused_chain(op, m, n, k, dtype_bytes=dtype.itemsize,
                                 epilogue=epilogue, form=form,
                                 has_bias=has_bias, machine=mach)
        if cfg is not None:
            plan = plan_from_blocks(m, n, k, cfg.params["bm"],
                                    cfg.params["bn"], cfg.params["bk"],
                                    dtype_bytes=dtype.itemsize, machine=mach)
            # the registry stores the *measured* winner; the ambient
            # machine's VMEM budget still vetoes it
            fused = bool(cfg.params.get("fused", 1)) and chain.fits_vmem
        else:
            plan = chain.gemm
            fused = chain.fused_wins
        return _observed(Resolution(op, pol, source, True, gemm_plan=plan,
                          block=chain.block, machine=mach.name, fused=fused,
                          chain=chain))
    if op in ("gemm", "syrk"):
        m, n, k = shape
        if cfg is not None:
            plan = plan_from_blocks(m, n, k, cfg.params["bm"],
                                    cfg.params["bn"], cfg.params["bk"],
                                    dtype_bytes=dtype.itemsize, machine=mach)
        else:
            plan = plan_gemm(m, n, k, dtype_bytes=dtype.itemsize,
                             machine=mach)
        return _observed(Resolution(op, pol, source, True, gemm_plan=plan,
                          machine=mach.name))
    if op == "gemv":
        m, n = shape
        if cfg is not None:
            plan = plan_from_blocks(m, 1, n, cfg.params["bm"],
                                    cfg.params["bn"], cfg.params["bk"],
                                    dtype_bytes=dtype.itemsize, machine=mach)
        else:
            plan = plan_gemm(m, 1, n, dtype_bytes=dtype.itemsize,
                             machine=mach)
        return _observed(Resolution(op, pol, source, True, gemm_plan=plan,
                          machine=mach.name))
    # trsm
    n, nrhs = shape
    block = cfg.params["block"] if cfg is not None \
        else plan_trsm(n, nrhs, dtype_bytes=dtype.itemsize,
                       machine=mach).block
    return _observed(Resolution(op, pol, source, True, block=block, machine=mach.name))


def _gemm_exec(a, b, res: Resolution, interpret: Optional[bool]):
    if not res.use_pallas or 0 in a.shape or 0 in b.shape:
        # degenerate operands (e.g. a wide-LU trailing block with no rows
        # left) cannot tile a Pallas grid; plain jnp handles empties
        return a @ b
    _counters.inc("kernel.launch")
    from repro.kernels import ops                   # lazy: kernels optional
    if b.ndim == 1:                                 # matvec through the MXU
        return ops.gemm(a, b[:, None], plan=res.gemm_plan, use_pallas=True,
                        interpret=interpret)[:, 0]
    return ops.gemm(a, b, plan=res.gemm_plan, use_pallas=True,
                    interpret=interpret)


def dispatch(op: str, *args, policy: Optional[str] = None,
             use_kernel: Optional[bool] = None,
             interpret: Optional[bool] = None,
             registry: Optional[Registry] = None,
             machine: Optional[MachineSpec] = None, **kw):
    """One entry point for every BLAS-3 / blocked-LAPACK kernel call.

    dispatch("gemm", a, b)             -> a @ b (by policy)
    dispatch("syrk", a, trans=False)   -> a a^T / a^T a (by policy)
    dispatch("gemv", a, x, trans=...)  -> op(a) x (by policy)
    dispatch("trsm", a, b, lower=..., unit_diag=..., left=..., block=...)
    dispatch("gemm+epilogue", a, b, bias=..., epilogue=...)
                                       -> act(a @ b + bias); streamed in one
                                          fused kernel when the chain plan
                                          says fusing wins
    dispatch("trsm+gemm", l11, ap, bl, c, form=..., unit_diag=..., fuse=...)
                                       -> (x, c - bl x) / (x, c - x^T x);
                                          fuse=None defers to the chain
                                          plan, True/False forces

    alpha/beta epilogues stay in :mod:`repro.blas`; this layer only
    resolves and runs the kernel-shaped core of each op. An explicit
    ``machine`` scopes the whole call (including the cores it forwards
    to); ``None`` uses the ambient current machine.
    """
    if machine is not None:
        with _arch.machine_scope(machine):
            return dispatch(op, *args, policy=policy, use_kernel=use_kernel,
                            interpret=interpret, registry=registry, **kw)
    if op == "gemm":
        a, b = args
        n_out = b.shape[1] if b.ndim == 2 else 1
        res = resolve("gemm", (a.shape[0], n_out, a.shape[1]), a.dtype,
                      policy, use_kernel, registry)
        return _gemm_exec(a, b, res, interpret)
    if op == "syrk":
        (a,) = args
        trans = kw.pop("trans", False)
        op_a = a.T if trans else a
        res = resolve("syrk", (op_a.shape[0], op_a.shape[0], op_a.shape[1]),
                      a.dtype, policy, use_kernel, registry)
        return _gemm_exec(op_a, op_a.T, res, interpret)
    if op == "gemv":
        a, x = args
        trans = kw.pop("trans", False)
        op_a = a.T if trans else a
        res = resolve("gemv", op_a.shape, a.dtype, policy, use_kernel,
                      registry)
        if not res.use_pallas:
            return op_a @ x
        return _gemm_exec(op_a, x[:, None], res, interpret)[:, 0]
    if op == "trsm":
        a, b = args
        from repro.blas import level3               # lazy: avoid import cycle
        return level3.trsm(a, b, policy=policy, use_kernel=use_kernel,
                           interpret=interpret, registry=registry, **kw)
    if op == "pdgemm":
        a, b = args
        from repro.blas import distributed          # lazy: avoid import cycle
        return distributed.pdgemm(a, b, policy=policy, use_kernel=use_kernel,
                                  interpret=interpret, registry=registry,
                                  **kw)
    if op == "gemm+epilogue":
        a, b = args
        bias = kw.pop("bias", None)
        epilogue = kw.pop("epilogue", "none")
        res = resolve("gemm+epilogue", (a.shape[0], b.shape[1], a.shape[1]),
                      a.dtype, policy, use_kernel, registry,
                      epilogue=epilogue, has_bias=bias is not None)
        from repro.kernels import fused as _fk      # lazy: kernels optional
        if not res.use_pallas:
            return _fk.apply_epilogue(a @ b, epilogue, bias)
        _counters.inc("kernel.launch")
        if res.fused:
            with _fk.fused_span("gemm_bias_act", res.chain,
                                epilogue=epilogue,
                                flops=2 * a.shape[0] * b.shape[1]
                                * a.shape[1],
                                bytes=res.chain.fused_hbm_bytes):
                return _fk.gemm_bias_act(a, b, bias=bias, epilogue=epilogue,
                                         plan=res.gemm_plan,
                                         interpret=interpret)
        # staged: the dispatcher GEMM kernel, then the epilogue as a
        # second pass over the HBM-resident product
        from repro.kernels import ops
        out = ops.gemm(a, b, plan=res.gemm_plan, use_pallas=True,
                       interpret=interpret)
        return _fk.apply_epilogue(out, epilogue, bias)
    if op == "trsm+gemm":
        l11, a_panel, b_left, c = args
        form = kw.pop("form", "lu")
        unit_diag = kw.pop("unit_diag", False)
        fuse = kw.pop("fuse", None)
        res = resolve("trsm+gemm", (c.shape[0], c.shape[1], l11.shape[0]),
                      c.dtype, policy, use_kernel, registry, form=form)
        do_fuse = res.fused if fuse is None \
            else (bool(fuse) and res.use_pallas)
        if c.shape[0] == 0:
            # degenerate wide-LU trailing block (columns remain, rows do
            # not): the staged chain handles the empty GEMM
            do_fuse = False
        if do_fuse:
            from repro.kernels import fused as _fk
            _counters.inc("kernel.launch")
            m, n, nb = c.shape[0], c.shape[1], l11.shape[0]
            with _fk.fused_span("trsm_gemm", res.chain, form=form,
                                flops=nb * nb * n + 2 * m * n * nb,
                                bytes=res.chain.fused_hbm_bytes):
                return _fk.trsm_gemm(l11, a_panel, b_left, c, form=form,
                                     unit_diag=unit_diag,
                                     row_block=res.block,
                                     interpret=interpret)
        # staged dispatcher chain: TRSM then GEMM, X round-tripping HBM -
        # operation-for-operation the blocked drivers' historical trailing
        # update, so fuse=False keeps their numerics bitwise
        from repro.blas import level3               # lazy: avoid import cycle
        x = level3.trsm(l11, a_panel, lower=True, unit_diag=unit_diag,
                        left=True, policy=res.policy, interpret=interpret,
                        registry=registry)
        bl = x.T if form == "syrk" else b_left
        upd = dispatch("gemm", bl, x, policy=res.policy, interpret=interpret,
                       registry=registry)
        return x, c - upd
    raise ValueError(f"unknown op {op!r}; expected one of {OPS}")
