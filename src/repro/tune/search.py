"""Measured config sweeps, seeded by the analytical model - not brute force.

Candidate generation asks :mod:`repro.core.codesign` for the model's own
pick plus its VMEM-feasible neighbors, then *ranks* them with the same two
models the rest of the repo is built on:

* :mod:`repro.core.roofline` terms - a candidate's achievable FLOP rate is
  ``min(PEAK, arithmetic_intensity * HBM_BW)`` at its tiling;
* :mod:`repro.core.pipeline_model` eq. 2 - the HBM->VMEM grid is a software
  pipeline whose "instructions" are grid steps and whose hazards are the
  K-carried accumulator dependencies, so ``tpi(p, n_i, n_h, ...)`` prices
  the per-step overhead (fill never amortized on short grids, fig. 2).

Only the ``top_k`` model-ranked candidates are actually measured (wall
time of the jitted kernel, interpret mode on CPU), and the measured winner
is recorded in the registry. This is the ELAPS loop: model proposes,
measurement disposes.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import arch as _arch
from repro.arch import MachineSpec
from repro.core import pipeline_model
from repro.core.codesign import (GemmPlan, plan_from_blocks, plan_fused_chain,
                                 plan_gemm, plan_trsm)
from repro.tune import measure as _measure
from repro.tune.registry import KernelConfig, Registry, default_registry


# machine resolution + registry-key component: the shared arch helpers
# (recording here and lookup in dispatch must agree on the namespace rule)
_mach = _arch.resolve_machine
_mach_key = _arch.machine_key_component


def _block_grid(mach: MachineSpec) -> Tuple[int, ...]:
    """Sweep neighborhood: 1x / 2x / 4x the machine's systolic edge."""
    return (mach.pe.mxu, 2 * mach.pe.mxu, 4 * mach.pe.mxu)


def model_score(plan: GemmPlan, m: int, n: int, k: int,
                dtype_bytes: int,
                machine: Optional[MachineSpec] = None) -> float:
    """Modeled seconds for one GEMM at this tiling (lower is better)."""
    mach = _mach(machine)
    flops = 2.0 * m * n * k
    roofline_rate = min(mach.pe.peak_flops,
                        plan.arithmetic_intensity * mach.memory.hbm_bw)
    compute_s = flops / roofline_rate
    # grid pipeline through eq. 2: steps are instructions, the K-carried
    # accumulator dependence is the hazard, DMA time is the logic delay,
    # per-step launch overhead is the latch overhead. Depth 2 = the kernel's
    # double buffering.
    g0, g1, g2 = plan.grid
    steps = max(g0 * g1 * g2, 1)
    hazards = g0 * g1 * max(g2 - 1, 0)
    t_dma = (plan.bm * plan.bk + plan.bk * plan.bn) * dtype_bytes         / mach.memory.hbm_bw
    per_step = float(pipeline_model.tpi(
        2.0, n_i=float(steps), n_h=float(hazards), gamma=0.5, t_p=t_dma,
        t_o=mach.memory.pipeline_fill_s))
    return max(compute_s, per_step * steps)


def gemm_candidates(m: int, n: int, k: int, dtype_bytes: int = 4,
                    max_candidates: int = 8,
                    vmem_budget: Optional[int] = None,
                    machine: Optional[MachineSpec] = None) -> List[GemmPlan]:
    """Model pick first, then its VMEM-feasible neighbors, ranked by
    :func:`model_score`. Never empty."""
    mach = _mach(machine)
    vmem_budget = mach.memory.vmem_bytes if vmem_budget is None         else vmem_budget
    seed = plan_gemm(m, n, k, dtype_bytes=dtype_bytes, machine=mach)
    seen = {(seed.bm, seed.bn, seed.bk)}
    cands = [seed]
    grid = _block_grid(mach)
    for bm in grid:
        for bn in grid:
            for bk in grid:
                p = plan_from_blocks(m, n, k, bm, bn, bk,
                                     dtype_bytes=dtype_bytes, machine=mach)
                key = (p.bm, p.bn, p.bk)
                if key in seen or p.vmem_bytes > vmem_budget:
                    continue
                seen.add(key)
                cands.append(p)
    ranked = sorted(cands, key=lambda p: model_score(p, m, n, k, dtype_bytes,
                                                     machine=mach))
    # the model seed always survives the cut (it is the fallback config)
    top = ranked[:max_candidates]
    if seed not in top:
        top[-1] = seed
    return top


@dataclasses.dataclass(frozen=True)
class SweepResult:
    """Trajectory record of one tuned op: every measured candidate plus the
    winner that went into the registry."""

    op: str
    shape: Tuple[int, ...]
    dtype: str
    backend: str
    measured: Tuple[dict, ...]          # [{params..., seconds}] model order
    best: KernelConfig
    model_params: dict                  # what the model alone would pick

    def to_json(self) -> dict:
        return {"op": self.op, "shape": list(self.shape), "dtype": self.dtype,
                "backend": self.backend, "measured": list(self.measured),
                "best": self.best.to_json(), "model_params": self.model_params}


# The one wall-clock path shared by the sweeps and the benchmark drivers
# now lives in repro.tune.measure (ELAPS-style repetition controller:
# per-rep samples, median + spread, every rep individually synchronized).
# measure_wall_time/_timeit stay importable from here for callers; the
# historical one-shot average (which left `out` unbound at reps=0 and only
# synchronized the final async dispatch) is gone.
measure_wall_time = _measure.measure_wall_time
_timeit = measure_wall_time


def tune_gemm(m: int, n: int, k: int, dtype=jnp.float32,
              registry: Optional[Registry] = None, top_k: int = 3,
              reps: int = 2, interpret: Optional[bool] = None,
              seed: int = 0,
              machine: Optional[MachineSpec] = None) -> SweepResult:
    """Sweep Pallas GEMM block shapes for one (m, n, k, dtype); record the
    measured winner in the registry keyed by the shape bucket (plus the
    machine component for a non-default ``machine``)."""
    from repro.kernels import ops                   # lazy: kernels optional
    mach = _mach(machine)
    reg = registry if registry is not None else default_registry()
    backend = jax.default_backend()
    dtype = jnp.dtype(dtype)
    model_pick = plan_gemm(m, n, k, dtype_bytes=dtype.itemsize, machine=mach)
    cands = gemm_candidates(m, n, k, dtype_bytes=dtype.itemsize,
                            max_candidates=max(top_k, 1), machine=mach)
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32)).astype(dtype)
    b = jnp.asarray(rng.normal(size=(k, n)).astype(np.float32)).astype(dtype)
    measured = []
    best_i, best_t = 0, None
    for i, plan in enumerate(cands):
        f = jax.jit(lambda x, y, p=plan: ops.gemm(
            x, y, plan=p, use_pallas=True, interpret=interpret))
        ms = _measure.measure(f, a, b, min_reps=reps, max_reps=2 * reps)
        t = ms.seconds_median
        model_s = model_score(plan, m, n, k, dtype.itemsize, machine=mach)
        measured.append({"bm": plan.bm, "bn": plan.bn, "bk": plan.bk,
                         "seconds": t, **ms.row_fields(),
                         "model_s": model_s,
                         "model_residual": _measure.model_residual(model_s, t)})
        if best_t is None or t < best_t:
            best_i, best_t = i, t
    win = cands[best_i]
    cfg = reg.record("gemm", (m, n, k), dtype, backend,
                     {"bm": win.bm, "bn": win.bn, "bk": win.bk},
                     source="sweep", measured_s=best_t,
                     machine=_mach_key(mach))
    return SweepResult("gemm", (m, n, k), dtype.name, backend,
                       tuple(measured), cfg,
                       {"bm": model_pick.bm, "bn": model_pick.bn,
                        "bk": model_pick.bk})


def tune_fused_gemm(m: int, n: int, k: int, epilogue: str = "relu",
                    dtype=jnp.float32, has_bias: bool = True,
                    registry: Optional[Registry] = None, reps: int = 2,
                    interpret: Optional[bool] = None, seed: int = 0,
                    machine: Optional[MachineSpec] = None) -> SweepResult:
    """Measure the fused GEMM+epilogue kernel against the staged chain
    (Pallas GEMM, then the epilogue as a separate jnp pass) at the chain
    plan's tiling, and record the measured winner under ``gemm+epilogue``.

    The registry entry carries the tiling plus a ``fused`` flag (0/1):
    dispatch honors the flag when resolving ``policy="tuned"``, so the
    sweep decides *whether* to fuse on this machine, not just how to
    tile. The chain model's ``fused_wins`` verdict is reported alongside
    as ``model_params`` for the trajectory record.
    """
    from repro.kernels import fused as _fk          # lazy: kernels optional
    from repro.kernels import ops                   # lazy: kernels optional
    mach = _mach(machine)
    reg = registry if registry is not None else default_registry()
    backend = jax.default_backend()
    dtype = jnp.dtype(dtype)
    chain = plan_fused_chain("gemm+epilogue", m, n, k,
                             dtype_bytes=dtype.itemsize, epilogue=epilogue,
                             has_bias=has_bias, machine=mach)
    plan = chain.gemm
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32)).astype(dtype)
    b = jnp.asarray(rng.normal(size=(k, n)).astype(np.float32)).astype(dtype)
    bias = (jnp.asarray(rng.normal(size=(n,)).astype(np.float32)).astype(dtype)
            if has_bias else None)

    def staged(x, y, bb):
        c = ops.gemm(x, y, plan=plan, use_pallas=True, interpret=interpret)
        return _fk.apply_epilogue(c, epilogue, bb)

    def fused_fn(x, y, bb):
        return _fk.gemm_bias_act(x, y, bias=bb, epilogue=epilogue,
                                 plan=plan, interpret=interpret)

    measured = []
    best_i, best_t = 0, None
    for i, (name, fn) in enumerate((("staged", staged), ("fused", fused_fn))):
        f = jax.jit(fn)
        ms = _measure.measure(f, a, b, bias, min_reps=reps, max_reps=2 * reps)
        t = ms.seconds_median
        measured.append({"variant": name, "fused": int(name == "fused"),
                         "bm": plan.bm, "bn": plan.bn, "bk": plan.bk,
                         "seconds": t, **ms.row_fields(),
                         "model_s": (chain.fused_time if name == "fused"
                                     else chain.unfused_time)})
        if best_t is None or t < best_t:
            best_i, best_t = i, t
    fused_won = int(measured[best_i]["fused"])
    cfg = reg.record("gemm+epilogue", (m, n, k), dtype, backend,
                     {"bm": plan.bm, "bn": plan.bn, "bk": plan.bk,
                      "fused": fused_won},
                     source="sweep", measured_s=best_t,
                     machine=_mach_key(mach))
    return SweepResult("gemm+epilogue", (m, n, k), dtype.name, backend,
                       tuple(measured), cfg,
                       {"bm": plan.bm, "bn": plan.bn, "bk": plan.bk,
                        "fused": int(chain.fused_wins)})


def seed_registry_from_model(registry: Optional[Registry] = None,
                             gemm_shapes: Sequence[Tuple[int, int, int]] = (),
                             trsm_shapes: Sequence[Tuple[int, int]] = (),
                             dtypes: Sequence = (jnp.float32,),
                             backend: Optional[str] = None,
                             machine: Optional[MachineSpec] = None) -> int:
    """Record the *model's* pick for every (op, shape, dtype) as a real
    registry entry (``source="model"``, unmeasured).

    This is how non-swept dtypes (float64, bfloat16) get first-class
    registry entries instead of silently falling back at lookup time:
    the analytic planners are dtype-aware (operand bytes change the VMEM
    and roofline terms), so each dtype gets its own seeded config, and a
    later measured sweep simply overwrites the entry in place. Returns
    the number of entries recorded.
    """
    mach = _mach(machine)
    mkey = _mach_key(mach)
    reg = registry if registry is not None else default_registry()
    backend = backend or jax.default_backend()
    count = 0
    for dtype in dtypes:
        dt = jnp.dtype(dtype)
        for m, n, k in gemm_shapes:
            p = plan_gemm(m, n, k, dtype_bytes=dt.itemsize, machine=mach)
            reg.record("gemm", (m, n, k), dt, backend,
                       {"bm": p.bm, "bn": p.bn, "bk": p.bk}, source="model",
                       machine=mkey)
            count += 1
        for n, nrhs in trsm_shapes:
            p = plan_trsm(n, nrhs, dtype_bytes=dt.itemsize, machine=mach)
            reg.record("trsm", (n, nrhs), dt, backend,
                       {"block": p.block}, source="model", machine=mkey)
            count += 1
    return count


def trsm_candidates(n: int, nrhs: int, dtype_bytes: int = 4,
                    blocks: Sequence[int] = (16, 32, 64, 128),
                    machine: Optional[MachineSpec] = None) -> List[int]:
    """Model pick first, then the remaining distinct feasible widths."""
    seedb = plan_trsm(n, nrhs, dtype_bytes=dtype_bytes,
                      machine=machine).block
    out = [seedb]
    for b in blocks:
        b_ = min(int(b), max(int(n), 1))
        if b_ not in out:
            out.append(b_)
    return out


def tune_trsm(n: int, nrhs: int = 8, dtype=jnp.float32,
              registry: Optional[Registry] = None, reps: int = 2,
              blocks: Sequence[int] = (16, 32, 64, 128),
              seed: int = 0,
              machine: Optional[MachineSpec] = None) -> SweepResult:
    """Sweep the blocked-TRSM diagonal width; record the measured winner.

    Measured on the reference inner-GEMM path (the block trade-off - serial
    substitution vs trailing update - is the same on both paths, and the
    interpret-mode kernel would drown it in emulation overhead on CPU).
    """
    from repro.blas import level3                   # lazy: avoid import cycle
    mach = _mach(machine)
    reg = registry if registry is not None else default_registry()
    backend = jax.default_backend()
    dtype = jnp.dtype(dtype)
    rng = np.random.default_rng(seed)
    t_np = np.tril(rng.normal(size=(n, n))).astype(np.float32) \
        + 4.0 * np.eye(n, dtype=np.float32)
    t = jnp.asarray(t_np).astype(dtype)
    b = jnp.asarray(rng.normal(size=(n, nrhs)).astype(np.float32)).astype(dtype)
    cands = trsm_candidates(n, nrhs, dtype_bytes=dtype.itemsize, blocks=blocks,
                            machine=mach)
    measured = []
    best_i, best_t = 0, None
    for i, blk in enumerate(cands):
        f = jax.jit(lambda tt, bb, nb=blk: level3.trsm(
            tt, bb, lower=True, block=nb, policy="reference"))
        ms = _measure.measure(f, t, b, min_reps=reps, max_reps=2 * reps)
        sec = ms.seconds_median
        model_s = plan_trsm(n, nrhs, dtype_bytes=dtype.itemsize,
                            candidates=(blk,), machine=mach).modeled_time
        measured.append({"block": blk, "seconds": sec, **ms.row_fields(),
                         "model_s": model_s,
                         "model_residual": _measure.model_residual(model_s,
                                                                   sec)})
        if best_t is None or sec < best_t:
            best_i, best_t = i, sec
    cfg = reg.record("trsm", (n, nrhs), dtype, backend,
                     {"block": cands[best_i]}, source="sweep",
                     measured_s=best_t, machine=_mach_key(mach))
    return SweepResult("trsm", (n, nrhs), dtype.name, backend,
                       tuple(measured), cfg, {"block": cands[0]})
