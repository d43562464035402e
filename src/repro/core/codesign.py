"""TPU adaptation of the paper's codesign methodology.

The paper picks RTL pipeline register counts from a workload model. On TPU
the hardware pipelines are fixed, but the *same equation* governs three
software-visible micro-architectural knobs, and this module sets them
analytically (DESIGN.md section 2 maps each one):

1. **Accumulator count U** for reduction loops. A dependent FP-add chain on
   the VPU exposes the add latency L exactly like an under-filled pipeline;
   U parallel partial sums fill the latency window like p pipeline slots.
   The cost of a length-n reduction with U accumulators is

       t(U) = n * max(1, L/U) + L * ceil(log2 U) + c_o * U

   (steady-state issue, final combine tree, bookkeeping/register overhead) -
   eq. 2's three terms with p -> U, t_p -> L, t_o -> c_o; the unconstrained
   minimum sits at U ~ L, the paper's p_opt once hazards saturate.

2. **Pallas block shapes.** The HBM->VMEM grid pipeline is a software
   pipeline: its "depth" is the grid length, its "latch overhead" the
   per-step DMA setup. Fig. 2's saturation (small workloads never amortize
   pipeline fill) becomes: choose blocks so the grid has enough steps to
   reach steady state, subject to VMEM capacity and MXU alignment.

3. **Collective schedule depth** (number of microbatch chunks overlapping
   compute with reduce-scatter) - same fill/overhead trade-off; used by
   train/grad.py.

Every planner takes ``machine=`` (a :class:`repro.arch.MachineSpec`;
``None`` = the ambient :func:`repro.arch.current_machine`, default
``"tpu-like"``), so the whole codesign layer is parameterized by a
swappable machine instead of import-time globals. The module-level
constants below are the ``"tpu-like"`` spec's values - kept so existing
callers and the default-machine planner outputs stay bit-identical.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

from repro import arch as _arch
from repro.arch import MachineSpec

# ------------------- TPU v5e constants (= the "tpu-like" spec) --------------
_TPU = _arch.get(_arch.DEFAULT_MACHINE)
PEAK_BF16_FLOPS = _TPU.pe.peak_flops      # per chip
HBM_BW = _TPU.memory.hbm_bw               # bytes/s per chip
ICI_BW = _TPU.memory.ici_bw               # bytes/s per link (task constants)
VMEM_BYTES = _TPU.memory.vmem_bytes       # usable VMEM budget we plan against
MXU = _TPU.pe.mxu                         # systolic array edge
SUBLANE = _TPU.pe.sublane                 # VPU sublanes (fp32)
LANE = _TPU.pe.lane                       # VPU lanes
VPU_ADD_LATENCY = _TPU.fpu.add_latency    # cycles, dependent-add chain
VREG_BUDGET = _TPU.pe.vreg_budget         # architectural vector registers
ACC_OVERHEAD = _TPU.fpu.acc_overhead      # c_o: issue slots of bookkeeping
                                          # per extra accumulator
PIPELINE_FILL_S = _TPU.memory.pipeline_fill_s   # per grid-step fill (fig. 2)


# every planner resolves machine= through the one shared arch helper
_machine = _arch.resolve_machine


def resolve_dtype_bytes(dtype=None, dtype_bytes: Optional[int] = None,
                        machine: Optional[MachineSpec] = None) -> int:
    """The one shared dtype-width default for every planner.

    Precedence: an explicit ``dtype`` (itemsize), then an explicit
    ``dtype_bytes``, then the machine's native compute dtype (bfloat16 ->
    2 for ``"tpu-like"``, float64 -> 8 for ``"paper-pe"``). This replaces
    the historical per-planner defaults (``plan_gemm`` assumed 2 while
    ``plan_factorization``/``plan_trsm`` assumed 4).
    """
    if dtype is not None:
        import numpy as np
        try:
            return int(np.dtype(dtype).itemsize)
        except TypeError:
            import jax.numpy as jnp
            return int(jnp.dtype(dtype).itemsize)
    if dtype_bytes is not None:
        return int(dtype_bytes)
    return _machine(machine).dtype_bytes()


def reduction_cost(n: float, u: int, latency: Optional[float] = None,
                   overhead: Optional[float] = None,
                   machine: Optional[MachineSpec] = None) -> float:
    """Issue-slot cost of reducing n elements with u parallel accumulators.

    ``latency``/``overhead`` default to the machine's dependent-add chain
    latency and accumulator bookkeeping cost.
    """
    m = _machine(machine)
    latency = m.fpu.add_latency if latency is None else latency
    overhead = m.fpu.acc_overhead if overhead is None else overhead
    u = max(1, int(u))
    steady = n * max(1.0, latency / u)
    combine = latency * math.ceil(math.log2(u)) if u > 1 else 0.0
    return steady + combine + overhead * u


def optimal_accumulators(n: float, latency: Optional[float] = None,
                         overhead: Optional[float] = None,
                         max_u: Optional[int] = None,
                         power_of_two: bool = True,
                         machine: Optional[MachineSpec] = None) -> int:
    """U minimizing :func:`reduction_cost` - the eq.-3 analogue on TPU.

    For large n the optimum is U ~ latency (fill the add pipe); for tiny n
    the combine tree + overhead terms pull it back - same shape as the
    paper's fig. 3 curves. Defaults (latency, overhead, register budget)
    come from ``machine``.
    """
    m = _machine(machine)
    latency = m.fpu.add_latency if latency is None else latency
    overhead = m.fpu.acc_overhead if overhead is None else overhead
    max_u = m.pe.vreg_budget // 2 if max_u is None else max_u
    candidates = range(1, max_u + 1)
    if power_of_two:
        candidates = [1 << k for k in range(0, max_u.bit_length()) if (1 << k) <= max_u]
    best = min(candidates, key=lambda u: reduction_cost(n, u, latency, overhead))
    return int(best)


def _acc_bytes(dtype_bytes: int) -> int:
    """Bytes/elem of the kernel's VMEM accumulator: per-precision, matching
    kernels.gemm.accumulator_dtype (f64 operands -> f64 accumulator, all
    narrower dtypes -> f32)."""
    return 8 if dtype_bytes >= 8 else 4


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _round_down_pow2(x: int) -> int:
    return 1 << max(x.bit_length() - 1, 0)


@dataclasses.dataclass(frozen=True)
class GemmPlan:
    """Pallas GEMM tiling picked by the model.

    ``ridge`` is the machine's compute/memory roofline knee
    (peak_flops / hbm_bw); it defaults to the "tpu-like" value so plans
    built without a machine keep the historical semantics.
    """

    bm: int
    bn: int
    bk: int
    accumulators: int             # U for the k-loop partials
    grid: Tuple[int, int, int]
    vmem_bytes: int
    arithmetic_intensity: float   # flops / HBM byte at this tiling
    ridge: float = PEAK_BF16_FLOPS / HBM_BW

    @property
    def compute_bound(self) -> bool:
        return self.arithmetic_intensity >= self.ridge


def plan_gemm(m: int, n: int, k: int, dtype_bytes: Optional[int] = None,
              vmem_budget: Optional[int] = None,
              min_grid_steps: int = 4, dtype=None,
              machine: Optional[MachineSpec] = None) -> GemmPlan:
    """Choose (bm, bn, bk) for C[m,n] += A[m,k] B[k,n] on the MXU.

    Policy (each clause is one paper concept):
      * MXU alignment: all block dims multiples of the machine's systolic
        edge (clamped to the padded problem) - full-tile occupancy.
      * VMEM capacity: A-, B-blocks double-buffered + fp32 accumulator block
        must fit the budget - the RF/LM capacity constraint of the PE/APE.
      * Grid length >= min_grid_steps so the HBM->VMEM software pipeline
        reaches steady state (fig. 2 saturation).
      * Maximize bm*bn (arithmetic intensity ~ harmonic mean of block dims),
        then bk.

    ``dtype`` overrides ``dtype_bytes``; both default to the machine's
    native dtype (:func:`resolve_dtype_bytes`). ``machine`` parameterizes
    the alignment, capacity, and roofline terms.
    """
    mach = _machine(machine)
    dtype_bytes = resolve_dtype_bytes(dtype, dtype_bytes, mach)
    vmem_budget = mach.memory.vmem_bytes if vmem_budget is None else vmem_budget
    mxu = mach.pe.mxu
    ridge = mach.pe.peak_flops / mach.memory.hbm_bw
    pm, pn, pk = (_round_up(max(d, 1), mxu) for d in (m, n, k))
    best: Optional[GemmPlan] = None
    cands = [mxu, 2 * mxu, 4 * mxu, 8 * mxu]
    for bm in cands:
        if bm > pm and bm != mxu:
            continue
        for bn in cands:
            if bn > pn and bn != mxu:
                continue
            for bk in (4 * mxu, 8 * mxu, 16 * mxu, 2 * mxu, mxu):
                if bk > pk and bk != mxu:
                    continue
                bm_, bn_, bk_ = min(bm, pm), min(bn, pn), min(bk, pk)
                # double-buffered A and B blocks + per-precision C accumulator
                vmem = 2 * (bm_ * bk_ + bk_ * bn_) * dtype_bytes \
                    + bm_ * bn_ * _acc_bytes(dtype_bytes)
                if vmem > vmem_budget:
                    continue
                # grid covers the block-padded problem (kernel pads inputs
                # to block multiples, not just MXU multiples)
                grid = (-(-m // bm_), -(-n // bn_), -(-k // bk_))
                steps = grid[0] * grid[1] * grid[2]
                if steps < min_grid_steps and (bm_, bn_, bk_) != (mxu, mxu, mxu):
                    continue
                ai = (2 * bm_ * bn_ * bk_) / ((bm_ * bk_ + bk_ * bn_) * dtype_bytes
                                              + bm_ * bn_ * dtype_bytes / max(grid[2], 1))
                cand = GemmPlan(bm_, bn_, bk_,
                                optimal_accumulators(bk_ // mxu, max_u=8,
                                                     machine=mach),
                                grid, vmem, ai, ridge)
                key = (cand.arithmetic_intensity, bk_)
                if best is None or key > (best.arithmetic_intensity, best.bk):
                    best = cand
    if best is None:  # degenerate tiny problem: single MXU tile
        bm_, bn_, bk_ = min(mxu, pm), min(mxu, pn), min(mxu, pk)
        vmem = 2 * (bm_ * bk_ + bk_ * bn_) * dtype_bytes \
            + bm_ * bn_ * _acc_bytes(dtype_bytes)
        ai = (2 * bm_ * bn_ * bk_) / ((bm_ * bk_ + bk_ * bn_ + bm_ * bn_) * dtype_bytes)
        best = GemmPlan(bm_, bn_, bk_, 1,
                        (-(-m // bm_), -(-n // bn_), -(-k // bk_)), vmem, ai,
                        ridge)
    return best


def plan_from_blocks(m: int, n: int, k: int, bm: int, bn: int, bk: int,
                     dtype_bytes: Optional[int] = None, dtype=None,
                     machine: Optional[MachineSpec] = None) -> GemmPlan:
    """Rebuild a full :class:`GemmPlan` from explicit block dims.

    This is how registry entries (``{"bm","bn","bk"}``) and sweep
    candidates become executable plans: grid, VMEM footprint, and
    arithmetic intensity are re-derived exactly as :func:`plan_gemm`
    derives them for its own picks. ``dtype`` overrides ``dtype_bytes``.
    """
    mach = _machine(machine)
    dtype_bytes = resolve_dtype_bytes(dtype, dtype_bytes, mach)
    bm_, bn_, bk_ = (max(int(b), 1) for b in (bm, bn, bk))
    grid = (-(-m // bm_), -(-n // bn_), -(-k // bk_))
    vmem = 2 * (bm_ * bk_ + bk_ * bn_) * dtype_bytes \
        + bm_ * bn_ * _acc_bytes(dtype_bytes)
    ai = (2 * bm_ * bn_ * bk_) / ((bm_ * bk_ + bk_ * bn_) * dtype_bytes
                                  + bm_ * bn_ * dtype_bytes / max(grid[2], 1))
    return GemmPlan(bm_, bn_, bk_,
                    optimal_accumulators(bk_ // mach.pe.mxu, max_u=8,
                                         machine=mach),
                    grid, vmem, ai, mach.pe.peak_flops / mach.memory.hbm_bw)


# ----------------------------- distributed GEMM ----------------------------

@dataclasses.dataclass(frozen=True)
class PdgemmPlan:
    """SUMMA pdgemm schedule on a (px, py) mesh: per-step local tiling plus
    the roofline extended with a per-hop collective term.

    The mesh is the paper's 'more parallel accumulators' applied across
    devices: the global K reduction is split into ``steps`` panel updates,
    each a local GEMM (``local`` - planned exactly like the single-device
    kernel) fed by ring broadcasts whose per-hop bytes are priced against
    the inter-chip link, the way :mod:`repro.core.roofline` prices
    collective bytes against the machine's ICI bandwidth.
    """

    px: int
    py: int
    steps: int                    # SUMMA panel steps = px * py
    k_fine: int                   # k-panel width per step
    local: GemmPlan               # tiling of one local panel update
    compute_s: float              # per-device GEMM flops under the roofline
    collective_s: float           # per-device ring-broadcast bytes / ici_bw
    collective_bytes: int         # on-wire bytes per device, all steps

    @property
    def modeled_time(self) -> float:
        return max(self.compute_s, self.collective_s)

    @property
    def collective_bound(self) -> bool:
        return self.collective_s > self.compute_s


def plan_pdgemm(m: int, n: int, k: int, px: int, py: int,
                dtype_bytes: Optional[int] = None, dtype=None,
                machine: Optional[MachineSpec] = None) -> PdgemmPlan:
    """Plan the SUMMA ``pdgemm`` on a (px, py) mesh.

    Per step (one of ``px * py`` fine k-panels) each device receives an
    A-panel over a ``py``-ring and a B-panel over a ``px``-ring
    (:func:`repro.distributed.collectives.ring_bcast`), then runs a local
    ``(m/px, k_fine) @ (k_fine, n/py)`` update on the Pallas path. The
    collective term sums the per-hop bytes of both rings
    (``ring_bcast_bytes``) over all steps against the machine's ICI
    bandwidth; the compute term is the local flops under the
    single-device roofline at the ``local`` tiling. ``modeled_time`` is
    their max (overlap assumed), so the plan exposes where the mesh stops
    paying - the cross-device analogue of fig. 2's pipeline-fill
    saturation.
    """
    from repro.distributed.collectives import ring_bcast_bytes
    mach = _machine(machine)
    dtype_bytes = resolve_dtype_bytes(dtype, dtype_bytes, mach)
    px, py = max(int(px), 1), max(int(py), 1)
    steps = px * py
    m_l = -(-max(m, 1) // px)
    n_l = -(-max(n, 1) // py)
    k_f = max(-(-max(k, 1) // steps), 1)
    local = plan_gemm(m_l, n_l, k_f, dtype_bytes=dtype_bytes, machine=mach)
    flops = 2.0 * m_l * n_l * k_f * steps
    rate = min(mach.pe.peak_flops,
               local.arithmetic_intensity * mach.memory.hbm_bw)
    compute_s = flops / rate + steps * mach.memory.pipeline_fill_s
    a_panel = m_l * k_f * dtype_bytes
    b_panel = k_f * n_l * dtype_bytes
    coll_bytes = steps * (ring_bcast_bytes(a_panel, py)
                          + ring_bcast_bytes(b_panel, px))
    return PdgemmPlan(px, py, steps, k_f, local, compute_s,
                      coll_bytes / mach.memory.ici_bw, coll_bytes)


# ------------------------- blocked-factorization plans ----------------------
# Serial-chain cycles exposed per panel column: the paper's section-4.2
# hazard profile per routine, priced at the machine's per-class pipeline
# depths. potrf: sqrt then a dependent div per column; getrf: pivot-compare
# (adder) + div; geqrf: norm-sqrt, alpha-add, div scale, tau div.
def _panel_chain_cycles(mach: MachineSpec) -> Dict[str, int]:
    d = mach.fpu.depths
    return {"potrf": d["sqrt"] + d["div"],
            "getrf": d["add"] + d["div"],
            "geqrf": d["sqrt"] + d["add"] + 2 * d["div"]}


_PANEL_CHAIN_CYCLES = _panel_chain_cycles(_TPU)
# flops(n) ~ coeff * n^3 for the square factorization. Public alias below:
# benchmarks derive Gflop/s from the same table the model plans with.
_FACTOR_FLOP_COEFF = {"potrf": 1.0 / 3.0, "getrf": 2.0 / 3.0, "geqrf": 4.0 / 3.0}
FACTOR_FLOP_COEFF = _FACTOR_FLOP_COEFF
MXU_CLOCK = _TPU.pe.mxu_clock             # cycles/s implied by peak
VPU_FLOPS = _TPU.pe.vpu_flops             # vector (non-MXU) peak


@dataclasses.dataclass(frozen=True)
class FactorizationPlan:
    """Panel width + trailing-update GEMM tiling for a blocked factorization."""

    kind: str                     # "potrf" | "getrf" | "geqrf"
    block: int                    # panel width nb (the LAPACK NB)
    gemm: GemmPlan                # plan for the widest trailing update
    panel_time: float             # modeled seconds in serial panels
    trailing_time: float          # modeled seconds in GEMM trailing updates
    batch: int = 1

    @property
    def modeled_time(self) -> float:
        return self.panel_time + self.trailing_time

    @property
    def panel_fraction(self) -> float:
        t = self.modeled_time
        return self.panel_time / t if t > 0 else 0.0


def _factorization_time(n: int, nb: int, kind: str, dtype_bytes: int,
                        batch: int, mach: MachineSpec) -> Tuple[float, float]:
    """(panel_s, trailing_s) for one size-n factorization at panel width nb.

    Panel: the unblocked path is hazard-bound — per column, a serial
    sqrt/div chain of the machine's per-class depths (eq.-2's exposed
    latency, unhidable by ILP) plus its rank-1 update flops at VPU rate.
    Trailing: DGEMM under the roofline — the k-extent of the update IS the
    panel width, so arithmetic intensity (and hence the achieved fraction of
    peak) grows with nb until the peak/hbm_bw knee; each panel step also
    pays one software-pipeline fill (fig. 2's unamortized-fill region).
    """
    chain = _panel_chain_cycles(mach)[kind] / mach.pe.mxu_clock
    coeff = _FACTOR_FLOP_COEFF[kind]
    fill = mach.memory.pipeline_fill_s
    panel_s = 0.0
    trailing_s = 0.0
    for j0 in range(0, n, nb):
        b = min(nb, n - j0)
        m = n - j0
        panel_s += b * chain + (coeff * 3.0) * m * b * b / mach.pe.vpu_flops \
            + fill
        rest = n - j0 - b
        if rest <= 0:
            continue
        # trailing update ~ (rest x b) @ (b x rest) (potrf/getrf) or the
        # compact-WY triple product (geqrf ~ 2x that)
        gf = 2.0 if kind == "geqrf" else 1.0
        flops = gf * 2.0 * rest * b * rest
        bytes_moved = gf * (2 * rest * b + 2 * rest * rest) * dtype_bytes
        ai = flops / bytes_moved
        rate = min(mach.pe.peak_flops, ai * mach.memory.hbm_bw)
        trailing_s += flops / rate + fill
    return batch * panel_s, batch * trailing_s


def plan_factorization(n: int, kind: str = "potrf",
                       dtype_bytes: Optional[int] = None,
                       batch: int = 1,
                       candidates: Tuple[int, ...] = (8, 16, 32, 64, 128),
                       dtype=None,
                       machine: Optional[MachineSpec] = None) -> FactorizationPlan:
    """Pick the panel width NB for a blocked right-looking factorization.

    Same trade-off as the paper's pipeline-depth equation: the panel is the
    serial (hazard) term that grows with NB, the trailing update is the
    throughput term whose GEMM efficiency grows with NB (arithmetic
    intensity ~ NB until the roofline knee). The minimum of the summed model
    is the software analogue of eq. 3's p_opt.
    """
    if kind not in _FACTOR_FLOP_COEFF:
        raise ValueError(f"unknown factorization kind: {kind!r}")
    mach = _machine(machine)
    dtype_bytes = resolve_dtype_bytes(dtype, dtype_bytes, mach)
    n = max(int(n), 1)
    best_nb, best_t = None, None
    for nb in candidates:
        if nb > n and best_nb is not None:
            continue
        nb_ = min(nb, n)
        p, t = _factorization_time(n, nb_, kind, dtype_bytes, batch, mach)
        if best_t is None or p + t < best_t:
            best_nb, best_t = nb_, p + t
    rest = max(n - best_nb, 1)
    gemm = plan_gemm(rest, rest, best_nb, dtype_bytes=dtype_bytes,
                     machine=mach)
    p, t = _factorization_time(n, best_nb, kind, dtype_bytes, batch, mach)
    return FactorizationPlan(kind, best_nb, gemm, p, t, batch=batch)


def modeled_factorization_time(n: int, kind: str = "potrf",
                               block: Optional[int] = None,
                               dtype_bytes: Optional[int] = None,
                               batch: int = 1, dtype=None,
                               machine: Optional[MachineSpec] = None) -> float:
    """Modeled seconds of one blocked factorization at a *fixed* panel
    width (``block=None`` = the model's own pick). This is the modeled_s
    the benchmark rows' ``model_residual`` compares the measured median
    against: same panel/trailing decomposition as
    :func:`plan_factorization`, evaluated at the block the bench actually
    ran."""
    if kind not in _FACTOR_FLOP_COEFF:
        raise ValueError(f"unknown factorization kind: {kind!r}")
    mach = _machine(machine)
    dtype_bytes = resolve_dtype_bytes(dtype, dtype_bytes, mach)
    n = max(int(n), 1)
    if block is None:
        return plan_factorization(n, kind=kind, dtype_bytes=dtype_bytes,
                                  batch=batch, machine=mach).modeled_time
    nb = min(max(int(block), 1), n)
    p, t = _factorization_time(n, nb, kind, dtype_bytes, batch, mach)
    return p + t


@dataclasses.dataclass(frozen=True)
class TrsmPlan:
    """Diagonal-block width for the blocked triangular solve."""

    block: int
    panel_time: float             # modeled seconds in serial substitutions
    trailing_time: float          # modeled seconds in off-diagonal GEMMs

    @property
    def modeled_time(self) -> float:
        return self.panel_time + self.trailing_time


def plan_trsm(n: int, nrhs: int = 1, dtype_bytes: Optional[int] = None,
              candidates: Tuple[int, ...] = (16, 32, 64, 128),
              dtype=None,
              machine: Optional[MachineSpec] = None) -> TrsmPlan:
    """Pick the diagonal-block width for the blocked TRSM.

    Same structure as :func:`plan_factorization`: the diagonal substitution
    scan is the serial divider-hazard chain (one dependent div per row, a
    block-wide AXPY at VPU rate - work that grows with the block); the
    off-diagonal updates are GEMMs whose per-panel pipeline fill shrinks as
    the block grows. The modeled minimum is eq. 3's p_opt in software.
    ``dtype`` overrides ``dtype_bytes``.
    """
    mach = _machine(machine)
    dtype_bytes = resolve_dtype_bytes(dtype, dtype_bytes, mach)
    n = max(int(n), 1)
    nrhs = max(int(nrhs), 1)
    # pivotless div chain
    chain = _panel_chain_cycles(mach)["getrf"] / mach.pe.mxu_clock
    fill = mach.memory.pipeline_fill_s
    best: Optional[TrsmPlan] = None
    for b in candidates:
        b_ = min(b, n)
        steps = -(-n // b_)
        # serial part: n dependent divides + the in-block AXPYs at VPU rate
        panel = n * chain + 2.0 * n * b_ * nrhs / mach.pe.vpu_flops \
            + steps * fill
        # off-diagonal GEMMs: ~ n*(n-b)/2 * nrhs MACs under the roofline
        flops = max(n - b_, 0) * n * nrhs
        if flops > 0:
            bytes_moved = (max(n - b_, 0) * b_ + 2 * n * nrhs) * dtype_bytes
            ai = flops / max(bytes_moved, 1)
            rate = min(mach.pe.peak_flops, ai * mach.memory.hbm_bw)
            trailing = flops / rate + steps * fill
        else:
            trailing = 0.0
        cand = TrsmPlan(b_, panel, trailing)
        if best is None or cand.modeled_time < best.modeled_time:
            best = cand
        if b_ >= n:
            break
    return best


# ------------------------------- fused chains -------------------------------
# FBLAS-style streaming composition (1907.07929): when consecutive tile
# stages share an intermediate, keeping it resident in VMEM deletes its HBM
# round trip. The chain plan prices both executions - staged (each stage
# pays its own reads/writes plus a pipeline fill) vs. streamed (one fused
# kernel, the intermediate never leaves VMEM) - so the dispatcher can pick.

FUSED_CHAIN_KINDS = ("gemm+epilogue", "trsm+gemm")

# extra VPU flops per output element (the bias add is priced separately);
# only the roofline term consumes these, so coarse integers suffice
EPILOGUE_FLOP_COST = {"none": 0, "relu": 1, "gelu": 8}


@dataclasses.dataclass(frozen=True)
class FusedChainPlan:
    """Fused vs. staged pricing of one two-stage tile chain.

    ``gemm+epilogue``: C = act(A B + bias); the staged path writes A B to
    HBM and re-reads it for the epilogue pass. ``trsm+gemm``: the blocked
    factorizations' trailing pair X = L11^{-1} AP then C -= B X (lu form)
    or C -= X^T X (syrk form); the staged path round-trips X through HBM.
    """

    kind: str                     # one of FUSED_CHAIN_KINDS
    form: str                     # epilogue name | "lu" | "syrk"
    gemm: GemmPlan                # tiling of the GEMM stage
    block: int                    # fused-kernel row-block height
    vmem_bytes: int               # fused kernel's resident VMEM footprint
    fits_vmem: bool               # vmem_bytes <= the machine budget
    unfused_hbm_bytes: int        # modeled HBM traffic, staged execution
    fused_hbm_bytes: int          # modeled HBM traffic, streamed execution
    unfused_time: float           # roofline seconds, staged (2 fills)
    fused_time: float             # roofline seconds, streamed (1 fill)

    @property
    def hbm_bytes_saved(self) -> int:
        return max(self.unfused_hbm_bytes - self.fused_hbm_bytes, 0)

    @property
    def fused_wins(self) -> bool:
        """Fuse iff the streamed kernel fits VMEM and the model says it is
        no slower - the streaming analogue of eq. 3's p_opt decision."""
        return self.fits_vmem and self.fused_time <= self.unfused_time


def _stage_time(flops: float, bytes_moved: float, mach: MachineSpec) -> float:
    """Roofline seconds of one kernel stage (compute vs. HBM stream max)."""
    return max(flops / mach.pe.peak_flops,
               bytes_moved / mach.memory.hbm_bw)


def plan_fused_chain(kind: str, m: int, n: int, k: int,
                     dtype_bytes: Optional[int] = None, dtype=None,
                     epilogue: str = "none", has_bias: bool = True,
                     form: str = "lu",
                     machine: Optional[MachineSpec] = None) -> FusedChainPlan:
    """Price a two-stage tile chain fused vs. staged.

    ``kind="gemm+epilogue"``: (m, n, k) is the GEMM problem; ``epilogue``
    / ``has_bias`` shape the second stage. ``kind="trsm+gemm"``: the
    trailing update C[m, n] consuming X = L11^{-1} AP with panel width k
    (the LAPACK NB); ``form="lu"`` reads a separate B[m, k] (getrf),
    ``form="syrk"`` reuses X as both GEMM operands (potrf, m == n).
    The GEMM-stage tiling reuses :func:`plan_gemm`; the solve-stage time
    reuses :func:`plan_trsm` - both at the chain's machine and dtype.
    """
    if kind not in FUSED_CHAIN_KINDS:
        raise ValueError(f"unknown fused chain {kind!r}; "
                         f"expected one of {FUSED_CHAIN_KINDS}")
    mach = _machine(machine)
    db = resolve_dtype_bytes(dtype, dtype_bytes, mach)
    fill = mach.memory.pipeline_fill_s
    budget = mach.memory.vmem_bytes
    m, n, k = max(int(m), 1), max(int(n), 1), max(int(k), 1)
    g = plan_gemm(m, n, k, dtype_bytes=db, machine=mach)
    if kind == "gemm+epilogue":
        if epilogue not in EPILOGUE_FLOP_COST:
            raise ValueError(f"unknown epilogue {epilogue!r}; expected one "
                             f"of {tuple(EPILOGUE_FLOP_COST)}")
        bias_bytes = n * db if has_bias else 0
        gemm_bytes = (m * k + k * n + m * n) * db
        epi_flops = (EPILOGUE_FLOP_COST[epilogue]
                     + (1 if has_bias else 0)) * m * n
        epi_bytes = 2 * m * n * db + bias_bytes    # re-read + re-write C
        unfused_b = gemm_bytes + epi_bytes
        fused_b = gemm_bytes + bias_bytes          # C written exactly once
        unfused_t = _stage_time(2.0 * m * n * k, gemm_bytes, mach) \
            + _stage_time(epi_flops, epi_bytes, mach) + 2 * fill
        fused_t = _stage_time(2.0 * m * n * k + epi_flops, fused_b, mach) \
            + fill
        # the epilogue streams one bias block alongside the GEMM's blocks
        vmem = g.vmem_bytes + g.bn * db
        return FusedChainPlan(kind, epilogue, g, g.bm, int(vmem),
                              vmem <= budget, int(unfused_b), int(fused_b),
                              unfused_t, fused_t)
    # trsm+gemm
    if form not in ("lu", "syrk"):
        raise ValueError(f"unknown trsm+gemm form {form!r}; "
                         f"expected 'lu' or 'syrk'")
    t = plan_trsm(k, n, dtype_bytes=db, machine=mach)
    x_bytes = k * n * db
    solve_bytes = k * k * db + k * n * db + x_bytes   # L11 + AP in, X out
    b_bytes = 0 if form == "syrk" else m * k * db
    x_reread = 2 * x_bytes if form == "syrk" else x_bytes
    gemm_flops = 2.0 * m * n * k
    unfused_gemm_b = x_reread + b_bytes + 2 * m * n * db
    fused_gemm_b = b_bytes + 2 * m * n * db           # X stays in VMEM
    solve_t = t.modeled_time
    unfused_t = solve_t + _stage_time(gemm_flops, unfused_gemm_b, mach) \
        + 2 * fill
    fused_t = solve_t + _stage_time(gemm_flops, fused_gemm_b, mach) + fill
    # fused-kernel residency: the pipeline double-buffers every block - L11,
    # AP and the X output (full n width: the solve cannot be column-tiled),
    # the C in/out row blocks and, for the lu form, B's - and the X scratch
    # stays resident at accumulator width. The row block halves from the
    # GEMM tile until that fits the machine's budget.
    sub = max(mach.pe.sublane, 1)
    acc = _acc_bytes(db)

    def residency(rows: int) -> int:
        blocks = k * k + 2 * k * n + 2 * rows * n \
            + (rows * k if form == "lu" else 0)
        return 2 * blocks * db + k * n * acc

    bm = min(g.bm, _round_up(m, sub))
    while residency(bm) > budget and bm > sub:
        bm = max(sub, _round_up(bm // 2, sub))
    vmem = residency(bm)
    return FusedChainPlan(kind, form, g, bm, int(vmem), vmem <= budget,
                          int(solve_bytes + unfused_gemm_b),
                          int(solve_bytes + fused_gemm_b),
                          unfused_t, fused_t)


@dataclasses.dataclass(frozen=True)
class AttentionPlan:
    """Flash-attention tiling: KV blocks stream through VMEM; the online
    softmax running (m, l, o) triple is the dependent accumulator chain."""

    block_q: int
    block_k: int
    grid_kv: int
    vmem_bytes: int


def plan_attention(seq_q: int, seq_k: int, head_dim: int,
                   dtype_bytes: int = 2,
                   vmem_budget: Optional[int] = None,
                   machine: Optional[MachineSpec] = None) -> AttentionPlan:
    """KV/Q block sizes for the streaming-softmax kernel.

    The online-softmax rescale is a serial dependence per KV block (the
    paper's hazard): larger block_k amortizes it (fewer rescales) at the
    cost of VMEM; block_q adds independent rows (free ILP, like dgemv's
    independent inner products).
    """
    mach = _machine(machine)
    vmem_budget = mach.memory.vmem_bytes if vmem_budget is None else vmem_budget
    lane, sublane = mach.pe.lane, mach.pe.sublane
    hd = _round_up(head_dim, lane)
    block_q = min(_round_up(min(seq_q, 512), sublane),
                  _round_up(seq_q, sublane))
    block_k = 1024
    while block_k > 128:
        # q, k, v blocks (double-buffered k/v) + scores + fp32 o/m/l
        vmem = (block_q * hd * dtype_bytes + 2 * 2 * block_k * hd * dtype_bytes
                + block_q * block_k * 4 + block_q * (hd + 2) * 4)
        if vmem <= vmem_budget:
            break
        block_k //= 2
    block_k = min(block_k, _round_up(seq_k, lane))
    vmem = (block_q * hd * dtype_bytes + 2 * 2 * block_k * hd * dtype_bytes
            + block_q * block_k * 4 + block_q * (hd + 2) * 4)
    return AttentionPlan(block_q, block_k, -(-seq_k // block_k), vmem)


@dataclasses.dataclass(frozen=True)
class SSDPlan:
    """Mamba-2 SSD chunking: the cross-chunk state recurrence is the serial
    hazard chain; chunk size trades recurrence steps against the quadratic
    within-chunk term - the same busy/non-busy split as eq. 1."""

    chunk: int
    n_chunks: int
    vmem_bytes: int


def plan_ssd(seq: int, heads: int, head_dim: int, state: int,
             dtype_bytes: int = 2, vmem_budget: Optional[int] = None,
             machine: Optional[MachineSpec] = None) -> SSDPlan:
    """Chunk length for the SSD scan.

    Within-chunk cost ~ c^2 * d (quadratic, parallel); cross-chunk cost is a
    serial chain of length seq/c with latency ~ state update. Minimizing
    c^2*d*(seq/c) + (seq/c)*L gives c* ~ sqrt-ish; we clamp to VMEM and
    hardware alignment, defaulting to the canonical 256 where it fits.
    """
    mach = _machine(machine)
    vmem_budget = mach.memory.vmem_bytes if vmem_budget is None else vmem_budget
    sublane = mach.pe.sublane
    best_c = 256
    for c in (256, 128, 64):
        vmem = (c * head_dim * dtype_bytes * 3 + c * c * 4
                + head_dim * state * 4 + c * state * dtype_bytes * 2)
        if vmem <= vmem_budget and c <= max(seq, 64):
            best_c = c
            break
    best_c = min(best_c, max(_round_up(seq, sublane), sublane))
    vmem = (best_c * head_dim * dtype_bytes * 3 + best_c * best_c * 4
            + head_dim * state * 4 + best_c * state * dtype_bytes * 2)
    return SSDPlan(best_c, -(-seq // best_c), vmem)


def characterize_and_plan(profile, machine: Optional[MachineSpec] = None) -> Dict[str, object]:
    """End-to-end: a WorkloadProfile -> TPU kernel knobs.

    The paper's p_opt for the adder pipe becomes the accumulator count; the
    mul pipe's hazard-freedom means the MXU side has no knob (it is always
    saturable, the 'flat curve' of section 4.1).
    """
    add = profile.pipes.get("add")
    n = float(add.n_i) if add else 0.0
    return {
        "accumulators": optimal_accumulators(max(n, 1.0), machine=machine),
        "hazard_ratios": profile.hazard_ratios(),
        "popt": profile.popt_closed_form(),
    }
