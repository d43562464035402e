"""Measured-machine calibration: fit a MachineSpec from micro-benchmarks.

Every machine in :mod:`repro.arch.registry` is hand-declared; this module
closes the ELAPS-style loop (arXiv:1504.08035, 1209.2364) for the backend
the process is actually running on. A small micro-benchmark suite runs
under the adaptive repetition controller of :mod:`repro.tune.measure`:

* **GEMM ladder** - square f32 matmuls of increasing size; the best
  sustained flop rate fits ``PEGeometry.peak_flops`` (the MXU/SIMD
  throughput term every roofline in the repo prices against).
* **Streaming copy + reduction** - large-array traversals; the best
  sustained byte rate fits ``MemorySpec.hbm_bw`` (the HBM-class bandwidth
  term of the roofline).
* **Dependent chains per op class** - a loop-carried mul / add / div /
  sqrt chain exposes each class's effective dependent-op latency exactly
  like an under-filled pipeline (the paper's eq.-2 hazard term); the
  measured latency ratios, anchored at the base spec's multiplier depth,
  fit ``FPUSpec.depths``.

The fitted sections replace their counterparts in a *base* spec (default:
chosen by device kind, :data:`BASE_BY_DEVICE_KIND`) - power/area stays
the base's, since wall-clock micro-benchmarks cannot observe pJ/flop or
die area - and the result is a frozen, JSON-serializable
:class:`~repro.arch.spec.MachineSpec` named ``calibrated-<backend>`` that
is registered into the machine
registry (``arch.get("calibrated-cpu")``) and can round-trip through
``save``/``load`` like any other spec.

By construction the fitted machine's modeled time for the *best* rung of
the GEMM ladder and of the stream suite equals the measured median (the
fit is that rung's rate); :data:`CALIBRATION_TOLERANCE` is the documented
band within which those modeled-vs-measured residuals must stay for a
calibration to be considered sane (see ``docs/benchmarking.md``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Mapping, Optional, Sequence, Tuple

from repro.arch import registry as _registry
from repro.arch.spec import MachineSpec, OP_CLASSES

# |model_residual| band the best-rung micro-bench rows must satisfy for a
# calibration to be accepted as self-consistent (documented tolerance of
# the acceptance loop; the best rungs are exact fits up to rep noise, so
# this bounds measurement spread, not model error).
CALIBRATION_TOLERANCE = 0.35

# base spec per device kind (``jax.devices()[0].device_kind``): the spec
# whose unmeasured sections (power/area, geometry) a calibration keeps. A
# device missing here has no base to stand on and is refused.
BASE_BY_DEVICE_KIND = {"cpu": "cpu-host", "TPU v5 lite": "tpu-like"}

# fitted pipeline depths are clamped into this range: >= 1 by FPUSpec's
# validation, <= 64 so one noisy chain sample cannot declare an absurd pipe
_DEPTH_RANGE = (1, 64)


@dataclasses.dataclass(frozen=True)
class CalibrationResult:
    """A fitted machine plus the micro-bench evidence behind it.

    ``report`` rows carry ``{"bench", "params", "seconds_median",
    "seconds_spread", "reps", "modeled_s", "model_residual"}`` - the same
    timing-field convention as every benchmark JSON row, with ``modeled_s``
    computed *under the fitted machine* so the residuals say how well the
    calibrated spec explains its own evidence.
    """

    machine: MachineSpec
    report: Tuple[Dict[str, Any], ...]
    backend: str

    def best_residual(self, bench: str) -> float:
        """Smallest |model_residual| over the rows of one bench family."""
        rs = [abs(r["model_residual"]) for r in self.report
              if r["bench"] == bench]
        if not rs:
            raise ValueError(f"no report rows for bench {bench!r}")
        return min(rs)

    def to_json(self) -> Dict[str, Any]:
        return {"backend": self.backend, "machine": self.machine.to_json(),
                "report": [dict(r) for r in self.report]}


def _measure_mod():
    # lazy: repro.tune imports repro.arch at package-import time, so the
    # arch package cannot import repro.tune back at module level
    from repro.tune import measure
    return measure


def run_microbenchmarks(gemm_sizes: Sequence[int] = (64, 128, 256),
                        stream_elems: int = 1 << 22,
                        chain_iters: int = 256,
                        reps: Optional[int] = None,
                        min_reps: int = 3, max_reps: int = 10,
                        rel_spread: float = 0.2) -> Dict[str, Any]:
    """Run the calibration suite on the running backend.

    Returns raw evidence: per-rung GEMM measurements (+ flops), the two
    stream measurements (+ bytes), and the per-op-class dependent-chain
    latencies. All timing goes through the adaptive controller
    (``reps=N`` pins exact rep counts for deterministic duration).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    meas = _measure_mod()
    kw = dict(reps=reps, min_reps=min_reps, max_reps=max_reps,
              rel_spread=rel_spread)

    rng = np.random.default_rng(0)
    gemm = []
    for n in gemm_sizes:
        a = jnp.asarray(rng.normal(size=(n, n)).astype(np.float32))
        b = jnp.asarray(rng.normal(size=(n, n)).astype(np.float32))
        m = meas.measure(jax.jit(lambda x, y: x @ y), a, b, **kw)
        gemm.append({"n": int(n), "flops": 2.0 * n ** 3, "measurement": m})

    itemsize = 4
    x = jnp.asarray(rng.normal(size=int(stream_elems)).astype(np.float32))
    stream = []
    # copy: one read + one write stream; reduction: one read stream
    m = meas.measure(jax.jit(lambda v: v + jnp.float32(0.0)), x, **kw)
    stream.append({"kind": "copy", "bytes": 2 * int(stream_elems) * itemsize,
                   "measurement": m})
    m = meas.measure(jax.jit(jnp.sum), x, **kw)
    stream.append({"kind": "reduction", "bytes": int(stream_elems) * itemsize,
                   "measurement": m})

    # loop-carried dependent chains: per iteration exactly one op of the
    # class on an 8-lane value, latency-bound by construction
    c = jnp.float32(1.0000001)
    chain_body = {
        "mul": lambda i, v: v * c,
        "add": lambda i, v: v + c,
        "div": lambda i, v: v / c,
        "sqrt": lambda i, v: jnp.sqrt(v) + jnp.float32(0.5),
    }
    v0 = jnp.full((8,), 2.0, dtype=jnp.float32)
    chains = {}
    for cls in OP_CLASSES:
        f = jax.jit(lambda v, body=chain_body[cls]: lax.fori_loop(
            0, int(chain_iters), body, v))
        m = meas.measure(f, v0, **kw)
        chains[cls] = {"iters": int(chain_iters), "measurement": m,
                       "latency_s": m.seconds_median / int(chain_iters)}

    return {"backend": jax.default_backend(),
            "device_kind": jax.devices()[0].device_kind, "gemm": gemm,
            "stream": stream, "chains": chains}


def _fit_depths(chains: Mapping[str, Mapping[str, Any]],
                base: MachineSpec) -> Dict[str, int]:
    """Effective pipeline depth per op class from dependent-chain latency
    ratios, anchored at the base spec's multiplier depth (wall-clock alone
    fixes ratios, not the cycle time)."""
    lat = {k: float(chains[k]["latency_s"]) for k in OP_CLASSES}
    anchor = base.fpu.depths["mul"] / max(lat["mul"], 1e-12)
    lo, hi = _DEPTH_RANGE
    return {k: min(max(int(round(lat[k] * anchor)), lo), hi)
            for k in OP_CLASSES}


def fit_machine(results: Mapping[str, Any],
                base: Optional[MachineSpec] = None,
                name: Optional[str] = None) -> MachineSpec:
    """Fit FPU/Memory/PE parameters from :func:`run_microbenchmarks`
    evidence into a copy of ``base`` (default: the spec
    :data:`BASE_BY_DEVICE_KIND` names for the measured device kind)."""
    backend = results["backend"]
    if base is None:
        kind = results["device_kind"]
        if kind not in BASE_BY_DEVICE_KIND:
            raise ValueError(f"no base machine spec for device kind "
                             f"{kind!r}; known kinds: "
                             f"{sorted(BASE_BY_DEVICE_KIND)}")
        base = _registry.get(BASE_BY_DEVICE_KIND[kind])
    name = name or f"calibrated-{backend}"

    peak = max(r["flops"] / r["measurement"].seconds_median
               for r in results["gemm"])
    bw = max(r["bytes"] / r["measurement"].seconds_median
             for r in results["stream"])
    depths = _fit_depths(results["chains"], base)

    return MachineSpec(
        name=name,
        native_dtype="float32",          # the dtype the suite measured at
        fpu=dataclasses.replace(base.fpu, depths=depths),
        memory=dataclasses.replace(base.memory, hbm_bw=float(bw)),
        pe=dataclasses.replace(base.pe, peak_flops=float(peak)),
        power_area=base.power_area,      # not observable from wall clock
    )


def _report(results: Mapping[str, Any],
            machine: MachineSpec) -> Tuple[Dict[str, Any], ...]:
    """Modeled-vs-measured rows for the fitted machine, in the shared
    bench-row field convention."""
    meas = _measure_mod()
    peak = machine.pe.peak_flops
    bw = machine.memory.hbm_bw
    rows = []

    def row(bench, params, m, modeled_s):
        rows.append({"bench": bench, "params": params, **m.row_fields(),
                     "converged": m.converged, "modeled_s": modeled_s,
                     "model_residual": meas.model_residual(
                         modeled_s, m.seconds_median)})

    for r in results["gemm"]:
        n = r["n"]
        ai = r["flops"] / (3.0 * n * n * 4)         # A, B in; C out (f32)
        row("gemm", {"n": n}, r["measurement"],
            r["flops"] / min(peak, ai * bw))
    for r in results["stream"]:
        row("stream", {"kind": r["kind"]}, r["measurement"],
            r["bytes"] / bw)
    anchor_lat = results["chains"]["mul"]["latency_s"] \
        / machine.fpu.depths["mul"]
    for cls in OP_CLASSES:
        c = results["chains"][cls]
        row("chain", {"op_class": cls, "iters": c["iters"]},
            c["measurement"],
            machine.fpu.depths[cls] * anchor_lat * c["iters"])
    return tuple(rows)


def calibrate_full(backend: Optional[str] = None,
                   base: Optional[MachineSpec] = None,
                   name: Optional[str] = None, *,
                   register: bool = True, overwrite: bool = True,
                   path: Optional[str] = None,
                   **bench_kwargs) -> CalibrationResult:
    """Run the suite, fit a machine, register it, and return machine +
    evidence report. ``bench_kwargs`` forward to
    :func:`run_microbenchmarks` (sizes / rep budgets - tests shrink them).
    ``path`` additionally writes the fitted spec's JSON there.
    """
    import jax
    got = jax.default_backend()
    if backend is not None and backend != got:
        raise ValueError(f"cannot calibrate backend {backend!r} from a "
                         f"process running on {got!r}")
    results = run_microbenchmarks(**bench_kwargs)
    machine = fit_machine(results, base=base, name=name)
    if register:
        _registry.register(machine, overwrite=overwrite)
    if path is not None:
        machine.save(path)
    return CalibrationResult(machine=machine, report=_report(results, machine),
                             backend=results["backend"])


def calibrate(backend: Optional[str] = None,
              base: Optional[MachineSpec] = None,
              name: Optional[str] = None, *,
              register: bool = True, overwrite: bool = True,
              path: Optional[str] = None,
              **bench_kwargs) -> MachineSpec:
    """Measure the running backend and return the fitted, registered
    ``calibrated-<backend>`` :class:`MachineSpec` (the ``arch.calibrate()``
    entry point; :func:`calibrate_full` keeps the evidence report)."""
    return calibrate_full(backend, base, name, register=register,
                          overwrite=overwrite, path=path,
                          **bench_kwargs).machine


def load_or_calibrate(path: str, **calibrate_kwargs) -> MachineSpec:
    """The persistence convention for calibrated machines: load ``path``
    and register the spec if the file is a valid MachineSpec JSON;
    on a missing *or corrupt* file fall back to a fresh
    :func:`calibrate` run and write its result to ``path``."""
    try:
        spec = MachineSpec.load(path)
    except (OSError, ValueError):
        return calibrate(path=path, **calibrate_kwargs)
    register = calibrate_kwargs.get("register", True)
    if register:
        _registry.register(spec, overwrite=True)
    return spec
