"""Distributed BLAS sweep: mesh shape x matrix size x policy -> trajectory.

Runs SUMMA GEMM and the mesh-parallel batched factorizations - through
the :mod:`repro.linalg` front-end with a mesh-bearing ExecutionContext,
so the routing layer itself is on the measured path - over every mesh
shape that fits the device count,
recording wall time, the resolved kernel config (including the registry's
mesh key component), and the :func:`repro.core.codesign.plan_pdgemm` model
terms (compute vs per-hop collective bytes) - so the cross-device
co-design claim is a measured trajectory, not prose.

Device note: the sweep runs in the calling process, on the meshes that
fit the devices it sees; a chip belongs to one process, so no child is
started. XLA fixes the host device count at first jax init, so a
standalone CPU rehearsal forces 8 virtual CPU devices via ``XLA_FLAGS``
*before* importing jax (only when run as ``__main__``).

Standalone:  PYTHONPATH=src python -m benchmarks.bench_distributed_blas \
                 [--fast] [--out benchmarks/out/BENCH_distributed.json]
Driver:      registered in benchmarks.run as "distributed_blas".
"""
from __future__ import annotations

import argparse
import json
import os
import sys

_N_DEVICES = 8
_DEV_FLAG = f"--xla_force_host_platform_device_count={_N_DEVICES}"


def _with_device_flag(flags: str) -> str:
    """Append the forced-device-count flag to an XLA_FLAGS value,
    preserving whatever else is already there."""
    if "xla_force_host_platform_device_count" in flags:
        return flags
    return f"{flags} {_DEV_FLAG}".strip()


if __name__ == "__main__":  # CPU rehearsal: virtual devices before jax init
    os.environ["XLA_FLAGS"] = _with_device_flag(os.environ.get("XLA_FLAGS", ""))

import jax
import jax.numpy as jnp
import numpy as np

MESHES = [(1, 1), (2, 2), (4, 2)]
GEMM_SHAPES = [(64, 64, 64), (128, 128, 64)]
FAST_GEMM = [(32, 32, 32), (64, 48, 32)]
FACTOR_GRID = [("potrf", 8, 48), ("getrf", 8, 48)]
FAST_FACTOR = [("potrf", 8, 32), ("getrf", 8, 32)]
POLICIES = ("reference", "model", "tuned")
_OUT_DEFAULT = os.path.join(os.path.dirname(__file__), "out",
                            "BENCH_distributed.json")


def sweep(gemm_shapes=None, factor_grid=None, policies=POLICIES, reps=1):
    """Returns trajectory rows over mesh x shape x policy; every row
    records the mesh shape and the resolved config."""
    from repro import arch as _arch
    from repro import linalg
    from repro.blas import distributed as dblas
    from repro.core.codesign import (FACTOR_FLOP_COEFF,
                                     modeled_factorization_time, plan_pdgemm)
    from repro.tune import dispatch
    from repro.tune.measure import measure, model_residual

    rng = np.random.default_rng(0)
    rows = []
    ndev = jax.device_count()
    if ndev < _N_DEVICES:
        print(f"note: {ndev} device(s) visible - meshes larger than that "
              f"are skipped", file=sys.stderr)
    meshes = [(px, py) for px, py in MESHES if px * py <= ndev]
    for px, py in meshes:
        mesh = dblas.make_blas_mesh(px, py)
        mkey = dblas.mesh_key(mesh)
        for m, n, k in (gemm_shapes if gemm_shapes is not None
                        else GEMM_SHAPES):
            a = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
            b = jnp.asarray(rng.normal(size=(k, n)).astype(np.float32))
            plan = plan_pdgemm(m, n, k, px, py, dtype_bytes=4)
            for pol in policies:
                res = dispatch.resolve("pdgemm", (m, n, k), jnp.float32,
                                       policy=pol, mesh=(px, py))
                ctx = dict(policy=pol, mesh=(px, py))
                f = jax.jit(lambda x, y, c=dict(ctx): linalg.gemm(
                    x, y, context=c))
                ms = measure(f, a, b, min_reps=reps, max_reps=2 * reps)
                t = ms.seconds_median
                rows.append({
                    "op": "pdgemm", "mesh": [px, py], "mesh_key": mkey,
                    "shape": [m, n, k], "policy": pol,
                    "dtype": "float32",
                    "context": linalg.ExecutionContext(**ctx).describe(),
                    "resolution": res.describe(),
                    "seconds_per_call": t, **ms.row_fields(),
                    "model_residual": model_residual(plan.modeled_time, t),
                    **_arch.bench_metrics(2.0 * m * n * k / t / 1e9),
                    "model": {"compute_s": plan.compute_s,
                              "collective_s": plan.collective_s,
                              "collective_bytes": plan.collective_bytes,
                              "collective_bound": plan.collective_bound,
                              "steps": plan.steps, "k_fine": plan.k_fine},
                })
        for kind, batch, nsz in (factor_grid if factor_grid is not None
                                 else FACTOR_GRID):
            x = rng.normal(size=(batch, nsz, nsz)).astype(np.float32)
            if kind == "potrf":
                x = x @ np.swapaxes(x, 1, 2) + nsz * np.eye(
                    nsz, dtype=np.float32)
            xj = jnp.asarray(x)
            fn = {"potrf": linalg.batched_cholesky,
                  "getrf": linalg.batched_lu}[kind]
            for pol in policies:
                ctx = dict(policy=pol, mesh=(px, py))
                f = jax.jit(lambda v, c=dict(ctx): fn(
                    v, context=c).factors)
                ms = measure(f, xj, min_reps=reps, max_reps=2 * reps)
                t = ms.seconds_median
                res = dispatch.resolve("gemm", (nsz, nsz, nsz), jnp.float32,
                                       policy=pol)
                flops = batch * FACTOR_FLOP_COEFF[kind] * nsz ** 3
                model_s = modeled_factorization_time(
                    nsz, kind=kind, batch=batch, dtype=jnp.float32)
                rows.append({
                    "op": f"batched_{kind}", "mesh": [px, py],
                    "mesh_key": mkey, "shape": [batch, nsz, nsz],
                    "policy": pol, "dtype": "float32",
                    "context": linalg.ExecutionContext(**ctx).describe(),
                    "resolution": res.describe(),
                    "seconds_per_call": t, **ms.row_fields(),
                    "model_residual": model_residual(model_s, t),
                    **_arch.bench_metrics(flops / t / 1e9),
                })
    return rows


def record(rows) -> dict:
    return {
        "benchmark": "distributed_blas",
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "meshes": sorted({tuple(r["mesh"]) for r in rows}),
        "rows": rows,
    }


def _emit_rows(emit, rec) -> None:
    for r in rec["rows"]:
        mesh = "x".join(str(d) for d in r["mesh"])
        shape = "x".join(str(d) for d in r["shape"])
        name = f"distributed_blas,{r['op']},mesh{mesh},{shape},{r['policy']}"
        emit(name, r["seconds_per_call"] * 1e3, "ms_per_call")
        if "gflops" in r:
            emit(name, r["gflops"], "gflops")


def run(emit, fast: bool = True):
    """benchmarks.run driver entry: sweep in this process, on the devices
    it already holds, then emit from the JSON artifact."""
    out = _OUT_DEFAULT
    os.makedirs(os.path.dirname(out), exist_ok=True)
    rows = sweep(gemm_shapes=FAST_GEMM if fast else None,
                 factor_grid=FAST_FACTOR if fast else None,
                 reps=1 if fast else 2)
    rec = record(rows)
    with open(out, "w") as f:
        json.dump(rec, f, indent=2)
    _emit_rows(emit, rec)
    emit("distributed_blas,device_count", rec["device_count"], "devices")
    emit("distributed_blas,json", out, "path")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=_OUT_DEFAULT)
    ap.add_argument("--fast", action="store_true", help="CI-sized grid")
    args = ap.parse_args()
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    rows = sweep(gemm_shapes=FAST_GEMM if args.fast else None,
                 factor_grid=FAST_FACTOR if args.fast else None,
                 reps=1 if args.fast else 2)
    rec = record(rows)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(rec, f, indent=2)
    print(f"wrote {len(rows)} rows -> {args.out} "
          f"({rec['device_count']} devices)")
    for r in rows:
        mesh = "x".join(str(d) for d in r["mesh"])
        shape = "x".join(str(d) for d in r["shape"])
        extra = f" {r['gflops']:8.3f} Gflop/s" if "gflops" in r else ""
        print(f"{r['op']:14s} mesh={mesh:4s} {shape:>10s} "
              f"{r['policy']:9s} {r['seconds_per_call']*1e3:9.2f} ms{extra}")


if __name__ == "__main__":
    main()
