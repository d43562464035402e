"""Tuner sweep: measured GEMM/TRSM configs -> persistent registry + trajectory.

Runs the :mod:`repro.tune.search` sweeps over a standard shape grid, writes
the winning configs to ``tune_registry.json`` (the cache
``REPRO_TUNE_REGISTRY`` should point at), and records the full trajectory -
every measured candidate, the model's own pick, and the post-sweep
``dispatch.resolve`` outcome per shape - to ``BENCH_tune.json`` so tuning
quality is comparable across PRs.

Standalone:  PYTHONPATH=src python -m benchmarks.bench_tune \
                 [--fast] [--out-dir benchmarks/out]
Driver:      registered in benchmarks.run as "tune".
"""
from __future__ import annotations

import argparse
import json
import os

import jax
import jax.numpy as jnp

from repro import arch, linalg
from repro.tune import dispatch, search
from repro.tune.registry import Registry

GEMM_SHAPES = [(64, 64, 64), (128, 128, 64), (128, 64, 128)]
TRSM_SHAPES = [(64, 8), (128, 8)]
FAST_GEMM = [(32, 32, 32), (64, 64, 64)]
FAST_TRSM = [(48, 4)]
# measured sweeps per dtype the kernel path executes on this backend;
# float64 additionally gets model-seeded entries (no measurement - the
# default jax config would silently downcast the operands)
SWEEP_DTYPES = (jnp.float32, jnp.bfloat16)
SEED_DTYPES = (jnp.float64,)


def sweep(registry: Registry, gemm_shapes=None, trsm_shapes=None,
          top_k: int = 3, reps: int = 2, dtypes=SWEEP_DTYPES):
    """Run every sweep into ``registry`` per dtype; returns trajectory
    rows. Non-measurable dtypes (float64 without X64) get model-seeded
    registry entries via :func:`repro.tune.search.seed_registry_from_model`
    so their tuned lookups hit real configs instead of falling back."""
    rows = []
    gshapes = gemm_shapes if gemm_shapes is not None else GEMM_SHAPES
    tshapes = trsm_shapes if trsm_shapes is not None else TRSM_SHAPES

    def _with_winner_stats(r):
        """Lift the winning candidate's controller stats (median / spread /
        reps / model_residual) to the row's top level, the shared bench-row
        convention the perf-regression gate reads."""
        win = min(r["measured"], key=lambda c: c["seconds"])
        r.update({k: win[k] for k in ("seconds_median", "seconds_spread",
                                      "reps", "model_residual")})
        return r

    for dtype in dtypes:
        for m, n, k in gshapes:
            r = _with_winner_stats(search.tune_gemm(
                m, n, k, dtype=dtype, registry=registry,
                top_k=top_k, reps=reps).to_json())
            r.update(arch.bench_metrics(
                2.0 * m * n * k / max(r["best"]["measured_s"], 1e-12) / 1e9))
            rows.append(r)
        for n, nrhs in tshapes:
            r = _with_winner_stats(search.tune_trsm(
                n, nrhs, dtype=dtype, registry=registry,
                reps=reps).to_json())
            r.update(arch.bench_metrics(
                n * n * nrhs / max(r["best"]["measured_s"], 1e-12) / 1e9))
            rows.append(r)
    search.seed_registry_from_model(registry, gemm_shapes=gshapes,
                                    trsm_shapes=tshapes, dtypes=SEED_DTYPES)
    return rows


def record(registry: Registry, rows) -> dict:
    """JSON record: trajectory + the resolution every row now gets from the
    freshly written registry (must be source="registry" - a lookup miss
    here means the schema broke)."""
    resolutions = []
    for r in rows:
        res = dispatch.resolve(r["op"], tuple(r["shape"]), jnp.dtype(r["dtype"]),
                               policy="tuned", registry=registry)
        resolutions.append(res.describe())
    return {
        "benchmark": "tune",
        "backend": jax.default_backend(),
        "policy": "tuned",
        "dtypes": sorted({r["dtype"] for r in rows}),
        "context": linalg.ExecutionContext(
            policy="tuned", registry=registry.path).describe(),
        "registry_path": registry.path,
        "registry_entries": len(registry),
        "rows": rows,
        "resolutions": resolutions,
        "all_hits": all(r["source"] == "registry" for r in resolutions),
    }


def run(emit, fast: bool = True):
    """benchmarks.run driver entry: CSV rows + registry + JSON artifact."""
    out_dir = os.path.join(os.path.dirname(__file__), "out")
    os.makedirs(out_dir, exist_ok=True)
    reg = Registry(path=os.path.join(out_dir, "tune_registry.json"))
    rows = sweep(reg, gemm_shapes=FAST_GEMM if fast else None,
                 trsm_shapes=FAST_TRSM if fast else None,
                 top_k=2 if fast else 3, reps=1 if fast else 2)
    reg.save()
    rec = record(reg, rows)
    for r in rows:
        shape = "x".join(str(d) for d in r["shape"])
        cfg = "/".join(f"{k}={v}" for k, v in sorted(r["best"]["params"].items()))
        emit(f"tune,{r['op']},{shape},{cfg}", r["best"]["measured_s"] * 1e3,
             "ms_per_call")
    emit("tune,registry", reg.path, "path")
    out = os.path.join(out_dir, "BENCH_tune.json")
    with open(out, "w") as f:
        json.dump(rec, f, indent=2)
    emit("tune,all_hits", int(rec["all_hits"]), "bool")
    emit("tune,json", out, "path")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out-dir", default="benchmarks/out")
    ap.add_argument("--fast", action="store_true", help="CI-sized grid")
    args = ap.parse_args()
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    os.makedirs(args.out_dir, exist_ok=True)
    reg = Registry(path=os.path.join(args.out_dir, "tune_registry.json"))
    rows = sweep(reg, gemm_shapes=FAST_GEMM if args.fast else None,
                 trsm_shapes=FAST_TRSM if args.fast else None,
                 top_k=2 if args.fast else 3, reps=1 if args.fast else 2)
    reg.save()
    rec = record(reg, rows)
    out = os.path.join(args.out_dir, "BENCH_tune.json")
    with open(out, "w") as f:
        json.dump(rec, f, indent=2)
    print(f"wrote {len(rows)} sweeps -> {out}; registry -> {reg.path} "
          f"({len(reg)} entries, all_hits={rec['all_hits']})")
    for r in rows:
        print(f"{r['op']:5s} {'x'.join(str(d) for d in r['shape']):>12s} "
              f"best={r['best']['params']} model={r['model_params']}")


if __name__ == "__main__":
    main()
