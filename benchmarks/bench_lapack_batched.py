"""Batched blocked LAPACK sweep: batch x size x block wall time + Gflop/s.

Records the trajectory the ISSUE-1 tentpole opens: how the batched
factorizations scale as the trailing updates ride the GEMM hot path, and
how the measured best block compares with the codesign model's
``plan_factorization`` choice.

Standalone:  PYTHONPATH=src python -m benchmarks.bench_lapack_batched \
                 [--fast] [--out benchmarks/out/lapack_batched.json]
Driver:      registered in benchmarks.run as "lapack_batched".
"""
from __future__ import annotations

import argparse
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro import arch, lapack, linalg, tune
from repro.core.codesign import FACTOR_FLOP_COEFF as FLOP_COEFF
from repro.core.codesign import (modeled_factorization_time,
                                 plan_factorization)
from repro.tune.measure import measure, model_residual

FACTOR_FN = {"potrf": lapack.batched_potrf, "getrf": lapack.batched_getrf,
             "geqrf": lapack.batched_geqrf}


def sweep(batches=(1, 8, 32), sizes=(32, 64, 128), blocks=(8, 16, 32, None),
          kinds=("potrf", "getrf", "geqrf"), reps=3, policy="reference",
          dtype=jnp.float32):
    """Returns a list of row dicts, one per (kind, batch, n, block); every
    row carries the dtype, the resolved ExecutionContext, and the policy
    its trailing updates resolved through the repro.tune dispatcher."""
    rng = np.random.default_rng(0)
    rows = []
    dtype = jnp.dtype(dtype)
    ctx_desc = linalg.ExecutionContext(policy=policy).describe()
    for kind in kinds:
        fn = FACTOR_FN[kind]
        for n in sizes:
            a = rng.normal(size=(max(batches), n, n)).astype(np.float32)
            if kind == "potrf":
                a = a @ np.swapaxes(a, 1, 2) + n * np.eye(n, dtype=np.float32)
            a = a.astype(dtype)
            gemm_cfg = tune.resolve(
                "gemm", (n, n, n), dtype, policy=policy).describe()
            for b in batches:
                x = jnp.asarray(a[:b])
                for block in blocks:
                    f = jax.jit(lambda m, k=kind, nb=block: FACTOR_FN[k](
                        m, block=nb, policy=policy).factors)
                    ms = measure(f, x, min_reps=reps, max_reps=2 * reps)
                    t = ms.seconds_median
                    flops = b * FLOP_COEFF[kind] * n ** 3
                    nb_eff = (block if block is not None else
                              plan_factorization(n, kind=kind).block)
                    model_s = modeled_factorization_time(
                        n, kind=kind, block=nb_eff, batch=b, dtype=dtype)
                    row = {
                        "kind": kind, "batch": b, "n": n,
                        "block": nb_eff,
                        "planned": block is None,
                        "policy": policy,
                        "dtype": dtype.name,
                        "context": ctx_desc,
                        "trailing_resolution": gemm_cfg,
                        "seconds_per_call": t, **ms.row_fields(),
                        "model_residual": model_residual(model_s, t),
                        **arch.bench_metrics(flops / t / 1e9),
                    }
                    if kind in ("potrf", "getrf") and n > nb_eff:
                        # the per-item trailing updates route through the
                        # trsm+gemm chain; record its resolved fuse verdict
                        # and modeled HBM traffic for the widest step
                        form = "syrk" if kind == "potrf" else "lu"
                        res_c = tune.resolve(
                            "trsm+gemm", (n - nb_eff, n - nb_eff, nb_eff),
                            dtype, policy=policy, form=form)
                        row["fused"] = bool(res_c.fused)
                        if res_c.chain is not None:
                            ch = res_c.chain
                            row["modeled_hbm_bytes"] = ch.fused_hbm_bytes \
                                if res_c.fused else ch.unfused_hbm_bytes
                            row["hbm_bytes_saved"] = ch.hbm_bytes_saved
                    rows.append(row)
    return rows


def record(rows) -> dict:
    """JSON record: config + rows + per-(kind, batch, n) best block vs the
    codesign model's pick."""
    best = {}
    for r in rows:
        key = (r["kind"], r["batch"], r["n"])
        if key not in best or r["seconds_per_call"] < best[key]["seconds_per_call"]:
            best[key] = r
    summary = [{
        "kind": k, "batch": b, "n": n,
        "best_block": v["block"],
        "best_gflops": v["gflops"],
        "planned_block": plan_factorization(n, kind=k, batch=b).block,
    } for (k, b, n), v in sorted(best.items())]
    return {
        "benchmark": "lapack_batched",
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "policy": rows[0]["policy"] if rows else None,
        "dtype": rows[0]["dtype"] if rows else None,
        "context": rows[0]["context"] if rows else None,
        "rows": rows,
        "summary": summary,
    }


# CI-sized grid shared by run() and main(--fast)
FAST_GRID = dict(batches=(1, 8), sizes=(32, 64), blocks=(8, 16, None), reps=2)


def run(emit, fast: bool = True):
    """benchmarks.run driver entry: CSV rows + JSON artifact."""
    rows = sweep(**FAST_GRID) if fast else sweep()
    for r in rows:
        name = f"lapack_batched,{r['kind']},b{r['batch']},n{r['n']},nb{r['block']}"
        emit(name, r["seconds_per_call"] * 1e3, "ms_per_call")
        emit(name, r["gflops"], "gflops")
    out = os.path.join(os.path.dirname(__file__), "out", "lapack_batched.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(record(rows), f, indent=2)
    emit("lapack_batched,json", out, "path")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="benchmarks/out/lapack_batched.json")
    ap.add_argument("--fast", action="store_true",
                    help="small grid (CI-sized)")
    args = ap.parse_args()
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    rows = sweep(**FAST_GRID) if args.fast else sweep()
    rec = record(rows)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(rec, f, indent=2)
    print(f"wrote {len(rows)} rows -> {args.out}")
    for s in rec["summary"]:
        print(f"{s['kind']:6s} batch={s['batch']:<3d} n={s['n']:<4d} "
              f"best_block={s['best_block']:<4} model={s['planned_block']:<4} "
              f"{s['best_gflops']:.2f} Gflop/s")


if __name__ == "__main__":
    main()
