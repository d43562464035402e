"""Benchmark driver: one module per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--only name] [--fast] [--list]``
Prints ``name,value,derived`` CSV rows (``--list`` prints the registered
benches without running anything). ``--trace [DIR]`` additionally runs
every selected bench under a :mod:`repro.obs` trace and writes one
Chrome-format artifact per bench to ``DIR/trace_<name>.json`` (default
``benchmarks/out``) - load it in ``chrome://tracing`` / Perfetto, or
summarize with ``python scripts/trace_report.py <file>``.
"""
from __future__ import annotations

import argparse
import contextlib
import os
import sys
import time
import traceback

MODULES = [
    ("theory", "benchmarks.bench_theory"),                # figs 2-4
    ("characterization", "benchmarks.bench_characterization"),  # figs 6-10
    ("pe_cpi", "benchmarks.bench_pe_cpi"),                # figs 12-13
    ("synthesis", "benchmarks.bench_synthesis"),          # tables 1-2
    ("blas", "benchmarks.bench_blas"),                    # substrate perf
    ("lapack_batched", "benchmarks.bench_lapack_batched"),  # batched sweep
    ("tune", "benchmarks.bench_tune"),                    # tuner sweep -> registry
    ("distributed_blas", "benchmarks.bench_distributed_blas"),  # mesh sweep
    ("census", "benchmarks.bench_census"),                # section 4 on zoo
    ("roofline", "benchmarks.bench_roofline"),            # dry-run reader
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--fast", action="store_true",
                    help="skip the slow PE stream sweeps")
    ap.add_argument("--list", action="store_true",
                    help="print registered benches (name, module) and exit")
    ap.add_argument("--trace", nargs="?", const="benchmarks/out",
                    default=None, metavar="DIR",
                    help="trace each bench via repro.obs and write "
                         "DIR/trace_<name>.json (Chrome trace_event format; "
                         "default DIR: benchmarks/out)")
    args = ap.parse_args()

    if args.list:
        for name, modpath in MODULES:
            print(f"{name:18s} {modpath}")
        return

    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()

    def emit(name, value, unit):
        print(f"{name},{value},{unit}", flush=True)

    failures = []
    for name, modpath in MODULES:
        if args.only and name != args.only:
            continue
        if args.fast and name in ("pe_cpi", "census"):
            continue
        mod = __import__(modpath, fromlist=["run"])
        # perf_counter, not time.time(): the wall clock can step under NTP
        # mid-benchmark and corrupt the recorded duration
        t0 = time.perf_counter()
        print(f"# === {name} ===", flush=True)
        try:
            with contextlib.ExitStack() as st:
                tr = None
                if args.trace is not None:
                    from repro import obs
                    tr = st.enter_context(obs.trace(f"bench.{name}"))
                if name == "pe_cpi":
                    mod.run(emit, n=32 if args.fast else 48)
                else:
                    mod.run(emit)
            if tr is not None:
                from repro.obs import save_chrome_trace
                os.makedirs(args.trace, exist_ok=True)
                path = os.path.join(args.trace, f"trace_{name}.json")
                save_chrome_trace(tr, path)
                print(f"# trace: {path} ({len(tr.events)} events)",
                      flush=True)
        except Exception:
            failures.append(name)
            traceback.print_exc()
        print(f"# {name} done in {time.perf_counter() - t0:.1f}s", flush=True)
    if failures:
        print(f"# FAILURES: {failures}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
