"""Readings that the limits of `correct` are set from.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 [--what control]

``--what program`` (the default) makes, for each seed, the cell's inputs
and calls the program on them as a run does, and prints the numbers the
check compares: the lower readings. ``--what control`` puts the plain
reference in the program's place, computed one precision below the one the
configuration states (float32 at three bfloat16 passes in place of
HIGHEST): the upper readings, which the limits must fail. One line of JSON
per seed; ``--n`` sets a smaller size (the tests use it on the CPU).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

from bench import run                       # noqa: E402
from bench.reference import dense           # noqa: E402

LOWER = {
    "solve": lambda a, b: dense.lu_solve(a, b, precision="bf16x3"),
    "cholesky": lambda a: dense.cholesky(a, precision="bf16x3"),
    "gemm": lambda a, b: dense.matmul(a, b, "bf16x3"),
}


def readings(spec: dict, seed: int, devices, what: str = "program",
             n: int | None = None) -> list:
    """The compared numbers of the answers to the cell's distinct problems
    for one seed, from the program or from the control."""
    config = dict(spec["config"], **({"n": n} if n else {}))
    traffic = spec["traffic"]
    entry_mod = run.load_module("entries", traffic["entry"])
    routine = LOWER[traffic["op"]] if what == "control" else None
    entry = entry_mod.Entry(config, traffic, seed, devices, routine=routine)
    answers = {i: entry.call(i) for i in range(traffic.get("inputs", 1))}
    return entry.check(answers)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--what", choices=("program", "control"),
                    default="program")
    ap.add_argument("--n", type=int, default=None)
    args = ap.parse_args(argv)
    spec = run.cell_spec(args.workload, run.load_json(ROOT / "BENCHMARK.json"))
    import jax
    run.enable_cache()
    devices = jax.devices()[:spec["cell"]["chips"]]
    for seed in args.seeds:
        t = time.perf_counter()
        got = readings(spec, seed, devices, args.what, args.n)
        print(json.dumps({"workload": args.workload, "what": args.what,
                          "seed": seed, "n": args.n or spec["config"]["n"],
                          "seconds": time.perf_counter() - t,
                          "compared": {c["name"]: c["value"] for c in got}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
