"""Readings that a model cell's limits of `correct` are set from.

    python3 bench/zoo_control.py --workload <cell> --seeds 1 2 3 \
        [--what program fp8 x_dt] [--prompts 1]

For each seed it makes the cell's weights and prompts as a run does and
sends each of ``--prompts`` prompts (seed + j mod ``inputs``, j < prompts)
through the entry's own check, with the configuration's limits:

- ``program``: the program's prefill and decode steps against the
  reference -- the lower readings, which must come out correct;
- ``fp8``: the reference computed one precision below the configuration's
  bfloat16 (every product's operands and the residual stream rounded to
  float8 e4m3) in the program's place -- an upper reading, which must come
  out not correct;
- ``x_dt``: the reference with Mamba-2's skip term taken as D (x dt) in
  place of D x -- the other upper reading, likewise.

The reference's own rows are computed once per prompt and shared by every
``--what``. One line of JSON per seed, and a ``[control]`` line on
standard error per reading with its ``correct``.
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

WHATS = ("program", "fp8", "x_dt")


def readings(spec: dict, seed: int, devices, whats=("program",),
             prompts: int | None = None) -> list:
    """Each prompt's check under each of ``whats``, for one seed:
    [{what, prompt, correct, compared: {name: {value, limit}}}]."""
    traffic = spec["traffic"]
    n = traffic["inputs"]
    entry = run.load_module("entries", traffic["entry"]).Entry(
        spec["config"], traffic, seed, devices)
    out = []
    for j in range(prompts or n):
        i = (seed + j) % n
        for what in whats:
            entry.control = None if what == "program" else what
            answer = entry.call(i) if what == "program" else None
            compared = entry.check({i: answer})
            ok = all(c["value"] <= c["limit"] for c in compared)
            out.append({"what": what, "prompt": i, "correct": ok,
                        "compared": {c["name"]: {"value": c["value"],
                                                 "limit": c["limit"]}
                                     for c in compared}})
            print(f"[control] seed {seed} prompt {i} {what}: correct: "
                  f"{str(ok).lower()} " + " ".join(
                      f"{c['name']}={c['value']:.6g} (limit {c['limit']})"
                      for c in compared), file=sys.stderr, flush=True)
    entry.control = None
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--what", choices=WHATS, nargs="+", default=["program"])
    ap.add_argument("--prompts", type=int, default=None)
    args = ap.parse_args(argv)
    spec = run.cell_spec(args.workload, run.load_json(ROOT / "BENCHMARK.json"))
    import jax
    run.enable_cache()
    devices = jax.devices()[:spec["cell"]["chips"]]
    for seed in args.seeds:
        t = time.perf_counter()
        got = readings(spec, seed, devices, args.what, args.prompts)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "seconds": time.perf_counter() - t,
                          "readings": got}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
