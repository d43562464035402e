"""The control of `correct`: the plain reference in the program's place,
at three bfloat16 passes instead of float32, fails the limits that the
program meets -- here at sizes a test run holds."""
import json
import subprocess
import sys
import textwrap

import jax
import pytest

from bench import control, run
from bench.tests.test_bench_run import SMALL_N, cpu_env

SPEC = run.load_json(run.ROOT / "BENCHMARK.json")


def readings(cell, what, seed):
    spec = run.cell_spec(cell, SPEC)
    devices = jax.devices()[:spec["cell"]["chips"]]
    return control.readings(spec, seed, devices, what, n=SMALL_N[cell])


@pytest.mark.parametrize("cell", ["gesv.n8192", "potrf.n8192"])
@pytest.mark.parametrize("seed", [21, 2 ** 32 + 22])
def test_program_meets_and_control_fails_the_limits(cell, seed):
    prog = readings(cell, "program", seed)
    ctrl = readings(cell, "control", seed)
    assert prog and all(c["value"] <= c["limit"] for c in prog), prog
    assert any(c["value"] > c["limit"] for c in ctrl), ctrl


GEMM = textwrap.dedent("""
    import json
    import jax
    from bench.tests.test_bench_control import readings
    print(json.dumps({w: readings("summa.n16384.2x2", w, 31)
                      for w in ("program", "control")}))
""")


def test_gemm_control_fails_the_limit():
    p = subprocess.run([sys.executable, "-c", GEMM], cwd=run.ROOT,
                       env=cpu_env(devices=4), capture_output=True,
                       text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert all(c["value"] <= c["limit"] for c in out["program"])
    assert any(c["value"] > c["limit"] for c in out["control"])
