"""The harness end to end on the CPU at small sizes: the refusal without a
chip, the result line, and `correct` coming out false when the timed path
is broken underneath."""
import json
import os
import shutil
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from bench import run

ROOT = run.ROOT
SPEC = run.load_json(ROOT / "BENCHMARK.json")
PEAKS = run.load_json(run.BENCH / "peaks.json")["devices"]["TPU v5 lite"]
# the sizes the CPU runs at; the limits are the configurations' own
SMALL_N = {"gesv.n8192": 2048, "potrf.n8192": 2048, "summa.n16384.2x2": 512}


def cpu_env(devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    if devices > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return env


def bench_cmd(*extra):
    return [sys.executable, "bench/run.py", "--workload", "gesv.n8192",
            "--seed", "3000000017", "--seconds", "1", "--trace", "0",
            *extra]


def test_refuses_to_run_without_a_tpu():
    p = subprocess.run(bench_cmd(), cwd=ROOT, env=cpu_env(),
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_refuses_to_run_from_the_benchmark_files_alone(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(run.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = cpu_env()
    env["PYTHONPATH"] = str(tmp_path)
    p = subprocess.run(bench_cmd(), cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_unknown_workload_is_an_error():
    with pytest.raises(SystemExit):
        run.cell_spec("no.such.cell", SPEC)


def test_keeper_samples_from_the_seed():
    def sample(seed, n=50, k=3):
        kp = run.Keeper(k, seed)
        for i in range(n):
            kp.offer(i, f"answer {i}")
        return sorted(kp.kept)
    assert sample(7) == sample(7)
    assert len(sample(7)) == 3 and len(set(sample(7))) == 3
    assert len({tuple(sample(s)) for s in range(20)}) > 1
    assert sample(7, n=2) == [0, 1]


def small_spec(cell):
    spec = run.cell_spec(cell, SPEC)
    spec["config"] = dict(spec["config"], n=SMALL_N[cell])
    return spec


def run_small(cell, seconds=0.5, seed=2 ** 33 + 11):
    spec = small_spec(cell)
    devices = jax.devices()[:spec["cell"]["chips"]]
    return run.run_cell(spec, seed, seconds, False, devices, PEAKS)


@pytest.mark.parametrize("cell", ["gesv.n8192", "potrf.n8192"])
def test_a_run_is_correct_and_prints_the_contract_keys(cell):
    res = run_small(cell)
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "compared"]
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    assert set(res["metrics"]) >= {"setup_s", "time_to_solution_s"}
    assert res["device"]["platform"] == "cpu"
    for c in res["compared"].values():
        assert c["value"] <= c["limit"]


@pytest.mark.parametrize("cell,routine", [("gesv.n8192", "solve"),
                                          ("potrf.n8192", "cholesky")])
def test_an_answer_altered_where_it_is_produced_is_not_correct(
        cell, routine, monkeypatch):
    from repro import linalg
    good = getattr(linalg, routine)

    def altered(*args):
        out = good(*args)               # one entry off by 1% of the largest
        return out.at[0].add(1e-2 * jnp.max(jnp.abs(out)))
    monkeypatch.setattr(linalg, routine, altered)
    res = run_small(cell)
    assert res["correct"] is False and res["failed"] >= 1


def test_a_compile_inside_the_window_is_not_correct(monkeypatch):
    from bench.entries import linalg_call  # noqa: F401  (module by path)
    spec = small_spec("potrf.n8192")
    entry_mod = run.load_module("entries", "linalg_call")
    real = entry_mod.Entry.call

    def call_and_compile(self, i):
        jax.jit(lambda x: x + 1)(jnp.ones(i + 1)).block_until_ready()
        return real(self, i)
    monkeypatch.setattr(run, "load_module", lambda kind, name: (
        _Patched(entry_mod, call_and_compile) if kind == "entries"
        else _load(kind, name)))
    res = run.run_cell(spec, 5, 0.3, False, jax.devices()[:1], PEAKS)
    assert res["correct"] is False


_load = run.load_module


class _Patched:
    def __init__(self, mod, call):
        class Entry(mod.Entry):
            pass
        Entry.call = call
        self.Entry = Entry


SUMMA = textwrap.dedent("""
    import json, sys
    import jax
    from bench import run
    from bench.tests.test_bench_run import PEAKS, small_spec
    spec = small_spec("summa.n16384.2x2")
    devices = jax.devices()[:4]
    out = {"sound": run.run_cell(spec, 12345, 0.5, False, devices, PEAKS)}
    import repro.blas.distributed as dist
    dist.ring_bcast = lambda x, axis, size, owner: x
    out["no_exchange"] = run.run_cell(spec, 12345, 0.5, False, devices,
                                      PEAKS)
    print(json.dumps(out))
""")


def test_summa_is_correct_and_fails_without_the_exchange_between_chips():
    p = subprocess.run([sys.executable, "-c", SUMMA], cwd=ROOT,
                       env=cpu_env(devices=4), capture_output=True,
                       text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["sound"]["correct"] is True
    assert out["sound"]["device"]["count"] == 4
    assert out["no_exchange"]["correct"] is False


def test_call_summary_names_the_slowest_calls_and_when():
    lat = [0.15, 0.14, 0.9, 0.14, 0.2]
    starts = [0.0, 0.15, 0.29, 1.19, 1.33]
    s = run.call_summary(lat, starts)
    assert s.startswith("first=0.150000 median=0.150000 ")
    assert s.endswith("slowest=#2@0.290s:0.900000 #4@1.330s:0.200000 "
                      "#0@0.000s:0.150000")


@pytest.mark.parametrize("cell", ["gesv.n8192", "potrf.n8192"])
def test_the_first_call_after_set_up_compiles_nothing(cell):
    spec = small_spec(cell)
    entry = run.load_module("entries", "linalg_call").Entry(
        spec["config"], spec["traffic"], 2 ** 31 + 3, jax.devices()[:1])
    with run.CompileCounter() as compiles:
        entry.call(0)
        entry.call(1)
    assert compiles.n == 0


@pytest.mark.parametrize("op,flops", [
    ("solve", 2 / 3 * 1000 ** 3 + 2 * 1000 ** 2),
    ("cholesky", 1000 ** 3 / 3),
    ("gemm", 2 * 1000 ** 3)])
def test_nominal_flops_are_lapack_and_blas_counts(op, flops):
    mod = run.load_module("entries", "linalg_call")
    assert mod.nominal_flops(op, 1000) == pytest.approx(flops, rel=1e-15)


def test_seeds_past_32_bits_give_their_own_inputs():
    mod = run.load_module("entries", "linalg_call")

    def draw(seed):
        return float(jax.random.normal(mod._seed_key(seed), ()))
    seeds = [5, 2 ** 32 + 5, 2 ** 33 + 5, 2 ** 31 + 5]
    assert len({draw(s) for s in seeds}) == len(seeds)
    assert draw(2 ** 33 + 5) == draw(2 ** 33 + 5)


def test_compile_counter_counts_builds_and_cache_loads_apart():
    x = jnp.ones(7)
    with run.CompileCounter() as c:
        jax.jit(lambda x: x * 3 + 1)(x).block_until_ready()
        jax.monitoring.record_event(run.CompileCounter.HIT)
    assert c.n == 1 and c.hits == 1
    jax.monitoring.record_event(run.CompileCounter.HIT)
    assert c.hits == 1
