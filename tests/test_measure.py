"""ELAPS-style measurement layer + calibration + perf-gate tests.

Covers the ISSUE-6 acceptance surface: reps validation, per-rep sample
shape, repetition-controller convergence on synthetic noisy timers, the
calibrate -> register -> JSON -> reload persistence convention (with
corrupt/missing-file fallback), finite model_residual fields in a fast
bench row, and the spread-aware regression gate's pass/fail behavior.
"""
import importlib.util
import itertools
import json
import math
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro import arch
from repro.tune import measure as M
from repro.tune import search

# calibration settings small enough for test budgets (one warm-up + one
# timed rep per micro-bench, tiny operands)
FAST_CAL = dict(gemm_sizes=(16, 32), stream_elems=1 << 16, chain_iters=32,
                reps=1)


# ----------------------------- reps validation ------------------------------

@pytest.mark.parametrize("reps", [0, -1])
def test_measure_wall_time_rejects_nonpositive_reps(reps):
    with pytest.raises(ValueError, match="reps"):
        M.measure_wall_time(lambda: 1.0, reps=reps)
    with pytest.raises(ValueError, match="reps"):
        M.measure(lambda: 1.0, reps=reps)


def test_controller_validates_budgets():
    with pytest.raises(ValueError, match="min_reps"):
        M.repetition_controller(lambda: 1.0, min_reps=0)
    with pytest.raises(ValueError, match="max_reps"):
        M.repetition_controller(lambda: 1.0, min_reps=4, max_reps=2)
    with pytest.raises(ValueError, match="rel_spread"):
        M.repetition_controller(lambda: 1.0, rel_spread=-0.1)
    with pytest.raises(ValueError, match="sample"):
        M.Measurement.from_samples([])


def test_search_reexports_shared_helper():
    # the historical import paths must stay the one shared timing helper
    assert search.measure_wall_time is M.measure_wall_time
    assert search._timeit is M.measure_wall_time


# ------------------------- per-rep samples + stats --------------------------

def test_measure_pinned_reps_sample_shape():
    m = M.measure(lambda x: x * 2.0, jnp.float32(3.0), reps=4)
    assert m.reps == 4 and len(m.samples) == 4
    assert all(s > 0 for s in m.samples)
    assert m.seconds_median == pytest.approx(
        float(np.median(np.asarray(m.samples))))
    assert m.seconds_spread >= 0
    assert set(m.row_fields()) == {"seconds_median", "seconds_spread", "reps"}
    blob = json.loads(json.dumps(m.to_json()))
    assert blob["reps"] == 4 and len(blob["samples"]) == 4


def test_median_robust_to_outlier():
    m = M.Measurement.from_samples([1.0, 1.0, 1.0, 100.0])
    assert m.seconds_median == 1.0
    assert m.seconds_min == 1.0
    assert m.seconds_mean > 1.0


# ------------------------ controller convergence ----------------------------

def test_controller_converges_on_quiet_timer():
    quiet = itertools.cycle([1.00, 1.01, 0.99])
    m = M.repetition_controller(lambda: next(quiet), min_reps=3, max_reps=50,
                                rel_spread=0.10)
    assert m.converged
    assert m.reps == 3                       # stopped at the first check
    assert m.seconds_median == pytest.approx(1.0)


def test_controller_exhausts_budget_on_noisy_timer():
    noisy = itertools.cycle([0.1, 1.0, 10.0])
    m = M.repetition_controller(lambda: next(noisy), min_reps=3, max_reps=7,
                                rel_spread=0.01)
    assert not m.converged
    assert m.reps == 7                       # the rep budget, not beyond
    assert m.seconds_median == pytest.approx(1.0)


def test_controller_keeps_sampling_until_spread_tightens():
    # loud at first, then quiet: the controller must ride past min_reps
    samples = iter([1.0, 5.0, 0.2] + [1.0] * 40)
    m = M.repetition_controller(lambda: next(samples), min_reps=3,
                                max_reps=40, rel_spread=0.05)
    assert m.converged
    assert 3 < m.reps < 40


# -------------------------- model residual ----------------------------------

def test_model_residual_semantics():
    assert M.model_residual(1.0, 1.0) == 0.0
    assert M.model_residual(0.5, 1.0) == pytest.approx(0.5)
    assert M.model_residual(2.0, 1.0) == pytest.approx(-1.0)
    assert math.isnan(M.model_residual(1.0, 0.0))
    assert math.isnan(M.model_residual(1.0, float("nan")))


# ---------------------- calibration + persistence ---------------------------

def test_calibrate_registers_and_fits(tmp_path):
    res = arch.calibrate_full(**FAST_CAL)
    m = res.machine
    assert m.name == "calibrated-cpu"
    assert arch.get("calibrated-cpu") == m
    assert m.pe.peak_flops > 0 and m.memory.hbm_bw > 0
    assert all(d >= 1 for d in m.fpu.depths.values())
    # the fitted machine must explain its own best-rung evidence within the
    # documented tolerance (docs/benchmarking.md)
    assert res.best_residual("gemm") <= arch.CALIBRATION_TOLERANCE
    assert res.best_residual("stream") <= arch.CALIBRATION_TOLERANCE
    for row in res.report:
        assert math.isfinite(row["model_residual"])
        assert row["reps"] >= 1 and row["seconds_median"] > 0
    # report + spec both JSON-serializable
    json.dumps(res.to_json())


def test_calibrate_roundtrip_persistence(tmp_path):
    p = str(tmp_path / "calibrated.json")
    spec = arch.calibrate(path=p, **FAST_CAL)
    assert os.path.exists(p)
    assert arch.MachineSpec.load(p) == spec
    # reload path: no re-measurement, same registered spec
    again = arch.load_or_calibrate(p, **FAST_CAL)
    assert again == spec
    assert arch.get("calibrated-cpu") == spec


def test_load_or_calibrate_missing_file_calibrates(tmp_path):
    p = str(tmp_path / "nope" / "calibrated.json")
    os.makedirs(os.path.dirname(p))
    spec = arch.load_or_calibrate(p, **FAST_CAL)
    assert spec.name == "calibrated-cpu"
    assert os.path.exists(p)                 # fallback wrote the file
    assert arch.MachineSpec.load(p) == spec


def test_load_or_calibrate_corrupt_file_falls_back(tmp_path):
    p = str(tmp_path / "calibrated.json")
    with open(p, "w") as f:
        f.write("{not json")
    spec = arch.load_or_calibrate(p, **FAST_CAL)
    assert spec.name == "calibrated-cpu"
    assert arch.MachineSpec.load(p) == spec  # rewritten, valid again


def test_calibrate_rejects_foreign_backend():
    with pytest.raises(ValueError, match="backend"):
        arch.calibrate(backend="tpu", **FAST_CAL)


@pytest.mark.parametrize("kind,base", [("cpu", "cpu-host"),
                                       ("TPU v5 lite", "tpu-like"),
                                       ("TPU v4", None)])
def test_calibration_base_follows_device_kind(kind, base):
    """The base spec comes from the device kind; a device with no entry
    is refused rather than priced as some other chip."""
    from repro.arch.calibrate import BASE_BY_DEVICE_KIND, fit_machine
    assert BASE_BY_DEVICE_KIND.get(kind) == base
    if base is None:
        with pytest.raises(ValueError, match="device kind"):
            fit_machine({"backend": "tpu", "device_kind": kind})


# ----------------------- bench rows carry the fields ------------------------

def test_fast_bench_rows_have_measurement_fields(tmp_path):
    from benchmarks import bench_blas

    out = str(tmp_path / "blas.json")
    bench_blas.run(lambda *a: None, fast=True, out=out)
    with open(out) as f:
        doc = json.load(f)
    assert doc["rows"], "fast bench produced no rows"
    # fused_chain pricing rows are modeled-only by design (never timed;
    # the regression gate skips them) - everything else must be measured
    measured = [r for r in doc["rows"] if not r.get("modeled_only")]
    assert measured
    for row in measured:
        for field in ("seconds_median", "seconds_spread", "reps",
                      "model_residual"):
            assert field in row, f"{row['op']} row lacks {field}"
        assert row["reps"] >= 1
        assert row["seconds_median"] > 0
        assert math.isfinite(row["model_residual"])
        assert row["seconds_median"] == pytest.approx(
            row["seconds_per_call"])
    # the per-op resolution fix: factorization rows name their own op
    # (gemm_bias_act resolves through the fused-chain op)
    fact_rows = [r for r in measured if r["op"] != "gemm"]
    assert fact_rows
    for row in fact_rows:
        want = "gemm+epilogue" if row["op"] == "gemm_bias_act" else row["op"]
        assert row["resolution"]["for_op"] == want


# --------------------------- regression gate --------------------------------

def _load_gate():
    path = os.path.join(os.path.dirname(__file__), os.pardir, "scripts",
                        "check_perf_regression.py")
    spec = importlib.util.spec_from_file_location("check_perf_regression",
                                                  os.path.abspath(path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _doc(med, spread=0.05):
    return {"rows": [{"op": "gemm", "n": 64, "dtype": "float32",
                      "seconds_median": med, "seconds_spread": spread,
                      "reps": 5}]}


def test_gate_passes_identical_and_fails_degraded():
    gate = _load_gate()
    base = _doc(1.0)
    ok, checked, _ = gate.compare(base, _doc(1.0), tol=0.2, spread_k=3.0)
    assert checked == 1 and not ok
    fails, _, _ = gate.compare(base, _doc(10.0), tol=0.2, spread_k=3.0)
    assert len(fails) == 1
    # inside the spread-widened allowance: 1 * (1 + .2 + 3*.05) = 1.35
    ok2, _, _ = gate.compare(base, _doc(1.30), tol=0.2, spread_k=3.0)
    assert not ok2
    fails2, _, _ = gate.compare(base, _doc(1.40), tol=0.2, spread_k=3.0)
    assert len(fails2) == 1


def test_gate_self_test_on_committed_trajectory():
    gate = _load_gate()
    committed = os.path.join(os.path.dirname(__file__), os.pardir,
                             "benchmarks", "out", "blas.json")
    assert gate.self_test(os.path.abspath(committed), tol=0.5,
                          spread_k=3.0) == 0


def test_gate_skips_rows_without_controller_fields():
    gate = _load_gate()
    legacy = {"rows": [{"op": "gemm", "n": 64, "seconds_per_call": 1.0}]}
    fails, checked, skipped = gate.compare(legacy, legacy, tol=0.5,
                                           spread_k=3.0)
    assert not fails and checked == 0 and skipped == 1
