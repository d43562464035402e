"""The metric readers' arithmetic on made-up run records."""
import json
from pathlib import Path

import pytest

from bench import run

SPEC = json.loads((run.ROOT / "BENCHMARK.json").read_text())
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def read(name, record):
    return run.load_module("metrics", name).read(record)


def window_record(lat, window):
    return {"setup_s": 12.5, "calls": len(lat), "window_s": window,
            "latencies": lat, "chips": 1, "peak": PEAK,
            "work": {"flops": 1e12}}


def traced_record(chips=4, calls=8):
    trace = {"window_s": 2.0, "busy_s": 1.5, "calls": calls,
             "class_s": {"matmul": 0.8, "collective": 0.4, "other": 0.3},
             "collective_exposed_s": 0.16}
    return {"calls": calls, "window_s": 2.0, "latencies": [0.25] * calls,
            "chips": chips, "peak": PEAK, "work": {"flops": 2 * 16384 ** 3},
            "trace": trace}


def test_end_to_end_readers():
    lat = [0.1] * 95 + [0.2] * 5
    r = window_record(lat, 10.5)
    assert read("setup_s", r) == 12.5
    assert read("time_to_solution_s", r) == pytest.approx(0.105)
    assert read("p95_call_s", r) == pytest.approx(0.105)
    lat = [i / 100 for i in range(1, 101)]
    assert read("p95_call_s", window_record(lat, 50.5)) \
        == pytest.approx(0.9505)


def test_time_to_solution_is_the_window_over_its_calls():
    """Not a median: a window of 3 calls of 14 s that ran 42.6 s."""
    r = window_record([14.0, 14.3, 14.3], 42.6)
    assert read("time_to_solution_s", r) == pytest.approx(14.2)
    assert read("p95_call_s", r) is None          # too few calls for a tail


def test_per_layer_readers():
    r = traced_record()
    assert read("panel_s.linalg", r) == pytest.approx(0.3 / 8)
    assert read("matmul_s.linalg", r) == pytest.approx(0.1)
    flops_per_chip = 2 * 16384 ** 3 / 4
    assert read("matmul_roofline.linalg", r) == pytest.approx(
        100 * flops_per_chip / 0.1 / 197e12)
    assert read("mfu.linalg", r) == pytest.approx(
        100 * 2 * 16384 ** 3 * 8 / 2.0 / (4 * 197e12))
    assert read("idle_share.linalg", r) == pytest.approx(25.0)
    assert read("collective_exposed_s.summa", r) == pytest.approx(0.02)


def test_readers_return_nothing_where_there_is_nothing_to_read():
    r = window_record([0.1] * 30, 3.0)
    for m in SPEC["per_layer"]:
        assert read(m["name"], r) is None, m["name"]
    t = traced_record()
    t["trace"]["class_s"] = {"matmul": 0.0, "collective": 0.0,
                             "other": 1.5}
    assert read("matmul_roofline.linalg", t) is None
    assert read("matmul_s.linalg", t) is None
    assert read("collective_exposed_s.summa", t) is None
    # a traced run reports no end-to-end time
    assert read("time_to_solution_s", t) is None


def test_every_metric_has_a_reader_that_loads():
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert callable(run.load_module("metrics", m["name"]).read)
