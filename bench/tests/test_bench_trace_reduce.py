"""The reduction from a profiler trace to per-layer numbers, on synthetic
events and on a small trace recorded on a TPU v5e."""
import gzip
from pathlib import Path

import pytest

from bench import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"
MS = 1e6          # nanoseconds in a millisecond


def ev(start_ms, dur_ms, name):
    return (start_ms * MS, dur_ms * MS, name)


def test_union_subtract_length():
    u = tr.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert u == [[0, 3], [5, 8]]
    assert tr.length(u) == 6
    assert tr.subtract([[0, 10]], u) == [[3, 5], [8, 10]]
    assert tr.subtract([[0, 3]], [[0, 3]]) == []
    assert tr.subtract([[2, 6]], [[0, 1], [3, 4], [5, 9]]) == [[2, 3], [4, 5]]


def test_self_times_take_nested_events_out_of_their_parent():
    evs = [(0, 10, "while"), (1, 3, "a"), (4, 8, "b"), (5, 6, "c")]
    own, outer = tr.self_times(evs)
    assert own == [4, 2, 3, 1]
    assert outer == [True, False, True, False]


HLO = """\
HloModule jit_f, entry_computation_layout={()->f32[]}

%fused_computation.1 (p0: f32[8,8], p1: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8]{1,0} parameter(0)
  %p1 = f32[8,8]{1,0} parameter(1)
  ROOT %convolution.3 = f32[8,8]{1,0} convolution(f32[8,8]{1,0} %p0, f32[8,8]{1,0} %p1), dim_labels=bf_io->bf
}

%fused_computation.2 (p0: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8]{1,0} parameter(0)
  ROOT %add.1 = f32[8,8]{1,0} add(f32[8,8]{1,0} %p0, f32[8,8]{1,0} %p0)
}

ENTRY %main.9 (a: f32[8,8], b: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8]{1,0} parameter(0)
  %b = f32[8,8]{1,0} parameter(1)
  %fusion.1 = f32[8,8]{1,0} fusion(f32[8,8]{1,0} %a, f32[8,8]{1,0} %b), kind=kOutput, calls=%fused_computation.1
  %fusion.2 = f32[8,8]{1,0} fusion(f32[8,8]{1,0} %fusion.1), kind=kLoop, calls=%fused_computation.2
  %collective-permute-start.1 = (f32[8,8]{1,0}, f32[8,8]{1,0}) collective-permute-start(f32[8,8]{1,0} %fusion.2), source_target_pairs={{0,1},{1,0}}
  %collective-permute-done.1 = f32[8,8]{1,0} collective-permute-done((f32[8,8]{1,0}, f32[8,8]{1,0}) %collective-permute-start.1)
  %custom-call.4 = f32[8,8]{1,0} custom-call(f32[8,8]{1,0} %a), custom_call_target="tpu_custom_call"
  ROOT %dot.5 = f32[8,8]{1,0} dot(f32[8,8]{1,0} %custom-call.4, f32[8,8]{1,0} %b), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""


def test_hlo_classes_follow_fusions_into_their_computations():
    c = tr.hlo_classes(HLO)
    assert c["fusion.1"] == "matmul"
    assert c["fusion.2"] == "other"
    assert c["collective-permute-start.1"] == "collective"
    assert c["collective-permute-done.1"] == "collective"
    assert c["custom-call.4"] == "matmul"
    assert c["dot.5"] == "matmul"
    assert tr.classify("%fusion.1 = f32[8,8] fusion(...)", c) == "matmul"
    # without the module: only what the event's own text says
    assert tr.classify("%fusion.1 = f32[8,8] fusion(...)") == "other"
    assert tr.classify("%all-reduce.2 = f32[] all-reduce(f32[] %x)") \
        == "collective"


def synthetic():
    """Two chips; a 20 ms window of two calls."""
    classes = {"fusion.1": "matmul", "fusion.2": "other",
               "cp.1": "collective", "while.1": "other"}
    dev0 = [ev(0, 4, "%fusion.1 = x"), ev(4, 2, "%fusion.2 = x"),
            ev(6, 3, "%cp.1 = x"), ev(7, 1, "%fusion.1 = x"),
            ev(12, 6, "%while.1 = x"), ev(13, 2, "%fusion.1 = x")]
    dev1 = [ev(0, 10, "%fusion.1 = x"), ev(10, 4, "%cp.1 = x")]
    host = [ev(0, 9, "bench.call"), ev(10, 10, "bench.call"),
            ev(9, 3, "host.prepare"), ev(18, 2, "host.sync"),
            ev(0, 20, "python")]
    return {"devices": {0: dev0, 1: dev1}, "host": host}, classes


def test_reduce_busy_idle_classes_and_exposed_collectives():
    events, classes = synthetic()
    r = tr.reduce(events, 2, classes)
    assert r["window_s"] == pytest.approx(0.020)
    assert r["calls"] == 2
    # chip 0 busy [0, 9] + [12, 18] = 15 ms; chip 1 busy 14 ms
    assert r["busy_s"] == pytest.approx(0.0145)
    # matmul: chip 0 4 + 1 + 2, chip 1 10 -> 8.5 ms a chip
    assert r["class_s"]["matmul"] == pytest.approx(0.0085)
    # collective: 3 - 1 nested and 4 ms; other: 2 + (6 - 2 nested) on chip 0
    assert r["class_s"]["collective"] == pytest.approx(0.003)
    assert r["class_s"]["other"] == pytest.approx(0.003)
    assert sum(r["class_s"].values()) == pytest.approx(r["busy_s"])
    # exposed collective: chip 0 [6,7] + [8,9] = 2 ms, chip 1 4 ms
    assert r["collective_exposed_s"] == pytest.approx(0.003)
    top = dict(r["top_ops"])
    assert top["%fusion.1 = x"] == pytest.approx(0.0085)


def test_idle_gaps_are_named_by_the_innermost_host_span():
    events, classes = synthetic()
    r = tr.reduce(events, 2, classes)
    gaps = {name: sec for name, sec in r["idle_gaps"]}
    assert gaps["host.prepare"] == pytest.approx(0.003)
    assert gaps["host.sync"] == pytest.approx(0.002)
    assert sum(gaps.values()) == pytest.approx(0.020 - 0.015)


def test_reduce_needs_a_window_and_a_device():
    events, _ = synthetic()
    with pytest.raises(ValueError):
        tr.reduce({"devices": events["devices"], "host": []})
    with pytest.raises(ValueError):
        tr.reduce({"devices": {}, "host": events["host"]})


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """potrf at n = 256, two traced calls on one TPU v5e: the profile and
    the compiled module's HLO text. To record them again, run the traced
    window of ``run.run_cell`` with ``jax.profiler.start_trace(dir)``, then
    gzip ``dir``'s ``*.xplane.pb`` and the entry's ``hlo_text()``."""
    xplane = tmp_path_factory.mktemp("trace") / "potrf.xplane.pb"
    xplane.write_bytes(gzip.decompress(
        (DATA / "potrf.n256.xplane.pb.gz").read_bytes()))
    hlo = gzip.decompress((DATA / "potrf.n256.hlo.txt.gz").read_bytes())
    return tr.load(str(xplane)), tr.hlo_classes(hlo.decode())


def test_recorded_trace_loads(recorded):
    events, classes = recorded
    assert list(events["devices"]) == [0]
    assert sum(n == "bench.call" for _, _, n in events["host"]) == 2
    assert any(c == "matmul" for c in classes.values())
    # every operation the chip ran is an instruction of the module
    every = {tr.op_name(n) for _, _, n in events["devices"][0]}
    assert every <= set(classes)


def test_recorded_trace_reduces_to_sane_numbers(recorded):
    events, classes = recorded
    r = tr.reduce(events, 1, classes)
    assert r["calls"] == 2 and r["n_ops"] > 1000
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["class_s"]["matmul"] > 0 and r["class_s"]["other"] > 0
    assert r["class_s"]["collective"] == 0
    assert r["collective_exposed_s"] == 0
    assert sum(r["class_s"].values()) == pytest.approx(r["busy_s"],
                                                       rel=1e-6)
    assert len(r["top_ops"]) == 10 and r["idle_gaps"]
