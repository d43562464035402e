"""The split of device time by driver stage: the span each instruction of
a module compiled on the CPU takes, and the split of synthetic device
events and of a gesv trace recorded on a TPU v5e."""
import gzip
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax import lax

from bench import span_split as ss
from bench import trace_reduce as tr
from repro import obs

DATA = Path(__file__).resolve().parent / "data"
MS = 1e6          # nanoseconds in a millisecond
STAGES = ("getrf.panel", "getrf.swap", "getrf.trailing", "gesv.getrs")


def demo(a, b):
    """A routine with a looped panel stage, a trailing stage and glue
    under the routine's span alone."""
    with obs.span("linalg.demo"):
        with obs.span("demo.panel"):
            a = lax.fori_loop(0, 3, lambda i, c: jnp.sin(c) * 2.0 + c[::-1],
                              a)
        with obs.span("demo.trailing"):
            c = jnp.tril(jnp.exp(a) @ b).T
        return c, c[::-1] + 1.0


@pytest.fixture(scope="module")
def hlo():
    x = jnp.ones((8, 8), jnp.float32)
    return jax.jit(demo).lower(x, x).compile().as_text()


def instructions(hlo_text):
    """{computation: {instruction: line}} and the entry's name."""
    comps, _, _ = ss._parse(hlo_text)
    entry = re.search(r"^ENTRY %?([\w.\-]+)", hlo_text, re.M).group(1)
    return {c: dict(i) for c, i in comps.items()}, entry


def opcode(line):
    return re.search(r" ([\w\-]+)\(", line.split(" = ", 1)[1]).group(1)


def first_operand(line):
    return re.search(r"\(%?([\w.\-]+)", line.split(" = ", 1)[1]).group(1)


def strip(hlo_text, name):
    """The module with one instruction's metadata taken out."""
    return re.sub(r"(%" + re.escape(name) + r" = [^\n]*?), metadata=\{[^}]*\}",
                  r"\1", hlo_text)


@pytest.mark.parametrize("op_name, want", [
    ("jit(f)/linalg.solve/getrf.panel/while/body/closed_call/getrf.swap/"
     "scatter", "getrf.swap"),
    ("jit(f)/linalg.solve/getrf.panel/while/body/mul", "getrf.panel"),
    ("jit(f)/linalg.solve/concatenate", "unstaged"),
    ("jit(f)/jit(tril)/select_n", "unstaged"),
    ("transpose(jvp(f))/dot_general", "unstaged"),
])
def test_stage_is_the_innermost_dotted_segment(op_name, want):
    assert ss.stage_of(op_name) == want


def test_named_instructions_take_their_own_stage(hlo):
    comps, entry = instructions(hlo)
    spans = ss.hlo_spans(hlo)
    for name, line in comps[entry].items():
        m = ss.OP_NAME.search(line)
        if m and "demo." in m.group(1):
            assert spans[name] == ss.stage_of(m.group(1)), line


def test_fusion_without_metadata_takes_its_roots_stage(hlo):
    comps, entry = instructions(hlo)
    (fusion,) = [n for n, l in comps[entry].items()
                 if opcode(l) == "fusion" and "demo.trailing/exp" in l]
    spans = ss.hlo_spans(strip(hlo, fusion))
    assert spans[fusion] == "demo.trailing"
    # its operand comes from the panel: the root, not the operand, decides
    assert spans[first_operand(comps[entry][fusion])] == "demo.panel"


def test_copy_without_metadata_takes_its_producers_stage(hlo):
    comps, entry = instructions(hlo)
    copies = [n for n, l in comps[entry].items()
              if opcode(l) == "copy" and "metadata" not in l
              and "demo.trailing" in comps[entry].get(first_operand(l), "")]
    assert copies
    spans = ss.hlo_spans(hlo)
    for name in copies:
        assert spans[name] == "demo.trailing"


def test_while_body_ops_without_metadata_take_the_whiles_stage(hlo):
    comps, entry = instructions(hlo)
    (loop,) = [l for l in comps[entry].values() if opcode(l) == "while"]
    spans = ss.hlo_spans(hlo)
    bare = [n for key in ("body", "condition")
            for n, l in comps[re.search(key + r"=%?([\w.\-]+)",
                                        loop).group(1)].items()
            if "metadata" not in l]
    assert bare
    for name in bare:
        assert spans[name] == "demo.panel", name


def test_op_under_the_routine_span_alone_is_unstaged(hlo):
    comps, entry = instructions(hlo)
    glue = [n for n, l in comps[entry].items()
            if re.search(r'op_name="jit\(demo\)/linalg\.demo/[a-z_]+"', l)]
    assert glue
    spans = ss.hlo_spans(hlo)
    for name in glue:
        assert spans[name] == "unstaged"
        # though what it reads comes from a stage
        assert spans[first_operand(comps[entry][name])] == "demo.trailing"


def synthetic(hlo_text):
    """One chip, one 20 ms call: every entry instruction that runs, one
    after another, and the while holding three rounds of its body."""
    comps, entry = instructions(hlo_text)
    (loop,) = [n for n, l in comps[entry].items() if opcode(l) == "while"]
    body = comps[re.search(r"body=%?([\w.\-]+)",
                           comps[entry][loop]).group(1)]
    evs, t = [], 0.0
    for name, line in comps[entry].items():
        if opcode(line) in ("parameter", "tuple", "get-tuple-element",
                            "constant"):
            continue
        if name == loop:
            evs.append((t * MS, 6 * MS, f"%{name} = while"))
            for i in range(3):
                for j, b in enumerate(body):
                    evs.append(((t + 2 * i + 0.2 * j) * MS, 0.1 * MS,
                                f"%{b} = x"))
            t += 6
        else:
            evs.append((t * MS, 1 * MS, f"%{name} = x"))
            t += 1
    host = [(0.0, 20 * MS, "bench.call")]
    return {"devices": {0: evs}, "host": host}


def test_split_adds_up_to_the_classes(hlo):
    events = synthetic(hlo)
    r = tr.reduce(events, 1, tr.hlo_classes(hlo))
    s = ss.split(events, 1, ss.hlo_spans(hlo))
    assert sum(s["span_s"].values()) == pytest.approx(
        sum(r["class_s"].values()), rel=1e-9)
    assert sum(s["span_ops"].values()) == r["n_ops"]
    # the loop's own time and its body's rounds are the panel's
    assert s["span_s"]["demo.panel"] >= 0.006 - 1e-12
    assert s["span_s"]["demo.trailing"] > 0
    assert s["span_s"]["unstaged"] > 0


def test_split_without_the_module_is_unstaged(hlo):
    s = ss.split(synthetic(hlo))
    assert set(s["span_s"]) == {"unstaged"}


def test_split_needs_a_window_and_a_device(hlo):
    events = synthetic(hlo)
    with pytest.raises(ValueError):
        ss.split({"devices": events["devices"], "host": []})
    with pytest.raises(ValueError):
        ss.split({"devices": {}, "host": events["host"]})


def recorded(tmp_path, name):
    xplane = tmp_path / f"{name}.xplane.pb"
    xplane.write_bytes(gzip.decompress(
        (DATA / f"{name}.xplane.pb.gz").read_bytes()))
    hlo_text = gzip.decompress(
        (DATA / f"{name}.hlo.txt.gz").read_bytes()).decode()
    return tr.load(str(xplane)), hlo_text


def test_recorded_potrf_reduces_as_before(tmp_path):
    """Recorded before the drivers' spans named the program: all of it is
    unstaged, and the classes read what they read."""
    events, hlo_text = recorded(tmp_path, "potrf.n256")
    r = tr.reduce(events, 1, tr.hlo_classes(hlo_text))
    s = ss.split(events, 1, ss.hlo_spans(hlo_text))
    assert set(s["span_s"]) == {"unstaged"}
    assert s["span_s"]["unstaged"] == pytest.approx(
        sum(r["class_s"].values()), rel=1e-9)


def test_recorded_gesv_splits_by_stage(tmp_path):
    """gesv at n = 512 (four panels), one traced call on a TPU v5e. To
    record it again, run ``run.run_cell``'s traced window on a cell of
    ``lapack-dense-f32`` with ``n`` 512 and keep the ``.xplane.pb`` and
    the entry's ``hlo_text()``, gzipped."""
    events, hlo_text = recorded(tmp_path, "gesv.n512")
    r = tr.reduce(events, 1, tr.hlo_classes(hlo_text))
    s = ss.split(events, 1, ss.hlo_spans(hlo_text))
    for stage in STAGES:
        assert s["span_s"].get(stage, 0) > 0, stage
    assert s["span_s"].get("unstaged", 0) < 0.1 * r["busy_s"]
    assert sum(s["span_s"].values()) == pytest.approx(
        sum(r["class_s"].values()), rel=1e-9)
    assert sum(s["span_ops"].values()) == r["n_ops"]
