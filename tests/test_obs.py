"""repro.obs: tracer semantics, routine threading, exporters, counters.

Four contracts under test:

1. **Numerics invariance**: tracing never changes results - traced /
   untraced / ``obs=False``-suppressed runs of the same routine are
   bitwise identical.
2. **Threading**: routines traced under ``linalg.use`` produce nested
   spans (routine -> panel/trailing) whose resolved provenance agrees
   with a direct :func:`repro.tune.dispatch.resolve` call; the mesh leg
   runs in a subprocess (8 forced host devices, pattern of
   ``tests/test_distributed_blas.py``) and validates per-hop collective
   bytes plus the Chrome artifact end-to-end.
3. **Export round-trip**: the Chrome trace survives ``json.loads`` with
   monotonic timestamps; the JSON-lines form round-trips the frozen
   :data:`repro.obs.EVENT_FIELDS` schema.
4. **Graceful is not silent**: a corrupt registry file warns exactly
   once per path and fires ``registry.corrupt_fallback`` (satellite of
   ISSUE 7).
"""
import json
import os
import subprocess
import sys
import textwrap
import warnings

import jax.numpy as jnp
import numpy as np
import pytest

from repro import linalg, obs
from repro import tune


def _mk(rng, shape):
    return jnp.asarray(rng.normal(size=shape).astype(np.float32))


# ------------------------------ tracer core ---------------------------------

def test_span_nesting_and_ids():
    with obs.trace("t") as tr:
        with obs.span("outer", cat="a") as so:
            with obs.span("inner", cat="b", k=1):
                pass
            obs.event("tick", cat="c")
        assert so.annotate(extra=2) is so
    assert not obs.enabled()
    # children (and instants) land before their parent closes
    assert [e.name for e in tr.events] == ["inner", "tick", "outer"]
    inner, tick, outer = tr.events
    assert inner.parent == outer.id
    assert tick.parent == outer.id
    assert tick.t_end is None                       # instant
    assert outer.attrs["extra"] == 2
    assert inner.t_start >= outer.t_start
    assert inner.t_end <= outer.t_end


def test_disabled_path_is_noop():
    assert not obs.enabled()
    assert obs.current_trace() is None
    assert obs.event("x") is None
    assert obs.annotate(a=1) is False
    with obs.span("x") as sp:                       # usable as a with-block
        assert sp is obs.NOOP_SPAN
        assert sp.annotate(a=1) is sp
        assert obs.annotate(a=1) is False           # nothing was opened


def test_roofline_annotation_prices_flops():
    from repro import arch
    with obs.trace("t") as tr:
        with obs.span("work", cat="k", flops=10 ** 9, bytes=10 ** 6):
            pass
    (sp,) = tr.events
    mach = arch.current_machine()
    assert sp.attrs["machine"] == mach.name
    want = max(10 ** 9 / mach.pe.peak_flops,
               10 ** 6 / mach.memory.hbm_bw)
    assert sp.attrs["modeled_s"] == pytest.approx(want)
    assert sp.attrs["fraction_of_modeled_peak"] > 0
    wall = sp.attrs["wall_s"]
    assert sp.attrs["model_residual"] == pytest.approx(
        tune.model_residual(want, wall))


def test_counters_delta():
    before = obs.counters_snapshot()
    obs.inc("kernel.launch")
    obs.inc("collective.bytes", 128)
    d = obs.counters_delta(before)
    assert d["kernel.launch"] == 1
    assert d["collective.bytes"] == 128
    assert obs.counter("kernel.launch") >= 1
    for name in ("kernel.launch", "collective.bytes"):
        assert name in obs.KNOWN_COUNTERS


# --------------------- numerics invariance (bitwise) ------------------------

def test_tracing_is_bitwise_invisible(rng):
    a = _mk(rng, (96, 64))
    b = _mk(rng, (64, 48))
    with linalg.use(policy="model"):
        q0, r0 = linalg.qr(a, block=16)
        c0 = linalg.gemm(a, b)
    with obs.trace("t") as tr:
        with linalg.use(policy="model"):
            q1, r1 = linalg.qr(a, block=16)
            c1 = linalg.gemm(a, b)
        with linalg.use(policy="model", obs=False):   # suppressed capture
            q2, r2 = linalg.qr(a, block=16)
            c2 = linalg.gemm(a, b)
    for x0, x1, x2 in ((q0, q1, q2), (r0, r1, r2), (c0, c1, c2)):
        assert np.asarray(x0).tobytes() == np.asarray(x1).tobytes()
        assert np.asarray(x0).tobytes() == np.asarray(x2).tobytes()
    # the obs=False block contributed nothing to the trace
    assert len(tr.spans(name="linalg.qr")) == 1
    assert len(tr.spans(name="linalg.gemm")) == 1


# --------------- spans name the compiled program's operations --------------

def _op_names(fn, *args):
    import jax
    import re
    txt = jax.jit(fn).lower(*args).compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', txt))


@pytest.mark.parametrize("routine, stages", [
    ("solve", ("linalg.solve", "getrf.panel", "getrf.swap",
               "getrf.trailing", "gesv.getrs")),
    ("cholesky", ("linalg.cholesky", "potrf.panel", "potrf.trailing")),
])
def test_compiled_program_names_the_driver_stages(rng, routine, stages):
    """With no trace capturing, every driver stage's name is a segment of
    the ``op_name`` of the operations it traced, e.g.
    ``.../getrf.panel/while/body/closed_call/getrf.swap/scatter``."""
    assert not obs.enabled()
    a = _mk(rng, (48, 48))
    if routine == "solve":
        names = _op_names(lambda a, b: linalg.solve(a, b, block=16),
                          a, _mk(rng, (48,)))
    else:
        spd = a @ a.T / 48 + jnp.eye(48)
        names = _op_names(lambda a: linalg.cholesky(a, block=16), spd)
    segments = {seg for name in names for seg in name.split("/")}
    for stage in stages:
        assert stage in segments, (stage, sorted(segments))


def _largest_writes(hlo_text, stage_pattern):
    """{instruction: elements it writes} for the compiled instructions
    whose ``op_name`` matches ``stage_pattern``. An in-place update
    (``dynamic-update-slice``, ``scatter``, or a fusion rooted in one)
    writes its update operand, not its whole result; any other
    instruction writes its result (a tuple: its largest element)."""
    import re
    instr = re.compile(r"\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s"
                       r"([a-z][\w\-]*)\((.*)$")
    update_operand = {"dynamic-update-slice": 1, "scatter": 2}

    def elements(typ):
        return max([int(np.prod([int(d) for d in dims.split(",") if d]))
                    for dims in re.findall(r"\w+\[([\d,]*)\]", typ)] or [0])

    types, roots, ops, comp = {}, {}, [], None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            comp = line.replace("ENTRY ", "").split()[0].lstrip("%")
        m = instr.match(line)
        if not m:
            continue
        root, name, typ, opcode, rest = m.groups()
        operands = re.findall(r"%([\w.\-]+)", rest.split("),")[0])
        types[name] = typ
        if root:
            roots[comp] = (opcode, operands)
        callee = re.search(r"\bcalls=%?([\w.\-]+)", rest)
        op_name = re.search(r'op_name="([^"]*)"', rest)
        if op_name and re.search(stage_pattern, op_name.group(1)):
            ops.append((name, typ, opcode, operands,
                        callee and callee.group(1)))
    out = {}
    for name, typ, opcode, operands, callee in ops:
        if opcode == "fusion" and roots.get(callee, ("",))[0] \
                in update_operand:
            opcode, operands = roots[callee]
        if opcode in update_operand:
            typ = types[operands[update_operand[opcode]]]
        out[name] = elements(typ)
    return out


def test_lu_panel_and_swaps_never_pass_over_the_whole_matrix(rng):
    """The LU panel is factored on the (n - j0) x nb panel alone and its
    row interchanges touch 2 nb rows: no operation under ``getrf.panel``
    or ``getrf.swap`` writes n x n elements or more."""
    import jax
    n = 512
    txt = jax.jit(lambda a, b: linalg.solve(a, b, block=64)).lower(
        _mk(rng, (n, n)), _mk(rng, (n,))).compile().as_text()
    writes = _largest_writes(txt, r"getrf\.(panel|swap)")
    assert writes, "no operation named by the LU panel's spans"
    assert max(writes.values()) >= 64 * 64       # the panel is there ...
    big = {k: v for k, v in writes.items() if v >= n * n}
    assert not big, big                          # ... the matrix is not


def test_spans_traced_under_jit_are_marked_and_not_priced(rng):
    import jax
    a = _mk(rng, (48, 48))
    spd = a @ a.T / 48 + jnp.eye(48)
    with obs.trace("jit") as tr:
        jax.jit(lambda a: linalg.cholesky(a, block=16))(spd)
        linalg.cholesky(spd, block=16)              # eager: priced
    jitted, eager = (tr.spans(name="potrf.panel")[:3],
                     tr.spans(name="potrf.panel")[3:])
    assert len(jitted) == len(eager) == 3
    for sp in jitted:
        assert sp.attrs["traced"] is True
        assert sp.t_end >= sp.t_start                # tracing's wall time
        assert "achieved_gflops" not in sp.attrs
        assert "fraction_of_modeled_peak" not in sp.attrs
    for sp in eager:
        assert "traced" not in sp.attrs
        assert sp.attrs["fraction_of_modeled_peak"] > 0


def test_spans_land_on_the_profiler_host_timeline(rng, tmp_path):
    """An eager call's ``linalg.<op>`` span and its driver stages are host
    events of a JAX profiler trace, captured or not."""
    import glob
    import jax
    a = _mk(rng, (48, 48))
    spd = a @ a.T / 48 + jnp.eye(48)
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.block_until_ready(linalg.cholesky(spd, block=16))
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    host = {e.name for p in pd.planes if p.name.startswith("/host:")
            for line in p.lines for e in line.events}
    assert {"linalg.cholesky", "potrf.panel", "potrf.trailing"} <= host


# ------------------- routine threading (no-mesh leg) ------------------------

def test_traced_qr_has_nested_panel_spans(rng):
    a = _mk(rng, (96, 64))
    with obs.trace("qr") as tr:
        with linalg.use(policy="model"):
            linalg.qr(a, block=16)
    (qr_span,) = tr.spans(name="linalg.qr")
    assert qr_span.cat == "routine"
    assert qr_span.attrs["shape"] == [96, 64]
    assert qr_span.attrs["dtype"] == "float32"
    assert qr_span.attrs["flops"] > 0
    panels = tr.spans(cat="panel")
    trailing = tr.spans(cat="trailing")
    assert len(panels) == 4 and len(trailing) == 3   # kmax=64, nb=16
    for sp in panels + trailing:
        assert sp.parent == qr_span.id
        assert sp.attrs["flops"] > 0
    # resolve provenance events nest under the trailing spans
    resolves = tr.spans(name="tune.resolve")
    assert resolves and all(e.cat == "resolve" for e in resolves)
    trailing_ids = {sp.id for sp in trailing}
    assert all(e.parent in trailing_ids for e in resolves)


def test_resolve_provenance_agrees_with_dispatcher(rng):
    a = _mk(rng, (64, 32))
    b = _mk(rng, (32, 48))
    with obs.trace("gemm") as tr:
        with linalg.use(policy="model"):
            linalg.gemm(a, b)
    (ev,) = tr.spans(name="tune.resolve")
    direct = tune.resolve("gemm", (64, 48, 32), jnp.float32,
                          policy="model").describe()
    for key in ("op", "policy", "source", "use_pallas", "machine", "config"):
        assert ev.attrs[key] == direct[key], key
    assert ev.attrs["source"] == "model"


def test_context_obs_field_routes_capture(rng):
    a = _mk(rng, (32, 24))
    tr = obs.Trace("explicit")
    with linalg.use(policy="model", obs=tr):
        linalg.gemm(a.T, a)
    tr.finish()
    assert tr.spans(name="linalg.gemm")
    assert tr.counters.get("dispatch.resolve", 0) >= 1
    ctx = linalg.ExecutionContext(obs=tr)
    assert ctx.describe()["obs"] == "explicit"
    assert linalg.ExecutionContext(obs=False).describe()["obs"] is False
    with pytest.raises(ValueError):
        linalg.ExecutionContext(obs="not-a-trace")


def test_measure_annotates_enclosing_span():
    f = jnp.sin
    x = jnp.ones((128,), jnp.float32)
    with obs.trace("m") as tr:
        with obs.span("timed", cat="bench"):
            m = tune.measure_op(f, x, reps=2)
    (sp,) = tr.spans(name="timed")
    assert sp.attrs["measure_reps"] == m.reps == 2
    assert sp.attrs["measure_seconds_median"] == pytest.approx(
        m.seconds_median)
    # with no open span the summary lands as an instant event instead
    with obs.trace("m2") as tr2:
        tune.measure_op(f, x, reps=1)
    assert tr2.spans(name="tune.measure")


# ------------------------------ exporters -----------------------------------

def _small_trace(rng):
    a = _mk(rng, (96, 64))
    with obs.trace("export") as tr:
        with linalg.use(policy="model"):
            linalg.qr(a, block=16)
            linalg.gemm(a.T, a)
    return tr


def test_chrome_export_round_trips(rng, tmp_path):
    tr = _small_trace(rng)
    path = str(tmp_path / "trace.json")
    obs.save_chrome_trace(tr, path)
    with open(path) as f:
        blob = json.loads(f.read())
    assert blob["otherData"]["schema_version"] == obs.SCHEMA_VERSION
    assert blob["otherData"]["trace_name"] == "export"
    assert blob["otherData"]["counters"]["dispatch.resolve"] >= 1
    evs = blob["traceEvents"]
    assert len(evs) == len(tr.events)
    ts = [e["ts"] for e in evs]
    assert ts == sorted(ts)                          # monotonic start times
    for e in evs:
        assert e["ph"] in ("X", "i")
        if e["ph"] == "X":
            assert e["dur"] >= 0
    # provenance survives the export
    assert any(e["name"] == "tune.resolve" and "source" in e["args"]
               for e in evs)


def test_jsonl_export_round_trips(rng, tmp_path):
    tr = _small_trace(rng)
    path = str(tmp_path / "trace.jsonl")
    obs.save_jsonl(tr, path)
    lines = [json.loads(l) for l in open(path)]
    assert lines[0]["kind"] == "header"
    assert lines[0]["schema_version"] == obs.SCHEMA_VERSION
    assert lines[-1]["kind"] == "counters"
    events = [l for l in lines if l["kind"] == "event"]
    assert len(events) == len(tr.events)
    for e in events:
        assert set(e) == set(obs.EVENT_FIELDS) | {"kind"}
    starts = [e["t_start"] for e in events]
    assert starts == sorted(starts)


def test_trace_report_validates_both_formats(rng, tmp_path):
    tr = _small_trace(rng)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = os.path.join(root, "scripts", "trace_report.py")
    chrome = str(tmp_path / "t.json")
    jsonl = str(tmp_path / "t.jsonl")
    obs.save_chrome_trace(tr, chrome)
    obs.save_jsonl(tr, jsonl)
    for p in (chrome, jsonl):
        r = subprocess.run([sys.executable, script, "--validate", p],
                           capture_output=True, text=True)
        assert r.returncode == 0, f"{r.stdout}\n{r.stderr}"
        assert "trace OK" in r.stdout
    # a tampered schema version must fail validation
    blob = json.loads(open(chrome).read())
    blob["otherData"]["schema_version"] = 999
    bad = str(tmp_path / "bad.json")
    with open(bad, "w") as f:
        json.dump(blob, f)
    r = subprocess.run([sys.executable, script, "--validate", bad],
                       capture_output=True, text=True)
    assert r.returncode == 1


def test_trace_report_rejects_malformed_roofline_attrs(rng, tmp_path):
    """PR 9 satellite: --validate cross-checks span flops/bytes against
    the schema types and rejects non-finite / negative
    fraction_of_modeled_peak in either exporter format."""
    tr = _small_trace(rng)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = os.path.join(root, "scripts", "trace_report.py")
    chrome = str(tmp_path / "t.json")
    obs.save_chrome_trace(tr, chrome)
    for poison, needle in (({"flops": -5}, "flops"),
                           ({"bytes": "many"}, "bytes"),
                           ({"fraction_of_modeled_peak": float("nan")},
                            "fraction_of_modeled_peak"),
                           ({"fraction_of_modeled_peak": -0.25},
                            "fraction_of_modeled_peak")):
        blob = json.loads(open(chrome).read())
        spans = [e for e in blob["traceEvents"] if e.get("ph") == "X"]
        spans[0]["args"].update(poison)
        # python json writes/reads NaN/Infinity literals (allow_nan)
        bad = str(tmp_path / "bad_attr.json")
        with open(bad, "w") as f:
            json.dump(blob, f)
        r = subprocess.run([sys.executable, script, "--validate", bad],
                           capture_output=True, text=True)
        assert r.returncode == 1, f"{poison} passed validation"
        assert needle in r.stdout
    # jsonl leg: same rejection through the attrs dict
    jsonl = str(tmp_path / "t.jsonl")
    obs.save_jsonl(tr, jsonl)
    lines = open(jsonl).read().splitlines()
    recs = [json.loads(l) for l in lines]
    ev = next(r for r in recs if r["kind"] == "event")
    ev["attrs"]["flops"] = float("inf")
    bad = str(tmp_path / "bad.jsonl")
    with open(bad, "w") as f:
        f.write("\n".join(json.dumps(r) for r in recs))
    r = subprocess.run([sys.executable, script, "--validate", bad],
                       capture_output=True, text=True)
    assert r.returncode == 1 and "flops" in r.stdout


def test_summary_mentions_routines(rng):
    tr = _small_trace(rng)
    text = obs.summary(tr)
    assert "linalg.qr" in text
    assert "dispatch.resolve" in text


# -------------------- corrupt-registry fallback (satellite) -----------------

def test_corrupt_registry_warns_once_and_counts(tmp_path):
    from repro.tune.registry import Registry
    path = str(tmp_path / "corrupt.json")
    with open(path, "w") as f:
        f.write("{ not json")
    before = obs.counters_snapshot()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        reg = Registry(path=path)
        assert reg.load() == 0
        assert reg.load_error is not None
        reg2 = Registry(path=path)                  # second load, same path
        assert reg2.load() == 0
    ours = [x for x in w if issubclass(x.category, RuntimeWarning)
            and "falling back to model-planned" in str(x.message)]
    assert len(ours) == 1, "corrupt-registry warning must fire exactly once"
    d = obs.counters_delta(before)
    assert d["registry.corrupt_fallback"] == 2      # counted every load
    assert d["registry.load"] == 2
    # numerics still resolve (model fallback), provenance says so
    res = tune.resolve("gemm", (32, 32, 32), jnp.float32, policy="tuned",
                       registry=reg)
    assert res.source == "fallback-model"


def test_missing_registry_counts_cold_start(tmp_path):
    from repro.tune.registry import Registry
    before = obs.counters_snapshot()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        reg = Registry(path=str(tmp_path / "never-written.json"))
        assert reg.load() == 0
    assert not [x for x in w if issubclass(x.category, RuntimeWarning)]
    d = obs.counters_delta(before)
    assert d["registry.missing_fallback"] == 1
    assert d.get("registry.corrupt_fallback", 0) == 0


# ------------------------- serve smoke (satellite) --------------------------

def test_serve_batch_traces_requests():
    from repro.launch.serve import Request, serve_batch
    from repro.models import model_zoo as zoo
    from repro.models.config import ModelConfig
    cfg = ModelConfig("t", "dense", n_layers=1, d_model=32, n_heads=2,
                      n_kv=1, d_ff=64, vocab=64, dtype="float32")
    import jax
    params = zoo.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    reqs = [Request(rng.integers(0, 64, size=4).astype(np.int32), 2)
            for _ in range(2)]
    tr = obs.Trace("serve")
    outs, stats = serve_batch(params, cfg, reqs, max_len=16,
                              context=linalg.ExecutionContext(obs=tr))
    tr.finish()
    assert len(outs) == 2 and all(len(o) == 6 for o in outs)
    (batch,) = tr.spans(name="serve.batch")
    assert batch.attrs["requests"] == 2
    assert tr.spans(name="serve.prefill")
    (dec,) = tr.spans(name="serve.decode")
    assert dec.attrs["steps"] == stats["steps"]
    assert len(tr.spans(name="serve.request")) == 2


# ---------------------- mesh acceptance leg (subprocess) --------------------

_ENV = dict(os.environ,
            XLA_FLAGS="--xla_force_host_platform_device_count=8",
            PYTHONPATH="src")


def test_traced_mesh_trace_has_collectives_and_provenance(tmp_path):
    """The ISSUE-7 acceptance criterion: traced qr + gemm under a (2, 2)
    mesh yields a Chrome trace with resolved-config provenance, per-hop
    collective bytes, and fraction-of-modeled-peak - and the artifact
    passes ``trace_report.py --validate``."""
    out = str(tmp_path / "mesh_trace.json")
    code = textwrap.dedent(f"""
    import json
    import numpy as np
    import jax.numpy as jnp
    from repro import linalg, obs

    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((96, 64)).astype(np.float32))
    before = obs.counters_snapshot()
    with obs.trace("mesh") as tr:
        with linalg.use(policy="model", mesh=(2, 2)):
            linalg.qr(a, block=16)
            linalg.gemm(a, a.T)
    obs.save_chrome_trace(tr, {out!r})

    assert tr.spans(name="linalg.qr") and tr.spans(name="linalg.gemm")
    # distributed gemm rode pdgemm -> ring_bcast: per-hop bytes recorded
    colls = tr.spans(name="collective.ring_bcast")
    assert colls, "no ring_bcast events under the (2, 2) mesh"
    for ev in colls:
        assert ev.attrs["hops"] >= 1
        assert ev.attrs["per_hop_bytes"] > 0
        assert ev.attrs["wire_bytes"] == \\
            ev.attrs["per_hop_bytes"] * ev.attrs["hops"]
    assert tr.counters.get("collective.hops", 0) >= 1
    assert tr.counters.get("collective.bytes", 0) > 0
    # provenance + roofline on the routine spans
    assert any(e.attrs.get("source") for e in tr.spans(name="tune.resolve"))
    assert any("fraction_of_modeled_peak" in e.attrs
               for e in tr.spans(cat="routine"))
    print("mesh trace OK")
    """)
    r = subprocess.run([sys.executable, "-c", code], env=_ENV,
                       capture_output=True, text=True, timeout=900,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "mesh trace OK" in r.stdout
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    rv = subprocess.run([sys.executable,
                         os.path.join(root, "scripts", "trace_report.py"),
                         "--validate", out],
                        capture_output=True, text=True)
    assert rv.returncode == 0, f"{rv.stdout}\n{rv.stderr}"
    blob = json.loads(open(out).read())
    names = {e["name"] for e in blob["traceEvents"]}
    assert "collective.ring_bcast" in names
    assert "tune.resolve" in names
