"""The chip benchmark: one run of one cell, found by name in BENCHMARK.json.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is a configuration (``bench/configs/<config>.json``) under a traffic
mix (``bench/traffic/<traffic>.json``). The traffic names the entry that
drives the program (``bench/entries/<entry>.py``); each metric is a reader
of its own (``bench/metrics/<metric>.py``). Nothing here names a cell, an
entry or a metric, so a new one is a new file and a new line in
BENCHMARK.json.

A run: set-up (the entry makes its inputs from the seed on the device and
compiles its calls ahead of time, or loads them from the persistent
compilation cache), then a closed-loop window (one
call at a time, each ending on the device, started until ``--seconds``
have passed; the window ends when the last call started in it ends), then
the check of the answers against the plain reference. With ``--trace 1`` a
short window of ``trace_calls`` calls runs under the profiler instead, and
the per-layer metrics are read from its trace.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced), then ``compared``: each number the check compared, with its
limit. The same numbers are the last lines of standard error. Without a
TPU, or with fewer chips than the cell asks for, it exits non-zero and
prints no result.
"""
import time

T_START = time.perf_counter()

import argparse            # noqa: E402
import importlib.util      # noqa: E402
import json                # noqa: E402
import random              # noqa: E402
import shutil              # noqa: E402
import statistics          # noqa: E402
import sys                 # noqa: E402
import tempfile            # noqa: E402
from pathlib import Path   # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# the persistent compilation cache: one fixed directory in the checkout
CACHE_DIR = ROOT / ".jax_cache"


class NoChip(RuntimeError):
    """The machine has no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = BENCH / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_spec(name: str, bench: dict) -> dict:
    """The cell's entry, configuration, traffic and metric readers."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    cell = cells[name]
    config = load_json(BENCH / "configs" / f"{cell['config']}.json")
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")

    def mine(metric):
        return name in metric.get("workloads", [name])
    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)]}


def devices_for(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX found "
                     f"{len(devs)}")
    return devs[:chips]


def enable_cache() -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts the executables built while it is open (``n``): each compile
    and each load from the persistent compilation cache, of which ``hits``
    counts the loads."""

    BUILD = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __enter__(self):
        import jax
        self.n = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_build)
        jax.monitoring.register_event_listener(self._on_hit)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_build)
        jax.monitoring.unregister_event_listener(self._on_hit)

    def _on_build(self, event, secs, **_):
        if event == self.BUILD:
            self.n += 1

    def _on_hit(self, event, **_):
        if event == self.HIT:
            self.hits += 1


class Keeper:
    """Reservoir sample, drawn from the seed, of the window's answers."""

    def __init__(self, k: int, seed: int):
        self.k = k
        self.rng = random.Random(seed)
        self.kept = {}
        self.seen = 0

    def offer(self, i: int, out) -> None:
        self.seen += 1
        if len(self.kept) < self.k:
            self.kept[i] = out
            return
        j = self.rng.randrange(self.seen)
        if j < self.k:
            drop = sorted(self.kept)[j]
            del self.kept[drop]
            self.kept[i] = out


def run_window(entry, seconds: float, keeper: Keeper):
    """Closed loop: calls start while ``seconds`` have not passed; returns
    (latencies, start offsets, window seconds)."""
    lat, starts = [], []
    t0 = time.perf_counter()
    i = 0
    while True:
        ts = time.perf_counter()
        if ts - t0 >= seconds:
            break
        out = entry.call(i)
        lat.append(time.perf_counter() - ts)
        starts.append(ts - t0)
        keeper.offer(i, out)
        i += 1
    return lat, starts, time.perf_counter() - t0


def call_summary(lat: list, starts: list) -> str:
    """The first call, the median and the three slowest (index, start
    offset, seconds): where a window ran long, and when."""
    slow = sorted(range(len(lat)), key=lambda i: -lat[i])[:3]
    return (f"first={lat[0]:.6f} median={statistics.median(lat):.6f} "
            "slowest=" + " ".join(f"#{i}@{starts[i]:.3f}s:{lat[i]:.6f}"
                                  for i in slow))


def run_traced(entry, calls: int, keeper: Keeper, trace_dir: str):
    import jax
    jax.profiler.start_trace(trace_dir)
    try:
        lat, starts = [], []
        t0 = time.perf_counter()
        for i in range(calls):
            ts = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.call", i=i):
                out = entry.call(i)
            lat.append(time.perf_counter() - ts)
            starts.append(ts - t0)
            keeper.offer(i, out)
        window = time.perf_counter() - t0
    finally:
        jax.profiler.stop_trace()
    return lat, starts, window


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        st = d.memory_stats() or {}
        peaks.append(int(st.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def read_metrics(specs, record: dict) -> dict:
    out = {}
    for m in specs:
        v = load_module("metrics", m["name"]).read(record)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def run_cell(spec: dict, seed: int, seconds: float, trace: bool,
             devices, peaks: dict) -> dict:
    """One run of a cell on ``devices``; returns the result object."""
    import jax

    from bench import trace_reduce
    config, traffic = spec["config"], spec["traffic"]
    log(f"imports and chip: {time.perf_counter() - T_START:.3f} s")
    entry_mod = load_module("entries", traffic["entry"])
    keeper = Keeper(traffic.get("checked", 1), seed)
    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        with CompileCounter() as compiles:
            entry = entry_mod.Entry(config, traffic, seed, devices)
            setup_s = time.perf_counter() - T_START
            in_setup = compiles.n
            log(f"setup_s={setup_s} executables_in_setup={in_setup} "
                f"from_cache={compiles.hits}")
            if trace:
                lat, starts, window = run_traced(
                    entry, traffic["trace_calls"], keeper, tdir)
            else:
                lat, starts, window = run_window(entry, seconds, keeper)
            in_window = compiles.n - in_setup
        red = None
        if trace:
            red = trace_reduce.reduce_dir(tdir, len(devices),
                                          entry.hlo_text())
    finally:
        if trace:
            shutil.rmtree(tdir, ignore_errors=True)
    log(f"calls={len(lat)} window_s={window} "
        f"executables_in_window={in_window}")
    log(f"call_s {call_summary(lat, starts)}")
    record = {"setup_s": setup_s, "chips": len(devices), "peak": peaks,
              "work": entry.work, "calls": len(lat), "window_s": window,
              "latencies": lat, "trace": red}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": memory_peak(devices)}
    if trace:
        log(f"trace: busy_s={red['busy_s']} window_s={red['window_s']} "
            f"ops={red['n_ops']} classes={red['class_s']} "
            f"exposed={red['collective_exposed_s']}")
        for name, sec in red["top_ops"]:
            log(f"trace op {sec:.6f} {name[:150]}")
        for name, sec in red["idle_gaps"]:
            log(f"trace gap {sec:.6f} {name[:150]}")
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
    metrics = read_metrics(spec["per_layer"] if trace else spec["end_to_end"],
                           record)

    compared = entry.check(keeper.kept)
    failed = sum(1 for c in compared if not c["value"] <= c["limit"])
    correct = bool(compared) and failed == 0 and in_window == 0
    result = {"correct": correct, "attempted": len(lat), "failed": failed,
              "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = {"device_ops": red["top_ops"][:10],
                               "idle_gaps": red["idle_gaps"][:10]}
    result["compared"] = {c["name"]: {"value": c["value"],
                                      "limit": c["limit"]}
                          for c in compared}
    if in_window:
        log(f"NOT CORRECT: {in_window} executables built inside the window")
    for c in compared:
        log(f"compared {c['name']}={c['value']} limit={c['limit']}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = cell_spec(args.workload, load_json(ROOT / "BENCHMARK.json"))
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    import jax
    try:
        devices = devices_for(spec["cell"]["chips"])
    except NoChip as e:
        log(f"refusing to run: {e}")
        return 2
    peaks = load_json(BENCH / "peaks.json")["devices"].get(
        devices[0].device_kind)
    if peaks is None:
        log(f"no peaks for device kind {devices[0].device_kind!r} in "
            f"bench/peaks.json")
        return 2
    enable_cache()
    log(f"device {devices[0].device_kind} x{len(devices)} "
        f"jax {jax.__version__} cache {CACHE_DIR}")
    result = run_cell(spec, args.seed, args.seconds, bool(args.trace),
                      devices, peaks)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
