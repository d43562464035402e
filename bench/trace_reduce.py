"""Reduce a JAX profiler trace to the benchmark's per-layer numbers.

Input: the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData``. What is used of it:

- each device plane (``/device:TPU:<i>``), line ``XLA Ops``: one event per
  operation run on that device, with its start and duration;
- the host planes: the benchmark's own ``bench.*`` annotations, and
  whatever else the host was doing (spans of every thread).

The window is the span from the first ``bench.call`` annotation's start to
the last one's end. Within it, per device:

- busy: the union of the operations' intervals; idle = window - busy;
- each operation's own time: its duration less that of the operations
  nested in it (a loop's body events lie inside the loop's event);
- each operation's class: ``matmul`` (a dot, a convolution or a Pallas
  kernel, or a fusion that holds one), ``collective`` (all-reduce,
  all-gather, collective-permute, all-to-all, reduce-scatter, send/recv, or
  a fusion that holds one) or ``other``. The class is read from the
  compiled module's HLO text (the trace names each operation by its HLO
  instruction, and gives no category through ``ProfileData``); without it,
  from the event's own text;
- exposed collective time: the part of the collectives' union during which
  no other innermost operation runs on that device.

Device numbers are means over the chips traced. Idle gaps (of a
microsecond or more) are those of the first chip, each named by the
innermost host span that covers its middle.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

COLLECTIVE = re.compile(
    r" (all-reduce|all-gather|collective-permute|all-to-all|reduce-scatter|"
    r"collective-broadcast|send|recv)(-start|-done)?\(")
MATMUL = re.compile(r" (convolution|dot)\(|custom_call_target=\"tpu_custom_call")
INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
HEADER = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+) \(.*\{\s*$")
CALLED = re.compile(r"calls=%?([\w.\-]+)")

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
CLASSES = ("matmul", "collective", "other")


def _own_class(text: str) -> str | None:
    text = text.split(", metadata=")[0]
    if COLLECTIVE.search(text):
        return "collective"
    if MATMUL.search(text):
        return "matmul"
    return None


def hlo_classes(hlo_text: str) -> dict:
    """Instruction name -> class, from a compiled module's HLO text. A
    fusion takes the class of what the computation it calls holds: a
    collective, else a dot, a convolution or a Pallas kernel
    (``tpu_custom_call``)."""
    comps, cur = {}, None
    for line in hlo_text.splitlines():
        m = INSTR.match(line)
        if m and cur is not None:
            comps[cur].append((m.group(1), line))
            continue
        h = HEADER.match(line)
        if h:
            cur = h.group(1)
            comps[cur] = []
    memo = {}

    def of_line(line, depth=0):
        own = _own_class(line)
        if own or depth > 8 or " fusion(" not in line:
            return own or "other"
        found = "other"
        for callee in CALLED.findall(line.split(", metadata=")[0]):
            if callee not in memo:
                memo[callee] = "other"
                got = [of_line(b, depth + 1) for _, b in comps.get(callee, [])]
                memo[callee] = ("collective" if "collective" in got else
                                "matmul" if "matmul" in got else "other")
            if memo[callee] != "other":
                found = memo[callee]
        return found

    return {name: of_line(line) for instrs in comps.values()
            for name, line in instrs}


def op_name(event_name: str) -> str:
    """The instruction name of a device event (``%fusion.12 = ...``)."""
    m = INSTR.match(event_name)
    return m.group(1) if m else event_name


def classify(event_name: str, classes: dict | None = None) -> str:
    """``matmul`` | ``collective`` | ``other`` for one device operation:
    from the compiled HLO where given, else from the event's own text."""
    if classes:
        c = classes.get(op_name(event_name))
        if c is not None:
            return c
    return _own_class(event_name) or "other"


def load(path: str) -> dict:
    """Events of one ``.xplane.pb``: {"devices": {id: [(start_ns, dur_ns,
    name)]}, "host": [(start_ns, dur_ns, name)]}."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            evs = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    evs.append((float(e.start_ns), float(e.duration_ns),
                                e.name))
            devices[int(m.group(1))] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    host.append((float(e.start_ns), float(e.duration_ns),
                                 e.name))
    return {"devices": devices, "host": host}


def union(intervals) -> list:
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list:
    """Merged intervals a minus merged intervals b."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def self_times(evs) -> list:
    """Each event's duration less its nested events', and whether it has
    any (the ``XLA Ops`` line nests a loop's body under the loop); evs as
    (start, end, ...) sorted by start, the longer first."""
    own = [e[1] - e[0] for e in evs]
    parent = [False] * len(evs)
    stack = []
    for i, (s, e, *_) in enumerate(evs):
        while stack and evs[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(e, evs[stack[-1]][1]) - s
            parent[stack[-1]] = True
        stack.append(i)
    return own, parent


def reduce(events: dict, chips: int | None = None, classes=None,
           top: int = 10) -> dict:
    """The per-layer numbers of a trace's window (seconds; see module)."""
    calls = [(s, s + d) for s, d, n in events["host"] if n == "bench.call"]
    if not calls:
        raise ValueError("no bench.call annotation in the trace")
    lo = min(s for s, _ in calls)
    hi = max(e for _, e in calls)
    devs = sorted(events["devices"])[:chips]
    if not devs:
        raise ValueError("no device plane in the trace")

    busy_sum, exposed_sum = 0.0, 0.0
    class_ns = dict.fromkeys(CLASSES, 0.0)
    op_ns = defaultdict(float)
    n_ops = 0
    first_busy = None
    for d in devs:
        evs = sorted((max(s, lo), min(s + dur, hi), n)
                     for s, dur, n in events["devices"][d]
                     if s + dur > lo and s < hi)
        evs.sort(key=lambda e: (e[0], e[0] - e[1]))
        n_ops += len(evs)
        by_class = defaultdict(list)
        for (s, e, n), own, outer in zip(evs, *self_times(evs)):
            cls = classify(n, classes)
            class_ns[cls] += own
            op_ns[n.split(", metadata=")[0][:160]] += own
            if cls == "collective" or not outer:
                by_class[cls].append((s, e))
        busy = union([(s, e) for s, e, _ in evs])
        busy_sum += length(busy)
        coll = union(by_class["collective"])
        compute = union(by_class["matmul"] + by_class["other"])
        exposed_sum += length(subtract(coll, compute))
        if first_busy is None:
            first_busy = busy
    nd = len(devs)

    # gaps of a microsecond or more: shorter ones are the rounding between
    # back-to-back operations' timestamps
    gaps = [g for g in subtract([[lo, hi]], first_busy) if g[1] - g[0] >= 1e3]
    spans = [(s, s + d, n) for s, d, n in events["host"]]
    named = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (s + e) / 2
        cover = [(b - a, n) for a, b, n in spans if a <= mid < b]
        named.append([min(cover)[1] if cover else "no host span",
                      (e - s) / 1e9])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_sum / nd / 1e9,
        "chips": nd,
        "n_ops": n_ops,
        "calls": len(calls),
        "class_s": {k: v / nd / 1e9 for k, v in class_ns.items()},
        "collective_exposed_s": exposed_sum / nd / 1e9,
        "top_ops": [[n, v / nd / 1e9] for n, v in
                    sorted(op_ns.items(), key=lambda x: -x[1])[:top]],
        "idle_gaps": named,
    }


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def reduce_dir(trace_dir: str, chips: int | None = None,
               hlo_text: str | None = None) -> dict:
    classes = hlo_classes(hlo_text) if hlo_text else None
    return reduce(load(find_xplane(trace_dir)), chips, classes)
