"""Split a traced window's device time by the program's driver stages.

``repro.obs.span`` writes its name into the ``op_name`` metadata of every
operation traced inside it (``jax.named_scope``), e.g.
``jit(<lambda>)/linalg.solve/getrf.panel/while/body/getrf.swap/scatter``.
A driver stage is named ``<routine>.<stage>``: lower case, one dot.
JAX's own name-stack segments (``jit(...)``, ``while``, ``body``,
``closed_call``, primitive names) never have that form.

Each instruction of the compiled module gets one span, read from the HLO
text:

- the innermost stage segment of its ``op_name``; an ``op_name`` with
  none (only a routine span, ``linalg.*``, or no span at all) makes it
  ``unstaged``;
- where it has no ``op_name``, in order: the span of the root of the
  computation it calls (a fusion); of its first operand's producer,
  followed at most ``STEPS`` steps (XLA's copies); of the instruction
  that calls the computation holding it (a ``while`` body or condition
  takes its ``while``'s span); otherwise ``unstaged``.

Operations that XLA fuses across two stages go to the stage of the
fusion's root.

``split`` then sums each device operation's own time in the window by its
span, exactly as ``trace_reduce.reduce`` sums it by class, so the spans'
seconds add up to the classes' seconds.
"""
from __future__ import annotations

import re
from collections import defaultdict

from bench import trace_reduce as tr

UNSTAGED = "unstaged"
STEPS = 4
STAGE = re.compile(r"[a-z][a-z0-9_]*\.[a-z][a-z0-9_]*")
OP_NAME = re.compile(r'op_name="([^"]*)"')
CALLEE = re.compile(
    r"\b(?:calls|to_apply|body|condition)=%?([\w.\-]+)"
    r"|\bbranch_computations=\{([^}]*)\}")
ATTRS = re.compile(r", (?:calls|to_apply|body|condition|"
                   r"branch_computations|metadata|backend_config)=")
REF = re.compile(r"%([\w.\-]+)")


def stage_of(op_name: str) -> str:
    """The innermost ``<routine>.<stage>`` segment of an ``op_name``
    (``linalg.*`` is a routine, not a stage), else ``unstaged``."""
    for seg in reversed(op_name.split("/")):
        if STAGE.fullmatch(seg) and not seg.startswith("linalg."):
            return seg
    return UNSTAGED


def _parse(hlo_text: str):
    """{computation: [(instruction, line)]}, each computation's root, and
    {computation: (calling computation, calling instruction)}."""
    comps, roots, callers, cur = {}, {}, {}, None
    for line in hlo_text.splitlines():
        m = tr.INSTR.match(line)
        if m and cur is not None:
            comps[cur].append((m.group(1), line))
            if line.lstrip().startswith("ROOT "):
                roots[cur] = m.group(1)
            for one, many in CALLEE.findall(line.split(", metadata=")[0]):
                for callee in [one] if one else REF.findall(many):
                    callers.setdefault(callee, (cur, m.group(1)))
            continue
        h = tr.HEADER.match(line)
        if h:
            cur = h.group(1)
            comps[cur] = []
    return comps, roots, callers


def hlo_spans(hlo_text: str) -> dict:
    """Instruction name -> span (see module), for every instruction of a
    compiled module's HLO text."""
    comps, roots, callers = _parse(hlo_text)
    lines = {c: dict(instrs) for c, instrs in comps.items()}
    memo, active = {}, set()

    def first_operand(line):
        body = line.split(" = ", 1)[1]
        refs = REF.findall(ATTRS.split(body)[0])
        return refs[0] if refs else None

    def of(comp, name, steps):
        key = (comp, name)
        if key in memo:
            return memo[key]
        if key in active or name not in lines.get(comp, {}):
            return None
        line = lines[comp][name]
        m = OP_NAME.search(line)
        if m:
            return stage_of(m.group(1))
        active.add(key)
        try:
            found = None
            callee = CALLEE.search(line.split(", metadata=")[0])
            if callee and callee.group(1) in roots and " fusion(" in line:
                found = of(callee.group(1), roots[callee.group(1)], steps)
            if found is None and steps > 0:
                operand = first_operand(line)
                if operand:
                    found = of(comp, operand, steps - 1)
            if found is None and comp in callers:
                found = of(*callers[comp], STEPS)
            return found
        finally:
            active.discard(key)

    out = {}
    for comp, instrs in comps.items():
        for name, _ in instrs:
            span = of(comp, name, STEPS)
            memo[(comp, name)] = out[name] = span or UNSTAGED
    return out


def split(events: dict, chips: int | None = None,
          spans: dict | None = None) -> dict:
    """``span_s``: span -> own device seconds in the window, mean over the
    chips; ``span_ops``: span -> device operations in the window, summed
    over the chips (they add up to ``reduce``'s ``n_ops``). The window,
    the chips and each operation's own time are ``trace_reduce.reduce``'s.
    """
    calls = [(s, s + d) for s, d, n in events["host"] if n == "bench.call"]
    if not calls:
        raise ValueError("no bench.call annotation in the trace")
    lo = min(s for s, _ in calls)
    hi = max(e for _, e in calls)
    devs = sorted(events["devices"])[:chips]
    if not devs:
        raise ValueError("no device plane in the trace")
    spans = spans or {}
    span_ns, span_ops = defaultdict(float), defaultdict(int)
    for d in devs:
        evs = sorted((max(s, lo), min(s + dur, hi), n)
                     for s, dur, n in events["devices"][d]
                     if s + dur > lo and s < hi)
        evs.sort(key=lambda e: (e[0], e[0] - e[1]))
        own, _ = tr.self_times(evs)
        for (_, _, n), t in zip(evs, own):
            span = spans.get(tr.op_name(n), UNSTAGED)
            span_ns[span] += t
            span_ops[span] += 1
    nd = len(devs)
    return {"span_s": {k: v / nd / 1e9 for k, v in span_ns.items()},
            "span_ops": dict(span_ops)}
