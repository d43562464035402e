"""Process-global monotonic counters: always-on runtime accounting.

Counters are the cheap half of :mod:`repro.obs`: unconditional integer
increments (one dict ``+=`` per occurrence, no contextvar lookup), so the
layers that matter can account every occurrence - even when no trace is
active. A :class:`repro.obs.Trace` snapshots the counter table at start
and again at finish, so each trace reports the *delta* it covered.

The names below are the frozen vocabulary the rest of the repo
increments (``scripts/check_api_surface.py`` guards it; add new names
there in the same PR):

``dispatch.resolve``
    One per :func:`repro.tune.dispatch.resolve` call - every kernel-shaped
    BLAS/LAPACK core resolves exactly once per (traced) call.
``dispatch.registry_hit`` / ``dispatch.registry_miss``
    Tuned-policy resolutions that found / missed a registry config
    (miss == ``source="fallback-model"``).
``registry.load``
    :meth:`repro.tune.registry.Registry.load` invocations.
``registry.missing_fallback``
    Loads that found no file (cold start - normal, not warned).
``registry.corrupt_fallback``
    Loads that found an unreadable/schema-incompatible file (warned once
    per path via ``warnings.warn``).
``kernel.launch``
    Pallas kernel launches funneled through the dispatch GEMM executor.
``kernel.ssd_scan`` / ``kernel.flash_attention``
    Model calls that :mod:`repro.kernels.ops` sends to the Pallas SSD
    scan or flash-attention kernel (not to the jnp oracle). Counted at
    trace time: one per call site traced into a program, not per
    execution (a scanned stack traces one period of its layers).
``trsm.inverted_blocks``
    Diagonal blocks that :func:`repro.blas.level3.trsm`'s blocked path
    inverts (all of a call's at once, as one batched stack). Counted at
    trace time: a call's block count each time it is traced.
``collective.hops`` / ``collective.bytes``
    Ring-broadcast ppermute hops and on-wire bytes (counted at trace
    time: a jit-cached SUMMA call re-runs the collective without
    re-tracing, so these count *distinct traced schedules*, not
    executions).
"""
from __future__ import annotations

from typing import Dict

# the frozen counter vocabulary (see module docstring); incrementing an
# unlisted name is allowed (prototyping) but the API-surface guard keeps
# this tuple in sync with what shipping code uses
KNOWN_COUNTERS = (
    "dispatch.resolve",
    "dispatch.registry_hit",
    "dispatch.registry_miss",
    "registry.load",
    "registry.missing_fallback",
    "registry.corrupt_fallback",
    "kernel.launch",
    "kernel.ssd_scan",
    "kernel.flash_attention",
    "trsm.inverted_blocks",
    "collective.hops",
    "collective.bytes",
)

_counts: Dict[str, int] = {}


def inc(name: str, n: int = 1) -> int:
    """Add ``n`` to counter ``name`` (created at 0); returns the new value."""
    v = _counts.get(name, 0) + int(n)
    _counts[name] = v
    return v


def value(name: str) -> int:
    """Current value of ``name`` (0 if never incremented)."""
    return _counts.get(name, 0)


def snapshot() -> Dict[str, int]:
    """Copy of the whole counter table (monotonic; never reset by traces)."""
    return dict(_counts)


def delta(before: Dict[str, int]) -> Dict[str, int]:
    """Counters that moved since ``before`` (a :func:`snapshot`), as
    name -> increment. Names absent from ``before`` count from 0."""
    return {k: v - before.get(k, 0) for k, v in _counts.items()
            if v != before.get(k, 0)}


def reset() -> None:
    """Zero every counter (tests only - counters are process-monotonic;
    shipping code should diff :func:`snapshot`\\ s instead)."""
    _counts.clear()
