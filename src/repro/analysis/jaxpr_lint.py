"""Dtype-flow lint over traced jaxprs (rules DF001-DF004, KL003).

Everything here works on the output of ``jax.make_jaxpr`` - tracing only,
no execution - which is what lets ``analysis.check`` sweep the whole
``repro.linalg`` surface in CI without paying a single kernel launch.
The walker recurses through every higher-order primitive (pjit, scan,
while, cond, shard_map, pallas_call, ...) by structurally discovering
sub-jaxprs in eqn params, tracking whether it is *inside a Pallas kernel
body* - several rules only apply there (KL003) or need the distinction
for messages.
"""
from __future__ import annotations

from typing import Iterator, List, Optional, Sequence, Tuple

import jax.numpy as jnp

from repro.analysis.rules import Finding, make_finding

# primitives that move data or control to the host; none belong in a
# traced BLAS/LAPACK routine body (DF004)
HOST_PRIMITIVES = ("pure_callback", "io_callback", "debug_callback",
                   "callback", "device_put")


def _source_location(eqn) -> Optional[str]:
    """Best-effort user frame of one eqn ("file:line"); None when the
    tracer did not keep source info (private API - degrade, never fail)."""
    try:
        from jax._src import source_info_util
        frame = source_info_util.user_frame(eqn.source_info.traceback)
        if frame is None:
            return None
        return f"{frame.file_name}:{frame.start_line}"
    except Exception:
        return None


def _subjaxprs(value):
    """Yield every (Closed)Jaxpr reachable from one eqn param value."""
    if hasattr(value, "eqns"):                       # a raw Jaxpr
        yield value
    elif hasattr(value, "jaxpr"):                    # a ClosedJaxpr
        yield value.jaxpr
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _subjaxprs(v)


def iter_eqns(jaxpr, in_pallas: bool = False) -> Iterator[Tuple[object, bool]]:
    """Yield (eqn, in_pallas) over a jaxpr and all nested sub-jaxprs."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)           # accept ClosedJaxpr
    for eqn in jaxpr.eqns:
        yield eqn, in_pallas
        inner_pallas = in_pallas or eqn.primitive.name == "pallas_call"
        for name, value in eqn.params.items():
            for sub in _subjaxprs(value):
                yield from iter_eqns(sub, in_pallas=inner_pallas)


def _out_avals(eqn):
    return [v.aval for v in eqn.outvars if hasattr(v, "aval")]


def _in_avals(eqn):
    return [v.aval for v in eqn.invars if hasattr(v, "aval")]


def _is_f64(dtype) -> bool:
    try:
        return jnp.dtype(dtype) == jnp.dtype("float64")
    except TypeError:
        return False


def _is_64bit_int(dtype) -> bool:
    try:
        dt = jnp.dtype(dtype)
    except TypeError:
        return False
    return dt.kind in ("i", "u") and dt.itemsize == 8


def _width(dtype) -> int:
    return jnp.dtype(dtype).itemsize


def lint_dtype_flow(closed_jaxpr, routine: Optional[str] = None,
                    accum_dtype=None) -> List[Finding]:
    """DF001/DF002/DF003/DF004 + KL003 over one traced jaxpr.

    ``closed_jaxpr`` is a ``jax.make_jaxpr`` result; operand dtypes come
    from its ``in_avals``. ``accum_dtype`` is the active context's
    accumulation dtype - an explicit f64 accumulator legitimizes f64
    intermediates over f32 operands (DF001 stands down).
    """
    findings: List[Finding] = []
    in_dtypes = [a.dtype for a in closed_jaxpr.in_avals
                 if hasattr(a, "dtype")]
    f64_inputs = any(_is_f64(d) for d in in_dtypes)
    f64_expected = f64_inputs or (accum_dtype is not None
                                  and _is_f64(accum_dtype))
    # var id -> (source dtype, via dtype) for convert_element_type chains
    convert_origin = {}
    df1 = df3 = kl3 = 0                  # first-hit reporting, total counts
    for eqn, in_pallas in iter_eqns(closed_jaxpr.jaxpr):
        name = eqn.primitive.name
        loc = None
        if name in HOST_PRIMITIVES:
            findings.append(make_finding(
                "DF004", f"host primitive {name!r} in traced body",
                routine=routine, location=_source_location(eqn)))
            continue
        outs = _out_avals(eqn)
        # weak-typed f64 scalars are python-literal artifacts (e.g.
        # jnp.where(c, 1.0, -1.0) under x64); JAX's weak-type promotion
        # cannot let them widen an array result, so only committed
        # (non-weak) float64 intermediates count as silent promotion
        if not f64_expected and any(
                _is_f64(getattr(a, "dtype", None))
                and not getattr(a, "weak_type", False) for a in outs):
            df1 += 1
            if df1 == 1:
                findings.append(make_finding(
                    "DF001",
                    f"float64 intermediate from {name!r} under a non-f64 "
                    "context (operands "
                    f"{[str(d) for d in in_dtypes]})",
                    routine=routine, location=_source_location(eqn)))
        if in_pallas and any(
                _is_64bit_int(getattr(a, "dtype", None)) for a in outs):
            kl3 += 1
            if kl3 == 1:
                findings.append(make_finding(
                    "KL003",
                    f"64-bit integer index dtype from {name!r} inside a "
                    "Pallas kernel body (must stay int32 under x64)",
                    routine=routine, location=_source_location(eqn)))
        if name == "dot_general":
            ins = [getattr(a, "dtype", None) for a in _in_avals(eqn)]
            out = getattr(outs[0], "dtype", None) if outs else None
            if (len(ins) >= 2 and all(_is_f64(d) for d in ins[:2])
                    and out is not None and not _is_f64(out)):
                findings.append(make_finding(
                    "DF002",
                    f"f64 operands accumulate into {out} dot_general "
                    "output (accumulator narrower than operands)",
                    routine=routine, location=_source_location(eqn)))
        if name == "convert_element_type":
            src = _in_avals(eqn)
            dst = outs[0] if outs else None
            if src and dst is not None and hasattr(src[0], "dtype"):
                src_dt, dst_dt = src[0].dtype, dst.dtype
                prior = convert_origin.get(id(eqn.invars[0]))
                if (prior is not None and prior == jnp.dtype(dst_dt)
                        and _width(src_dt) < _width(dst_dt)
                        and jnp.dtype(dst_dt).kind == "f"):
                    df3 += 1
                    if df3 == 1:
                        findings.append(make_finding(
                            "DF003",
                            f"convert round-trip {dst_dt} -> {src_dt} -> "
                            f"{dst_dt} through a narrower dtype",
                            routine=routine, location=_source_location(eqn)))
                for ov in eqn.outvars:
                    convert_origin[id(ov)] = jnp.dtype(src_dt)
    return findings
