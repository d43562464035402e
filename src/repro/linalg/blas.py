"""dtype-generic BLAS front-end, routed by the active ExecutionContext.

Every routine here:

* accepts float32/float64 operands (bfloat16 storage where the kernel
  path supports it) and an explicit ``dtype=`` cast,
* resolves policy / registry / accumulation dtype from the
  active :class:`repro.linalg.ExecutionContext` (``context=`` overrides
  per call),
* routes to the distributed backend when the context carries a mesh
  (``gemm`` -> SUMMA :func:`repro.blas.distributed.pdgemm`, ``trsm`` ->
  :func:`repro.blas.distributed.pdtrsm`, ``syrk`` through ``pdgemm``);
  routines without a mesh backend (vector ops, ``gemv``, batched GEMM)
  run locally under any context,
* supports a leading batch axis on the matrix routines (3-D operands are
  vmapped over the local path).

The numeric cores live in :mod:`repro.blas.level1`/``level2``/``level3``;
this layer only resolves the context and casts dtypes, so a call under the
default context is bit-identical to the core it calls.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro import arch as _arch
from repro import obs as _obs
from repro.blas import level1 as _l1
from repro.blas import level2 as _l2
from repro.blas import level3 as _l3
from repro.linalg.context import (current, resolved_accum_dtype,
                                  resolved_machine, resolved_mesh,
                                  resolved_obs, resolved_policy,
                                  resolved_registry)


def _routine(op, info=None):
    """Routine wrapper: machine scoping + one obs span per public call.

    The resolved ``ctx.machine`` becomes the ambient
    :func:`repro.arch.machine_scope` for the whole call, so every nested
    planner/tuner resolution - the trailing updates inside a blocked
    factorization included - sees it without kwarg threading. A ``None``
    machine inherits whatever scope (or the process default) is already
    active.

    The body always runs under the ``linalg.<op>`` span, so every
    operation of the call carries the routine's name in the compiled
    program and the call shows on the profiler's host timeline. When a
    trace is capturing (the ambient :func:`repro.obs.trace` scope, or an
    explicit ``ctx.obs``), the span is captured and annotated by
    ``info(*args, **kw)`` - shapes, dtype, flop/byte counts - which the
    span prices against the ambient machine at close
    (``docs/observability.md``). With no capture active the wrapper
    takes a dict-free early return into the numeric body. An annotation
    failure never breaks the call (``info`` runs under ``except``).

    The body traces under ``jax.default_matmul_precision("highest")``: a
    float32 routine multiplies in float32. XLA on a TPU otherwise rounds
    float32 matmul operands to one bfloat16 pass, which no LAPACK-style
    residual survives; bfloat16 operands are exact either way, and CPU
    numerics are unchanged.
    """
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, context=None, **kw):
            with jax.default_matmul_precision("highest"):
                return body(*args, context=context, **kw)

        def body(*args, context=None, **kw):
            ctx = current(context)
            mach = resolved_machine(ctx)
            tr = resolved_obs(ctx)
            if tr is None and not _obs.enabled():
                # fast path: no capture anywhere
                with _obs.span("linalg." + op):
                    if mach is None:
                        return fn(*args, context=ctx, **kw)
                    with _arch.machine_scope(mach):
                        return fn(*args, context=ctx, **kw)
            with contextlib.ExitStack() as st:
                if mach is not None:
                    st.enter_context(_arch.machine_scope(mach))
                if tr is None:
                    # ctx.obs=False under an ambient trace: mask capture
                    # for the whole body (nested spans included)
                    st.enter_context(_obs.capture(None))
                elif tr is not _obs.current_trace():
                    st.enter_context(_obs.capture(tr))
                sp = st.enter_context(_obs.span("linalg." + op,
                                                cat="routine"))
                if tr is not None and info is not None:
                    try:
                        sp.annotate(**info(*args, **kw))
                    except Exception:
                        pass
                return fn(*args, context=ctx, **kw)
        # the static analyzer's drift oracle: repro.analysis.check reads
        # the routine name and its flops/bytes annotation fn off the
        # wrapper to compare against jaxpr_census-derived counts (CM001/2)
        wrapper._analysis_op = op
        wrapper._analysis_info = info
        return wrapper
    return deco


# --------------------- span annotation (traced calls only) ------------------

def _shape(x):
    return tuple(int(d) for d in getattr(x, "shape", ()))


def _nbytes(*arrays) -> int:
    """Total operand bytes (arrays without shape/dtype - e.g. python
    scalars - count 0); works on jit tracers (shape/dtype are static)."""
    total = 0
    for x in arrays:
        shp = getattr(x, "shape", None)
        dt = getattr(x, "dtype", None)
        if shp is None or dt is None:
            continue
        n = 1
        for d in shp:
            n *= int(d)
        total += n * jnp.dtype(dt).itemsize
    return total


def _dtype_name(*arrays) -> str:
    return jnp.result_type(*[a for a in arrays if a is not None]).name


def _gemm_info(a, b, c=None, alpha=1.0, beta=0.0, transa=False, transb=False,
               **kw):
    sa, sb = _shape(a), _shape(b)
    batch = sa[0] if len(sa) == 3 else 1
    m = sa[-1] if transa else sa[-2]
    k = sa[-2] if transa else sa[-1]
    n = sb[-2] if transb else sb[-1]
    out_itemsize = jnp.dtype(jnp.result_type(
        *[v for v in (a, b, c) if v is not None])).itemsize
    return {"shape": ([m, n, k] if batch == 1 else [batch, m, n, k]),
            "dtype": _dtype_name(a, b, c),
            "flops": 2 * batch * m * n * k,
            "bytes": _nbytes(a, b, c) + batch * m * n * out_itemsize}


def _gemm_bias_act_info(a, b, bias=None, epilogue="none", **kw):
    sa, sb = _shape(a), _shape(b)
    batch = sa[0] if len(sa) == 3 else 1
    m, k, n = sa[-2], sa[-1], sb[-1]
    out_itemsize = jnp.dtype(jnp.result_type(a, b)).itemsize
    return {"shape": ([m, n, k] if batch == 1 else [batch, m, n, k]),
            "dtype": _dtype_name(a, b), "epilogue": epilogue,
            "flops": 2 * batch * m * n * k + batch * m * n,
            "bytes": _nbytes(a, b, bias) + batch * m * n * out_itemsize}


def _syrk_info(a, c=None, alpha=1.0, beta=0.0, lower=True, trans=False, **kw):
    sa = _shape(a)
    batch = sa[0] if len(sa) == 3 else 1
    n = sa[-1] if trans else sa[-2]
    k = sa[-2] if trans else sa[-1]
    return {"shape": ([n, k] if batch == 1 else [batch, n, k]),
            "dtype": _dtype_name(a, c), "flops": 2 * batch * n * n * k,
            "bytes": _nbytes(a, c)}


def _trsm_info(a, b, lower=True, unit_diag=False, left=True, block=None,
               **kw):
    sa, sb = _shape(a), _shape(b)
    batch = sa[0] if len(sa) == 3 else 1
    n = sa[-1]
    nrhs = sb[-1] if len(sb) >= 2 else 1
    return {"shape": ([n, nrhs] if batch == 1 else [batch, n, nrhs]),
            "dtype": _dtype_name(a, b), "flops": batch * n * n * nrhs,
            "bytes": _nbytes(a, b)}


def _gemv_info(a, x, y=None, alpha=1.0, beta=0.0, trans=False, **kw):
    sa = _shape(a)
    batch = sa[0] if len(sa) == 3 else 1
    m, n = sa[-2], sa[-1]
    return {"shape": ([m, n] if batch == 1 else [batch, m, n]),
            "dtype": _dtype_name(a, x, y), "flops": 2 * batch * m * n,
            "bytes": _nbytes(a, x, y)}


def _ger_info(alpha, x, y, a, **kw):
    m, n = _shape(a)[-2:]
    return {"shape": [m, n], "dtype": _dtype_name(x, y, a),
            "flops": 2 * m * n, "bytes": _nbytes(x, y, a)}


def _trsv_info(a, b, **kw):
    n = _shape(a)[-1]
    return {"shape": [n], "dtype": _dtype_name(a, b), "flops": n * n,
            "bytes": _nbytes(a, b)}


def _vec_info(flop_per_elem):
    def info(*args, **kw):
        arrs = [a for a in args if getattr(a, "shape", None) is not None
                or isinstance(a, (list, tuple))]
        x = arrs[0] if arrs else args[0]
        x = jnp.asarray(x) if getattr(x, "shape", None) is None else x
        n = 1
        for d in _shape(x):
            n *= d
        return {"shape": list(_shape(x)), "dtype": _dtype_name(x),
                "flops": flop_per_elem * n, "bytes": _nbytes(*args)}
    return info


def _dtypes(ctx, dtype, *arrays):
    """(storage dtype, compute dtype) for this call, or (None, None).

    (None, None) - the passthrough fast path - means no explicit ``dtype``
    and no context accumulation dtype: operands reach the numeric core
    untouched, so results are bitwise what the core produces. Otherwise:
    storage = the explicit ``dtype`` or the result type of *all* operands
    (accumulands like ``c``/``y`` participate in the promotion, as they
    would in plain jnp); compute = the context's accumulation dtype
    (upcast) or the storage dtype.
    """
    acc = resolved_accum_dtype(ctx)
    if dtype is None and acc is None:
        return None, None
    arrs = [a for a in arrays if a is not None]
    store = jnp.dtype(dtype) if dtype is not None else jnp.result_type(*arrs)
    comp = jnp.dtype(acc) if acc is not None else store
    return store, comp


def _cast(x, to):
    if x is None:
        return None
    x = jnp.asarray(x)
    if to is None or x.dtype == to:
        return x
    return x.astype(to)


def _kw(ctx):
    """Context fields -> the kwargs every numeric core takes."""
    return dict(policy=resolved_policy(ctx), registry=resolved_registry(ctx))


# -------------------------------- level 3 -----------------------------------

@_routine("gemm", _gemm_info)
def gemm(a, b, c=None, alpha=1.0, beta=0.0, transa: bool = False,
         transb: bool = False, dtype=None, context=None) -> jnp.ndarray:
    """C <- alpha * op(A) op(B) + beta * C, any supported dtype.

    2-D operands run the policy-dispatched local kernel path; with a mesh
    in the active context they run SUMMA ``pdgemm`` instead. 3-D operands
    (leading batch axis) vmap the local path. Oracle:
    ``tests/test_linalg.py`` / ``tests/test_differential_blas.py``.
    """
    ctx = current(context)
    store, comp = _dtypes(ctx, dtype, a, b, c)
    a_, b_, c_ = _cast(a, comp), _cast(b, comp), _cast(c, comp)
    if a_.ndim == 3:
        kw = _kw(ctx)
        f = lambda x, y: _l3.gemm(x, y, transa=transa, transb=transb, **kw)
        out = jax.vmap(f)(a_, b_)
        out = alpha * out
        if c_ is not None:
            out = out + beta * c_
        return _cast(out, store)
    mesh = resolved_mesh(ctx)
    if mesh is not None:
        from repro.blas import distributed as _dist
        op_a = a_.T if transa else a_
        op_b = b_.T if transb else b_
        out = _dist.pdgemm(op_a, op_b, mesh, c=c_, alpha=alpha, beta=beta,
                           **_kw(ctx))
        return _cast(out, store)
    out = _l3.gemm(a_, b_, c=c_, alpha=alpha, beta=beta, transa=transa,
                   transb=transb, **_kw(ctx))
    return _cast(out, store)


@_routine("gemm_bias_act", _gemm_bias_act_info)
def gemm_bias_act(a, b, bias=None, epilogue: str = "none", dtype=None,
                  context=None) -> jnp.ndarray:
    """C = act(A B + bias): GEMM with a streamed bias/activation epilogue.

    Under the kernel policies the whole chain resolves as the
    ``"gemm+epilogue"`` op: one fused Pallas launch when
    :func:`repro.core.codesign.plan_fused_chain` says streaming wins,
    else the staged kernel + epilogue pass. Always local (no mesh
    backend); 3-D operands vmap the local path with a shared ``bias``.
    Oracle: ``tests/test_fusion.py``.
    """
    ctx = current(context)
    store, comp = _dtypes(ctx, dtype, a, b, bias)
    a_, b_, bias_ = _cast(a, comp), _cast(b, comp), _cast(bias, comp)
    kw = _kw(ctx)
    if a_.ndim == 3:
        f = lambda x, y: _l3.gemm_bias_act(x, y, bias=bias_,
                                           epilogue=epilogue, **kw)
        return _cast(jax.vmap(f)(a_, b_), store)
    out = _l3.gemm_bias_act(a_, b_, bias=bias_, epilogue=epilogue, **kw)
    return _cast(out, store)


@_routine("syrk", _syrk_info)
def syrk(a, c=None, alpha=1.0, beta=0.0, lower: bool = True,
         trans: bool = False, dtype=None, context=None) -> jnp.ndarray:
    """C <- alpha op(A) op(A)^T + beta C, symmetric output.

    Under a mesh the product runs through SUMMA ``pdgemm`` before the
    triangle mirror; locally it shares the GEMM kernel path (and its
    registry entries).
    """
    ctx = current(context)
    store, comp = _dtypes(ctx, dtype, a, c)
    a_, c_ = _cast(a, comp), _cast(c, comp)
    mesh = resolved_mesh(ctx)
    if mesh is not None and a_.ndim == 2:
        from repro.blas import distributed as _dist
        op_a = a_.T if trans else a_
        full = alpha * _dist.pdgemm(op_a, op_a.T, mesh, **_kw(ctx))
        if c_ is not None:
            full = full + beta * c_
        return _cast(_l3.mirror_triangle(full, lower), store)
    kw = _kw(ctx)
    if a_.ndim == 3:
        f = lambda x, y: _l3.syrk(x, c=y, alpha=alpha, beta=beta,
                                  lower=lower, trans=trans, **kw)
        out = jax.vmap(f)(a_, c_) if c_ is not None else jax.vmap(
            lambda x: _l3.syrk(x, alpha=alpha, lower=lower, trans=trans,
                               **kw))(a_)
        return _cast(out, store)
    out = _l3.syrk(a_, c=c_, alpha=alpha, beta=beta, lower=lower,
                   trans=trans, **kw)
    return _cast(out, store)


@_routine("trsm", _trsm_info)
def trsm(a, b, lower: bool = True, unit_diag: bool = False,
         left: bool = True, block: Optional[int] = None, dtype=None,
         context=None) -> jnp.ndarray:
    """Solve op(T) X = B (or X op(T) = B), blocked, any supported dtype.

    Under a mesh the right-hand-side columns are sharded via ``pdtrsm``;
    locally the off-diagonal GEMM updates follow the context policy onto
    the kernel path. 3-D operands vmap the local path.
    """
    ctx = current(context)
    store, comp = _dtypes(ctx, dtype, a, b)
    a_, b_ = _cast(a, comp), _cast(b, comp)
    kw = _kw(ctx)
    if a_.ndim == 3:
        f = lambda t, r: _l3.trsm(t, r, lower=lower, unit_diag=unit_diag,
                                  left=left, block=block, **kw)
        return _cast(jax.vmap(f)(a_, b_), store)
    mesh = resolved_mesh(ctx)
    if mesh is not None:
        from repro.blas import distributed as _dist
        out = _dist.pdtrsm(a_, b_, mesh, lower=lower, unit_diag=unit_diag,
                           left=left, block=block, **kw)
        return _cast(out, store)
    out = _l3.trsm(a_, b_, lower=lower, unit_diag=unit_diag, left=left,
                   block=block, **kw)
    return _cast(out, store)


# -------------------------------- level 2 -----------------------------------

@_routine("gemv", _gemv_info)
def gemv(a, x, y=None, alpha=1.0, beta=0.0, trans: bool = False,
         dtype=None, context=None) -> jnp.ndarray:
    """y <- alpha*op(A) x + beta*y. Kernel policies run op(A) x through
    the Pallas GEMM path (shared registry entries); no mesh backend -
    always local. 3-D a / 2-D x vmap over the batch axis."""
    ctx = current(context)
    store, comp = _dtypes(ctx, dtype, a, x, y)
    a_, x_, y_ = _cast(a, comp), _cast(x, comp), _cast(y, comp)
    kw = _kw(ctx)
    if a_.ndim == 3:
        f = lambda m, v: _l2.gemv(m, v, trans=trans, **kw)
        out = alpha * jax.vmap(f)(a_, x_)
        if y_ is not None:
            out = out + beta * y_
        return _cast(out, store)
    out = _l2.gemv(a_, x_, y=y_, alpha=alpha, beta=beta, trans=trans, **kw)
    return _cast(out, store)


@_routine("ger", _ger_info)
def ger(alpha, x, y, a, dtype=None, context=None) -> jnp.ndarray:
    """A <- alpha * x y^T + A (rank-1 update, pure jnp)."""
    ctx = current(context)
    store, comp = _dtypes(ctx, dtype, x, y, a)
    out = _l2.ger(alpha, _cast(x, comp), _cast(y, comp), _cast(a, comp))
    return _cast(out, store)


@_routine("trsv", _trsv_info)
def trsv(a, b, lower: bool = True, unit_diag: bool = False, dtype=None,
         context=None) -> jnp.ndarray:
    """Solve op(T) x = b via the row-sequential scan (the divider-hazard
    chain); the blocked, policy-dispatched form is :func:`trsm`."""
    ctx = current(context)
    store, comp = _dtypes(ctx, dtype, a, b)
    out = _l2.trsv(_cast(a, comp), _cast(b, comp), lower=lower,
                   unit_diag=unit_diag)
    return _cast(out, store)


# -------------------------------- level 1 -----------------------------------

@_routine("dot", _vec_info(2))
def dot(x, y, schedule: str = "tree", accumulators: int = 8, dtype=None,
        context=None) -> jnp.ndarray:
    """Inner product with an explicit reduction schedule
    (tree/sequential/strided) - see :func:`repro.blas.level1.dot`.
    ``accum_dtype`` in the context upcasts the whole reduction."""
    ctx = current(context)
    store, comp = _dtypes(ctx, dtype, x, y)
    out = _l1.dot(_cast(x, comp), _cast(y, comp), schedule=schedule,
                  accumulators=accumulators)
    return _cast(out, store)


@_routine("axpy", _vec_info(2))
def axpy(alpha, x, y, dtype=None, context=None) -> jnp.ndarray:
    """y <- alpha*x + y."""
    ctx = current(context)
    store, comp = _dtypes(ctx, dtype, x, y)
    return _cast(_l1.axpy(alpha, _cast(x, comp), _cast(y, comp)), store)


@_routine("scal", _vec_info(1))
def scal(alpha, x, dtype=None, context=None) -> jnp.ndarray:
    """x <- alpha*x."""
    ctx = current(context)
    store, comp = _dtypes(ctx, dtype, x)
    return _cast(_l1.scal(alpha, _cast(x, comp)), store)


@_routine("nrm2", _vec_info(2))
def nrm2(x, dtype=None, context=None) -> jnp.ndarray:
    """Overflow-safe Euclidean norm."""
    ctx = current(context)
    store, comp = _dtypes(ctx, dtype, x)
    return _cast(_l1.nrm2(_cast(x, comp)), store)


@_routine("asum", _vec_info(1))
def asum(x, dtype=None, context=None) -> jnp.ndarray:
    """Sum of absolute values."""
    ctx = current(context)
    store, comp = _dtypes(ctx, dtype, x)
    return _cast(_l1.asum(_cast(x, comp)), store)


@_routine("iamax", _vec_info(1))
def iamax(x, context=None) -> jnp.ndarray:
    """Index of the first max-|x| element (0-based int; no dtype cast)."""
    return _l1.iamax(jnp.asarray(x))


@_routine("rot", _vec_info(6))
def rot(x, y, c, s, dtype=None, context=None):
    """Apply a Givens rotation: (c*x + s*y, c*y - s*x)."""
    ctx = current(context)
    store, comp = _dtypes(ctx, dtype, x, y)
    gx, gy = _l1.rot(_cast(x, comp), _cast(y, comp), c, s)
    return _cast(gx, store), _cast(gy, store)
