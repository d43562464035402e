"""Execution contexts: one scoped object replaces per-call kwarg threading.

Every :mod:`repro.linalg` routine resolves an :class:`ExecutionContext`
instead of taking ``policy=`` / ``registry=`` kwargs; the context is the
one place a call's dispatch path is chosen. It carries the *deployment
shape* of a call:

``policy``
    ``"reference" | "model" | "tuned"`` (``None`` = the process default,
    ``"reference"``; :func:`set_context` changes it).
``mesh``
    ``None`` for single-device execution, or a ``jax.sharding.Mesh`` /
    ``(px, py)`` tuple. With a mesh set, routines that have a distributed
    backend (``gemm`` -> SUMMA ``pdgemm``, ``trsm`` -> ``pdtrsm``, the
    batched factorizations -> the batch-sharded drivers) route there
    automatically; everything else stays local.
``registry``
    A :class:`repro.tune.registry.Registry`, a path string, or ``None``
    (the process-default registry). Path strings are normalized to one
    cached ``Registry`` per path so the file is read once.
``accum_dtype``
    Optional accumulation dtype: operands are upcast to it for the
    computation and the result is cast back to the storage dtype. ``None``
    (the default) leaves numerics exactly as the operand dtype dictates.
``machine``
    A :class:`repro.arch.MachineSpec` (or registered machine name) the
    call's planners and tuner lookups resolve against; ``None`` (the
    default) inherits the ambient :func:`repro.arch.current_machine` -
    the process default (``"tpu-like"`` unless
    :func:`repro.arch.set_default_machine` changed it) or an enclosing
    explicit ``arch.machine_scope``. Routines with a machine set enter an
    :func:`repro.arch.machine_scope` for their whole body, so nested
    resolutions - e.g. the trailing updates inside a blocked
    factorization - see the same machine.
``obs``
    Observability capture (:mod:`repro.obs`). ``None`` (the default)
    inherits the ambient :func:`repro.obs.trace` scope, if any;
    ``False`` suppresses capture inside the scope; an explicit
    :class:`repro.obs.Trace` routes the routines' spans into that trace
    regardless of the ambient scope. Like ``machine``, the routed
    capture covers the whole routine body (nested panel/trailing spans
    included).

Contexts layer: the module default, then :func:`set_context`, then nested
:func:`use` blocks, then a per-call ``context=`` override - inner layers
override only the fields they set (everything else is inherited through
the :data:`UNSET` sentinel). ``use`` scopes live in a
:class:`contextvars.ContextVar`, so concurrent threads (and asyncio
tasks) each see only their own scopes; :func:`set_context` replaces the
process-global base underneath every scope.

Whether the Pallas kernels interpret is not part of the context: the
kernels take it from the device (:func:`repro.kernels.ops.interpret_mode`,
interpret exactly when the backend is not a TPU).
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from typing import Any, Dict, Iterator, Mapping, Optional, Tuple


class _UnsetType:
    """Sentinel for 'inherit this field from the enclosing context'."""

    _instance: Optional["_UnsetType"] = None

    def __new__(cls):
        if cls._instance is None:
            cls._instance = super().__new__(cls)
        return cls._instance

    def __repr__(self) -> str:
        return "UNSET"

    def __bool__(self) -> bool:
        return False


UNSET = _UnsetType()

_FIELDS = ("policy", "mesh", "registry", "accum_dtype", "machine", "obs")


@dataclasses.dataclass(frozen=True)
class ExecutionContext:
    """One call's execution recipe; fields left :data:`UNSET` inherit."""

    policy: Any = UNSET
    mesh: Any = UNSET
    registry: Any = UNSET
    accum_dtype: Any = UNSET
    machine: Any = UNSET
    obs: Any = UNSET

    def __post_init__(self):
        if self.policy is not UNSET and self.policy is not None:
            from repro.tune.policy import POLICIES
            if self.policy not in POLICIES:
                raise ValueError(
                    f"unknown policy {self.policy!r}; expected one of "
                    f"{POLICIES} (or None for the process default)")
        if self.mesh is not UNSET and self.mesh is not None:
            if isinstance(self.mesh, tuple):
                if len(self.mesh) != 2:
                    raise ValueError(
                        f"tuple mesh must be (px, py); got {self.mesh!r}")
        if self.machine is not UNSET and self.machine is not None:
            from repro.arch import MachineSpec, get as _arch_get
            if isinstance(self.machine, str):
                _arch_get(self.machine)     # unknown names fail eagerly
            elif not isinstance(self.machine, MachineSpec):
                raise ValueError(
                    f"machine must be a MachineSpec, a registered machine "
                    f"name, or None; got {type(self.machine).__name__}")
        if self.obs is not UNSET and self.obs is not None \
                and self.obs is not False:
            from repro.obs import Trace
            if not isinstance(self.obs, Trace):
                raise ValueError(
                    f"obs must be a repro.obs.Trace, False (suppress), or "
                    f"None (inherit); got {type(self.obs).__name__}")

    def over(self, base: "ExecutionContext") -> "ExecutionContext":
        """This context layered over ``base``: set fields win."""
        merged = {f: (getattr(self, f) if getattr(self, f) is not UNSET
                      else getattr(base, f)) for f in _FIELDS}
        return ExecutionContext(**merged)

    def describe(self) -> Dict[str, Any]:
        """JSON-able summary (benchmarks attach this to every row)."""
        import numpy as np
        from repro.tune.policy import default_policy
        pol = self.policy if self.policy not in (UNSET, None) \
            else default_policy()
        mesh = None if self.mesh in (UNSET, None) else (
            list(self.mesh) if isinstance(self.mesh, tuple)
            else [int(self.mesh.shape[a]) for a in self.mesh.axis_names])
        reg = self.registry
        if reg is UNSET or reg is None:
            reg_path = None
        elif isinstance(reg, str):
            reg_path = reg
        else:
            reg_path = getattr(reg, "path", None)
        acc = None if self.accum_dtype in (UNSET, None) \
            else np.dtype(self.accum_dtype).name
        from repro import arch as _arch
        if self.machine in (UNSET, None):
            mach = _arch.current_machine().name
        elif isinstance(self.machine, str):
            mach = self.machine
        else:
            mach = self.machine.name
        if self.obs in (UNSET, None):
            obs_desc = None                         # inherit ambient trace
        elif self.obs is False:
            obs_desc = False
        else:
            obs_desc = getattr(self.obs, "name", "trace")
        return {"policy": pol, "mesh": mesh, "registry": reg_path,
                "accum_dtype": acc, "machine": mach, "obs": obs_desc}


# fully-resolved root: what a call sees with no context set anywhere
_DEFAULT = ExecutionContext(policy=None, mesh=None, registry=None,
                            accum_dtype=None, machine=None, obs=None)
# process-global base (set_context) + per-thread/task overlay scopes (use)
_base = _DEFAULT
_scopes: "contextvars.ContextVar[Tuple[ExecutionContext, ...]]" = \
    contextvars.ContextVar("repro_linalg_scopes", default=())


def _as_overlay(context, fields: Mapping[str, Any]) -> ExecutionContext:
    if context is not None and fields:
        raise TypeError("pass either a context object or field kwargs, "
                        "not both")
    if context is None:
        return ExecutionContext(**dict(fields))
    if isinstance(context, ExecutionContext):
        return context
    if isinstance(context, Mapping):
        return ExecutionContext(**dict(context))
    raise TypeError(f"context must be an ExecutionContext or mapping; "
                    f"got {type(context).__name__}")


def _active() -> ExecutionContext:
    ctx = _base
    for overlay in _scopes.get():
        ctx = overlay.over(ctx)
    return ctx


def current(call_override=None) -> ExecutionContext:
    """The active context, with an optional per-call overlay on top."""
    ctx = _active()
    if call_override is not None:
        ctx = _as_overlay(call_override, {}).over(ctx)
    return ctx


@contextlib.contextmanager
def use(context=None, **fields) -> Iterator[ExecutionContext]:
    """Scope a context: ``with repro.linalg.use(policy="tuned", mesh=(2, 2)):``.

    Accepts an :class:`ExecutionContext` (or mapping) positionally, or the
    fields as kwargs. Unset fields inherit from the enclosing scope.
    Scopes are per-thread/per-task (contextvars); exit restores exactly
    the scopes that were active at entry, so a stray
    :func:`reset_context` inside the block cannot unbalance anything.
    """
    overlay = _as_overlay(context, fields)
    token = _scopes.set(_scopes.get() + (overlay,))
    try:
        yield _active()
    finally:
        _scopes.reset(token)


def set_context(context=None, **fields) -> ExecutionContext:
    """Replace the process-global base context (under any active ``use``)."""
    global _base
    _base = _as_overlay(context, fields).over(_DEFAULT)
    return _base


def get_context() -> ExecutionContext:
    """The currently active (fully layered) context."""
    return _active()


def reset_context() -> None:
    """Reset the global base and this thread's scopes to the library
    default (tests)."""
    global _base
    _base = _DEFAULT
    _scopes.set(())


# ------------------------- lazy field normalizers ---------------------------

_registry_cache: Dict[str, Any] = {}
_mesh_cache: Dict[tuple, Any] = {}


def resolved_registry(ctx: ExecutionContext):
    """ctx.registry as a Registry-or-None (path strings cached per path)."""
    reg = ctx.registry
    if reg is UNSET or reg is None:
        return None
    if isinstance(reg, str):
        if reg not in _registry_cache:
            from repro.tune.registry import Registry
            _registry_cache[reg] = Registry(path=reg)
        return _registry_cache[reg]
    return reg


def resolved_mesh(ctx: ExecutionContext):
    """ctx.mesh as a jax Mesh-or-None ((px, py) tuples built lazily)."""
    mesh = ctx.mesh
    if mesh is UNSET or mesh is None:
        return None
    if isinstance(mesh, tuple):
        if mesh not in _mesh_cache:
            from repro.blas.distributed import make_blas_mesh
            _mesh_cache[mesh] = make_blas_mesh(*mesh)
        return _mesh_cache[mesh]
    return mesh


def resolved_policy(ctx: ExecutionContext):
    """ctx.policy as a policy-string-or-None (None = process default)."""
    return None if ctx.policy is UNSET else ctx.policy


def resolved_accum_dtype(ctx: ExecutionContext):
    return None if ctx.accum_dtype in (UNSET, None) else ctx.accum_dtype


def resolved_machine(ctx: ExecutionContext):
    """ctx.machine as a MachineSpec-or-None (names resolved through the
    arch registry; None = the process-default machine)."""
    mach = ctx.machine
    if mach is UNSET or mach is None:
        return None
    if isinstance(mach, str):
        from repro import arch as _arch
        return _arch.get(mach)
    return mach


def resolved_obs(ctx: ExecutionContext):
    """ctx.obs as a Trace-or-None. ``UNSET``/``None`` inherit the ambient
    :func:`repro.obs.current_trace`; ``False`` resolves to ``None`` even
    under an ambient trace (the routine wrappers additionally mask the
    ambient scope in that case, so nested spans stay suppressed too)."""
    o = ctx.obs
    if o is False:
        return None
    if o is UNSET or o is None:
        from repro.obs import current_trace
        return current_trace()
    return o
