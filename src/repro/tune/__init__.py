"""repro.tune - autotuning + persistent kernel-config registry behind a
unified BLAS/LAPACK dispatcher.

The paper's performance claims are "attained through tuning of several
algorithmic and architectural parameters" (block sizes, pipeline depths,
memory sizes). ELAPS (1504.08035) and the dense-linear-algebra performance
modeling line (1209.2364) both show the real optimum per shape/dtype/backend
comes from *measured sweeps seeded by a model*, not from the model alone.
This package is that loop, persisted:

    model (core.codesign + core.pipeline_model + core.roofline)
        -> candidate configs (search.gemm_candidates / trsm_candidates)
        -> measured sweep (search.tune_gemm / search.tune_trsm)
        -> persistent registry (registry.Registry, JSON on disk)
        -> every BLAS/LAPACK call (dispatch.dispatch)

Policy semantics
================
The policy is picked in one place, the :mod:`repro.linalg`
ExecutionContext (``linalg.use(policy=...)``, ``linalg.set_context``);
the front end resolves it into the ``policy`` argument that every BLAS-3 /
blocked-LAPACK numeric core takes:

``"reference"``
    Plain jnp (``a @ b``, scan substitutions). No Pallas, no registry.
    This is the oracle path and the default.
``"model"``
    Pallas kernel with the analytically chosen config - ``plan_gemm`` /
    ``plan_trsm`` from :mod:`repro.core.codesign` (the paper's
    pipeline-depth equation transplanted to block shapes).
``"tuned"``
    Pallas kernel with the *measured* best config from the registry, keyed
    by ``(op, shape-bucket, dtype, backend)``. A lookup miss (cold start,
    missing or corrupt registry file) falls back to exactly the ``model``
    resolution, so a cold-start ``tuned`` run is numerically identical to
    ``model``.

Whether the Pallas kernels interpret is not a policy: the kernels take it
from the device (:func:`repro.kernels.ops.interpret_mode`).

Registry file format
====================
One JSON object (schema version 1)::

    {"version": 1,
     "entries": {
       "gemm|256x256x128|float32|cpu": {
          "op": "gemm",
          "params": {"bm": 128, "bn": 128, "bk": 128},
          "source": "sweep",            # "sweep" | "model"
          "measured_s": 1.3e-4},
       "trsm|64x8|float32|cpu": {
          "op": "trsm", "params": {"block": 32}, ...}}}

Keys are ``op|shape-bucket|dtype|backend[|mesh]`` where the shape bucket
rounds every dimension up to the next power of two, so one sweep covers a
neighborhood of problem sizes. The optional trailing mesh component scopes
distributed ops to one device-mesh shape (e.g.
``"pdgemm|128x128x64|float32|cpu|x2y4"`` for a 2x4 ("x", "y") mesh);
single-device entries omit it, so pre-mesh registry files keep resolving
unchanged. Lookups go through an in-memory LRU; the file is read lazily
once and written with :meth:`Registry.save`.

Regenerating the cache
======================
``PYTHONPATH=src python -m benchmarks.bench_tune --out-dir benchmarks/out``
sweeps the standard shape grid, writes ``benchmarks/out/tune_registry.json``
and the ``benchmarks/out/BENCH_tune.json`` trajectory. Point the runtime at
a registry file with ``REPRO_TUNE_REGISTRY=/path/to/registry.json`` (or
``registry.set_default_path``). ``scripts/ci_check.sh`` runs a tiny smoke
sweep into a temp dir on every CI run so schema drift cannot land silently.
"""
from repro.tune import dispatch, measure, policy, registry, search
from repro.tune.dispatch import Resolution, dispatch as dispatch_op, resolve
from repro.tune.measure import (Measurement, measure_wall_time,
                                model_residual, repetition_controller)
from repro.tune.measure import measure as measure_op  # noqa: F401 (alias:
# the submodule itself is exported as `measure`; the callable is
# tune.measure.measure / tune.measure_op)
from repro.tune.policy import POLICIES, default_policy, resolve_policy
from repro.tune.registry import KernelConfig, Registry, default_registry
from repro.tune.search import (seed_registry_from_model, tune_fused_gemm,
                               tune_gemm, tune_trsm)

__all__ = [
    "POLICIES", "KernelConfig", "Measurement", "Registry", "Resolution",
    "default_policy", "default_registry", "dispatch", "dispatch_op",
    "measure", "measure_op", "measure_wall_time", "model_residual",
    "policy", "registry", "repetition_controller", "resolve",
    "resolve_policy", "search", "seed_registry_from_model",
    "tune_fused_gemm", "tune_gemm", "tune_trsm",
]
