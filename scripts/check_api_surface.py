#!/usr/bin/env python
"""CI guard for the repro.linalg public surface.

Asserts (1) ``repro.linalg.__all__`` is exactly the frozen list below,
(2) every routine keeps its dtype-generic, context-scoped signature
(``dtype`` and ``context`` keyword parameters), and (3) the
ExecutionContext field set is stable - so an accidental surface break
(renamed routine, dropped kwarg, new required positional) fails CI
instead of landing silently. Update the frozen lists *in the same PR* as
an intentional surface change.
"""
import inspect
import sys

EXPECTED_ALL = [
    # context machinery
    "ExecutionContext", "use", "get_context", "set_context", "reset_context",
    # BLAS level 1
    "axpy", "dot", "scal", "nrm2", "asum", "iamax", "rot",
    # BLAS level 2
    "gemv", "ger", "trsv",
    # BLAS level 3
    "gemm", "gemm_bias_act", "syrk", "trsm",
    # LAPACK
    "cholesky", "lu", "qr", "solve", "lstsq",
    # batched LAPACK
    "batched_cholesky", "batched_lu", "batched_qr", "batched_solve",
    "FactorizationResult",
]

# routine -> parameters that must exist (beyond the operands)
EXPECTED_PARAMS = {
    "gemm": {"a", "b", "c", "alpha", "beta", "transa", "transb", "dtype",
             "context"},
    "gemv": {"a", "x", "y", "alpha", "beta", "trans", "dtype", "context"},
    "syrk": {"a", "c", "alpha", "beta", "lower", "trans", "dtype", "context"},
    "trsm": {"a", "b", "lower", "unit_diag", "left", "block", "dtype",
             "context"},
    "axpy": {"alpha", "x", "y", "dtype", "context"},
    "dot": {"x", "y", "schedule", "accumulators", "dtype", "context"},
    "scal": {"alpha", "x", "dtype", "context"},
    "nrm2": {"x", "dtype", "context"},
    "asum": {"x", "dtype", "context"},
    "iamax": {"x", "context"},
    "rot": {"x", "y", "c", "s", "dtype", "context"},
    "ger": {"alpha", "x", "y", "a", "dtype", "context"},
    "trsv": {"a", "b", "lower", "unit_diag", "dtype", "context"},
    "gemm_bias_act": {"a", "b", "bias", "epilogue", "dtype", "context"},
    "cholesky": {"a", "block", "dtype", "context", "fuse"},
    "lu": {"a", "block", "dtype", "context", "fuse"},
    "qr": {"a", "block", "dtype", "context"},
    "solve": {"a", "b", "block", "dtype", "context"},
    "lstsq": {"a", "b", "block", "dtype", "context"},
    "batched_cholesky": {"a", "block", "dtype", "context"},
    "batched_lu": {"a", "block", "dtype", "context"},
    "batched_qr": {"a", "block", "dtype", "context"},
    "batched_solve": {"res", "b", "dtype", "context"},
}

EXPECTED_CONTEXT_FIELDS = {"policy", "mesh", "registry", "accum_dtype",
                           "machine", "obs"}

EXPECTED_ARCH_ALL = [
    # spec types
    "MachineSpec", "FPUSpec", "MemorySpec", "PEGeometry", "PowerAreaSpec",
    "OP_CLASSES",
    # registry
    "get", "register", "names", "DEFAULT_MACHINE",
    # ambient machine scoping
    "current_machine", "machine_scope", "set_default_machine",
    "resolve_machine", "machine_key_component",
    # built-in specs
    "TPU_LIKE", "PAPER_PE", "CPU_HOST",
    # measured-machine calibration
    "calibrate", "calibrate_full", "load_or_calibrate",
    "CalibrationResult", "CALIBRATION_TOLERANCE",
    # benchmark helper
    "bench_metrics",
]

# arch.calibrate* keyword surface (benchmark kwargs ride **bench_kwargs and
# are guarded on run_microbenchmarks instead)
EXPECTED_CALIBRATE_PARAMS = {"backend", "base", "name", "register",
                             "overwrite", "path"}
EXPECTED_MICROBENCH_PARAMS = {"gemm_sizes", "stream_elems", "chain_iters",
                              "reps", "min_reps", "max_reps", "rel_spread"}

# the measurement surface every sweep/bench/calibration times through
EXPECTED_TUNE_MEASURE = ["Measurement", "measure", "measure_wall_time",
                         "model_residual", "repetition_controller"]
EXPECTED_MEASUREMENT_FIELDS = {"samples", "seconds_median", "seconds_spread",
                               "reps", "converged", "target_spread"}
# the row fields every bench JSON row carries (docs/benchmarking.md;
# the perf-regression gate reads seconds_median/seconds_spread)
EXPECTED_ROW_FIELDS = {"seconds_median", "seconds_spread", "reps"}

# spec dataclass -> frozen field set (registry keys and serialized files
# depend on these names; change them only with a schema bump)
EXPECTED_ARCH_FIELDS = {
    "MachineSpec": {"name", "fpu", "memory", "pe", "power_area",
                    "native_dtype"},
    "FPUSpec": {"depths", "t_p", "t_o", "gamma", "acc_overhead"},
    "MemorySpec": {"hbm_bw", "vmem_bytes", "ici_bw", "hbm_bytes",
                   "pipeline_fill_s"},
    "PEGeometry": {"mxu", "sublane", "lane", "vreg_budget", "peak_flops"},
    "PowerAreaSpec": {"pj_per_flop", "pj_per_byte_hbm", "static_w",
                      "area_mm2"},
}

EXPECTED_MACHINE_NAMES = {"tpu-like", "paper-pe", "cpu-host"}

# the repro.obs tracing surface (docs/observability.md): exported names,
# the frozen per-event schema (exporters and scripts/trace_report.py
# parse these exact fields), and the counter vocabulary
EXPECTED_OBS_ALL = [
    # schema
    "SCHEMA_VERSION", "EVENT_FIELDS",
    # tracer
    "Trace", "Span", "trace", "capture", "span", "event", "annotate",
    "enabled", "current_trace", "NOOP_SPAN",
    # counters
    "KNOWN_COUNTERS", "inc", "counter", "counters_snapshot",
    "counters_delta", "reset_counters",
    # exporters
    "to_chrome_trace", "save_chrome_trace", "to_jsonl", "save_jsonl",
    "summary",
]
EXPECTED_EVENT_FIELDS = ("name", "cat", "id", "parent", "t_start", "t_end",
                         "attrs")
EXPECTED_COUNTERS = {
    "dispatch.resolve", "dispatch.registry_hit", "dispatch.registry_miss",
    "registry.load", "registry.missing_fallback", "registry.corrupt_fallback",
    "kernel.launch", "collective.hops", "collective.bytes",
    "kernel.ssd_scan", "kernel.flash_attention", "trsm.inverted_blocks",
}

# the repro.analysis static-verification surface (docs/static_analysis.md):
# exported names, the frozen rule-ID vocabulary (allowlists, docs, and
# seeded-violation tests key on IDs and severities), and the report /
# finding record layouts that CI artifacts serialize
EXPECTED_ANALYSIS_ALL = [
    "RULES", "Finding", "AnalysisReport",
    "check", "check_routine", "check_surface", "check_distributed",
    "surface_routines", "merge_reports", "allow", "Allowlist",
    "load_allowlist",
    "lint_bypass", "collect_bypass_sites", "load_bypass_allowlist",
]
EXPECTED_ANALYSIS_RULES = {
    "KL001": "error", "KL002": "error", "KL003": "error", "KL004": "error",
    "DF001": "error", "DF002": "error", "DF003": "warn", "DF004": "error",
    "CM001": "error", "CM002": "warn", "CM003": "warn",
    "CC001": "error", "CC002": "error", "CC003": "error",
    "SH001": "error", "SH002": "error", "SH003": "warn",
    "BY001": "error",
}
# trace-time collective metadata record (spmd_lint's record view): the
# analyzer, obs counters, and plan_pdgemm all key on these field names
EXPECTED_COLLECTIVE_RECORD_FIELDS = {"kind", "axis", "size", "src", "hops",
                                     "per_hop_bytes", "wire_bytes", "info"}
# the distributed acceptance meshes CI sweeps (degenerate/square/rect)
EXPECTED_SURFACE_MESHES = ((1, 1), (2, 2), (4, 2))
EXPECTED_REPORT_FIELDS = {"target", "cases", "findings", "suppressed",
                          "schema_version"}
EXPECTED_FINDING_FIELDS = {"rule", "severity", "routine", "message",
                           "location", "case", "suppressed", "suppressed_by"}


# the streaming-fusion surface (docs/fusion.md): kernel exports, the
# registry op strings dispatch resolves, the chain planner signature, and
# the FusedChainPlan record the benches/tests consume
EXPECTED_FUSED_KERNELS = ["EPILOGUES", "apply_epilogue", "fused_span",
                          "gemm_bias_act", "trsm_gemm"]
EXPECTED_EPILOGUES = ("none", "relu", "gelu")
EXPECTED_FUSED_OPS = ("gemm+epilogue", "trsm+gemm")
EXPECTED_FUSED_CHAIN_PARAMS = {"kind", "m", "n", "k", "dtype_bytes", "dtype",
                               "epilogue", "has_bias", "form", "machine"}
EXPECTED_FUSED_CHAIN_FIELDS = {"kind", "form", "gemm", "block", "vmem_bytes",
                               "fits_vmem", "unfused_hbm_bytes",
                               "fused_hbm_bytes", "unfused_time",
                               "fused_time"}


def check_fusion(errors) -> None:
    import dataclasses

    from repro import tune
    from repro.core import codesign as cd
    from repro.kernels import fused as fk
    from repro.tune import dispatch as td

    for name in EXPECTED_FUSED_KERNELS:
        if not hasattr(fk, name):
            errors.append(f"repro.kernels.fused lost {name}")
    if tuple(getattr(fk, "EPILOGUES", ())) != EXPECTED_EPILOGUES:
        errors.append(f"kernels.fused.EPILOGUES drifted: "
                      f"{getattr(fk, 'EPILOGUES', None)} "
                      f"!= {EXPECTED_EPILOGUES}")
    if tuple(getattr(td, "FUSED_OPS", ())) != EXPECTED_FUSED_OPS:
        errors.append(f"dispatch.FUSED_OPS drifted: "
                      f"{getattr(td, 'FUSED_OPS', None)} "
                      f"!= {EXPECTED_FUSED_OPS}")
    if not set(EXPECTED_FUSED_OPS) <= set(td.OPS):
        errors.append("fused registry ops missing from dispatch.OPS: "
                      f"{sorted(set(EXPECTED_FUSED_OPS) - set(td.OPS))}")
    if tuple(getattr(cd, "FUSED_CHAIN_KINDS", ())) != EXPECTED_FUSED_OPS:
        errors.append("codesign.FUSED_CHAIN_KINDS must match the dispatch "
                      "registry op strings")
    params = set(inspect.signature(cd.plan_fused_chain).parameters)
    lost = EXPECTED_FUSED_CHAIN_PARAMS - params
    if lost:
        errors.append(f"plan_fused_chain: lost parameters {sorted(lost)}")
    fields = {f.name for f in dataclasses.fields(cd.FusedChainPlan)}
    if fields != EXPECTED_FUSED_CHAIN_FIELDS:
        errors.append(f"FusedChainPlan fields drifted: {sorted(fields)} "
                      f"!= {sorted(EXPECTED_FUSED_CHAIN_FIELDS)}")
    if "tune_fused_gemm" not in tune.__all__:
        errors.append("repro.tune.__all__ lost tune_fused_gemm")


def check_arch(errors) -> None:
    import dataclasses

    from repro import arch

    got_all = list(arch.__all__)
    if got_all != EXPECTED_ARCH_ALL:
        missing = set(EXPECTED_ARCH_ALL) - set(got_all)
        extra = set(got_all) - set(EXPECTED_ARCH_ALL)
        errors.append(f"arch.__all__ drifted: missing={sorted(missing)} "
                      f"extra={sorted(extra)} (order matters too)")
    for cls_name, want in EXPECTED_ARCH_FIELDS.items():
        cls = getattr(arch, cls_name, None)
        if cls is None:
            errors.append(f"repro.arch lost {cls_name}")
            continue
        fields = {f.name for f in dataclasses.fields(cls)}
        if fields != want:
            errors.append(f"arch.{cls_name} fields drifted: "
                          f"{sorted(fields)} != {sorted(want)}")
    if not EXPECTED_MACHINE_NAMES <= set(arch.names()):
        errors.append(f"built-in machines missing: "
                      f"{sorted(EXPECTED_MACHINE_NAMES - set(arch.names()))}")

    for fn_name, want in (("calibrate", EXPECTED_CALIBRATE_PARAMS),
                          ("calibrate_full", EXPECTED_CALIBRATE_PARAMS)):
        fn = getattr(arch, fn_name, None)
        if fn is None:
            errors.append(f"repro.arch lost {fn_name}")
            continue
        params = set(inspect.signature(fn).parameters)
        lost = want - params
        if lost:
            errors.append(f"arch.{fn_name}: lost parameters {sorted(lost)}")
    import importlib
    # arch.calibrate the function shadows the submodule attribute
    _cal = importlib.import_module("repro.arch.calibrate")
    params = set(inspect.signature(_cal.run_microbenchmarks).parameters)
    lost = EXPECTED_MICROBENCH_PARAMS - params
    if lost:
        errors.append(f"arch.calibrate.run_microbenchmarks: lost "
                      f"parameters {sorted(lost)}")


def check_measure(errors) -> None:
    import dataclasses

    from repro import tune
    from repro.tune import measure as m

    for name in EXPECTED_TUNE_MEASURE:
        if not hasattr(m, name):
            errors.append(f"repro.tune.measure lost {name}")
        if name not in tune.__all__ and name != "measure":
            errors.append(f"repro.tune.__all__ lost {name}")
    if "measure" not in tune.__all__ or "measure_op" not in tune.__all__:
        errors.append("repro.tune.__all__ lost the measure submodule / "
                      "measure_op alias")
    fields = {f.name for f in dataclasses.fields(m.Measurement)}
    if fields != EXPECTED_MEASUREMENT_FIELDS:
        errors.append(f"Measurement fields drifted: {sorted(fields)} "
                      f"!= {sorted(EXPECTED_MEASUREMENT_FIELDS)}")
    try:
        row = m.Measurement.from_samples([1.0, 2.0, 3.0]).row_fields()
        if set(row) != EXPECTED_ROW_FIELDS:
            errors.append(f"Measurement.row_fields drifted: {sorted(row)} "
                          f"!= {sorted(EXPECTED_ROW_FIELDS)}")
    except Exception as e:  # pragma: no cover - surface break
        errors.append(f"Measurement.row_fields broken: {e!r}")
    # the sweeps' historical import path must keep working
    from repro.tune import search
    if getattr(search, "measure_wall_time", None) is not m.measure_wall_time:
        errors.append("repro.tune.search.measure_wall_time is no longer the "
                      "shared measure helper")
    if getattr(search, "_timeit", None) is not m.measure_wall_time:
        errors.append("repro.tune.search._timeit alias broken")


def check_obs(errors) -> None:
    from repro import obs

    got_all = list(obs.__all__)
    if got_all != EXPECTED_OBS_ALL:
        missing = set(EXPECTED_OBS_ALL) - set(got_all)
        extra = set(got_all) - set(EXPECTED_OBS_ALL)
        errors.append(f"obs.__all__ drifted: missing={sorted(missing)} "
                      f"extra={sorted(extra)} (order matters too)")
    if tuple(obs.EVENT_FIELDS) != EXPECTED_EVENT_FIELDS:
        errors.append(f"obs.EVENT_FIELDS drifted: {tuple(obs.EVENT_FIELDS)} "
                      f"!= {EXPECTED_EVENT_FIELDS} (schema bump needed)")
    if set(obs.KNOWN_COUNTERS) != EXPECTED_COUNTERS:
        errors.append(f"obs.KNOWN_COUNTERS drifted: "
                      f"{sorted(set(obs.KNOWN_COUNTERS) ^ EXPECTED_COUNTERS)}")
    if obs.SCHEMA_VERSION != 1:
        errors.append(f"obs.SCHEMA_VERSION bumped to {obs.SCHEMA_VERSION}: "
                      "update trace_report.py + this guard together")
    # the disabled-path contract: no ambient trace -> the shared no-op span
    if obs.enabled():
        errors.append("obs.enabled() is True at import with no trace active")
    with obs.span("surface-check") as sp:
        if sp is not obs.NOOP_SPAN:
            errors.append("obs.span() off-trace must enter as the NOOP_SPAN "
                          "singleton (dict-free disabled path)")


def check_analysis(errors) -> None:
    import dataclasses

    from repro import analysis

    got_all = list(analysis.__all__)
    if got_all != EXPECTED_ANALYSIS_ALL:
        missing = set(EXPECTED_ANALYSIS_ALL) - set(got_all)
        extra = set(got_all) - set(EXPECTED_ANALYSIS_ALL)
        errors.append(f"analysis.__all__ drifted: missing={sorted(missing)} "
                      f"extra={sorted(extra)} (order matters too)")
    got_rules = {r.id: r.severity for r in analysis.RULES.values()}
    if got_rules != EXPECTED_ANALYSIS_RULES:
        drifted = {rid for rid in set(got_rules) | set(EXPECTED_ANALYSIS_RULES)
                   if got_rules.get(rid) != EXPECTED_ANALYSIS_RULES.get(rid)}
        errors.append(f"analysis rule vocabulary drifted on {sorted(drifted)}"
                      ": IDs are frozen - an ID may gain wording but never "
                      "disappear or change severity silently")
    for cls_name, want in (("AnalysisReport", EXPECTED_REPORT_FIELDS),
                           ("Finding", EXPECTED_FINDING_FIELDS)):
        cls = getattr(analysis, cls_name, None)
        if cls is None:
            errors.append(f"repro.analysis lost {cls_name}")
            continue
        fields = {f.name for f in dataclasses.fields(cls)}
        if fields != want:
            errors.append(f"analysis.{cls_name} fields drifted: "
                          f"{sorted(fields)} != {sorted(want)} "
                          "(CI artifacts serialize these)")
    if analysis.check_surface.__defaults__ is None:
        errors.append("analysis.check_surface lost its defaulted grid")
    from repro.analysis import report as _report
    if tuple(getattr(_report, "SURFACE_MESHES", ())) != \
            EXPECTED_SURFACE_MESHES:
        errors.append(f"analysis SURFACE_MESHES drifted: "
                      f"{getattr(_report, 'SURFACE_MESHES', None)} "
                      f"!= {EXPECTED_SURFACE_MESHES}")
    from repro.distributed import collectives as _coll
    rec = getattr(_coll, "CollectiveRecord", None)
    if rec is None or not hasattr(_coll, "record_collectives"):
        errors.append("repro.distributed.collectives lost the "
                      "CollectiveRecord / record_collectives surface")
    else:
        fields = {f.name for f in dataclasses.fields(rec)}
        if fields != EXPECTED_COLLECTIVE_RECORD_FIELDS:
            errors.append(f"CollectiveRecord fields drifted: "
                          f"{sorted(fields)} != "
                          f"{sorted(EXPECTED_COLLECTIVE_RECORD_FIELDS)}")
    from repro.tune import dispatch as _td
    dm = getattr(_td, "DISPATCHED_MODULES", ())
    if not (isinstance(dm, tuple) and dm):
        errors.append("tune.dispatch.DISPATCHED_MODULES must stay a "
                      "non-empty tuple (BY001 provenance)")


def main() -> int:
    from repro import linalg

    errors = []
    check_arch(errors)
    check_measure(errors)
    check_obs(errors)
    check_fusion(errors)
    check_analysis(errors)
    got_all = list(linalg.__all__)
    if got_all != EXPECTED_ALL:
        missing = set(EXPECTED_ALL) - set(got_all)
        extra = set(got_all) - set(EXPECTED_ALL)
        errors.append(f"__all__ drifted: missing={sorted(missing)} "
                      f"extra={sorted(extra)} (order matters too)")

    for name, want in EXPECTED_PARAMS.items():
        fn = getattr(linalg, name, None)
        if fn is None:
            errors.append(f"routine {name} missing from repro.linalg")
            continue
        params = set(inspect.signature(fn).parameters)
        lost = want - params
        if lost:
            errors.append(f"{name}: lost parameters {sorted(lost)} "
                          f"(has {sorted(params)})")
        if name != "iamax" and "dtype" not in params:
            errors.append(f"{name}: must stay dtype-generic (dtype kwarg)")
        if "context" not in params:
            errors.append(f"{name}: must accept a per-call context override")

    import dataclasses
    fields = {f.name for f in dataclasses.fields(linalg.ExecutionContext)}
    if fields != EXPECTED_CONTEXT_FIELDS:
        errors.append(f"ExecutionContext fields drifted: {sorted(fields)} "
                      f"!= {sorted(EXPECTED_CONTEXT_FIELDS)}")

    if errors:
        print("repro.linalg API surface check FAILED:")
        for e in errors:
            print(f"  - {e}")
        return 1
    print(f"repro.linalg + repro.arch + repro.tune.measure + repro.obs + "
          f"fusion + analysis API surface OK ({len(EXPECTED_PARAMS)} "
          f"routines, "
          f"{len(EXPECTED_ALL)} linalg + {len(EXPECTED_ARCH_ALL)} arch + "
          f"{len(EXPECTED_OBS_ALL)} obs + {len(EXPECTED_ANALYSIS_ALL)} "
          f"analysis exported names, "
          f"{len(EXPECTED_ANALYSIS_RULES)} frozen rule IDs, "
          f"{len(EXPECTED_TUNE_MEASURE)} measurement names, "
          f"{len(EXPECTED_FUSED_KERNELS)} fused-kernel names)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
