"""repro.linalg - the dtype-generic, context-scoped BLAS/LAPACK front-end.

This package is the single public API of the repo's linear algebra stack:
one set of routine names (``gemm``, ``gemv``, ``syrk``, ``trsm``,
``axpy``, ``dot``, ..., ``cholesky``, ``lu``, ``qr``, ``solve`` and their
batched forms) over every supported dtype (float32/float64; bfloat16
storage on the kernel paths), with the deployment shape - policy, device
mesh, registry, accumulation dtype - carried by a scoped
:class:`ExecutionContext` instead of per-call kwarg threading::

    from repro import linalg

    c = linalg.gemm(a, b)                          # process-default context

    with linalg.use(policy="tuned"):               # scoped policy
        l = linalg.cholesky(spd)

    with linalg.use(policy="model", mesh=(2, 2)):  # SUMMA + sharded batch
        c = linalg.gemm(a, b)                      # routes to pdgemm
        r = linalg.batched_cholesky(spd_batch)     # batch-sharded driver

    with linalg.use(machine=arch.get("paper-pe")): # swap the machine model:
        c = linalg.gemm(a, b)                      # planners + tuner keys
                                                   # follow the MachineSpec

    linalg.set_context(policy="tuned",             # process-global default
                       registry="/path/to/registry.json")
    x = linalg.solve(a, b, context=dict(policy="reference"))  # per call

Callers never pick a namespace by deployment shape: the same ``gemm``
call runs plain jnp, the Pallas MXU kernel, a tuned registry config, or
the SUMMA mesh schedule depending only on the active context. Names
removed from the stack, and what replaces them, are listed in
``docs/migration.md``.
"""
from repro.lapack.batched import FactorizationResult
from repro.linalg.context import (UNSET, ExecutionContext, get_context,
                                  reset_context, set_context, use)
from repro.linalg.blas import (asum, axpy, dot, gemm, gemm_bias_act, gemv,
                               ger, iamax, nrm2, rot, scal, syrk, trsm,
                               trsv)
from repro.linalg.lapack import (batched_cholesky, batched_lu, batched_qr,
                                 batched_solve, cholesky, lstsq, lu, qr,
                                 solve)

__all__ = [
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
