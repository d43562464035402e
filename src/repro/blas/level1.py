"""Level-1 BLAS in JAX (the paper's section-4.1 workloads).

dtype-generic cores under their un-prefixed names (``dot``, ``axpy``, ...),
behind the :mod:`repro.linalg` front end. ``dot`` exposes the *schedule*
knob the paper's analysis is about: tree / sequential / strided-U
reductions produce identical values (up to FP reassociation) with very
different dependence structure; the strided form with
U = ``codesign.optimal_accumulators`` is the TPU-codesign schedule.

Level-1 routines are pure jnp (no policy - there is no kernel-shaped core
to dispatch); the policy mechanism starts at Level 2. All routines accept
float32/float64 (and bfloat16 storage) and are differential-tested against
NumPy oracles in ``tests/test_differential_blas.py`` and
``tests/test_blas.py``.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def dot(x: jnp.ndarray, y: jnp.ndarray, schedule: str = "tree",
        accumulators: int = 8) -> jnp.ndarray:
    """Inner product x^T y with an explicit reduction schedule.

    Parameters
    ----------
    x, y : (n,) arrays, same shape and dtype (float32/float64/bfloat16).
    schedule : {"tree", "sequential", "strided"}
        * ``"tree"`` - ``jnp.sum`` (XLA's tree reduce).
        * ``"sequential"`` - a single running sum: the fully serial hazard
          chain, one dependent add per element.
        * ``"strided"`` - ``accumulators`` parallel partial sums + a small
          combine tree: the paper's depth-p pipeline realized as software
          ILP (U from :func:`repro.core.codesign.optimal_accumulators`).
    accumulators : int
        U for the strided schedule; ignored otherwise.

    Returns
    -------
    jnp.ndarray
        Scalar of x's dtype. Schedules agree up to FP reassociation.

    Notes
    -----
    Oracle: ``tests/test_differential_blas.py`` (vs ``np.dot`` per
    schedule); schedule-equivalence in ``tests/test_blas.py``.
    """
    prods = x * y
    if schedule == "tree":
        return jnp.sum(prods)
    if schedule == "sequential":
        return lax.scan(lambda c, v: (c + v, None), jnp.zeros((), x.dtype),
                        prods)[0]
    if schedule == "strided":
        u = max(1, int(accumulators))
        n = prods.shape[0]
        pad = (-n) % u
        p = jnp.pad(prods, (0, pad)).reshape(-1, u)
        # each column is one accumulator chain; final tree over U partials
        partials = lax.scan(lambda c, row: (c + row, None),
                            jnp.zeros((u,), x.dtype), p)[0]
        return jnp.sum(partials)
    raise ValueError(schedule)


def axpy(alpha, x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """y <- alpha*x + y.

    Parameters
    ----------
    alpha : scalar; x, y : same-shape float arrays.

    Returns
    -------
    jnp.ndarray with y's shape. Oracle: ``tests/test_differential_blas.py``.
    """
    return alpha * x + y


def scal(alpha, x: jnp.ndarray) -> jnp.ndarray:
    """x <- alpha*x (any float dtype/shape).

    Oracle: ``tests/test_differential_blas.py``.
    """
    return alpha * x


def nrm2(x: jnp.ndarray) -> jnp.ndarray:
    """Euclidean norm of a vector, overflow-safe (reference-BLAS style).

    Scales by max|x| before squaring, so ||x|| is finite whenever the
    inputs are - the reference dnrm2 contract. Returns a scalar of x's
    dtype. Oracle: ``tests/test_differential_blas.py`` (vs
    ``np.linalg.norm``, including huge/tiny magnitudes).
    """
    amax = jnp.max(jnp.abs(x))
    scale_ = jnp.where(amax > 0, amax, 1.0)
    return scale_ * jnp.sqrt(jnp.sum((x / scale_) ** 2))


def asum(x: jnp.ndarray) -> jnp.ndarray:
    """Sum of absolute values (BLAS asum). Scalar of x's dtype.

    Oracle: ``tests/test_differential_blas.py``.
    """
    return jnp.sum(jnp.abs(x))


def iamax(x: jnp.ndarray) -> jnp.ndarray:
    """Index of the first max-|x| element (BLAS iamax, 0-based int).

    Oracle: ``tests/test_differential_blas.py`` (vs ``np.argmax(|x|)``).
    """
    return jnp.argmax(jnp.abs(x))


def rot(x, y, c, s):
    """Apply a Givens rotation to a vector pair.

    Parameters
    ----------
    x, y : same-shape float arrays; c, s : rotation cosine/sine scalars.

    Returns
    -------
    (x', y') = (c*x + s*y, c*y - s*x).
    Oracle: ``tests/test_differential_blas.py``.
    """
    return c * x + s * y, c * y - s * x
