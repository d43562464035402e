"""Entry: one call of ``repro.linalg`` (solve, cholesky or gemm) per step.

The call goes through the program's public path as a user makes it: the
routine, jitted, under the default context. The configuration's mesh, if
it has one, is the only thing set (``linalg.use(mesh=...)``).

Traffic parameters (``bench/traffic/<name>.json``):

- ``op``: ``solve`` | ``cholesky`` | ``gemm``;
- ``matrix``: ``normal`` (entries N(0, 1)) or ``spd`` (G G^T / n + I, G
  normal) -- made on the device from the seed;
- ``nrhs``: right-hand sides of a solve (1: a vector, as HPL);
- ``inputs``: distinct problems made in set-up; call i takes problem
  i mod ``inputs``;
- ``checked``: answers of the window checked, a sample drawn from the seed.

The configuration gives ``n``, ``dtype``, ``chips``, ``mesh`` and the
limits of the comparison.
"""
from __future__ import annotations

import math
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from bench.reference import dense as ref


def nominal_flops(op: str, n: int, nrhs: int = 1) -> float:
    """LAPACK's and BLAS's operation counts: gesv 2/3 n^3 + 2 n^2 nrhs,
    potrf n^3 / 3, gemm 2 n^3."""
    if op == "solve":
        return 2 / 3 * n ** 3 + 2 * n ** 2 * nrhs
    if op == "cholesky":
        return n ** 3 / 3
    if op == "gemm":
        return 2 * n ** 3
    raise ValueError(op)


def _seed_key(seed: int):
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 32),
                              (seed // 2 ** 32) % 2 ** 32)


class Entry:
    """``routine`` replaces the program's call (the control and the fault
    tests put a reference there)."""

    def __init__(self, config: dict, traffic: dict, seed: int, devices,
                 routine=None):
        from repro import linalg
        self.config, self.traffic, self.seed = config, traffic, seed
        self.op = traffic["op"]
        n = self.n = config["n"]
        dtype = jnp.dtype(config["dtype"])
        self.nrhs = traffic.get("nrhs", 1)
        mesh = config.get("mesh")
        if mesh:
            sharding = NamedSharding(
                Mesh(np.asarray(devices).reshape(mesh), ("x", "y")),
                P("x", "y"))
            ctx = linalg.use(mesh=tuple(mesh))
        else:
            sharding = SingleDeviceSharding(devices[0])
            ctx = linalg.use()
        self.work = {"flops": nominal_flops(self.op, n, self.nrhs),
                     "kind": "linalg"}

        k = traffic.get("inputs", 1)
        keys = jax.random.split(_seed_key(seed), k)
        matrix = traffic["matrix"]

        def make(key):
            ka, kb, kc = jax.random.split(key, 3)
            a = jax.random.normal(ka, (n, n), jnp.float32)
            if matrix == "spd":
                a = (jnp.matmul(a, a.T, precision="highest") / n
                     + jnp.eye(n, dtype=jnp.float32))
            a = a.astype(dtype)
            if self.op == "solve":
                shape = (n,) if self.nrhs == 1 else (n, self.nrhs)
                return a, jax.random.normal(kb, shape, jnp.float32
                                            ).astype(dtype)
            if self.op == "gemm":
                return a, jax.random.normal(kc, (n, n), jnp.float32
                                            ).astype(dtype)
            return (a,)

        t0 = time.perf_counter()
        self.inputs = []
        for key in keys:
            placed = tuple(sharding for _ in jax.eval_shape(make, key))
            self.inputs.append(jax.jit(make, out_shardings=placed)(key))

        if routine is None:
            routine = {"solve": linalg.solve, "cholesky": linalg.cholesky,
                       "gemm": linalg.gemm}[self.op]
        # compiled ahead of the window, or loaded from the persistent cache:
        # the first call then costs what the others do (to 0.02 s in a 14 s
        # solve, TPU v5e), so set-up makes no call of its own
        jax.block_until_ready(self.inputs)
        t1 = time.perf_counter()
        from repro.obs import counters
        from repro.tune.policy import default_policy
        before = counters.snapshot()
        with ctx:
            self.fn = jax.jit(lambda *args: routine(*args)).lower(
                *self.inputs[0]).compile()
        t2 = time.perf_counter()
        # which path the traced call took: the policy and the program's
        # trace-time counts (schedules traced, not executions)
        print(f"[bench] path: policy {default_policy()}, counters "
              f"{counters.delta(before)}", file=sys.stderr, flush=True)
        print(f"[bench] set-up: inputs {t1 - t0:.3f} s, compile or cache "
              f"load {t2 - t1:.3f} s", file=sys.stderr, flush=True)

    def call(self, i: int):
        """Call i, to completion on the device; returns its answer."""
        return jax.block_until_ready(
            self.fn(*self.inputs[i % len(self.inputs)]))

    def hlo_text(self) -> str:
        return self.fn.as_text()

    # ------------------------------ the check ------------------------------

    def check(self, kept: dict) -> list:
        """Each checked answer against the reference, worst case per
        number; returns [{name, value, limit}]."""
        limits = self.config["limits"][self.op]
        worst = {}
        for i in sorted(kept):
            args = self.inputs[i % len(self.inputs)]
            for name, value in getattr(self, "_check_" + self.op)(
                    kept[i], *args).items():
                worst[name] = max(worst.get(name, -math.inf), value)
        return [{"name": k, "value": float(v), "limit": limits[k]}
                for k, v in worst.items()]

    def _check_solve(self, x, a, b) -> dict:
        a64 = np.asarray(a, np.float64)
        x64 = np.asarray(x, np.float64)
        b64 = np.asarray(b, np.float64)
        return {"hpl_residual": ref.hpl_residual(a64, x64, b64)}

    def _check_cholesky(self, l, a) -> dict:
        return {"lapack_ratio": ref.cholesky_ratio(l, a)}

    def _check_gemm(self, c, a, b) -> dict:
        """The BLAS test's ratio max |C - C_ref| / (eps |A||B|) over a
        sample of C's entries drawn from the seed (``check_rows`` rows by
        ``check_cols`` columns), C_ref and |A||B| in float64 on the host."""
        rng = np.random.default_rng(self.seed % 2 ** 63)
        rows = np.sort(rng.choice(self.n, min(self.n,
                                              self.traffic["check_rows"]),
                                  replace=False))
        cols = np.sort(rng.choice(self.n, min(self.n,
                                              self.traffic["check_cols"]),
                                  replace=False))
        a_r = np.asarray(_take(a, rows, 0), np.float64)
        b_c = np.asarray(_take(b, cols, 1), np.float64)
        c_rc = np.asarray(_take(_take(c, rows, 0), cols, 1), np.float64)
        g = np.abs(a_r) @ np.abs(b_c)
        err = np.abs(c_rc - a_r @ b_c)
        return {"gemm_ratio": float(np.max(err / (np.finfo(np.float32).eps
                                                  * g)))}


def _take(t, idx, axis):
    return jax.jit(lambda t, i: jnp.take(t, i, axis=axis))(t, jnp.asarray(idx))
