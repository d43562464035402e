"""Chip smoke test: drive repro.linalg and one zoo model once, at real sizes.

    python chip_smoke.py [--seed N] [--out DIR]     # one TPU chip
    python chip_smoke.py --four-chips               # 4 chips: SUMMA, sharding
    JAX_PLATFORMS=cpu python chip_smoke.py --small  # CPU rehearsal, tiny sizes

Phases on one chip, each through the public entry points:

1. ``linalg.gemm`` at 8192^3 in bfloat16 and float32 under ``policy="model"``
   (the planned Pallas GEMM) against ``policy="reference"`` (XLA's dot).
2. An HPL-style dense solve (``linalg.solve``, n = 8192, float32) under
   ``model`` (the fused TRSM->GEMM trailing chain) and ``reference``, each
   held to HPL's scaled residual; ``linalg.cholesky`` and ``linalg.qr`` with
   LAPACK's test ratios.
3. ``linalg.batched_{cholesky,lu,qr}`` on 4096 x 64x64 float32, ``model``
   against ``reference``.
4. mamba2-130m at its published widths: 3 train steps through
   ``launch.train.train_loop`` on a 1x1 mesh (seq 2048, global batch 8), then
   one forward through the compiled SSD kernel against the jnp SSD path.

``--four-chips`` runs only SUMMA ``linalg.gemm`` on a (2, 2) mesh at 8192^3
float32 against the single-device product, and the mesh-sharded
``batched_cholesky`` against the local one.

Each phase prints one line: sizes, compile seconds (first call), steady
seconds (median over repeats, each ending in ``block_until_ready``), and
its error against the reference. Any failed phase makes the exit code
non-zero. The last line of standard output is the result:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Without a TPU (and without ``--small``) the script exits non-zero and
prints no result.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import statistics
import sys
import tempfile
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT / "tests"))

# LAPACK's test-ratio threshold (xCHKPO/xCHKQR) and HPL's residual bound
LAPACK_RATIO_MAX = 30.0
HPL_RESIDUAL_MAX = 16.0


class PhaseFailed(Exception):
    """A phase's result missed its reference."""


def sizes(small: bool) -> dict:
    if small:
        return dict(gemm=256, solve=256, qr=128, batch=64, bn=16,
                    seq=64, gbatch=2, four_gemm=256, four_batch=64)
    return dict(gemm=8192, solve=8192, qr=4096, batch=4096, bn=64,
                seq=2048, gbatch=8, four_gemm=8192, four_batch=4096)


def timed(fn, *args, reps: int = 3):
    """(output, compile seconds, median steady seconds, compiled HLO text).

    The function is jitted and compiled ahead of time; the first call after
    compiling is a warm-up, then ``reps`` calls each end in
    ``block_until_ready``."""
    import jax
    lowered = jax.jit(fn).lower(*args)
    t0 = time.perf_counter()
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    out = jax.block_until_ready(compiled(*args))
    steady = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = jax.block_until_ready(compiled(*args))
        steady.append(time.perf_counter() - t0)
    return out, compile_s, statistics.median(steady), compiled.as_text()


def report(name: str, **fields) -> None:
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[smoke] {name} {body}", flush=True)


def require(cond: bool, what: str) -> None:
    if not cond:
        raise PhaseFailed(what)


def require_kernel(hlo: str, on_tpu: bool, what: str) -> None:
    """On the chip a compiled Pallas kernel shows up as a Mosaic custom call;
    an interpret-mode kernel would have been lowered to plain HLO."""
    if on_tpu:
        require("tpu_custom_call" in hlo, f"{what}: no compiled Pallas kernel")


def max_err(got, want) -> float:
    """max |got - want|, on the device in float32."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x, y: jnp.max(jnp.abs(x.astype(jnp.float32)
                                             - y.astype(jnp.float32))))
    return float(f(got, want))


def within(got, want, rtol: float, atol: float) -> float:
    """max(|got - want| - (atol + rtol |want|)): <= 0 when every entry is
    within tolerance; on the device in float32."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x, y: jnp.max(
        jnp.abs(x.astype(jnp.float32) - y.astype(jnp.float32))
        - (atol + rtol * jnp.abs(y.astype(jnp.float32)))))
    return float(f(got, want))


# --------------------------------- phases -----------------------------------

def phase_gemm(sz, key, on_tpu):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from conftest import dtype_tolerances
    from repro import linalg
    from repro.tune import dispatch

    n = sz["gemm"]
    for dt in (jnp.bfloat16, jnp.float32):
        ka, kb = jax.random.split(jax.random.fold_in(key, n))
        a = jax.random.normal(ka, (n, n), jnp.float32).astype(dt)
        b = jax.random.normal(kb, (n, n), jnp.float32).astype(dt)
        res = dispatch.resolve("gemm", (n, n, n), dt, policy="model")
        plan = res.gemm_plan
        with linalg.use(policy="model"):
            got, c_s, s_s, hlo = timed(lambda x, y: linalg.gemm(x, y), a, b)
        require_kernel(hlo, on_tpu, f"gemm {jnp.dtype(dt).name}")
        with linalg.use(policy="reference"):
            want, rc_s, rs_s, _ = timed(lambda x, y: linalg.gemm(x, y), a, b)
        rtol, atol = dtype_tolerances(np.dtype(dt))
        atol *= math.sqrt(n)            # entries grow like sqrt(k)
        excess = within(got, want, rtol, atol)
        err = max_err(got, want)
        report(f"gemm.{jnp.dtype(dt).name}", m=n, n=n, k=n,
               blocks=f"{plan.bm}x{plan.bn}x{plan.bk}",
               source=res.source, compile_s=f"{c_s:.3f}",
               steady_s=f"{s_s:.6f}",
               tflops=f"{2 * n ** 3 / s_s / 1e12:.2f}",
               ref_compile_s=f"{rc_s:.3f}", ref_steady_s=f"{rs_s:.6f}",
               max_err=f"{err:.3e}", rtol=rtol, atol=f"{atol:.3e}")
        require(excess <= 0.0, f"gemm {jnp.dtype(dt).name} outside "
                               f"tolerance by {excess:.3e}")
        del a, b, got, want


def hpl_residual(a, x, b) -> float:
    """HPL's scaled residual ||Ax-b||_inf / (eps (||A||_inf ||x||_inf +
    ||b||_inf) n), in float64 on the host, eps the float32 unit roundoff."""
    import numpy as np
    a64 = np.asarray(a, np.float64)
    x64 = np.asarray(x, np.float64)
    b64 = np.asarray(b, np.float64)
    r = np.max(np.abs(a64 @ x64 - b64))
    eps = np.finfo(np.float32).eps / 2
    norm_a = np.max(np.sum(np.abs(a64), axis=1))
    return float(r / (eps * (norm_a * np.max(np.abs(x64))
                             + np.max(np.abs(b64))) * a64.shape[0]))


def phase_solve(sz, key, on_tpu):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import linalg
    from repro.lapack.lu import default_block
    from repro.tune import dispatch

    n = sz["solve"]
    ka, kb, ks = jax.random.split(jax.random.fold_in(key, n), 3)
    a = jax.random.normal(ka, (n, n), jnp.float32)
    b = jax.random.normal(kb, (n,), jnp.float32)
    nb = default_block(n, "getrf", jnp.float32)
    chain = dispatch.resolve("trsm+gemm", (n - nb, n - nb, nb), jnp.float32,
                             policy="model", form="lu")
    for pol in ("model", "reference"):
        with linalg.use(policy=pol):
            x, c_s, s_s, hlo = timed(lambda m, v: linalg.solve(m, v), a, b,
                                     reps=2)
        if pol == "model":
            require_kernel(hlo, on_tpu, "solve model")
        hpl = hpl_residual(a, x, b)
        report(f"solve.{pol}", n=n, dtype="float32", panel=nb,
               fused=chain.fused if pol == "model" else False,
               row_block=chain.block if pol == "model" else "-",
               compile_s=f"{c_s:.3f}", steady_s=f"{s_s:.6f}",
               hpl_residual=f"{hpl:.4f}", bound=HPL_RESIDUAL_MAX)
        require(hpl < HPL_RESIDUAL_MAX, f"solve {pol}: HPL residual {hpl}")
        del x

    # Cholesky of an SPD matrix: LAPACK's ||L L^T - A||_1 / (n ||A||_1 eps)
    g = jax.random.normal(ks, (n, n), jnp.float32)
    spd = jax.jit(lambda m: jnp.matmul(m, m.T, precision="highest") / n
                  + jnp.eye(n, dtype=m.dtype))(g)
    with linalg.use(policy="model"):
        l, c_s, s_s, hlo = timed(lambda m: linalg.cholesky(m), spd, reps=2)
    require_kernel(hlo, on_tpu, "cholesky model")
    ratio = lapack_ratio(lambda: np.asarray(l, np.float64)
                         @ np.asarray(l, np.float64).T, spd)
    report("cholesky.model", n=n, dtype="float32", compile_s=f"{c_s:.3f}",
           steady_s=f"{s_s:.6f}", lapack_ratio=f"{ratio:.4f}",
           bound=LAPACK_RATIO_MAX)
    require(ratio < LAPACK_RATIO_MAX, f"cholesky ratio {ratio}")
    del g, spd, l

    # QR: ||Q R - A||_1 / (n ||A||_1 eps) and ||Q^T Q - I||_1 / (n eps)
    nq = sz["qr"]
    aq = jax.random.normal(jax.random.fold_in(ka, nq), (nq, nq), jnp.float32)
    with linalg.use(policy="model"):
        (q, r), c_s, s_s, hlo = timed(lambda m: linalg.qr(m), aq, reps=2)
    require_kernel(hlo, on_tpu, "qr model")
    q64 = np.asarray(q, np.float64)
    ratio = lapack_ratio(lambda: q64 @ np.asarray(r, np.float64), aq)
    eps = np.finfo(np.float32).eps
    orth = float(np.max(np.sum(np.abs(q64.T @ q64 - np.eye(nq)), axis=0))
                 / (nq * eps))
    report("qr.model", n=nq, dtype="float32", compile_s=f"{c_s:.3f}",
           steady_s=f"{s_s:.6f}", lapack_ratio=f"{ratio:.4f}",
           orth_ratio=f"{orth:.4f}", bound=LAPACK_RATIO_MAX)
    require(ratio < LAPACK_RATIO_MAX and orth < LAPACK_RATIO_MAX,
            f"qr ratios {ratio}, {orth}")


def lapack_ratio(product, a) -> float:
    """||product() - A||_1 / (n ||A||_1 eps), float64 on the host."""
    import numpy as np
    a64 = np.asarray(a, np.float64)
    diff = product() - a64
    one = lambda m: float(np.max(np.sum(np.abs(m), axis=0)))  # noqa: E731
    return one(diff) / (a64.shape[0] * one(a64) * np.finfo(np.float32).eps)


def batch_inputs(key, batch, n):
    import jax
    import jax.numpy as jnp
    kg, kl, kq = jax.random.split(key, 3)
    g = jax.random.normal(kg, (batch, n, n), jnp.float32)
    eye = jnp.eye(n, dtype=jnp.float32)
    spd = jax.jit(lambda m: jnp.einsum("bij,bkj->bik", m, m,
                                       precision="highest") / n + eye)(g)
    gen = jax.random.normal(kl, (batch, n, n), jnp.float32) + 4.0 * eye
    rect = jax.random.normal(kq, (batch, n, n), jnp.float32)
    return spd, gen, rect


def factors_and_pivots(res):
    return res.factors, res.pivots


def phase_batched(sz, key, on_tpu):
    import numpy as np
    from conftest import dtype_tolerances
    from repro import linalg

    bsz, n = sz["batch"], sz["bn"]
    spd, gen, rect = batch_inputs(key, bsz, n)
    rtol, atol = dtype_tolerances(np.float32, scale=math.sqrt(n))
    cases = (("cholesky", linalg.batched_cholesky, spd),
             ("lu", linalg.batched_lu, gen),
             ("qr", linalg.batched_qr, rect))
    for name, fn, x in cases:
        outs = {}
        for pol in ("model", "reference"):
            with linalg.use(policy=pol):
                res, c_s, s_s, _ = timed(
                    lambda m, f=fn: factors_and_pivots(f(m)), x)
            outs[pol] = (res, c_s, s_s)
        (fm, pm), c_s, s_s = outs["model"]
        (fr, pr), rc_s, rs_s = outs["reference"]
        if pm is not None:
            require(np.array_equal(np.asarray(pm), np.asarray(pr)),
                    f"batched {name}: pivots differ")
        err = max_err(fm, fr)
        ok = within(fm, fr, rtol, atol) <= 0.0
        report(f"batched.{name}", batch=bsz, n=n, dtype="float32",
               compile_s=f"{c_s:.3f}", steady_s=f"{s_s:.6f}",
               ref_compile_s=f"{rc_s:.3f}", ref_steady_s=f"{rs_s:.6f}",
               max_err=f"{err:.3e}", rtol=f"{rtol:.1e}", atol=f"{atol:.1e}")
        require(ok, f"batched {name}: model vs reference max err {err}")


def phase_mamba(sz, seed, out_dir, small, on_tpu):
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import registry
    from repro.data.pipeline import DataConfig, make_batch
    from repro.launch.mesh import make_debug_mesh
    from repro.launch.train import reduce_config, train_loop
    from repro.models import model_zoo as zoo
    from repro.train.optimizer import AdamWConfig

    cfg = registry.get_config("mamba2-130m")
    if small:
        cfg = dataclasses.replace(reduce_config(cfg, 2, 64, 256, 2),
                                  ssm_head_dim=16, ssm_state=16, ssm_chunk=32)
    steps = 3
    data_cfg = DataConfig(vocab=cfg.vocab, global_batch=sz["gbatch"],
                          seq_len=sz["seq"], seed=seed)
    opt_cfg = AdamWConfig(warmup_steps=1, decay_steps=steps)
    mesh = make_debug_mesh(data=1, model=1)
    compiles = []

    def on_event(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(secs)

    # a fresh checkpoint directory: train_loop resumes from any checkpoint
    # it finds, and a rerun must train, not resume
    os.makedirs(out_dir, exist_ok=True)
    ckpt_dir = tempfile.mkdtemp(prefix="ckpt-", dir=out_dir)
    jax.monitoring.register_event_duration_secs_listener(on_event)
    t0 = time.perf_counter()
    try:
        state, losses = train_loop(cfg, opt_cfg, data_cfg, mesh, steps,
                                   ckpt_dir, log_every=1)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    train_s = time.perf_counter() - t0
    require(len(losses) == steps and all(np.isfinite(losses)),
            f"mamba2 train losses {losses}")
    report("mamba2.train", layers=cfg.n_layers, d_model=cfg.d_model,
           vocab=cfg.vocab, seq=sz["seq"], batch=sz["gbatch"], steps=steps,
           seconds=f"{train_s:.3f}", compile_s=f"{sum(compiles):.3f}",
           losses="/".join(f"{v:.4f}" for v in losses))

    # the compiled SSD kernel (use_pallas=None: Pallas on a TPU) against the
    # jnp chunked SSD the train step uses
    params = state["params"]
    batch = make_batch(cfg, data_cfg, 0)
    fwd = {}
    for name, use in (("kernel", None), ("jnp", False)):
        (logits, _), c_s, s_s, hlo = timed(
            lambda p, bt, u=use: zoo.forward(p, bt, cfg, use_pallas=u),
            params, batch)
        fwd[name] = (logits, c_s, s_s, hlo)
    logits, c_s, s_s, hlo = fwd["kernel"]
    require_kernel(hlo, on_tpu, "mamba2 forward")
    want = fwd["jnp"][0]
    err = max_err(logits, want)
    scale = float(jnp.max(jnp.abs(want.astype(jnp.float32))))
    rel = err / max(scale, 1e-30)
    report("mamba2.forward", seq=sz["seq"], batch=sz["gbatch"],
           compile_s=f"{c_s:.3f}", steady_s=f"{s_s:.6f}",
           jnp_steady_s=f"{fwd['jnp'][2]:.6f}", max_err=f"{err:.3e}",
           rel_err=f"{rel:.3e}", bound=5e-2)
    require(np.all(np.isfinite(np.asarray(logits, np.float32)))
            and rel < 5e-2, f"mamba2 kernel forward rel err {rel}")


def phase_four_chips(sz, key, on_tpu):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from conftest import dtype_tolerances
    from repro import linalg
    from repro.linalg.context import resolved_mesh

    ndev = len(jax.devices())
    require(ndev == 4, f"--four-chips needs 4 devices, found {ndev}")
    n = sz["four_gemm"]
    ka, kb, kc = jax.random.split(jax.random.fold_in(key, 4), 3)
    a = jax.random.normal(ka, (n, n), jnp.float32)
    b = jax.random.normal(kb, (n, n), jnp.float32)
    with linalg.use(policy="model", mesh=(2, 2)):
        mesh = resolved_mesh(linalg.get_context())
        got, c_s, s_s, hlo = timed(lambda x, y: linalg.gemm(x, y), a, b)
    mesh_devs = {d.id for d in mesh.devices.flat}
    out_devs = {d.id for d in got.sharding.device_set}
    require(len(mesh_devs) == 4 and out_devs == mesh_devs,
            f"SUMMA mesh devices {mesh_devs}, output on {out_devs}")
    require_kernel(hlo, on_tpu, "SUMMA gemm")
    with linalg.use(policy="model"):
        want, rc_s, rs_s, _ = timed(lambda x, y: linalg.gemm(x, y), a, b)
    rtol, atol = dtype_tolerances(np.float32)
    atol *= math.sqrt(n)
    err = max_err(got, want)
    ok = within(got, want, rtol, atol) <= 0.0
    report("summa.gemm", mesh="2x2", m=n, n=n, k=n, dtype="float32",
           devices=sorted(out_devs), compile_s=f"{c_s:.3f}",
           steady_s=f"{s_s:.6f}", tflops=f"{2 * n ** 3 / s_s / 1e12:.2f}",
           single_compile_s=f"{rc_s:.3f}", single_steady_s=f"{rs_s:.6f}",
           max_err=f"{err:.3e}", atol=f"{atol:.3e}")
    require(ok, f"SUMMA gemm vs single device max err {err}")

    bsz, nb = sz["four_batch"], sz["bn"]
    spd, _, _ = batch_inputs(kc, bsz, nb)
    with linalg.use(policy="model", mesh=(2, 2)):
        fs, c_s, s_s, _ = timed(lambda m: linalg.batched_cholesky(m).factors,
                                spd)
    with linalg.use(policy="model"):
        fl, rc_s, rs_s, _ = timed(lambda m: linalg.batched_cholesky(m).factors,
                                  spd)
    sh_devs = {d.id for d in fs.sharding.device_set}
    err = max_err(fs, fl)
    rtol, atol = dtype_tolerances(np.float32, scale=math.sqrt(nb))
    ok = within(fs, fl, rtol, atol) <= 0.0
    report("sharded.batched_cholesky", mesh="2x2", batch=bsz, n=nb,
           devices=sorted(sh_devs), compile_s=f"{c_s:.3f}",
           steady_s=f"{s_s:.6f}", local_steady_s=f"{rs_s:.6f}",
           max_err=f"{err:.3e}")
    require(len(sh_devs) == 4, f"sharded factors on devices {sh_devs}")
    require(ok, f"sharded batched_cholesky max err {err}")


# ---------------------------------- main ------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=str(ROOT / "smoke_out"),
                    help="directory for checkpoints")
    ap.add_argument("--small", action="store_true",
                    help="tiny sizes, any backend (CPU rehearsal)")
    ap.add_argument("--four-chips", action="store_true",
                    help="only the 4-chip SUMMA / sharded-batch phase")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.small:
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r}); "
              f"use --small for a CPU rehearsal", file=sys.stderr)
        return 2

    from repro.runtime.compile_cache import enable_compile_cache
    print(f"[smoke] device platform={dev.platform} kind={dev.device_kind!r} "
          f"count={len(jax.devices())} compile_cache={enable_compile_cache()}",
          flush=True)
    sz = sizes(args.small)
    key = jax.random.PRNGKey(args.seed)
    if args.four_chips:
        phases = [("four_chips", lambda: phase_four_chips(sz, key, on_tpu))]
    else:
        phases = [
            ("gemm", lambda: phase_gemm(sz, key, on_tpu)),
            ("solve", lambda: phase_solve(sz, key, on_tpu)),
            ("batched", lambda: phase_batched(sz, key, on_tpu)),
            ("mamba2", lambda: phase_mamba(sz, args.seed, args.out,
                                           args.small, on_tpu)),
        ]
    failed = []
    for name, run in phases:
        t0 = time.perf_counter()
        try:
            run()
        except Exception:        # reported, counted, and fails the run
            traceback.print_exc()
            failed.append(name)
        report(f"phase.{name}", ok=name not in failed,
               seconds=f"{time.perf_counter() - t0:.1f}")
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
