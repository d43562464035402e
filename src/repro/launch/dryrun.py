"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

``main`` forces 512 placeholder CPU devices before JAX initializes its
backend (the device count is locked at first use), so that
``make_production_mesh`` can build the production meshes; importing this
module changes nothing. For each cell this driver:

  1. builds the abstract inputs (ShapeDtypeStructs - zero allocation),
  2. jits the right step (train_step / prefill / serve_step) with the
     production in/out shardings,
  3. ``.lower().compile()`` - sharding mismatches, compile-time OOMs, or
     unsupported collectives fail HERE, which is the point,
  4. records memory_analysis + cost_analysis + the parsed collective bytes
     as a Roofline row (EXPERIMENTS.md sections Dry-run / Roofline).

Usage:
  python -m repro.launch.dryrun --arch minitron-8b --shape train_4k
  python -m repro.launch.dryrun --all --mesh both --out results/dryrun
"""
import argparse
import json
import os
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import registry
from repro.core import roofline as rl
from repro.distributed import sharding as sh
from repro.launch.mesh import make_production_mesh, mesh_name
from repro.models import model_zoo as zoo
from repro.models.frontends import frontend_tokens
from repro.train import train_state as ts
from repro.train.optimizer import AdamWConfig

_KEY_SPEC = jax.ShapeDtypeStruct((2,), jnp.uint32)


def _opt_cfg(cfg) -> AdamWConfig:
    return AdamWConfig(eight_bit=cfg.opt_8bit)


def abstract_state(cfg, opt_cfg):
    return jax.eval_shape(lambda k: ts.init_state(k, cfg, opt_cfg), _KEY_SPEC)


def abstract_caches(cfg, batch: int, max_len: int):
    if cfg.family == "encdec":
        params = jax.eval_shape(lambda k: zoo.init(k, cfg), _KEY_SPEC)
        mem = jax.ShapeDtypeStruct((batch, cfg.encoder_seq, cfg.d_model),
                                   jnp.bfloat16)
        return jax.eval_shape(
            lambda p, m: zoo.init_caches(p, cfg, batch, max_len, memory=m),
            params, mem)
    return jax.eval_shape(lambda: zoo.init_caches(None, cfg, batch, max_len))


def lower_cell(arch: str, shape_name: str, mesh, *, accum=None,
               model_axis_residual: bool = False, fsdp: bool = True,
               seq_shard_cache: bool = True, extra_tags=None,
               overrides=None):
    """Lower + compile one cell; returns (compiled, roofline_row).

    ``overrides``: dataclasses.replace kwargs applied to the arch config -
    the hillclimb knobs (remat_policy, accum_steps, dtype, ssm_chunk, ...).
    """
    import dataclasses as _dc
    cfg = registry.get_config(arch)
    if overrides:
        cfg = _dc.replace(cfg, **overrides)
    if accum is None:
        accum = cfg.accum_steps          # overrides-aware default
    shape = registry.SHAPE_BY_NAME[shape_name]
    kind, specs = registry.input_specs(arch, shape_name, accum=accum)
    opt_cfg = _opt_cfg(cfg)
    shard_fn = sh.make_shard_fn(mesh, model_axis_residual=model_axis_residual)
    chips = mesh.size
    n_params = zoo.param_count(cfg)
    n_active = zoo.active_param_count(cfg)
    tokens = shape.global_batch * shape.seq_len

    if kind == "train":
        state_abs = abstract_state(cfg, opt_cfg)
        st_specs = sh.state_specs(state_abs, mesh, fsdp=fsdp)
        st_sh = sh.to_shardings(st_specs, mesh)
        a = cfg.accum_steps if accum is None else accum
        b_specs = sh.batch_specs(specs, mesh, accum=max(a, 1))
        b_sh = sh.to_shardings(b_specs, mesh)
        step = ts.make_train_step(cfg, opt_cfg, shard_fn)
        jitted = jax.jit(step, in_shardings=(st_sh, b_sh),
                         out_shardings=(st_sh, None), donate_argnums=(0,))
        lowered = jitted.lower(state_abs, specs)
        model_flops = 6.0 * n_active * tokens
    elif kind == "prefill":
        params_abs = jax.eval_shape(lambda k: zoo.init(k, cfg), _KEY_SPEC)
        p_specs = sh.params_specs(params_abs, mesh, fsdp=fsdp)
        p_sh = sh.to_shardings(p_specs, mesh)
        b_specs = sh.batch_specs(specs, mesh, accum=1)
        b_sh = sh.to_shardings(b_specs, mesh)

        def prefill_step(params, batch):
            out = zoo.prefill(params, batch, cfg, shard_fn=shard_fn,
                              use_pallas=False)
            return out[0], out[2]                       # logits, caches

        jitted = jax.jit(prefill_step, in_shardings=(p_sh, b_sh))
        lowered = jitted.lower(params_abs, specs)
        model_flops = 2.0 * n_active * tokens
    else:  # decode
        params_abs = jax.eval_shape(lambda k: zoo.init(k, cfg), _KEY_SPEC)
        p_specs = sh.params_specs(params_abs, mesh, fsdp=fsdp)
        p_sh = sh.to_shardings(p_specs, mesh)
        caches_abs = abstract_caches(cfg, shape.global_batch, shape.seq_len)
        c_specs = sh.cache_specs(caches_abs, mesh, seq_shard=seq_shard_cache)
        c_sh = sh.to_shardings(c_specs, mesh)
        dp = sh.batch_axes(mesh)
        tok_spec = sh.batch_specs({"t": specs["token"]}, mesh)["t"]
        tok_sh = sh.to_shardings(tok_spec, mesh)

        def serve_step(params, token, caches, cache_index):
            return zoo.decode_step(params, token, cfg, caches, cache_index,
                                   shard_fn=shard_fn)

        jitted = jax.jit(serve_step,
                         in_shardings=(p_sh, tok_sh, c_sh, None),
                         out_shardings=(None, c_sh),
                         donate_argnums=(2,))
        lowered = jitted.lower(params_abs, specs["token"], caches_abs,
                               specs["cache_index"])
        model_flops = 2.0 * n_active * shape.global_batch

    t0 = time.time()
    compiled = lowered.compile()
    compile_s = time.time() - t0
    extra = {"compile_s": compile_s, "n_params": float(n_params),
             "n_active": float(n_active), "kind": kind,
             **(extra_tags or {})}
    row = rl.from_compiled(arch, shape_name, mesh_name(mesh), chips,
                           compiled, model_flops, extra=extra)
    return compiled, row


def main() -> None:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=registry.ARCHS)
    ap.add_argument("--shape", choices=[s.name for s in registry.SHAPES])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--mesh", choices=["pod", "multipod", "both"],
                    default="both")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--accum", type=int, default=None)
    ap.add_argument("--model-axis-residual", action="store_true")
    ap.add_argument("--no-seq-shard-cache", action="store_true")
    args = ap.parse_args()

    meshes = []
    if args.mesh in ("pod", "both"):
        meshes.append(("pod", make_production_mesh(multi_pod=False)))
    if args.mesh in ("multipod", "both"):
        meshes.append(("multipod", make_production_mesh(multi_pod=True)))

    if args.all:
        cells, skipped = registry.all_cells()
        for s in skipped:
            print(f"SKIP {s[0]} x {s[1]}: {s[2]}")
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    os.makedirs(args.out, exist_ok=True)
    for arch, shape in cells:
        for mname, mesh in meshes:
            tag = f"{arch}__{shape}__{mname}"
            path = os.path.join(args.out, tag + ".json")
            if os.path.exists(path):
                print(f"CACHED {tag}")
                continue
            print(f"LOWER  {tag} ...", flush=True)
            try:
                compiled, row = lower_cell(
                    arch, shape, mesh, accum=args.accum,
                    model_axis_residual=args.model_axis_residual,
                    seq_shard_cache=not args.no_seq_shard_cache)
                import gzip
                with gzip.open(os.path.join(args.out, tag + ".hlo.gz"),
                               "wt") as f:
                    f.write(compiled.as_text())
                mem = compiled.memory_analysis()
                print(f"  memory_analysis: {mem}")
                cost = compiled.cost_analysis()
                cost = cost[0] if isinstance(cost, list) else cost
                print(f"  flops={cost.get('flops', 0):.3e} "
                      f"bytes={cost.get('bytes accessed', 0):.3e}")
                print(f"  collectives: {row.coll_breakdown}")
                print(f"  terms: compute={row.compute_s * 1e3:.2f}ms "
                      f"memory={row.memory_s * 1e3:.2f}ms "
                      f"collective={row.collective_s * 1e3:.2f}ms "
                      f"dominant={row.dominant} "
                      f"roofline_frac={row.roofline_fraction:.3f}")
                with open(path, "w") as f:
                    json.dump(row.to_dict(), f, indent=1)
            except Exception:
                print(f"FAILED {tag}")
                traceback.print_exc()
                with open(os.path.join(args.out, tag + ".FAILED"), "w") as f:
                    f.write(traceback.format_exc())


if __name__ == "__main__":
    main()
