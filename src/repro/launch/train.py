"""Training launcher: ``python -m repro.launch.train --arch <id> ...``

Production loop shape: sharded state on the mesh, counter-based data
pipeline (each host generates its shard), atomic keep-N checkpointing with
restore-on-start (fault tolerance: a restarted job resumes from the latest
step automatically), heartbeat + straggler detection, gradient accumulation.

On this CPU container you run reduced configs (--layers/--d-model overrides
or --preset small); the full configs are exercised via the dry-run.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp

from repro.ckpt.manager import CheckpointManager
from repro.configs import registry
from repro.data.pipeline import DataConfig, make_batch
from repro.distributed import sharding as sh
from repro.launch.mesh import make_debug_mesh, make_production_mesh
from repro.models import model_zoo as zoo
from repro.runtime.fault_tolerance import Heartbeat, StragglerDetector
from repro.train import train_state as ts
from repro.train.optimizer import AdamWConfig


def reduce_config(cfg, layers=None, d_model=None, vocab=None, heads=None):
    """Shrink an assigned config to laptop scale, same family/topology."""
    upd = {}
    if layers:
        if cfg.layer_pattern:     # whole periods of the pattern
            period = len(cfg.layer_pattern)
            layers = -(-layers // period) * period
        upd["n_layers"] = layers
        upd["global_layers"] = tuple(
            i for i in cfg.global_layers if i < layers) or ((0,) if cfg.family == "hybrid" else ())
        if cfg.family == "encdec":
            upd["encoder_layers"] = max(2, layers // 2)
    if d_model:
        ratio = d_model / cfg.d_model
        upd["d_model"] = d_model
        upd["d_ff"] = max(32, int(cfg.d_ff * ratio)) if cfg.d_ff else 0
        upd["ssm_heads"] = 0                 # d_inner / ssm_head_dim
        if cfg.family == "moe":
            upd["d_expert"] = max(32, int((cfg.d_expert or cfg.d_ff) * ratio))
            upd["n_experts"] = min(cfg.n_experts, 8)
            upd["top_k"] = min(cfg.top_k, 2)
    if heads:
        upd["n_heads"] = heads
        upd["n_kv"] = max(1, min(cfg.n_kv, heads))
        upd["head_dim"] = (d_model or cfg.d_model) // heads
    if vocab:
        upd["vocab"] = vocab
    return dataclasses.replace(cfg, **upd)


def train_loop(cfg, opt_cfg, data_cfg, mesh, steps: int, ckpt_dir: str,
               save_interval: int = 50, log_every: int = 10,
               fail_at_step: int = -1, seed: int = 0):
    """Runs (or resumes) training; returns (final metrics, history)."""
    shard_fn = sh.make_shard_fn(mesh)
    mgr = CheckpointManager(ckpt_dir, save_interval=save_interval, keep=3)
    hb = Heartbeat(os.path.join(ckpt_dir, "heartbeat.json"))
    straggler = StragglerDetector()

    state_abs = jax.eval_shape(
        lambda k: ts.init_state(k, cfg, opt_cfg),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    st_specs = sh.state_specs(state_abs, mesh, fsdp=True)
    st_sh = sh.to_shardings(st_specs, mesh)

    restored, start = mgr.restore_latest(state_abs, shardings=st_sh)
    if restored is None:
        with mesh:
            state = jax.jit(
                lambda k: ts.init_state(k, cfg, opt_cfg),
                out_shardings=st_sh)(jax.random.PRNGKey(seed))
        start = 0
    else:
        state = restored
        start = start + 1
        print(f"[train] resumed from step {start - 1}")

    step_fn = jax.jit(ts.make_train_step(cfg, opt_cfg, shard_fn),
                      in_shardings=(st_sh, None), out_shardings=(st_sh, None),
                      donate_argnums=(0,))
    history = []
    accum = max(cfg.accum_steps, 1)
    for step in range(start, steps):
        if step == fail_at_step:
            from repro.runtime.fault_tolerance import SimulatedFailure
            raise SimulatedFailure(f"injected failure at step {step}")
        straggler.start()
        batch = make_batch(cfg, data_cfg, step, accum=accum)
        with mesh:
            state, metrics = step_fn(state, batch)
        loss = float(metrics["loss"])
        straggler.stop(step)
        hb.beat(step)
        history.append(loss)
        if step % log_every == 0 or step == steps - 1:
            print(f"[train] step {step:5d} loss {loss:.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} "
                  f"lr {float(metrics['lr']):.2e}", flush=True)
        if mgr.should_save(step) or step == steps - 1:
            mgr.save(step, state)
    print(f"[train] straggler report: {straggler.report()}")
    return state, history


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=registry.ARCHS, required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--vocab", type=int, default=4096)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="checkpoints")
    ap.add_argument("--mesh", choices=["none", "debug", "pod"],
                    default="debug")
    ap.add_argument("--full-config", action="store_true",
                    help="use the assigned full config (dry-run scale!)")
    args = ap.parse_args()

    cfg = registry.get_config(args.arch)
    if not args.full_config:
        cfg = reduce_config(cfg, args.layers, args.d_model, args.vocab,
                            args.heads)
        cfg = dataclasses.replace(cfg, accum_steps=1, dtype="float32")
    if args.mesh == "pod":
        mesh = make_production_mesh()
    elif args.mesh == "debug":
        n = len(jax.devices())
        mesh = make_debug_mesh(data=max(1, n // 2), model=min(2, n))
    else:
        mesh = make_debug_mesh(data=1, model=1)
    opt_cfg = AdamWConfig(lr=args.lr, eight_bit=cfg.opt_8bit,
                          warmup_steps=max(args.steps // 20, 5),
                          decay_steps=args.steps)
    data_cfg = DataConfig(vocab=cfg.vocab, global_batch=args.batch,
                          seq_len=args.seq)
    _, history = train_loop(cfg, opt_cfg, data_cfg, mesh, args.steps,
                            os.path.join(args.ckpt_dir, cfg.name))
    print(json.dumps({"first_loss": history[0], "last_loss": history[-1]}))


if __name__ == "__main__":
    main()
