"""Serving launcher: batched prefill + decode with a request queue.

``python -m repro.launch.serve --arch mamba2-130m --requests 8``

Implements the serving loop the decode shapes lower: a continuous-batching-
lite scheduler - requests with different prompt lengths are left-padded into
a batch, prefilled once, then decoded step-by-step with donated caches;
finished sequences are masked out. On the production mesh the same
serve_step runs with sequence-sharded KV caches (launch.dryrun lowers it).

The request loop runs under :func:`repro.linalg.use`, so a caller-supplied
``context`` (e.g. ``ExecutionContext(obs=trace)``) scopes the whole batch:
every routine the models reach through :mod:`repro.linalg` traces into the
ambient :mod:`repro.obs` capture, and the loop itself records
``serve.batch`` / ``serve.prefill`` / ``serve.decode`` spans plus one
``serve.request`` event per finished request.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro import linalg
from repro import obs as _obs
from repro.configs import registry
from repro.launch.train import reduce_config
from repro.models import model_zoo as zoo


@dataclasses.dataclass
class Request:
    prompt: np.ndarray          # (len,) int32
    max_new: int


def serve_batch(params, cfg, requests: List[Request], max_len: int,
                temperature: float = 0.0, seed: int = 0, context=None):
    """Prefill + decode a batch of requests; returns list of token arrays.

    ``context`` scopes the whole batch through :func:`repro.linalg.use`
    (``None`` inherits the ambient context), so an ``obs``-carrying
    context traces every linalg routine the request loop reaches - and
    the serve spans themselves route into the same capture
    (``obs=False`` suppresses an ambient trace for the whole batch).
    """
    import contextlib

    from repro.linalg.context import current, resolved_obs

    with contextlib.ExitStack() as st:
        st.enter_context(linalg.use(context))
        tr = resolved_obs(current())
        if tr is not _obs.current_trace():
            st.enter_context(_obs.capture(tr))
        return _serve_batch(params, cfg, requests, max_len,
                            temperature=temperature, seed=seed)


def _serve_batch(params, cfg, requests: List[Request], max_len: int,
                 temperature: float = 0.0, seed: int = 0):
    b = len(requests)
    plens = np.array([len(r.prompt) for r in requests])
    pmax = int(plens.max())
    toks = np.zeros((b, pmax), np.int32)           # right-aligned prompts
    for i, r in enumerate(requests):
        toks[i, pmax - len(r.prompt):] = r.prompt
    batch = {"tokens": jnp.asarray(toks)}

    with _obs.span("serve.batch", cat="serve", requests=b, max_len=max_len,
                   model=cfg.name, prompt_max=pmax):
        with _obs.span("serve.prefill", cat="serve", batch=b,
                       prompt_max=pmax):
            step = jax.jit(lambda p, t, c, i: zoo.decode_step(p, t, cfg, c, i))
            if cfg.layer_pattern:
                # a patterned stack's prefill hands decode its caches (KV
                # of max_len slots, SSM state and conv tail) directly
                last, _, caches = zoo.prefill(params, batch, cfg,
                                              use_pallas=False,
                                              max_len=max_len)
            else:
                # replay prompts through decode_step to fill caches (simple
                # + exact; the other families' prefill returns no cache in
                # decode's layout)
                caches = zoo.init_caches(params, cfg, b, max_len)
                for t in range(pmax):
                    last, caches = step(params, jnp.asarray(toks[:, t:t + 1]),
                                        caches, jnp.int32(t))

        key = jax.random.PRNGKey(seed)
        out = [list(r.prompt) for r in requests]
        done = np.zeros(b, bool)
        max_new = max(r.max_new for r in requests)
        t0 = time.perf_counter()
        cur = last
        with _obs.span("serve.decode", cat="serve", batch=b,
                       max_new=max_new) as dec:
            for n in range(max_new):
                lg = cur[:, -1].astype(jnp.float32)
                if temperature > 0:
                    key, sub = jax.random.split(key)
                    nxt = jax.random.categorical(sub, lg / temperature)
                else:
                    nxt = jnp.argmax(lg, -1)
                nxt = np.asarray(nxt, np.int32)
                for i in range(b):
                    if not done[i]:
                        out[i].append(int(nxt[i]))
                        if (len(out[i]) - len(requests[i].prompt)
                                >= requests[i].max_new):
                            done[i] = True
                            _obs.event("serve.request", cat="serve", index=i,
                                       prompt_len=int(plens[i]),
                                       new_tokens=len(out[i]) - int(plens[i]))
                if done.all():
                    break
                cur, caches = step(params, jnp.asarray(nxt)[:, None], caches,
                                   jnp.int32(pmax + n))
            dt = time.perf_counter() - t0
            tok_s = (b * (n + 1)) / max(dt, 1e-9)
            dec.annotate(steps=n + 1, decode_tokens_per_s=tok_s)
    return out, {"decode_tokens_per_s": tok_s, "steps": n + 1}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=registry.ARCHS, default="mamba2-130m")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--d-model", type=int, default=128)
    args = ap.parse_args()

    cfg = reduce_config(registry.get_config(args.arch), args.layers,
                        args.d_model, vocab=512, heads=4)
    cfg = dataclasses.replace(cfg, dtype="float32")
    params = zoo.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    reqs = [Request(rng.integers(0, cfg.vocab, size=rng.integers(4, 12)
                                 ).astype(np.int32), args.max_new)
            for _ in range(args.requests)]
    outs, stats = serve_batch(params, cfg, reqs, max_len=64)
    for i, o in enumerate(outs):
        print(f"req{i}: prompt={len(reqs[i].prompt)} -> {len(o)} tokens")
    print(stats)


if __name__ == "__main__":
    main()
