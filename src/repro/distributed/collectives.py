"""Manual collectives: compressed gradient sync and flash-decoding.

Two shard_map-level building blocks the pjit path cannot express on its own:

1. **int8-compressed gradient mean with error feedback.** Gradients are
   blockwise-quantized to int8 before crossing the (slow, cross-pod) link;
   the quantization residual is fed back into the next step's gradient
   (error feedback keeps SGD/Adam convergence - Karimireddy et al.). The
   collective moves 1/4 of the fp32 bytes; the HLO collective-bytes parser
   (core.roofline) sees exactly that reduction.

2. **Flash-decoding over a sequence-sharded KV cache.** Decode attention
   with the cache's S dim sharded over "model": each shard computes a
   partial softmax (m_i, l_i, o_i) over its chunk; the combine is two tiny
   collectives (pmax + psum) of (B, H, d)-sized tensors. This is the paper's
   'more parallel accumulators for the serial reduction' insight applied at
   cluster scale - and what fits mistral-large-123b's 1.5 TB decode cache.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from contextvars import ContextVar
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs as _obs
from repro.obs import counters as _counters

Q_BLOCK = 256


# ---------------------------------------------------------------------------
# trace-time collective metadata (the spmd_lint "record view")
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CollectiveRecord:
    """One trace-time communication/padding fact, kind-tagged.

    The distributed sibling of :class:`repro.tune.dispatch.Resolution`:
    everything here is computed in Python while shard_map traces, so a
    ``jax.make_jaxpr`` pass captures the full declared schedule without
    executing anything. ``repro.analysis.spmd_lint`` cross-checks these
    declarations against the jaxpr it traced (rules CC002/CC003/SH002).

    kinds: ``"ring_bcast"`` (one SUMMA panel movement - axis/size/src/
    hops/bytes), ``"pdgemm"`` (one whole pdgemm schedule - the global
    problem geometry ``info`` carries lets the analyzer re-price
    ``plan_pdgemm``'s collective term), ``"pad_batch"`` (one ragged-batch
    identity pad - ``info`` carries batch/pad/identity).
    """

    kind: str
    axis: Optional[str] = None
    size: int = 1
    src: int = 0
    hops: int = 0
    per_hop_bytes: int = 0
    wire_bytes: int = 0
    info: Optional[Dict] = None


_RECORD: "ContextVar[Optional[List[CollectiveRecord]]]" = ContextVar(
    "collective_record", default=None)


@contextlib.contextmanager
def record_collectives():
    """Collect every CollectiveRecord produced inside the scope.

    Mirrors :func:`repro.tune.dispatch.record_resolutions`: the records
    are emitted at trace time, so wrapping a ``jax.make_jaxpr`` call
    captures the declared collective schedule with no execution."""
    rec: List[CollectiveRecord] = []
    token = _RECORD.set(rec)
    try:
        yield rec
    finally:
        _RECORD.reset(token)


def emit_record(rec: CollectiveRecord) -> None:
    """Append to the active record_collectives() scope, if any (no-op
    otherwise - the hot path stays a single ContextVar read)."""
    lst = _RECORD.get()
    if lst is not None:
        lst.append(rec)


# ---------------------------------------------------------------------------
# ring broadcast: the SUMMA panel-movement primitive
# ---------------------------------------------------------------------------

def ring_bcast(val: jnp.ndarray, axis_name: str, size: int,
               src: int) -> jnp.ndarray:
    """Broadcast ``val`` from mesh index ``src`` along ``axis_name`` via a
    ring of ``size - 1`` :func:`jax.lax.ppermute` hops. Call inside
    shard_map.

    Each hop forwards the buffer one position around the ring; a device
    adopts the incoming value exactly when it is ``src``'s (step-th)
    successor, so after ``size - 1`` hops every participant holds ``src``'s
    panel. This is the pipelined alternative to a masked psum broadcast:
    hop ``t`` of panel ``s`` can overlap the local GEMM of panel ``s - 1``,
    and each hop moves only ``val.nbytes`` per link (see
    :func:`ring_bcast_bytes` - the accounting that
    :func:`repro.core.codesign.plan_pdgemm` prices).

    Observability: every call increments the ``collective.hops`` /
    ``collective.bytes`` counters and, under an active trace, records a
    ``collective.ring_bcast`` event with the per-hop panel bytes priced
    against the ambient machine's ``MemorySpec.ici_bw``. The accounting
    runs at *trace* time (this function executes inside shard_map
    tracing), so counts cover distinct traced schedules, not cached
    re-executions - see ``docs/observability.md``.
    """
    if size <= 1:
        emit_record(CollectiveRecord(
            kind="ring_bcast", axis=str(axis_name), size=int(size),
            src=int(src)))
        return val
    hops = size - 1
    n_elems = 1
    for d in val.shape:                     # static even on jit tracers
        n_elems *= int(d)
    panel_bytes = n_elems * jnp.dtype(val.dtype).itemsize
    wire_bytes = ring_bcast_bytes(panel_bytes, size)
    _counters.inc("collective.hops", hops)
    _counters.inc("collective.bytes", wire_bytes)
    emit_record(CollectiveRecord(
        kind="ring_bcast", axis=str(axis_name), size=int(size),
        src=int(src), hops=hops, per_hop_bytes=panel_bytes,
        wire_bytes=wire_bytes,
        info={"shape": list(val.shape),
              "dtype": jnp.dtype(val.dtype).name}))
    if _obs.enabled():
        attrs = {"axis": axis_name, "size": size, "src": int(src),
                 "hops": hops, "per_hop_bytes": panel_bytes,
                 "wire_bytes": wire_bytes, "shape": list(val.shape),
                 "dtype": jnp.dtype(val.dtype).name}
        try:
            from repro import arch          # lazy: avoid import cycle
            ici = arch.current_machine().memory.ici_bw
            if ici > 0:
                attrs.update(ici_bw=ici, modeled_hop_s=panel_bytes / ici,
                             modeled_s=wire_bytes / ici)
        except Exception:
            pass
        _obs.event("collective.ring_bcast", cat="collective", **attrs)
    idx = lax.axis_index(axis_name)
    perm = [((d - 1) % size, d) for d in range(size)]
    buf = val
    for step in range(size - 1):
        nxt = lax.ppermute(buf, axis_name, perm)
        buf = jnp.where(idx == (src + step + 1) % size, nxt, buf)
    return buf


def ring_bcast_bytes(panel_bytes: int, size: int) -> int:
    """On-wire bytes per participating link for one ring broadcast: the
    panel crosses ``size - 1`` hops, each carrying the full panel."""
    return int(panel_bytes) * max(int(size) - 1, 0)


def _quantize(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    flat = x.reshape(-1)
    pad = (-flat.size) % Q_BLOCK
    fp = jnp.pad(flat, (0, pad)).reshape(-1, Q_BLOCK)
    scale = jnp.max(jnp.abs(fp), axis=1, keepdims=True) / 127.0
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(fp / safe), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def _dequantize(q, scale, shape):
    fp = q.astype(jnp.float32) * scale
    n = 1
    for d in shape:
        n *= d
    return fp.reshape(-1)[:n].reshape(shape)


def compressed_mean(x: jnp.ndarray, err: jnp.ndarray, axis_name: str
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Mean of ``x`` over ``axis_name`` with int8 on-wire compression and
    error feedback. Call inside shard_map. Returns (mean, new_err)."""
    n = lax.psum(1, axis_name)
    y = x + err
    q, scale = _quantize(y)
    sent = _dequantize(q, scale, x.shape)
    new_err = y - sent                                  # feedback residual
    # on-wire: int8 codes all-gathered (bytes = n * size/4 vs fp32 ring 2x);
    qs = lax.all_gather(q, axis_name)                   # (n, blocks, Q)
    ss = lax.all_gather(scale, axis_name)
    total = jnp.sum(qs.astype(jnp.float32) * ss, axis=0)
    nel = 1
    for d in x.shape:
        nel *= d
    mean = total.reshape(-1)[:nel].reshape(x.shape) / n
    return mean, new_err


def compressed_grad_sync(mesh: Mesh, axis_name: str = "pod"):
    """jit-able pytree gradient mean over one mesh axis with compression.

    grads enter replicated over ``axis_name`` *per shard* semantics: inside
    shard_map each device holds its local gradient; returns the synced mean
    and the updated error-feedback buffers.
    """
    def sync(grads, errs):
        def one(g, e):
            return compressed_mean(g, e, axis_name)
        flat_g, td = jax.tree.flatten(grads)
        flat_e = td.flatten_up_to(errs)
        out = [one(g, e) for g, e in zip(flat_g, flat_e)]
        return td.unflatten([o[0] for o in out]), td.unflatten([o[1] for o in out])

    spec = P()                                          # replicated leaves
    return jax.shard_map(sync, mesh=mesh,
                         in_specs=(spec, spec), out_specs=(spec, spec),
                         check_vma=False)


# ---------------------------------------------------------------------------
# flash-decoding over a sequence-sharded cache
# ---------------------------------------------------------------------------

def _partial_softmax_attention(q, k, v, valid):
    """q (B,Hq,D); k,v (B,Hkv,Sc,D); valid (B,1,Sc) bool -> (o, m, l)."""
    b, hq, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    qf = q.astype(jnp.float32).reshape(b, hkv, g, d)
    s = jnp.einsum("bhgd,bhkd->bhgk", qf, k.astype(jnp.float32)) / (d ** 0.5)
    s = jnp.where(valid[:, :, None, :], s, -1e30)
    m = jnp.max(s, axis=-1)                              # (b,hkv,g)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhgk,bhkd->bhgd", p, v.astype(jnp.float32))
    return (o.reshape(b, hq, d), m.reshape(b, hq), l.reshape(b, hq))


def sharded_decode_attention(mesh: Mesh, dp_axes, kv_len_static: bool = False):
    """Builds decode_attn(q, k_cache, v_cache, kv_len) with the cache S dim
    sharded over "model" and batch over the DP axes.

    q: (B, Hq, D) replicated over "model"; caches (B, S, Hkv, D) sharded
    P(dp, "model", None, None). Output (B, Hq, D), "model"-replicated.
    """
    dp = tuple(dp_axes)
    n_model = mesh.shape["model"]

    def inner(q, k, v, kv_len):
        # per-shard chunk: S_local = S / n_model; global positions:
        idx = lax.axis_index("model")
        s_local = k.shape[1]
        kpos = idx * s_local + jnp.arange(s_local)
        valid = (kpos < kv_len)[None, None, :]
        kh = jnp.moveaxis(k, 2, 1)                      # (B,Hkv,Sc,D)
        vh = jnp.moveaxis(v, 2, 1)
        o, m, l = _partial_softmax_attention(q, kh, vh,
                                             jnp.broadcast_to(valid, (q.shape[0], 1, s_local)))
        m_g = lax.pmax(m, "model")                       # (B,Hq)
        corr = jnp.exp(m - m_g)
        l_g = lax.psum(l * corr, "model")
        o_g = lax.psum(o * corr[..., None], "model")
        safe = jnp.where(l_g > 0, l_g, 1.0)
        return (o_g / safe[..., None]).astype(q.dtype)

    qspec = P(dp if dp else None, None, None)
    kvspec = P(dp if dp else None, "model", None, None)
    return jax.shard_map(inner, mesh=mesh,
                         in_specs=(qspec, kvspec, kvspec, P()),
                         out_specs=qspec, check_vma=False)
