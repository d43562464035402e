"""Public jit'd wrappers over the Pallas kernels, with oracle dispatch.

Every op takes ``use_pallas`` (default True on TPU backends, False
elsewhere) so model code calls one API and gets: the Pallas kernel on TPU,
``interpret=True`` Pallas in kernel tests, and the pure-jnp oracle inside
the distributed CPU lowering path (where interpret-mode pallas_call cannot
be partitioned).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import dotp as _dotp
from repro.kernels import flash_attention as _fa
from repro.kernels import gemm as _gemm
from repro.kernels import ref
from repro.kernels import ssd_scan as _ssd


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def interpret_mode() -> bool:
    """Whether Pallas kernels run in interpret mode: exactly when the
    default backend is not a TPU. Every ``interpret=None`` default in the
    kernels, the BLAS/LAPACK drivers and :mod:`repro.linalg`'s context
    resolves here, so a TPU run always compiles its kernels and a CPU run
    always interprets them."""
    return not _on_tpu()


def gemm(a, b, plan=None, use_pallas: Optional[bool] = None,
         interpret: Optional[bool] = None):
    use = _on_tpu() if use_pallas is None else use_pallas
    if not use:
        return ref.gemm(a, b)
    return _gemm.gemm(a, b, plan=plan,
                      interpret=interpret)


def dotp(x, y, accumulators=None, use_pallas: Optional[bool] = None,
         interpret: Optional[bool] = None):
    use = _on_tpu() if use_pallas is None else use_pallas
    if not use:
        return ref.dotp(x, y)
    return _dotp.dotp(x, y, accumulators=accumulators,
                      interpret=interpret)


BLOCKED_ATTN_THRESHOLD = 2048


def attention(q, k, v, causal: bool = True, scale=None, q_offset: int = 0,
              window=None, kv_len=None, use_pallas: Optional[bool] = None,
              interpret: Optional[bool] = None, **block_kw):
    use = _on_tpu() if use_pallas is None else use_pallas
    if not use:
        if (window is not None and causal and q_offset == 0
                and q.shape[2] == k.shape[2]
                and k.shape[2] >= 4 * window):
            # banded path: O(S*2w) flops/bytes instead of O(S^2)
            return ref.banded_attention(q, k, v, window, scale=scale)
        if k.shape[2] >= BLOCKED_ATTN_THRESHOLD:
            # streaming path: O(S*block) memory, SPMD-partitionable
            return ref.blocked_attention(q, k, v, causal=causal, scale=scale,
                                         q_offset=q_offset, window=window)
        return ref.attention(q, k, v, causal=causal, scale=scale,
                             q_offset=q_offset, window=window)
    return _fa.attention(q, k, v, causal=causal, scale=scale,
                         q_offset=q_offset, window=window, kv_len=kv_len,
                         interpret=interpret,
                         **block_kw)


def ssd(x, a_log, B, C, chunk=None, use_pallas: Optional[bool] = None,
        interpret: Optional[bool] = None):
    """SSD in model layout: x (B, L, H, P), a_log (B, L, H), B/C (B, L, H, N).
    Returns y (B, L, H, P)."""
    use = _on_tpu() if use_pallas is None else use_pallas
    if not use:
        return ref.ssd_chunked(x, a_log, B, C, chunk=chunk or 64)
    xt = jnp.moveaxis(x, 2, 1)             # (B,H,L,P)
    at = jnp.moveaxis(a_log, 2, 1)         # (B,H,L)
    Bt = jnp.moveaxis(B, 2, 1)
    Ct = jnp.moveaxis(C, 2, 1)
    y = _ssd.ssd_scan(xt, at, Bt, Ct, chunk=chunk,
                      interpret=interpret)
    return jnp.moveaxis(y, 1, 2)
