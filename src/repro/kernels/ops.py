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

from repro.obs import counters as _counters
from repro.kernels import dotp as _dotp
from repro.kernels import flash_attention as _fa
from repro.kernels import gemm as _gemm
from repro.kernels import ref
from repro.kernels import ssd_scan as _ssd


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def interpret_mode() -> bool:
    """Whether Pallas kernels run in interpret mode: exactly when the
    default backend is not a TPU. Every kernel's ``interpret=None``
    default resolves here, and nothing above the kernels sets it, so a TPU
    run always compiles its kernels and a CPU run always interprets
    them."""
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
    _counters.inc("kernel.flash_attention")
    return _fa.attention(q, k, v, causal=causal, scale=scale,
                         q_offset=q_offset, window=window, kv_len=kv_len,
                         interpret=interpret,
                         **block_kw)


def ssd(x, a_log, B, C, chunk=None, use_pallas: Optional[bool] = None,
        interpret: Optional[bool] = None, return_state: bool = False):
    """SSD in model layout: x (B, L, H, P), a_log (B, L, H), B/C (B, L, H, N).
    Returns y (B, L, H, P), and with ``return_state`` the final state
    (B, H, P, N) in float32."""
    use = _on_tpu() if use_pallas is None else use_pallas
    if not use:
        return ref.ssd_chunked(x, a_log, B, C, chunk=chunk or 64,
                               return_state=return_state)
    _counters.inc("kernel.ssd_scan")
    xt = jnp.moveaxis(x, 2, 1)             # (B,H,L,P)
    at = jnp.moveaxis(a_log, 2, 1)         # (B,H,L)
    Bt = jnp.moveaxis(B, 2, 1)
    Ct = jnp.moveaxis(C, 2, 1)
    out = _ssd.ssd_scan(xt, at, Bt, Ct, chunk=chunk, interpret=interpret,
                        return_state=return_state)
    if return_state:
        return jnp.moveaxis(out[0], 1, 2), out[1]
    return jnp.moveaxis(out, 1, 2)
