"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret=True."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.codesign import plan_gemm
from repro.kernels import ops, ref
from repro.kernels.dotp import dotp as dotp_kernel
from repro.kernels.flash_attention import attention as fa_kernel
from repro.kernels.gemm import gemm as gemm_kernel
from repro.kernels.ssd_scan import ssd_scan


def _tol(dtype):
    return dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 \
        else dict(atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,n,k", [(128, 128, 128), (200, 150, 300),
                                   (64, 256, 512), (37, 53, 71)])
def test_gemm_kernel_sweep(rng, m, n, k, dtype):
    a = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32)).astype(dtype)
    b = jnp.asarray(rng.normal(size=(k, n)).astype(np.float32)).astype(dtype)
    got = gemm_kernel(a, b, interpret=True)
    want = ref.gemm(a, b)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_gemm_kernel_uses_plan(rng):
    plan = plan_gemm(256, 256, 256, dtype_bytes=4)
    a = jnp.asarray(rng.normal(size=(256, 256)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(256, 256)).astype(np.float32))
    got = gemm_kernel(a, b, plan=plan, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(a @ b), atol=1e-3,
                               rtol=1e-3)


@pytest.mark.parametrize("n", [128, 1000, 4096, 131])
@pytest.mark.parametrize("u", [1, 4, 8])
def test_dotp_kernel_sweep(rng, n, u):
    x = jnp.asarray(rng.normal(size=n).astype(np.float32))
    y = jnp.asarray(rng.normal(size=n).astype(np.float32))
    got = float(dotp_kernel(x, y, accumulators=u, interpret=True))
    want = float(np.dot(np.asarray(x, np.float64), np.asarray(y, np.float64)))
    assert got == pytest.approx(want, rel=1e-4, abs=1e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2)])
@pytest.mark.parametrize("causal,window", [(True, None), (False, None),
                                           (True, 40)])
def test_flash_attention_sweep(rng, dtype, hq, hkv, causal, window):
    b, s, d = 2, 96, 64
    q = jnp.asarray(rng.normal(size=(b, hq, s, d)).astype(np.float32)).astype(dtype)
    k = jnp.asarray(rng.normal(size=(b, hkv, s, d)).astype(np.float32)).astype(dtype)
    v = jnp.asarray(rng.normal(size=(b, hkv, s, d)).astype(np.float32)).astype(dtype)
    got = fa_kernel(q, k, v, causal=causal, window=window, block_q=16,
                    block_k=32, interpret=True)
    want = ref.attention(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_flash_attention_decode(rng):
    b, hq, hkv, s, d = 2, 8, 2, 160, 64
    q = jnp.asarray(rng.normal(size=(b, hq, 1, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, hkv, s, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(b, hkv, s, d)).astype(np.float32))
    got = fa_kernel(q, k, v, causal=True, q_offset=s - 1, block_q=8,
                    block_k=64, interpret=True)
    want = ref.attention(q, k, v, causal=True, q_offset=s - 1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-4)


def test_flash_attention_kv_len_mask(rng):
    """Padded cache: only kv_len entries participate."""
    b, h, s, d = 1, 2, 128, 32
    q = jnp.asarray(rng.normal(size=(b, h, 1, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, h, s, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(b, h, s, d)).astype(np.float32))
    kv_len = 70
    got = fa_kernel(q, k, v, causal=False, kv_len=kv_len, block_q=8,
                    block_k=32, interpret=True)
    want = ref.attention(q, k[:, :, :kv_len], v[:, :, :kv_len], causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-4)


@pytest.mark.parametrize("L,chunk", [(64, 16), (100, 32), (256, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_ssd_kernel_sweep(rng, L, chunk, dtype):
    b, h, p, n = 2, 3, 16, 8
    x = jnp.asarray(rng.normal(size=(b, h, L, p)).astype(np.float32)) * 0.5
    a = -jnp.abs(jnp.asarray(rng.normal(size=(b, h, L)).astype(np.float32))) * 0.3
    B = jnp.asarray(rng.normal(size=(b, h, L, n)).astype(np.float32)) * 0.5
    C = jnp.asarray(rng.normal(size=(b, h, L, n)).astype(np.float32)) * 0.5
    got = ssd_scan(x, a, B, C, chunk=chunk, interpret=True)
    # oracle on (B, L, H, ...) layout
    tr = lambda t: jnp.moveaxis(t, 1, 2)
    want = tr(ref.ssd(tr(x), jnp.moveaxis(a, 1, 2), tr(B), tr(C)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-4)


@pytest.mark.parametrize("L,chunk", [(64, 16), (100, 32), (256, 64),
                                     (200, 128)])
def test_ssd_kernel_final_state_matches_oracle(rng, L, chunk):
    """The kernel's final (P, N) state per (batch, head), what a prefill
    hands to decode, against the recurrence's; L = 100 and 200 are padded
    to whole chunks (padding decays by 1 and adds nothing)."""
    b, h, p, n = 2, 3, 16, 8
    x = jnp.asarray(rng.normal(size=(b, h, L, p)).astype(np.float32)) * 0.5
    a = -jnp.abs(jnp.asarray(rng.normal(size=(b, h, L)).astype(np.float32))) * 0.1
    B = jnp.asarray(rng.normal(size=(b, h, L, n)).astype(np.float32)) * 0.5
    C = jnp.asarray(rng.normal(size=(b, h, L, n)).astype(np.float32)) * 0.5
    y, state = ssd_scan(x, a, B, C, chunk=chunk, interpret=True,
                        return_state=True)
    tr = lambda t: jnp.moveaxis(t, 1, 2)
    want_y, want_state = ref.ssd(tr(x), tr(a), tr(B), tr(C),
                                 return_state=True)
    assert state.shape == (b, h, p, n) and state.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(state), np.asarray(want_state),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(y), np.asarray(tr(want_y)),
                               atol=5e-4)
    # the model-layout wrapper hands back the same state, kernel or oracle
    for use_pallas in (True, False):
        _, got = ops.ssd(tr(x), tr(a), tr(B), tr(C), chunk=chunk,
                         use_pallas=use_pallas, interpret=True,
                         return_state=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want_state),
                                   rtol=1e-4, atol=1e-4)


def test_kernel_counters_count_traced_kernel_calls(rng):
    from repro.obs import counters
    x = jnp.asarray(rng.normal(size=(1, 16, 2, 8)).astype(np.float32))
    a = -jnp.abs(jnp.asarray(rng.normal(size=(1, 16, 2)).astype(np.float32)))
    q = jnp.asarray(rng.normal(size=(1, 2, 16, 8)).astype(np.float32))
    before = counters.snapshot()
    ops.ssd(x, a, x, x, chunk=8, use_pallas=True, interpret=True)
    ops.attention(q, q, q, use_pallas=True, interpret=True)
    ops.ssd(x, a, x, x, chunk=8, use_pallas=False)
    ops.attention(q, q, q, use_pallas=False)
    assert counters.delta(before) == {"kernel.ssd_scan": 1,
                                      "kernel.flash_attention": 1}


def test_ssd_chunk_invariance(rng):
    """Chunk size must not change the math (fig.-1 eq. of SSD)."""
    b, h, L, p, n = 1, 2, 96, 8, 4
    x = jnp.asarray(rng.normal(size=(b, L, h, p)).astype(np.float32)) * 0.3
    a = -jnp.abs(jnp.asarray(rng.normal(size=(b, L, h)).astype(np.float32))) * 0.2
    B = jnp.asarray(rng.normal(size=(b, L, h, n)).astype(np.float32)) * 0.3
    C = jnp.asarray(rng.normal(size=(b, L, h, n)).astype(np.float32)) * 0.3
    outs = [np.asarray(ref.ssd_chunked(x, a, B, C, chunk=c))
            for c in (8, 24, 96)]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], atol=3e-4)


@given(sq=st.integers(1, 80), sk=st.integers(8, 160))
@settings(max_examples=12, deadline=None)
def test_property_blocked_attention_matches_ref(sq, sk):
    rng = np.random.default_rng(sq * 1000 + sk)
    q = jnp.asarray(rng.normal(size=(1, 2, sq, 16)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 2, sk, 16)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(1, 2, sk, 16)).astype(np.float32))
    off = max(sk - sq, 0)
    a = ref.attention(q, k, v, causal=True, q_offset=off)
    b = ref.blocked_attention(q, k, v, causal=True, q_offset=off, block_k=32)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


def test_ops_dispatch_cpu_paths(rng):
    """ops.* with use_pallas=None on CPU must take the oracle path."""
    a = jnp.asarray(rng.normal(size=(32, 32)).astype(np.float32))
    out = ops.gemm(a, a)
    np.testing.assert_allclose(np.asarray(out), np.asarray(a @ a), atol=1e-4,
                               rtol=1e-4)
