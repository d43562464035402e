"""Compile the main Pallas kernels for a described TPU v5e, at real widths.

Nothing runs: each kernel is lowered and compiled by the TPU compiler for a
``v5e:2x2`` topology that is described, not attached, so the compiler's
tiling and VMEM refusals - which interpret mode cannot see - fail here at no
chip cost. Every compile must produce a Mosaic kernel (``tpu_custom_call``).

The topology is built in a module-scoped fixture (never at import): only
one process at a time may load the TPU library, and the test workers import
every test file.
"""
import contextlib
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.codesign import plan_fused_chain, plan_gemm
from repro.kernels import flash_attention, fused, gemm, ssd_scan
from repro.lapack.lu import default_block


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


def _compile(fn, *shapes):
    with _no_persistent_cache():
        return jax.jit(fn).lower(*shapes).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_planned_gemm_compiles(one_chip, dtype):
    n = 8192
    plan = plan_gemm(n, n, n, dtype=dtype)
    s = jax.ShapeDtypeStruct((n, n), dtype, sharding=one_chip)
    _assert_kernel(_compile(
        lambda a, b: gemm.gemm(a, b, plan=plan, interpret=False), s, s))


def test_gemm_bias_act_compiles(one_chip):
    n, dt = 4096, jnp.bfloat16
    s = jax.ShapeDtypeStruct((n, n), dt, sharding=one_chip)
    bias = jax.ShapeDtypeStruct((n,), dt, sharding=one_chip)
    _assert_kernel(_compile(
        lambda a, b, c: fused.gemm_bias_act(a, b, c, epilogue="gelu",
                                            interpret=False), s, s, bias))


@pytest.mark.parametrize("form", ["lu", "syrk"])
def test_trsm_gemm_compiles_at_planned_panel(one_chip, form):
    """The first trailing update of a blocked n = 8192 factorization, at
    the panel width and row block the planners choose."""
    n, dt = 8192, jnp.float32
    kind = "getrf" if form == "lu" else "potrf"
    nb = default_block(n, kind, dt)
    rest = n - nb
    chain = plan_fused_chain("trsm+gemm", rest, rest, nb, dtype=dt,
                             form=form)
    assert chain.fused_wins

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, dt, sharding=one_chip)

    if form == "lu":
        compiled = _compile(
            lambda l, ap, bl, c: fused.trsm_gemm(
                l, ap, bl, c, form="lu", unit_diag=True,
                row_block=chain.block, interpret=False),
            shape(nb, nb), shape(nb, rest), shape(rest, nb),
            shape(rest, rest))
    else:
        compiled = _compile(
            lambda l, ap, c: fused.trsm_gemm(
                l, ap, None, c, form="syrk", row_block=chain.block,
                interpret=False),
            shape(nb, nb), shape(nb, rest), shape(rest, rest))
    _assert_kernel(compiled)


def test_flash_attention_compiles(one_chip):
    s = jax.ShapeDtypeStruct((1, 32, 2048, 128), jnp.bfloat16,
                             sharding=one_chip)
    _assert_kernel(_compile(
        lambda q, k, v: flash_attention.attention(q, k, v, interpret=False),
        s, s, s))


def test_ssd_scan_compiles_at_mamba2_130m_widths(one_chip):
    b, h, seq, p, n = 1, 24, 2048, 64, 128

    def shape(*dims, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dt, sharding=one_chip)

    _assert_kernel(_compile(
        lambda x, a, bm, cm: ssd_scan.ssd_scan(x, a, bm, cm, chunk=256,
                                               interpret=False),
        shape(b, h, seq, p), shape(b, h, seq, dt=jnp.float32),
        shape(b, h, seq, n), shape(b, h, seq, n)))


def test_ssd_scan_with_final_state_compiles_at_granite_widths(one_chip):
    """granite-4.0-h-micro's prefill of 32768 tokens: 64 heads x P 64 x N
    128, chunk 256, the final state handed to decode."""
    b, h, seq, p, n = 1, 64, 32768, 64, 128

    def shape(*dims, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dt, sharding=one_chip)

    compiled = _compile(
        lambda x, a, bm, cm: ssd_scan.ssd_scan(x, a, bm, cm, chunk=256,
                                               interpret=False,
                                               return_state=True),
        shape(b, h, seq, p), shape(b, h, seq, dt=jnp.float32),
        shape(b, h, seq, n), shape(b, h, seq, n))
    _assert_kernel(compiled)
    state = compiled.out_info[1]
    assert state.shape == (b, h, p, n) and state.dtype == jnp.float32


def test_flash_attention_compiles_at_granite_widths(one_chip):
    """GQA 32 query / 8 KV heads, head_dim 64, 32768 positions, the
    softmax scale 1/64 (granite's attention_multiplier)."""
    q = jax.ShapeDtypeStruct((1, 32, 32768, 64), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 8, 32768, 64), jnp.bfloat16,
                              sharding=one_chip)
    _assert_kernel(_compile(
        lambda q_, k_, v_: flash_attention.attention(
            q_, k_, v_, scale=1 / 64, interpret=False), q, kv, kv))
