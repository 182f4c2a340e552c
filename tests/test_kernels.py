"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # clean env: seeded-sweep fallback, see the shim
    from _hypothesis_compat import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.core.codec import pack_nibbles
from repro.kernels.log_quant import (log_dequantize_pallas,
                                     log_quantize_pack_pallas,
                                     log_quantize_pallas)


# ---------------------------------------------------------------- log_quant
@pytest.mark.parametrize("shape", [(7,), (64, 32), (3, 48, 16), (1000,), (513, 7)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("bits", [4, 8, 12])
def test_log_quant_matches_ref(shape, dtype, bits):
    x = (jax.random.normal(jax.random.PRNGKey(0), shape) * 2.0).astype(dtype)
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)))
    got = log_quantize_pallas(x, scale, bits=bits, alpha=10.0, interpret=True)
    want = ref.log_quantize_ref(x, scale, bits, 10.0)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    back = log_dequantize_pallas(got, scale, bits=bits, alpha=10.0, interpret=True)
    back_ref = ref.log_dequantize_ref(want, scale, bits, 10.0)
    np.testing.assert_allclose(np.asarray(back), np.asarray(back_ref), atol=1e-6)


def test_log_quant_zero_scale():
    x = jnp.zeros((16, 16))
    got = log_quantize_pallas(x, jnp.float32(0.0), interpret=True)
    assert int(jnp.max(jnp.abs(got.astype(jnp.int32)))) == 0


@pytest.mark.parametrize("shape", [(7,), (64, 32), (3, 48, 16), (1001,), (513, 7)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("bits", [3, 4])
def test_fused_quantize_pack_matches_two_stage(shape, dtype, bits):
    """One-pallas_call fused path == quantize-then-pack reference, byte for
    byte — including the zero pad byte on odd sizes."""
    x = (jax.random.normal(jax.random.PRNGKey(7), shape) * 2.0).astype(dtype)
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)))
    got = log_quantize_pack_pallas(x, scale, bits=bits, alpha=10.0,
                                   interpret=True)
    want = pack_nibbles(ref.log_quantize_ref(x, scale, bits, 10.0))
    assert got.shape == ((x.size + 1) // 2,) and got.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_fused_quantize_pack_zero_scale():
    got = log_quantize_pack_pallas(jnp.ones((16, 16)), jnp.float32(0.0),
                                   interpret=True)
    # zero scale falls back to scale 1.0, same as the unfused kernel
    want = pack_nibbles(ref.log_quantize_ref(jnp.ones((16, 16)),
                                             jnp.float32(0.0), 4, 10.0))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_fused_quantize_pack_rejects_wide_bits():
    with pytest.raises(ValueError, match="bits <= 4"):
        log_quantize_pack_pallas(jnp.ones(8), jnp.float32(1.0), bits=8,
                                 interpret=True)


@settings(max_examples=25, deadline=None)
@given(n=st.integers(1, 2048), bits=st.integers(3, 8),
       alpha=st.floats(0.5, 50.0), seed=st.integers(0, 999))
def test_log_quant_property(n, bits, alpha, seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (n,))
    scale = jnp.max(jnp.abs(x))
    got = log_quantize_pallas(x, scale, bits=bits, alpha=alpha, interpret=True)
    want = ref.log_quantize_ref(x, scale, bits, alpha)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("platform, interpret", [("cpu", True), ("tpu", False)])
def test_pallas_interpret_follows_the_backend(monkeypatch, platform, interpret):
    from repro.kernels import backend
    monkeypatch.setattr(backend.jax, "default_backend", lambda: platform)
    assert backend.pallas_interpret() is interpret


def test_pallas_interpret_refuses_other_backends(monkeypatch):
    """A GPU has no compiled kernels here; interpreting there would hide
    which device ran the work."""
    from repro.kernels import backend
    monkeypatch.setattr(backend.jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        backend.pallas_interpret()


# ----------------------------------------------------------- flash attention
@pytest.mark.parametrize("b,hq,hkv,s,d", [
    (1, 2, 2, 64, 32),     # MHA
    (2, 4, 2, 128, 64),    # GQA 2:1
    (1, 8, 1, 96, 64),     # MQA, unaligned seq
    (1, 4, 4, 33, 128),    # odd seq
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_matches_ref_causal(b, hq, hkv, s, d, dtype):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(kq, (b, hq, s, d)).astype(dtype)
    k = jax.random.normal(kk, (b, hkv, s, d)).astype(dtype)
    v = jax.random.normal(kv, (b, hkv, s, d)).astype(dtype)
    got = flash_attention_pallas(q, k, v, causal=True, block_q=32, block_k=32,
                                 interpret=True)
    want = ref.attention_ref(q, k, v, causal=True)
    atol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol)


@pytest.mark.parametrize("window", [1, 16, 64, 1000])
def test_flash_sliding_window(window):
    b, h, s, d = 1, 2, 80, 32
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(kq, (b, h, s, d))
    k = jax.random.normal(kk, (b, h, s, d))
    v = jax.random.normal(kv, (b, h, s, d))
    got = flash_attention_pallas(q, k, v, causal=True, window=window,
                                 block_q=16, block_k=16, interpret=True)
    want = ref.attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)


@pytest.mark.parametrize("block_q,block_k", [(16, 32), (64, 16), (128, 128)])
def test_flash_block_shape_invariance(block_q, block_k):
    """Output must not depend on tiling."""
    b, h, s, d = 1, 2, 100, 64
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(kq, (b, h, s, d))
    k = jax.random.normal(kk, (b, h, s, d))
    v = jax.random.normal(kv, (b, h, s, d))
    got = flash_attention_pallas(q, k, v, block_q=block_q, block_k=block_k,
                                 interpret=True)
    want = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)


def test_flash_scale_override():
    b, h, s, d = 1, 1, 32, 16
    q = jax.random.normal(jax.random.PRNGKey(4), (b, h, s, d))
    got = flash_attention_pallas(q, q, q, sm_scale=0.5, block_q=16, block_k=16,
                                 interpret=True)
    want = ref.attention_ref(q, q, q, causal=True, scale=0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)


# ------------------------------------------------------------- ssd_chunk
def _ssd_diag_oracle(x, a_cum, bm, cm):
    """Einsum oracle for the intra-chunk SSD term (matches ssm.ssd_chunked's
    y_diag with pre-chunked inputs)."""
    seg = a_cum[..., :, None] - a_cum[..., None, :]
    q = a_cum.shape[-1]
    i = jnp.arange(q)[:, None]
    j = jnp.arange(q)[None, :]
    L = jnp.where(i >= j, jnp.exp(seg), 0.0)         # (B,H,NC,Q,Q)
    s = jnp.einsum("bhcqn,bhckn->bhcqk", cm, bm)
    return jnp.einsum("bhcqk,bhckp->bhcqp", s * L, x)


@pytest.mark.parametrize("b,h,nc,q,p,n", [
    (1, 2, 3, 16, 8, 4), (2, 1, 2, 32, 16, 8), (1, 3, 1, 64, 32, 16),
])
def test_ssd_chunk_matches_oracle(b, h, nc, q, p, n):
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    x = jax.random.normal(ks[0], (b, h, nc, q, p))
    a = -jnp.cumsum(jnp.abs(jax.random.normal(ks[1], (b, h, nc, q))) * 0.1, -1)
    bm = jax.random.normal(ks[2], (b, h, nc, q, n))
    cm = jax.random.normal(ks[3], (b, h, nc, q, n))
    from repro.kernels.ssd_chunk import ssd_chunk_pallas
    got = ssd_chunk_pallas(x, a, bm, cm, interpret=True)
    want = _ssd_diag_oracle(x, a, bm, cm)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=1e-4)


def test_ssd_chunk_consistent_with_model_ssd():
    """Zero inter-chunk state (decay-isolated chunks) => ssd_chunked ==
    the kernel's intra-chunk term."""
    from repro.kernels.ssd_chunk import ssd_chunk_pallas
    from repro.models.ssm import ssd_chunked
    b, s, h, p, n, q = 1, 32, 2, 8, 4, 8
    ks = jax.random.split(jax.random.PRNGKey(6), 4)
    x = jax.random.normal(ks[0], (b, s, h, p))
    # strongly negative decay at chunk starts isolates chunks
    a = jnp.full((b, s, h), -0.05).at[:, ::q, :].set(-50.0)
    bm = jax.random.normal(ks[2], (b, s, h, n))
    cm = jax.random.normal(ks[3], (b, s, h, n))
    y_full, _ = ssd_chunked(x, a, bm, cm, q)
    nc = s // q
    xc = x.reshape(b, nc, q, h, p).transpose(0, 3, 1, 2, 4)
    ac = jnp.cumsum(a.reshape(b, nc, q, h).transpose(0, 3, 1, 2), -1)
    bc = bm.reshape(b, nc, q, h, n).transpose(0, 3, 1, 2, 4)
    cc = cm.reshape(b, nc, q, h, n).transpose(0, 3, 1, 2, 4)
    y_k = ssd_chunk_pallas(xc, ac, bc, cc, interpret=True)
    y_k = y_k.transpose(0, 2, 3, 1, 4).reshape(b, s, h, p)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_full),
                               atol=2e-4, rtol=1e-3)
