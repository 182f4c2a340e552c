"""Ahead-of-time compiles of the main-path Pallas kernels for TPU v5e.

The TPU compiler is installed even where no chip is attached, so each
kernel here is lowered with ``interpret=False`` and compiled for a chip of
a described ``v5e:2x2`` topology, at the widths the training step and the
serving cache use. A compile that passes says the kernel fits the chip's
tiling and memory; it runs nothing and measures no time. The chunked SSD
(jnp einsums, no kernel) is compiled too, and held to the compiler's own
count of its FLOPs and temporaries.

The topology is described inside a module fixture, never on import: only
one process at a time may load the TPU library, and pytest-xdist workers
import every test file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import log_quant
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.ssd_chunk import ssd_chunk_pallas
from repro.models.ssm import ssd_chunked

# mamba2-370m LQ-SGD factors: the tied embedding's P (vocab x rank 1) and
# the in_proj Q stacked over 48 layers (2*2048 + 2*128 + 32 = 4384 wide)
FACTOR_SHAPES = [(50280, 1), (48, 4384, 1)]
HEAD_DIM = 128
CACHE_ROWS = 8192  # quantized KV-cache blocks, one token's head_dim each


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs in /tmp
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, sharding, *shapes_dtypes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes_dtypes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("shape", FACTOR_SHAPES)
@pytest.mark.parametrize(
    "kernel, bits, dtype",
    [
        (log_quant.log_quantize_pallas, 8, jnp.float32),
        (log_quant.log_dequantize_pallas, 8, jnp.float32),
        (log_quant.log_quantize_pack_pallas, 4, jnp.float32),
    ],
    ids=["log_quantize", "log_dequantize", "log_quantize_pack"],
)
def test_wire_kernel_compiles(one_chip, kernel, bits, dtype, shape):
    fn = functools.partial(kernel, bits=bits, interpret=False)
    text = _compiled_text(fn, one_chip, (shape, dtype), ((), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("shape", FACTOR_SHAPES)
def test_pack_nibbles_compiles(one_chip, shape):
    fn = functools.partial(log_quant.pack_nibbles_pallas, interpret=False)
    assert "tpu_custom_call" in _compiled_text(fn, one_chip, (shape, jnp.int8))


@pytest.mark.parametrize("bits", [8, 4])
def test_dequantize_rows_compiles(one_chip, bits):
    nbytes = HEAD_DIM if bits == 8 else HEAD_DIM // 2
    fn = functools.partial(
        log_quant.log_dequantize_rows_pallas, bits=bits, interpret=False
    )
    text = _compiled_text(
        fn,
        one_chip,
        ((CACHE_ROWS, nbytes), jnp.int8),
        ((CACHE_ROWS, 1), jnp.float32),
    )
    assert "tpu_custom_call" in text


def test_flash_attention_compiles(one_chip):
    shape = (1, 32, 4096, HEAD_DIM)
    fn = functools.partial(flash_attention_pallas, causal=True, interpret=False)
    text = _compiled_text(fn, one_chip, *[(shape, jnp.bfloat16)] * 3)
    assert "tpu_custom_call" in text


def test_ssd_chunk_compiles(one_chip):
    # mamba2-370m: 32 heads of 64, state 128, chunk 256; 2048 tokens
    b, h, nc, q, p, n = 1, 32, 8, 256, 64, 128
    fn = functools.partial(ssd_chunk_pallas, interpret=False)
    text = _compiled_text(
        fn,
        one_chip,
        ((b, h, nc, q, p), jnp.float32),
        ((b, h, nc, q), jnp.float32),
        ((b, h, nc, q, n), jnp.float32),
        ((b, h, nc, q, n), jnp.float32),
    )
    assert "tpu_custom_call" in text


def test_ssd_grouped_core_cost(one_chip):
    # mamba2-370m's SSD core at the one-chip cell's widths, forward, remat
    # recompute and backward: 8 x 2048 tokens, 32 heads of 64, state 128,
    # chunk 256, B and C in one group. C·Bᵀ per group gives 9.2e10 FLOPs and
    # 0.81 GB of temporaries; per head, as with B and C repeated to every
    # head, 1.9e11 and 1.3 GB.
    b, s, h, p, g, n = 8, 2048, 32, 64, 1, 128
    core = jax.checkpoint(functools.partial(ssd_chunked, chunk=256))

    def grads(x, a, bm, cm, gy):
        def loss(*args):
            y, final_state = core(*args)
            return jnp.vdot(y, gy) + final_state.sum()

        return jax.grad(loss, argnums=(0, 1, 2, 3))(x, a, bm, cm)

    shapes = [(b, s, h, p), (b, s, h), (b, s, g, n), (b, s, g, n), (b, s, h, p)]
    args = [jax.ShapeDtypeStruct(x, jnp.float32, sharding=one_chip) for x in shapes]
    compiled = jax.jit(grads).lower(*args).compile()
    assert compiled.cost_analysis()["flops"] < 1.3e11
    assert compiled.memory_analysis().temp_size_in_bytes < 1.2e9
