"""Jit'd dispatch wrappers: Pallas kernels with pure-XLA fallbacks.

Model code calls these; ``backend="xla"`` (the default) routes to the jnp
oracle math, ``backend="pallas"`` to the TPU kernels (interpret mode on the
CPU, see ``repro.kernels.backend``). The two paths are assert_allclose-tested
against each other across shape/dtype sweeps.
"""
from __future__ import annotations

from repro.kernels import ref as _ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.log_quant import log_dequantize_pallas, log_quantize_pallas

__all__ = ["log_quantize", "log_dequantize", "flash_attention"]


def log_quantize(x, scale, *, bits=8, alpha=10.0, backend="xla", interpret=None):
    if backend == "pallas":
        return log_quantize_pallas(x, scale, bits=bits, alpha=alpha,
                                   interpret=interpret)
    return _ref.log_quantize_ref(x, scale, bits, alpha)


def log_dequantize(codes, scale, *, bits=8, alpha=10.0, backend="xla", interpret=None):
    if backend == "pallas":
        return log_dequantize_pallas(codes, scale, bits=bits, alpha=alpha,
                                     interpret=interpret)
    return _ref.log_dequantize_ref(codes, scale, bits, alpha)


def flash_attention(q, k, v, *, causal=True, window=None, sm_scale=None,
                    backend="xla", block_q=256, block_k=256, interpret=None,
                    xla_chunk_threshold=2048):
    if backend == "pallas":
        return flash_attention_pallas(
            q, k, v, causal=causal, window=window, sm_scale=sm_scale,
            block_q=block_q, block_k=block_k, interpret=interpret)
    if q.shape[2] > xla_chunk_threshold:
        return _ref.chunked_attention_ref(q, k, v, causal=causal,
                                          window=window, scale=sm_scale)
    return _ref.attention_ref(q, k, v, causal=causal, window=window, scale=sm_scale)
