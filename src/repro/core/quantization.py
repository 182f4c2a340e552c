"""Logarithmic quantization (paper Eq. 5/6) with b-bit discretization.

The paper's map:  q(x)    = sign(x) * log(1 + alpha*|x|) / log(1 + alpha)
inverse (Eq. 6):  x(q)    = sign(q) * ((1 + alpha)^{|q|} - 1) / alpha

``|q(x)| in [0, 1]`` requires ``|x| <= 1``, so tensors are normalized by a
scale (per-tensor max magnitude) before quantization; the scale travels with
the codes (1 float per tensor). The normalized magnitude is discretized to
``2^b`` uniform bins in [0, 1] ("separable symbol encoding"): one sign bit is
folded into the code by using signed integer levels in
``[-(2^b - 1), +(2^b - 1)]`` stored as int8/int16/int32 depending on ``b``;
on a real wire each value needs exactly ``b`` bits (b-1 magnitude + 1 sign —
matching the paper's "each quantized scalar requires only b bits").

All functions are pure-jnp so they jit/vmap/shard_map cleanly; the Pallas
fused kernel in ``repro.kernels.log_quant`` implements the same math and is
validated against this module.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

__all__ = [
    "LogQuantConfig",
    "log_compress",
    "log_expand",
    "quantize",
    "dequantize",
    "quantize_with_scale",
    "dequantize_with_scale",
    "code_dtype",
    "wire_bits",
]


@dataclasses.dataclass(frozen=True)
class LogQuantConfig:
    """Static parameters of the log-quantizer.

    bits:  total bits per scalar on the wire (sign + magnitude), paper b=8.
    alpha: curvature of the log map (paper Eq. 5), alpha > 0.
    """

    bits: int = 8
    alpha: float = 10.0

    def __post_init__(self):
        if not (2 <= self.bits <= 16):
            raise ValueError(f"bits must be in [2, 16], got {self.bits}")
        if self.alpha <= 0:
            raise ValueError(f"alpha must be > 0, got {self.alpha}")

    @property
    def levels(self) -> int:
        """Number of magnitude bins: 2^(b-1) - ... we use 2^(b-1)-1 positive
        levels so code fits a signed (b)-bit integer symmetrically."""
        return (1 << (self.bits - 1)) - 1


def code_dtype(bits: int):
    if bits <= 8:
        return jnp.int8
    return jnp.int16


def wire_bits(n_elements: int, bits: int) -> int:
    """Bits on the wire for ``n_elements`` quantized scalars (+32 for scale)."""
    return n_elements * bits + 32


def log_compress(x: jax.Array, alpha: float) -> jax.Array:
    """Paper Eq. 5 on normalized input (|x| <= 1): sign(x)*log1p(a|x|)/log1p(a)."""
    return jnp.sign(x) * jnp.log1p(alpha * jnp.abs(x)) / jnp.log1p(alpha)


def log_expand(q: jax.Array, alpha: float) -> jax.Array:
    """Paper Eq. 6: sign(q)*((1+a)^{|q|} - 1)/a  (inverse of log_compress)."""
    return jnp.sign(q) * jnp.expm1(jnp.abs(q) * jnp.log1p(alpha)) / alpha


# quantize/dequantize fold Eq. 5/6's constants into ONE host-computed factor
# each and write ``exp(.) - 1`` for ``expm1``: the Pallas kernels in
# ``repro.kernels.log_quant`` compute exactly these forms (Mosaic lowers no
# ``expm1``), and with a single constant there is no chain for XLA to
# refold, so the two backends agree bit for bit on the TPU too.


def quantize(x: jax.Array, cfg: LogQuantConfig) -> jax.Array:
    """Normalized input (|x| <= 1) -> signed integer codes in [-L, L]."""
    lv = cfg.levels
    x = x.astype(jnp.float32)
    q = jnp.sign(x) * jnp.log1p(cfg.alpha * jnp.abs(x)) * (lv / math.log1p(cfg.alpha))
    return jnp.clip(jnp.round(q), -lv, lv).astype(code_dtype(cfg.bits))


def dequantize(codes: jax.Array, cfg: LogQuantConfig) -> jax.Array:
    """Signed (or averaged) codes -> normalized float values (|x| <= 1)."""
    c = codes.astype(jnp.float32)
    rate = math.log1p(cfg.alpha) / cfg.levels
    return jnp.sign(c) * (jnp.exp(jnp.abs(c) * rate) - 1.0) / cfg.alpha


def quantize_with_scale(x: jax.Array, cfg: LogQuantConfig, scale: jax.Array | None = None):
    """Full pipeline: per-tensor max-normalize, log-quantize to codes.

    Returns ``(codes, scale)``. If ``scale`` is given (e.g. a globally
    p-maxed scale in the distributed path) it is used instead of the local
    max so every worker quantizes against the same grid.
    """
    x = x.astype(jnp.float32)
    if scale is None:
        scale = jnp.max(jnp.abs(x))
    # Guard: all-zero tensors quantize to zero codes with scale 1.
    safe = jnp.where(scale > 0, scale, 1.0)
    codes = quantize(x / safe, cfg)
    return codes, scale


def dequantize_with_scale(codes: jax.Array, scale: jax.Array, cfg: LogQuantConfig) -> jax.Array:
    return dequantize(codes, cfg) * scale


@functools.partial(jax.jit, static_argnames=("cfg",))
def roundtrip(x: jax.Array, cfg: LogQuantConfig) -> jax.Array:
    """quantize -> dequantize (used by tests / error analysis)."""
    codes, scale = quantize_with_scale(x, cfg)
    return dequantize_with_scale(codes, scale, cfg)
