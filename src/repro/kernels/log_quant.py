"""Pallas TPU kernel: fused normalize -> log-quantize -> b-bit codes (+inverse).

The paper's added compute (Eq. 5/6) is elementwise and VPU-bound. On GPU it
would be a trivial elementwise CUDA kernel over fp32. The TPU adaptation:

  * operate on (rows, 128·k) VMEM tiles — lane-aligned for the VPU;
  * emit int8 codes directly, so 1 byte/elem — not 4 — leaves VMEM toward
    HBM (the whole point of the kernel is shrinking the HBM<->VMEM and
    ICI traffic of the factor tensors);
  * the per-tensor scale rides in SMEM as a (1, 1) scalar;
  * a nibble pair (2i, 2i+1) sits in adjacent lanes. Mosaic cannot split
    or interleave lanes by reshaping, so the pack and unpack move codes
    between lanes by a product with a 0/1/16 matrix on the MXU. Codes and
    nibbles are small integers, exact in bf16, so the products are exact.

Validated against ``repro.kernels.ref`` in interpret mode on the CPU;
``tests/test_tpu_compile.py`` compiles every kernel here for v5e.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import pallas_interpret

__all__ = ["log_quantize_pallas", "log_dequantize_pallas",
           "log_quantize_pack_pallas", "pack_nibbles_pallas",
           "log_dequantize_rows_pallas"]

# the whole (1, 1) scale array, in scalar memory
_SCALE_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


# _quantize and _expand compute exactly the forms of ``core.quantization``
# quantize/dequantize (one host-folded constant each, ``exp(.) - 1`` for the
# ``expm1`` Mosaic does not lower), so kernel and reference agree bit for bit


def _quantize(x: jax.Array, s: jax.Array, *, alpha: float,
              levels: int) -> jax.Array:
    """Normalize by the scale ``s`` and log-quantize (Eq. 5) -> float codes."""
    safe = jnp.where(s > 0.0, s, 1.0)
    y = x.astype(jnp.float32) / safe
    q = jnp.sign(y) * jnp.log1p(alpha * jnp.abs(y)) * (levels / math.log1p(alpha))
    return jnp.clip(jnp.round(q), -levels, levels)


def _expand(codes: jax.Array, *, alpha: float, levels: int) -> jax.Array:
    """Codes -> normalized values (Eq. 6)."""
    c = codes.astype(jnp.float32)
    rate = math.log1p(alpha) / levels
    return jnp.sign(c) * (jnp.exp(jnp.abs(c) * rate) - 1.0) / alpha


def _iota2(rows: int, cols: int) -> tuple[jax.Array, jax.Array]:
    return (jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0),
            jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1))


def _lane_move(v: jax.Array, m: jax.Array) -> jax.Array:
    """Small integers ``v`` (int32) times the lane matrix ``m`` -> int32."""
    x = v.astype(jnp.float32).astype(jnp.bfloat16)
    return jnp.dot(x, m.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32).astype(jnp.int32)


def _pack_pairs(codes: jax.Array) -> jax.Array:
    """(bm, bn) int32 codes -> (bm, bn/2) int8, byte j = c[2j] | c[2j+1]<<4."""
    r, c = _iota2(codes.shape[1], codes.shape[1] // 2)
    pair = jnp.where(r == 2 * c, 1.0, jnp.where(r == 2 * c + 1, 16.0, 0.0))
    byte = _lane_move(codes & 0xF, pair)          # in [0, 255]
    return ((byte ^ 0x80) - 0x80).astype(jnp.int8)


def _unpack_pairs(v: jax.Array) -> jax.Array:
    """(bm, nb) int32 bytes -> (bm, 2nb) signed codes, the inverse layout."""
    lo = ((v & 0xF) ^ 8) - 8            # sign-extend the low nibble
    hi = (((v >> 4) & 0xF) ^ 8) - 8     # ...and the high one
    r, c = _iota2(v.shape[1], 2 * v.shape[1])
    return (_lane_move(lo, jnp.where(c == 2 * r, 1.0, 0.0))
            + _lane_move(hi, jnp.where(c == 2 * r + 1, 1.0, 0.0)))


def _quantize_kernel(x_ref, scale_ref, o_ref, *, alpha: float, levels: int):
    codes = _quantize(x_ref[...], scale_ref[0, 0], alpha=alpha, levels=levels)
    o_ref[...] = codes.astype(o_ref.dtype)


def _dequantize_kernel(c_ref, scale_ref, o_ref, *, alpha: float, levels: int):
    val = _expand(c_ref[...], alpha=alpha, levels=levels)
    o_ref[...] = (val * scale_ref[0, 0]).astype(o_ref.dtype)


def _pad2d(x: jax.Array, block: tuple[int, int]):
    """Flatten to 2-D and pad to block multiples. Returns (x2d, orig_shape, n)."""
    shape = x.shape
    n = x.size
    cols = block[1]
    rows = -(-n // cols)  # ceil
    pad = rows * cols - n
    x2 = jnp.pad(x.reshape(-1), (0, pad)).reshape(rows, cols)
    rpad = (-rows) % block[0]
    if rpad:
        x2 = jnp.pad(x2, ((0, rpad), (0, 0)))
    return x2, shape, n


def _unpad(y2: jax.Array, shape, n):
    return y2.reshape(-1)[:n].reshape(shape)


@functools.partial(jax.jit, static_argnames=("bits", "alpha", "block", "interpret"))
def log_quantize_pallas(x: jax.Array, scale: jax.Array, *, bits: int = 8,
                        alpha: float = 10.0, block: tuple[int, int] = (256, 512),
                        interpret: bool | None = None) -> jax.Array:
    """x (any shape), scale scalar -> signed b-bit codes (int8/int16), same shape.

    ``interpret=None`` asks :func:`repro.kernels.backend.pallas_interpret`."""
    levels = (1 << (bits - 1)) - 1
    out_dtype = jnp.int8 if bits <= 8 else jnp.int16
    x2, shape, n = _pad2d(x, block)
    rows, cols = x2.shape
    grid = (rows // block[0], cols // block[1])
    scale2 = jnp.asarray(scale, jnp.float32).reshape(1, 1)
    kernel = functools.partial(_quantize_kernel, alpha=alpha, levels=levels)
    y2 = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(block, lambda i, j: (i, j)), _SCALE_SPEC],
        out_specs=pl.BlockSpec(block, lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows, cols), out_dtype),
        interpret=pallas_interpret() if interpret is None else interpret,
    )(x2, scale2)
    return _unpad(y2, shape, n)


def _pack_kernel(lo_ref, hi_ref, o_ref):
    """Two 4-bit two's-complement codes -> one int8 byte (lo | hi << 4).

    Purely elementwise on the VPU: the even/odd interleave split happens in
    XLA outside the kernel, so no in-kernel relayout is needed."""
    lo = lo_ref[...].astype(jnp.int32)
    hi = hi_ref[...].astype(jnp.int32)
    o_ref[...] = ((lo & 0xF) | ((hi & 0xF) << 4)).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def pack_nibbles_pallas(codes: jax.Array, *, block: tuple[int, int] = (256, 512),
                        interpret: bool | None = None) -> jax.Array:
    """Signed 4-bit codes (int8 storage, any shape) -> packed int8 bytes.

    Byte ``i`` holds ``codes[2i]`` in its low nibble and ``codes[2i+1]`` in
    its high nibble — the same layout as the jnp reference packer in
    ``repro.core.codec``, so the two backends produce identical wire bytes.
    Output is 1-D of length ``ceil(codes.size / 2)``.
    """
    flat = codes.reshape(-1).astype(jnp.int8)
    if flat.size % 2:
        flat = jnp.pad(flat, (0, 1))
    lo, hi = flat[0::2], flat[1::2]
    lo2, shape, n = _pad2d(lo, block)
    hi2, _, _ = _pad2d(hi, block)
    rows, cols = lo2.shape
    grid = (rows // block[0], cols // block[1])
    y2 = pl.pallas_call(
        _pack_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(block, lambda i, j: (i, j)),
            pl.BlockSpec(block, lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec(block, lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows, cols), jnp.int8),
        interpret=pallas_interpret() if interpret is None else interpret,
    )(lo2, hi2)
    return _unpad(y2, shape, n)


def _quantize_pack_kernel(x_ref, scale_ref, o_ref, *, alpha: float,
                          levels: int):
    """Fused normalize -> log-quantize -> nibble-pack, one VMEM pass.

    The input block is (bm, bn) float; adjacent column pairs (2c, 2c+1)
    are adjacent FLAT elements (bn is even, so pairs never straddle rows
    or block boundaries), packed into the (bm, bn//2) int8 output block.
    Keeping the pair split in-kernel removes the XLA interleave
    (two strided gathers + a second kernel launch) between the separate
    quantize and pack calls — the codes never round-trip through HBM."""
    codes = _quantize(x_ref[...], scale_ref[0, 0], alpha=alpha, levels=levels)
    o_ref[...] = _pack_pairs(codes.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("bits", "alpha", "block",
                                             "interpret"))
def log_quantize_pack_pallas(x: jax.Array, scale: jax.Array, *,
                             bits: int = 4, alpha: float = 10.0,
                             block: tuple[int, int] = (256, 256),
                             interpret: bool | None = None) -> jax.Array:
    """x (any shape), scale scalar -> packed nibble bytes, ONE pallas_call.

    Fuses ``log_quantize_pallas`` + ``pack_nibbles_pallas`` for the b <= 4
    wire: byte ``i`` holds ``codes[2i]`` (low nibble) and ``codes[2i+1]``
    (high nibble) of the flattened input, identical to the jnp reference
    packer in ``repro.core.codec`` (pad elements quantize to code 0, the
    reference's pad byte). Output is 1-D of length ``ceil(x.size / 2)``.
    The pairing matrix is (bn, bn/2), so keep ``block[1]`` at 256: wider
    blocks square its cost.
    """
    if bits > 4:
        raise ValueError(f"nibble pack needs bits <= 4, got {bits}")
    if block[1] % 2:
        raise ValueError(f"block cols must be even, got {block}")
    levels = (1 << (bits - 1)) - 1
    x2, _, n = _pad2d(x, block)
    rows, cols = x2.shape
    grid = (rows // block[0], cols // block[1])
    scale2 = jnp.asarray(scale, jnp.float32).reshape(1, 1)
    kernel = functools.partial(_quantize_pack_kernel, alpha=alpha,
                               levels=levels)
    y2 = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(block, lambda i, j: (i, j)), _SCALE_SPEC],
        out_specs=pl.BlockSpec((block[0], block[1] // 2),
                               lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows, cols // 2), jnp.int8),
        interpret=pallas_interpret() if interpret is None else interpret,
    )(x2, scale2)
    return _unpad(y2, (-(-n // 2),), -(-n // 2))


def _dequant_rows_kernel(c_ref, s_ref, o_ref, *, alpha: float, levels: int,
                         packed: bool):
    """Per-ROW scaled dequantize (the KV-cache read path).

    ``c_ref`` is a (bm, bn) int8 block — raw b=8 codes, or nibble-packed
    b<=4 bytes when ``packed`` — and ``s_ref`` a (bm, 1) float32 block of
    per-row scales (one scale per cache block = one token's head_dim row),
    broadcast across the row. The unpack interleave stays in-kernel so the
    int codes never round-trip through HBM between unpack and expand."""
    v = c_ref[...].astype(jnp.int32)
    codes = _unpack_pairs(v) if packed else v
    val = _expand(codes, alpha=alpha, levels=levels)
    o_ref[...] = (val * s_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bits", "alpha", "block_rows",
                                             "interpret", "out_dtype"))
def log_dequantize_rows_pallas(packed: jax.Array, scales: jax.Array, *,
                               bits: int = 8, alpha: float = 10.0,
                               block_rows: int = 256,
                               interpret: bool | None = None,
                               out_dtype=jnp.float32) -> jax.Array:
    """Row-wise dequant-on-read: (R, nbytes) int8 + (R, 1) f32 -> (R, d).

    Each row is one quantized KV-cache block (a token's head_dim slice)
    with its own scale. For ``bits <= 4`` the input is nibble-packed (the
    training-wire byte layout: byte i = codes[2i] | codes[2i+1] << 4) and
    the output width is ``2 * nbytes``; for ``bits == 8`` it is 1:1. The
    grid tiles rows only — cache rows are short (head_dim), so a block is
    (block_rows, full width), lane-padded to keep the VPU happy.
    """
    if packed.ndim != 2 or scales.shape != (packed.shape[0], 1):
        raise ValueError(f"want (R, nbytes) codes + (R, 1) scales, got "
                         f"{packed.shape} / {scales.shape}")
    levels = (1 << (bits - 1)) - 1
    is_packed = bits <= 4
    r, nb = packed.shape
    rpad = (-r) % block_rows
    cpad = (-nb) % 128  # lane-align the byte dim
    c2 = jnp.pad(packed, ((0, rpad), (0, cpad)))
    s2 = jnp.pad(scales, ((0, rpad), (0, 0)))
    rows, cols = c2.shape
    out_cols = cols * 2 if is_packed else cols
    kernel = functools.partial(_dequant_rows_kernel, alpha=alpha,
                               levels=levels, packed=is_packed)
    y2 = pl.pallas_call(
        kernel,
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, out_cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, out_cols), out_dtype),
        interpret=pallas_interpret() if interpret is None else interpret,
    )(c2, s2)
    d = nb * 2 if is_packed else nb
    return y2[:r, :d]


@functools.partial(jax.jit, static_argnames=("bits", "alpha", "block", "interpret"))
def log_dequantize_pallas(codes: jax.Array, scale: jax.Array, *, bits: int = 8,
                          alpha: float = 10.0, block: tuple[int, int] = (256, 512),
                          interpret: bool | None = None,
                          out_dtype=jnp.float32) -> jax.Array:
    levels = (1 << (bits - 1)) - 1
    c2, shape, n = _pad2d(codes, block)
    rows, cols = c2.shape
    grid = (rows // block[0], cols // block[1])
    scale2 = jnp.asarray(scale, jnp.float32).reshape(1, 1)
    kernel = functools.partial(_dequantize_kernel, alpha=alpha, levels=levels)
    y2 = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(block, lambda i, j: (i, j)), _SCALE_SPEC],
        out_specs=pl.BlockSpec(block, lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows, cols), out_dtype),
        interpret=pallas_interpret() if interpret is None else interpret,
    )(c2, scale2)
    return _unpad(y2, shape, n)
