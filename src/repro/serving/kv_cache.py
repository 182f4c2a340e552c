"""Quantized, paged KV-cache layer: log-quant codes + per-block scales.

The paper's log-quantization codec (``repro.core.codec``) cuts wire bytes
on the training path; decode is memory-bandwidth-bound on KV-cache *reads*,
so the same codec applied to the cache cuts the serving hot path's HBM
traffic by the same 4x/8x. This module stores attention KV (and MLA latent)
cache leaves as b-bit log-quant codes plus one float32 scale per **block**,
where a block is one token's last-dim row — ``head_dim`` values per
(batch, kv_head, position) for attention, ``kv_lora_rank`` per
(batch, position) for the MLA latent. Codes are packed exactly as on the
training wire (nibble layout byte ``i`` = ``codes[2i] | codes[2i+1] << 4``
for b <= 4) by routing the encode through :class:`LogQuantCodec` — the
``pallas`` backend therefore reuses the fused ``log_quantize_pack_pallas``
kernel — and reads dequantize through the row-scaled Pallas kernel
(:func:`repro.kernels.log_quant.log_dequantize_rows_pallas`) or the jnp
reference, byte-identical between backends.

Per-block (not per-tensor) scales matter at serving time: a decode step
appends ONE token, and a per-block scale makes that append a pure
quantize + scatter of the new rows — no re-quantization of history, no
drifting global grid as the sequence grows.

Layout of a quantized leaf (:class:`QuantKV`, a registered pytree node —
``codes``/``scale`` are traced children, the codec knobs are static aux):

    raw   (..., S, d)                  cache_dtype
    codes (..., S, ceil(d/2)) int8     b <= 4 (nibble-packed, d padded even)
    codes (..., S, d)         int8     b == 8
    scale (..., S, 1)         float32

so cache-bytes/token equals the training wire's ``packed_wire_bits``
accounting plus 32 bits of scale sideband per block — the benchmark's
bytes-per-token gate checks exactly this identity.

The block-pool allocator (:class:`BlockPool`) below is the paging layer:
HBM is carved into fixed ``block_tokens`` pages and the scheduler admits a
request only when enough pages exist for its worst-case length — capacity
accounting at the same bytes-per-token the quantized layout actually
allocates, so q4 literally admits ~8x the concurrent requests of fp32.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.codec import LogQuantCodec, packed_wire_bits

__all__ = [
    "QuantKV",
    "CacheQuantConfig",
    "QUANT_CACHE_LEAVES",
    "quantize_kv",
    "dequantize_kv",
    "seq_update",
    "kv_update_token",
    "kv_read",
    "quantize_tree",
    "tree_is_quantized",
    "cache_bytes_per_token",
    "cache_bytes_per_token_accounting",
    "BlockPool",
]

# cache leaf names (tree_util keystr markers) eligible for quantization:
# append-only attention KV + MLA latent rows. SSM state / conv windows are
# read-modify-write every step (quantization error would compound), so
# they stay in the raw cache dtype.
QUANT_CACHE_LEAVES = ("'k'", "'v'", "'ckv'", "'krope'")


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class QuantKV:
    """One quantized cache leaf: packed codes + per-block scales.

    ``d`` is the logical last-dim size (head_dim / kv_lora_rank); for
    b <= 4 the codes' last dim is ``ceil(d/2)`` packed bytes."""

    codes: jax.Array
    scale: jax.Array
    bits: int = dataclasses.field(metadata=dict(static=True))
    alpha: float = dataclasses.field(metadata=dict(static=True))
    backend: str = dataclasses.field(metadata=dict(static=True))
    d: int = dataclasses.field(metadata=dict(static=True))


@dataclasses.dataclass(frozen=True)
class CacheQuantConfig:
    """Serving-cache codec knobs. ``bits`` in {4, 8} (0 = raw cache)."""

    bits: int = 8
    alpha: float = 10.0
    backend: str = "jnp_ref"

    def __post_init__(self):
        if self.bits not in (0, 4, 8):
            raise ValueError(f"cache bits must be 0, 4 or 8, got {self.bits}")


def _codec(bits: int, alpha: float, backend: str) -> LogQuantCodec:
    return LogQuantCodec(bits=bits, alpha=alpha, backend=backend)


def row_bytes(d: int, bits: int) -> int:
    """Packed container bytes of one d-element block (training-wire layout)."""
    return packed_wire_bits(d, bits) // 8


def quantize_kv(x: jax.Array, bits: int, alpha: float = 10.0,
                backend: str = "jnp_ref") -> QuantKV:
    """(..., S, d) values -> QuantKV with per-(..., S) block scales.

    The encode is the training-wire codec verbatim: per-block max-abs
    normalize, then ``LogQuantCodec.encode`` over the flattened rows (for
    b <= 4 the row is padded to even length first so nibble pairs never
    straddle block boundaries — pad positions quantize to code 0, the
    wire packer's pad byte)."""
    d = x.shape[-1]
    x = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    safe = jnp.where(scale > 0, scale, 1.0)
    xn = x / safe
    if bits <= 4 and d % 2:
        xn = jnp.pad(xn, [(0, 0)] * (xn.ndim - 1) + [(0, 1)])
    codec = _codec(bits, alpha, backend)
    wire = codec.encode(xn)
    codes = wire.reshape(x.shape[:-1] + (row_bytes(d, bits),))
    return QuantKV(codes=codes, scale=scale, bits=bits, alpha=alpha,
                   backend=backend, d=d)


def dequantize_kv(q: QuantKV, dtype=jnp.float32) -> jax.Array:
    """QuantKV -> (..., S, d) values in ``dtype`` (the dequant-on-read
    path: Pallas row kernel under backend='pallas', jnp reference else)."""
    lead = q.codes.shape[:-1]
    nb = q.codes.shape[-1]
    if q.backend == "pallas":
        from repro.kernels.log_quant import log_dequantize_rows_pallas
        flat = log_dequantize_rows_pallas(
            q.codes.reshape(-1, nb), q.scale.reshape(-1, 1).astype(jnp.float32),
            bits=q.bits, alpha=q.alpha)
        return flat[:, :q.d].reshape(lead + (q.d,)).astype(dtype)
    codec = _codec(q.bits, q.alpha, "jnp_ref")
    vals = codec.expand(codec.decode(q.codes.reshape(-1), q.codes.size
                                     * (2 if q.bits <= 4 else 1)))
    vals = vals.reshape(lead + (-1,))[..., :q.d]
    return (vals * q.scale).astype(dtype)


# --------------------------------------------------------------- updates

def seq_update(arr: jax.Array, new: jax.Array, idx: jax.Array,
               axis: int) -> jax.Array:
    """Write ``new`` (seq dim 1) into ``arr`` at sequence position ``idx``.

    Scalar ``idx``: one dynamic_update_slice (the classic decode append).
    Per-request ``idx`` of shape (B,) (batch is dim 0): a one-hot masked
    select over the seq axis — each request writes its own position, the
    continuous-batching path."""
    new = new.astype(arr.dtype)
    if jnp.ndim(idx) == 0:
        return jax.lax.dynamic_update_slice_in_dim(arr, new, idx, axis=axis)
    s = arr.shape[axis]
    oh = jnp.arange(s)[None, :] == idx[:, None]          # (B, S)
    shape = [1] * arr.ndim
    shape[0] = arr.shape[0]
    shape[axis] = s
    return jnp.where(oh.reshape(shape), new, arr)


def kv_update_token(leaf: Any, new_vals: jax.Array, idx: jax.Array,
                    axis: int) -> Any:
    """Append one token's values into a cache leaf (raw array OR QuantKV).

    ``new_vals`` carries seq dim 1 at ``axis``; for a QuantKV leaf the new
    rows are quantized against their own per-block scales and scattered
    into codes + scale — history is never touched."""
    if isinstance(leaf, QuantKV):
        qnew = quantize_kv(new_vals, leaf.bits, leaf.alpha, leaf.backend)
        return QuantKV(
            codes=seq_update(leaf.codes, qnew.codes, idx, axis),
            scale=seq_update(leaf.scale, qnew.scale, idx, axis),
            bits=leaf.bits, alpha=leaf.alpha, backend=leaf.backend, d=leaf.d)
    return seq_update(leaf, new_vals, idx, axis)


def kv_read(leaf: Any, dtype=jnp.float32) -> jax.Array:
    """Dequantize-on-read (identity for raw array leaves)."""
    if isinstance(leaf, QuantKV):
        return dequantize_kv(leaf, dtype)
    return leaf


# ------------------------------------------------------------- tree level

def _is_node(x: Any) -> bool:
    return isinstance(x, QuantKV)


def quantize_tree(caches: Any, qcfg: CacheQuantConfig) -> Any:
    """Convert eligible leaves of a raw cache pytree to QuantKV (identity
    when ``qcfg.bits == 0``). Stacked-scan leaves (leading repeats dim)
    pass through unchanged in structure — blocks are last-dim rows, so the
    extra leading dim is just more blocks."""
    if qcfg.bits == 0:
        return caches
    flat, treedef = jax.tree_util.tree_flatten_with_path(caches)
    out = []
    for kp, x in flat:
        path = jax.tree_util.keystr(kp)
        if any(m in path for m in QUANT_CACHE_LEAVES):
            out.append(quantize_kv(x, qcfg.bits, qcfg.alpha, qcfg.backend))
        else:
            out.append(x)
    return jax.tree_util.tree_unflatten(treedef, out)


def dequantize_tree(caches: Any, dtype=jnp.float32) -> Any:
    """Inverse of :func:`quantize_tree` (raw leaves pass through)."""
    return jax.tree_util.tree_map(
        lambda x: kv_read(x, dtype) if _is_node(x) else x, caches,
        is_leaf=_is_node)


def tree_is_quantized(caches: Any) -> bool:
    found = []
    jax.tree_util.tree_map(lambda x: found.append(_is_node(x)), caches,
                           is_leaf=_is_node)
    return any(found)


def cache_bytes_per_token(caches: Any, batch: int, max_seq: int) -> float:
    """MEASURED bytes per (request, position): total cache array bytes /
    (batch * max_seq) — every layer's K, V, scales, SSM state included."""
    total = sum(x.size * x.dtype.itemsize
                for x in jax.tree_util.tree_leaves(caches))
    return total / float(batch * max_seq)


def cache_bytes_per_token_accounting(caches: Any, batch: int,
                                     max_seq: int) -> float:
    """ACCOUNTED bytes per token from the wire codec's ``packed_wire_bits``
    (+32-bit scale per block) for quantized leaves, itemsize for raw ones.
    The serve benchmark hard-gates measured vs accounted within 2%."""
    total = 0.0
    for leaf in jax.tree_util.tree_leaves(caches, is_leaf=_is_node):
        if _is_node(leaf):
            blocks = leaf.scale.size
            total += blocks * (packed_wire_bits(leaf.d, leaf.bits) + 32) / 8.0
        else:
            total += leaf.size * leaf.dtype.itemsize
    return total / float(batch * max_seq)


# ------------------------------------------------------------ block pool

class BlockPool:
    """Fixed-size page allocator for KV-cache HBM (host-side accounting).

    The cache HBM is carved into ``n_blocks`` pages of ``block_tokens``
    positions each; a request holding L tokens owns ``ceil(L /
    block_tokens)`` pages. The scheduler admits a request only when its
    worst-case page count is free — slots can therefore be admitted and
    retired continuously without fragmentation, and the page budget is
    what converts a fixed HBM number into concurrent-request capacity
    (quantized caches shrink bytes/page, so the same HBM holds more
    pages' worth of requests)."""

    def __init__(self, n_blocks: int, block_tokens: int):
        if n_blocks < 1 or block_tokens < 1:
            raise ValueError("need n_blocks >= 1 and block_tokens >= 1")
        self.block_tokens = int(block_tokens)
        self._free: list[int] = list(range(int(n_blocks)))
        self._owned: dict[int, list[int]] = {}

    @property
    def n_free(self) -> int:
        return len(self._free)

    def blocks_for(self, n_tokens: int) -> int:
        return -(-int(n_tokens) // self.block_tokens)

    def can_alloc(self, n_tokens: int) -> bool:
        return self.blocks_for(n_tokens) <= self.n_free

    def alloc(self, owner: int, n_tokens: int) -> list[int]:
        """Reserve pages for ``owner`` (a request id); raises when the pool
        cannot hold them — callers must check :meth:`can_alloc` first."""
        n = self.blocks_for(n_tokens)
        if n > len(self._free):
            raise RuntimeError(f"pool exhausted: want {n} blocks, "
                               f"{len(self._free)} free")
        pages = [self._free.pop() for _ in range(n)]
        self._owned.setdefault(owner, []).extend(pages)
        return pages

    def release(self, owner: int) -> None:
        self._free.extend(self._owned.pop(owner, []))
