"""Axis-aware collectives + wire-byte accounting.

All compressor code talks to collectives through :class:`AxisComm`, which is
a thin wrapper over ``jax.lax`` named-axis collectives. The same code paths
therefore run:

  * inside ``jax.shard_map`` over the production mesh (manual data/pod axes),
  * under ``jax.vmap(..., axis_name=...)`` in single-device tests (vmap
    supports named-axis collectives, giving exact N-worker semantics), and
  * on a 1-sized axis (degenerate single-worker).

Byte accounting is *static* (computed from shapes at trace time, returned as
plain Python ints) so benchmarks/tables never need device work.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

__all__ = ["AxisComm", "CommRecord"]


@dataclasses.dataclass
class CommRecord:
    """Accumulated wire accounting for one sync call (per worker, bits).

    Two tiers:

    * ``add`` — *static* accounting (plain Python ints, known at trace
      time): the eager compressors only use this, so tables and benchmarks
      never need device work.
    * ``add_gated`` — *dynamic* accounting for lazily-aggregated groups
      (:mod:`repro.core.lazy`): the payload fires only when the traced
      ``gate`` is true, so the charged bits/collectives are jnp scalars.
      ``effective_bits``/``effective_collectives`` fold both tiers; on an
      eager-only record they stay plain ints (nothing traced escapes).
    """

    bits_sent: int = 0  # payload each worker puts on the wire (static)
    n_collectives: int = 0
    dyn_bits: object = 0          # gate-weighted payload (jnp scalar or 0)
    dyn_collectives: object = 0
    down_bits: int = 0  # server->worker broadcast payload (server wire)

    def add(self, bits: int, n: int = 1) -> None:
        self.bits_sent += int(bits)
        self.n_collectives += n

    def add_gated(self, bits: int, n: int, gate) -> None:
        """Charge ``bits``/``n`` only when the traced ``gate`` fires."""
        g = jnp.asarray(gate, jnp.float32)
        self.dyn_bits = self.dyn_bits + g * bits
        self.dyn_collectives = self.dyn_collectives + g * n

    def add_down(self, bits: int) -> None:
        """Charge downlink bytes (the server's aggregate broadcast). Pure
        bookkeeping for the asymmetric wire — the symmetric all-reduce
        has no server, so ``effective_bits`` (uplink) stays the headline
        and this tier stays static and separate."""
        self.down_bits += int(bits)

    def effective_bits(self):
        """Static + gate-weighted payload bits (int, or jnp scalar when a
        lazy group charged dynamically this sync)."""
        return self.bits_sent + self.dyn_bits

    def effective_collectives(self):
        return self.n_collectives + self.dyn_collectives


class AxisComm:
    """Named-axis collectives over the data-parallel axes."""

    def __init__(self, axis_names: tuple[str, ...]):
        if isinstance(axis_names, str):
            axis_names = (axis_names,)
        self.axis_names = tuple(axis_names)
        self._size: int | None = None

    def size(self) -> int:
        # accounting paths query this once per sync — cache per instance
        # (the axis sizes are fixed for the life of the trace context)
        if self._size is None:
            n = 1
            for a in self.axis_names:
                # psum of a unit weak-typed scalar: the canonical axis-size
                # query that works under both shard_map and vmap tracing
                n *= int(jax.lax.psum(1, a))
            self._size = n
        return self._size

    def psum(self, x: jax.Array) -> jax.Array:
        return jax.lax.psum(x, self.axis_names)

    def pmean(self, x: jax.Array) -> jax.Array:
        return jax.lax.pmean(x, self.axis_names)

    def pmax(self, x: jax.Array) -> jax.Array:
        return jax.lax.pmax(x, self.axis_names)

    def all_gather(self, x: jax.Array) -> jax.Array:
        """Gather over all DP axes -> leading axis of size ``self.size()``."""
        g = x
        # Gather innermost-first so the leading axes compose as
        # (axis0, axis1, ..., *x.shape); then flatten the gathered axes.
        for a in reversed(self.axis_names):
            g = jax.lax.all_gather(g, a, axis=0)
        return g.reshape((-1,) + x.shape)

    def fused_all_gather(self, xs: list[jax.Array]) -> list[jax.Array]:
        """ONE all-gather of every payload in ``xs``, concatenated flat.

        All arrays must share a dtype (one wire phase = one code dtype).
        Returns per-input gathered arrays of shape ``(N, x.size)`` — exactly
        what per-tensor ``all_gather(x.reshape(-1))`` calls would return,
        but with a single collective on the interconnect.
        """
        if not xs:
            return []
        if len({x.dtype for x in xs}) != 1:
            raise ValueError("fused_all_gather requires a single dtype; got "
                             f"{[str(x.dtype) for x in xs]}")
        flat = jnp.concatenate([x.reshape(-1) for x in xs])
        g = self.all_gather(flat)  # (N, total)
        outs, off = [], 0
        for x in xs:
            outs.append(g[:, off:off + x.size])
            off += x.size
        return outs

    def fused_pmax(self, xs: list[jax.Array]) -> list[jax.Array]:
        """ONE pmax over every (small) tensor in ``xs``; shapes preserved.
        Used to fuse the per-tensor quantization-scale reductions.

        Contract: every input must already be float32 — the fused buffer
        is a single f32 concatenate, and a silent upcast here would make
        the traced collective wider than the accounted one (the same
        reason ``fused_all_gather`` rejects mixed dtypes).
        """
        if not xs:
            return []
        bad = [str(x.dtype) for x in xs if x.dtype != jnp.float32]
        if bad:
            raise ValueError("fused_pmax requires float32 inputs (scale "
                             f"reductions are f32 by contract); got {bad}")
        flat = jnp.concatenate([x.reshape(-1) for x in xs])
        m = self.pmax(flat)
        outs, off = [], 0
        for x in xs:
            outs.append(m[off:off + x.size].reshape(x.shape))
            off += x.size
        return outs
