"""Pallas TPU kernel: Mamba-2 SSD intra-chunk block (arXiv:2405.21060).

Computes, per (batch, head, chunk) grid cell, the quadratic-within-chunk
term of state-space duality:

    S = C B^T                    (Q x Q, MXU)
    M = S * exp(segsum(a))       (causal decay mask, VPU)
    Y = M X                      (Q x Q @ Q x P, MXU)

This is the compute hot-spot of SSM training/prefill: two MXU matmuls per
tile with the decay mask fused between them in VMEM — the TPU analogue of
Mamba-2's fused CUDA chunk kernel (no shared-memory banking tricks needed;
the (Q, Q) tile lives in VREGs between the matmuls). Q defaults to 128 to
match the MXU tile. The inter-chunk recurrence stays in the lax.scan of
``repro.models.ssm`` (sequential, tiny).

Validated in interpret mode against the einsum path in ``ssm.ssd_chunked``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import pallas_interpret

__all__ = ["ssd_chunk_pallas"]


def _ssd_chunk_kernel(x_ref, acum_ref, b_ref, c_ref, o_ref):
    x = x_ref[0, 0].astype(jnp.float32)        # (Q, P)
    ac = acum_ref[0, 0].astype(jnp.float32)    # (Q, 1)
    bm = b_ref[0, 0].astype(jnp.float32)       # (Q, N)
    cm = c_ref[0, 0].astype(jnp.float32)       # (Q, N)
    s = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())))  # (Q, Q) MXU
    seg = ac - ac.reshape(1, -1)               # a_cum_i - a_cum_j
    q = s.shape[0]
    i = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    m = jnp.where(i >= j, jnp.exp(seg), 0.0)
    o_ref[0, 0] = (s * m) @ x                  # (Q, P) MXU


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_chunk_pallas(x: jax.Array, a_cum: jax.Array, bm: jax.Array,
                     cm: jax.Array, *,
                     interpret: bool | None = None) -> jax.Array:
    """Intra-chunk SSD term.

    x     (B, H, NC, Q, P)  dt-weighted inputs, chunked
    a_cum (B, H, NC, Q)     within-chunk cumulative log-decay
    bm/cm (B, H, NC, Q, N)  B/C projections (groups pre-broadcast)
    ->    (B, H, NC, Q, P)  Y_diag
    """
    b, h, nc, q, p = x.shape
    n = bm.shape[-1]
    grid = (b * h, nc)
    resh = lambda t: t.reshape((b * h,) + t.shape[2:])
    ac2 = resh(a_cum)[..., None]               # (BH, NC, Q, 1)

    out = pl.pallas_call(
        _ssd_chunk_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, q, p), lambda i, c: (i, c, 0, 0)),
            pl.BlockSpec((1, 1, q, 1), lambda i, c: (i, c, 0, 0)),
            pl.BlockSpec((1, 1, q, n), lambda i, c: (i, c, 0, 0)),
            pl.BlockSpec((1, 1, q, n), lambda i, c: (i, c, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, q, p), lambda i, c: (i, c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, nc, q, p), jnp.float32),
        interpret=pallas_interpret() if interpret is None else interpret,
    )(resh(x), ac2, resh(bm), resh(cm))
    return out.reshape(b, h, nc, q, p)
