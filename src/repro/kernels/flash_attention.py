"""Pallas TPU kernel: blocked causal (optionally sliding-window) attention.

The compute hot-spot of ``prefill_32k``. Online-softmax flash attention with
MXU-aligned (block_q x block_k) tiles, GQA-aware BlockSpec index maps (the
kv-head index is derived inside the index_map, so K/V blocks are fetched per
kv head, not per query head), f32 accumulation in VMEM scratch.

GPU->TPU adaptation: instead of warp-level softmax reductions, the online
update is expressed over (block_q, block_k) VREG tiles; block shapes default
to 256 ≥ the 128-lane layout and the 128x128 MXU tile.

Out-of-window/causal key blocks are masked (not skipped) in interpret mode —
block-level grid pruning is a compile-target optimization; correctness is
identical. Validated in interpret mode against ``attention_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.backend import pallas_interpret

__all__ = ["flash_attention_pallas"]

_NEG = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  sm_scale: float, causal: bool, window: int | None,
                  block_q: int, block_k: int, nk: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32) * sm_scale          # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)                     # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)                     # (bk, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)

    qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = jnp.ones(s.shape, jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, _NEG)

    m_prev = m_ref[...]                                     # (bq, 1)
    l_prev = l_ref[...]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = corr * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * corr + p @ v

    @pl.when(ki == nk - 1)
    def _fin():
        l = l_ref[...]
        safe_l = jnp.where(l > 0.0, l, 1.0)
        o_ref[0, 0] = (acc_ref[...] / safe_l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "sm_scale", "block_q", "block_k", "interpret"))
def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, window: int | None = None,
                           sm_scale: float | None = None,
                           block_q: int = 256, block_k: int = 256,
                           interpret: bool | None = None) -> jax.Array:
    """q: (B, Hq, S, D); k, v: (B, Hkv, S, D), Hq % Hkv == 0 -> (B, Hq, S, D).

    Sequence is padded to block multiples; causal masking keeps padded keys
    invisible to real queries (decoder-only: causal or causal+SWA only).
    """
    assert causal, "decoder-only framework: causal (optionally windowed) attention"
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    assert hq % hkv == 0, (hq, hkv)
    if sm_scale is None:
        sm_scale = 1.0 / float(d) ** 0.5

    bq = min(block_q, pl.next_power_of_2(s))
    bk = min(block_k, pl.next_power_of_2(s))
    s_pad = -(-s // bq) * bq
    s_pad = -(-s_pad // bk) * bk
    pad = s_pad - s
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))

    nq, nk = s_pad // bq, s_pad // bk
    group = hq // hkv

    kernel = functools.partial(
        _flash_kernel, sm_scale=float(sm_scale), causal=causal, window=window,
        block_q=bq, block_k=bk, nk=nk)

    out = pl.pallas_call(
        kernel,
        grid=(b, hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bb, h, qi, ki: (bb, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda bb, h, qi, ki, g=group: (bb, h // g, ki, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda bb, h, qi, ki, g=group: (bb, h // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda bb, h, qi, ki: (bb, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, s_pad, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),   # acc
            pltpu.VMEM((bq, 1), jnp.float32),   # running max m
            pltpu.VMEM((bq, 1), jnp.float32),   # running denom l
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=pallas_interpret() if interpret is None else interpret,
    )(qp, kp, vp)
    return out[:, :, :s, :]
