"""Mamba-2 (SSD — state-space duality, arXiv:2405.21060).

Chunked SSD forward: intra-chunk terms are attention-like einsums over
(chunk x chunk) tiles (MXU-dense — this is the TPU adaptation of the SSD
insight: the quadratic-within-chunk / recurrent-across-chunk split maps
tiles onto the MXU and the cross-chunk recurrence onto a lax.scan carry);
inter-chunk states propagate through a sequential ``lax.scan`` (memory-light
and sharding-friendly: batch/head dims stay partitioned, the scan is over
time only). B and C stay per group, (B,S,G,N), as the projection makes
them: C·Bᵀ is contracted once per group, never per head.

``ssd_naive`` is the step-by-step recurrence oracle used by tests; the
chunked path must match it for every chunk size.

Decode is O(1): a single state update per token (cache = conv window + SSM
state), which is why SSM archs run the ``long_500k`` shape.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import KeyGen, dense_init, gated_rms_norm

__all__ = ["init_mamba", "mamba_forward", "init_mamba_cache", "ssd_chunked",
           "ssd_naive"]

Params = dict[str, Any]


# --------------------------------------------------------------------------
# Core SSD math. Shapes: x (B,S,H,P) already dt-weighted; a (B,S,H) = dt*A
# (log-decay per step, <= 0); Bm/Cm (B,S,G,N), H % G == 0 (``ssd_naive``
# takes them per head, G == H).
# --------------------------------------------------------------------------
def ssd_naive(x, a, bm, cm, h0=None):
    """Sequential recurrence oracle: h_t = e^{a_t} h_{t-1} + B_t x_t^T."""
    b, s, h, p = x.shape
    n = bm.shape[-1]
    if h0 is None:
        h0 = jnp.zeros((b, h, p, n), jnp.float32)

    def step(hstate, inp):
        xt, at, bt, ct = inp  # (B,H,P), (B,H), (B,H,N), (B,H,N)
        decay = jnp.exp(at)[..., None, None]
        hstate = hstate * decay + jnp.einsum("bhp,bhn->bhpn", xt, bt)
        y = jnp.einsum("bhpn,bhn->bhp", hstate, ct)
        return hstate, y

    xs = (x.transpose(1, 0, 2, 3), a.transpose(1, 0, 2),
          bm.transpose(1, 0, 2, 3), cm.transpose(1, 0, 2, 3))
    hT, ys = jax.lax.scan(step, h0, xs)
    return ys.transpose(1, 0, 2, 3), hT  # (B,S,H,P), (B,H,P,N)


def _segsum(a):
    """(..., L) -> (..., L, L): S[i,j] = sum_{j<k<=i} a_k, -inf above diag."""
    L = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    seg = cs[..., :, None] - cs[..., None, :]
    i = jnp.arange(L)[:, None]
    j = jnp.arange(L)[None, :]
    return jnp.where(i >= j, seg, -jnp.inf)


def ssd_chunked(x, a, bm, cm, chunk: int, h0=None):
    """Chunked SSD; matches ``ssd_naive`` on B and C repeated to heads
    (up to fp assoc error).

    ``bm``/``cm`` are per group, (B,S,G,N) with ``H % G == 0``: heads
    ``g*H/G .. (g+1)*H/G - 1`` read group g. C·Bᵀ is contracted once per
    group and broadcast over the group's heads at the decay mask; G == H is
    the per-head computation.

    Returns (y (B,S,H,P), final_state (B,H,P,N)).
    """
    b, s, h, p = x.shape
    g, n = bm.shape[-2:]
    if h % g:
        raise ValueError(f"{h} heads do not split into {g} B/C groups")
    r = h // g
    pad = (-s) % chunk
    if pad:
        zpad = lambda t: jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
        x, a, bm, cm = map(zpad, (x, a, bm, cm))
    sp = x.shape[1]
    nc = sp // chunk
    # chunked views: (B, nc, Q, ...). x and y keep their heads whole; only
    # the grouped contractions view them as (G, R): split for the whole SSD,
    # the TPU relayouts y twice per pass instead of once.
    xc = x.reshape(b, nc, chunk, h, p).astype(jnp.float32)
    ac = a.reshape(b, nc, chunk, h).transpose(0, 1, 3, 2)  # (B,nc,H,Q)
    bc = bm.reshape(b, nc, chunk, g, n).astype(jnp.float32)
    cc = cm.reshape(b, nc, chunk, g, n).astype(jnp.float32)

    a_cum = jnp.cumsum(ac, axis=-1)                        # (B,nc,H,Q)
    # ---- intra-chunk (quadratic, attention-like) -------------------------
    with jax.named_scope("ssd.cb"):
        cb = jnp.einsum("bclgn,bcsgn->bcgls", cc, bc)      # (B,nc,G,Q,Q)
    with jax.named_scope("ssd.diag"):
        cb = jnp.broadcast_to(cb[:, :, :, None], (b, nc, g, r, chunk, chunk))
        mask = cb.reshape(b, nc, h, chunk, chunk) * jnp.exp(_segsum(ac))
        y_diag = jnp.einsum("bchls,bcshp->bclhp", mask, xc)
    # ---- per-chunk summary states ----------------------------------------
    with jax.named_scope("ssd.states"):
        decay_states = jnp.exp(a_cum[..., -1:] - a_cum)    # (B,nc,H,Q)
        xw = xc * decay_states.transpose(0, 1, 3, 2)[..., None]
        states = jnp.einsum("bclgrp,bclgn->bcgrpn",
                            xw.reshape(b, nc, chunk, g, r, p), bc)
    # ---- inter-chunk recurrence (sequential scan over chunks) ------------
    if h0 is None:
        h0 = jnp.zeros((b, h, p, n), jnp.float32)
    chunk_decay = jnp.exp(a_cum[..., -1])                  # (B,nc,H)

    def step(carry, inp):
        st, dec = inp                                      # (B,H,P,N),(B,H)
        prev = carry
        new = prev * dec[..., None, None] + st
        return new, prev                                   # emit state BEFORE chunk

    hT, prev_states = jax.lax.scan(
        step, h0, (states.reshape(b, nc, h, p, n).transpose(1, 0, 2, 3, 4),
                   chunk_decay.transpose(1, 0, 2)))
    # ---- contribution of carried-in state to each position ---------------
    with jax.named_scope("ssd.off"):
        prev_states = prev_states.transpose(1, 0, 2, 3, 4).reshape(
            b, nc, g, r, p, n)                             # (B,nc,G,R,P,N)
        y_off = jnp.einsum("bclgn,bcgrpn->bclgrp", cc, prev_states)
        state_decay = jnp.exp(a_cum).transpose(0, 1, 3, 2)  # (B,nc,Q,H)
        y_off = y_off.reshape(b, nc, chunk, h, p) * state_decay[..., None]
    y = (y_diag + y_off).reshape(b, sp, h, p)
    return y[:, :s], hT


# --------------------------------------------------------------------------
# Full Mamba-2 block.
# --------------------------------------------------------------------------
def _dims(cfg: ModelConfig):
    di = cfg.d_inner
    g, n = cfg.ssm_groups, cfg.ssm_state
    h = cfg.ssm_heads
    conv_ch = di + 2 * g * n
    return di, g, n, h, conv_ch


def init_mamba(kg: KeyGen, cfg: ModelConfig) -> Params:
    d = cfg.d_model
    di, g, n, h, conv_ch = _dims(cfg)
    return {
        "in_proj": dense_init(kg(), (d, 2 * di + 2 * g * n + h)),
        "conv_w": dense_init(kg(), (cfg.ssm_conv, conv_ch), in_dim=cfg.ssm_conv),
        "conv_b": jnp.zeros((conv_ch,)),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, h)),
        "D": jnp.ones((h,)),
        "dt_bias": jnp.log(jnp.expm1(jnp.linspace(1e-3, 0.1, h))),  # softplus^-1
        "norm": jnp.zeros((di,)),
        "out_proj": dense_init(kg(), (di, d)),
    }


def init_mamba_cache(cfg: ModelConfig, batch: int, dtype) -> Params:
    di, g, n, h, conv_ch = _dims(cfg)
    return {
        "conv": jnp.zeros((batch, cfg.ssm_conv - 1, conv_ch), dtype),
        "ssm": jnp.zeros((batch, h, cfg.ssm_head_dim, n), jnp.float32),
    }


def _split_in(proj, cfg):
    di, g, n, h, _ = _dims(cfg)
    z = proj[..., :di]
    xbc = proj[..., di:2 * di + 2 * g * n]
    dt = proj[..., 2 * di + 2 * g * n:]
    return z, xbc, dt


def _causal_conv(xbc, w, bias):
    """Depthwise causal conv, width K: xbc (B,S,C), w (K,C)."""
    k = w.shape[0]
    xp = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    out = sum(xp[:, i:xp.shape[1] - (k - 1 - i), :] * w[i][None, None, :]
              for i in range(k))
    return out + bias[None, None, :]


def _ssm_inputs(xbc_conv, dt_raw, p: Params, cfg: ModelConfig):
    di, g, n, h, _ = _dims(cfg)
    b = xbc_conv.shape[0]
    s = xbc_conv.shape[1]
    xbc_conv = jax.nn.silu(xbc_conv.astype(jnp.float32))
    xs = xbc_conv[..., :di].reshape(b, s, h, cfg.ssm_head_dim)
    bm = xbc_conv[..., di:di + g * n].reshape(b, s, g, n)
    cm = xbc_conv[..., di + g * n:].reshape(b, s, g, n)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])  # (B,S,H)
    a = -jnp.exp(p["A_log"].astype(jnp.float32))                     # (H,)
    return xs, bm, cm, dt, a


def mamba_forward(p: Params, x: jax.Array, cfg: ModelConfig, *,
                  cache: Params | None = None,
                  cache_index: jax.Array | None = None
                  ) -> tuple[jax.Array, Params | None]:
    """Full-sequence (train/prefill) or single-token (decode) Mamba-2 block."""
    b, s, d = x.shape
    di, g, n, h, conv_ch = _dims(cfg)
    proj = x @ p["in_proj"].astype(x.dtype)
    z, xbc, dt_raw = _split_in(proj, cfg)

    if cache is not None and s == 1:
        return _mamba_step(p, cfg, z, xbc, dt_raw, cache)

    xbc_conv = _causal_conv(xbc.astype(jnp.float32), p["conv_w"], p["conv_b"])
    xs, bm, cm, dt, a = _ssm_inputs(xbc_conv, dt_raw, p, cfg)
    y, hT = ssd_chunked(xs * dt[..., None], dt * a[None, None, :], bm, cm,
                        cfg.ssm_chunk)
    y = y + xs * p["D"].astype(jnp.float32)[None, None, :, None]
    y = y.reshape(b, s, di).astype(x.dtype)
    y = gated_rms_norm(y, z, p["norm"], cfg.norm_eps)
    out = y @ p["out_proj"].astype(x.dtype)

    new_cache = None
    if cache is not None:
        kw = cfg.ssm_conv - 1
        tail = xbc[:, -kw:, :] if s >= kw else jnp.pad(
            xbc, ((0, 0), (kw - s, 0), (0, 0)))
        new_cache = {"conv": tail.astype(cache["conv"].dtype), "ssm": hT}
    return out, new_cache


def _mamba_step(p: Params, cfg: ModelConfig, z, xbc, dt_raw, cache):
    """O(1) decode update."""
    b = z.shape[0]
    di, g, n, h, conv_ch = _dims(cfg)
    window = jnp.concatenate([cache["conv"].astype(jnp.float32),
                              xbc.astype(jnp.float32)], axis=1)  # (B,K,C)
    conv = jnp.einsum("bkc,kc->bc", window, p["conv_w"]) + p["conv_b"]
    xs, bm, cm, dt, a = _ssm_inputs(conv[:, None, :], dt_raw, p, cfg)
    xs, bm, cm, dt = xs[:, 0], bm[:, 0], cm[:, 0], dt[:, 0]  # drop seq dim
    r, hp = h // g, cfg.ssm_head_dim
    decay = jnp.exp(dt * a[None, :])                          # (B,H)
    xg = (xs * dt[..., None]).reshape(b, g, r, hp)            # heads as (G,R)
    hs = cache["ssm"] * decay[..., None, None] + jnp.einsum(
        "bgrp,bgn->bgrpn", xg, bm).reshape(b, h, hp, n)
    y = jnp.einsum("bgrpn,bgn->bgrp", hs.reshape(b, g, r, hp, n), cm)
    y = y.reshape(b, h, hp) + xs * p["D"][None, :, None]
    y = y.reshape(b, 1, di).astype(z.dtype)
    y = gated_rms_norm(y, z, p["norm"], cfg.norm_eps)
    out = y @ p["out_proj"].astype(z.dtype)
    new_cache = {"conv": window[:, 1:, :].astype(cache["conv"].dtype), "ssm": hs}
    return out, new_cache
