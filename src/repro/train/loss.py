"""Causal-LM loss (next-token CE, f32) + MoE aux + MTP auxiliary loss."""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.model import HEAD_SCOPE, forward

__all__ = ["lm_loss"]


def _ce(logits: jax.Array, targets: jax.Array) -> jax.Array:
    with jax.named_scope(HEAD_SCOPE):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def lm_loss(params: Any, batch: dict[str, jax.Array], cfg: ModelConfig, *,
            backend: str = "xla", remat_scan: bool = False,
            unroll_scan: bool = False, head_chunk: int = 0
            ) -> tuple[jax.Array, dict[str, jax.Array]]:
    """batch: {'tokens': (B,S) or (B,S,cb) int32, optional 'cond': (B,L,D)}.

    Returns (scalar loss, metrics). Target = next token (shifted); the last
    position is masked. MTP (if enabled) adds CE against t+2 at 0.3 weight
    (DeepSeek-V3's lambda). MoE aux joins at cfg.router_aux_coef.
    """
    tokens = batch["tokens"]
    if head_chunk and not cfg.mtp and not cfg.n_codebooks:
        return _lm_loss_chunked(params, batch, cfg, backend=backend,
                                remat_scan=remat_scan,
                                unroll_scan=unroll_scan, chunk=head_chunk)
    logits, _, aux = forward(params, tokens, cfg, cond=batch.get("cond"),
                             backend=backend, remat_scan=remat_scan,
                             unroll_scan=unroll_scan)
    tgt = jnp.roll(tokens, -1, axis=1)
    nll = _ce(logits, tgt)                      # (B, S[, cb])
    if cfg.n_codebooks:
        nll = jnp.mean(nll, axis=-1)
    s = tokens.shape[1]
    mask = (jnp.arange(s) < s - 1).astype(jnp.float32)[None, :]
    loss = jnp.sum(nll * mask) / (jnp.sum(mask) * tokens.shape[0])
    metrics = {"ce": loss}
    if "mtp_logits" in aux:
        tgt2 = jnp.roll(tokens, -2, axis=1)
        mask2 = (jnp.arange(s) < s - 2).astype(jnp.float32)[None, :]
        mtp_nll = _ce(aux["mtp_logits"], tgt2)
        mtp = jnp.sum(mtp_nll * mask2) / (jnp.sum(mask2) * tokens.shape[0])
        loss = loss + 0.3 * mtp
        metrics["mtp_ce"] = mtp
    if cfg.n_experts:
        loss = loss + cfg.router_aux_coef * aux["moe_aux"]
        metrics["moe_aux"] = aux["moe_aux"]
    metrics["loss"] = loss
    return loss, metrics


def _lm_loss_chunked(params, batch, cfg, *, backend, remat_scan, unroll_scan,
                     chunk):
    """CE with the LM head fused per sequence-chunk: never materializes the
    full (B, S, V) logits (a 4-17 GB/device f32 temp for 128k-262k vocabs).
    Numerically identical to the plain path (same masking/averaging)."""
    from repro.models.model import apply_head

    tokens = batch["tokens"]
    hidden, _, aux = forward(params, tokens, cfg, cond=batch.get("cond"),
                             backend=backend, remat_scan=remat_scan,
                             unroll_scan=unroll_scan, return_hidden=True)
    b, s, d = hidden.shape
    tgt = jnp.roll(tokens, -1, axis=1)
    pad = (-s) % chunk
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        tgt = jnp.pad(tgt, ((0, 0), (0, pad)))
    nc = hidden.shape[1] // chunk
    hc = hidden.reshape(b, nc, chunk, d).transpose(1, 0, 2, 3)
    tc = tgt.reshape(b, nc, chunk).transpose(1, 0, 2)

    def one(args):
        h, t = args
        logits = apply_head(params, h, cfg)
        return _ce(logits, t)

    nll = jax.lax.map(one, (hc, tc))                  # (nc, B, chunk)
    nll = nll.transpose(1, 0, 2).reshape(b, nc * chunk)[:, :s]
    mask = (jnp.arange(s) < s - 1).astype(jnp.float32)[None, :]
    loss = jnp.sum(nll * mask) / (jnp.sum(mask) * b)
    metrics = {"ce": loss}
    if cfg.n_experts:
        loss = loss + cfg.router_aux_coef * aux["moe_aux"]
        metrics["moe_aux"] = aux["moe_aux"]
    metrics["loss"] = loss
    return loss, metrics
