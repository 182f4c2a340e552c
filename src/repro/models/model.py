"""TransformerLM: embed -> lead layers -> scan(pattern) -> tail -> head.

The layer stack is ``lead + pattern * repeats + tail`` (configs/base.py).
The repeated pattern is executed under ``jax.lax.scan`` with per-position
parameter stacks (leading dim = repeats) — HLO stays small for 48-80 layer
models and the stacked leaves are exactly what the compressor treats as
``stacked`` (per-layer low-rank compression).

Supports: token embeddings (plain, or summed multi-codebook for MusicGen),
a conditioning-prefix (stub frontend embeddings, §6 of DESIGN.md), tied or
separate LM heads (per-codebook heads for MusicGen), and DeepSeek's MTP
(multi-token-prediction) auxiliary head at train time.

Modes (same function, driven by cache args):
  * train:   caches=None                      -> logits
  * prefill: caches=zeros, x = full prompt    -> logits, filled caches
  * decode:  caches=state, x = 1 token        -> logits, updated caches
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig
from repro.models.blocks import init_layer, init_layer_cache, layer_forward
from repro.models.common import KeyGen, dense_init, embed_init, rms_norm

__all__ = ["init_params", "stacked_flags", "forward", "init_caches",
           "count_params"]

Params = dict[str, Any]


# ---------------------------------------------------------------- init
def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    cfg.validate()
    kg = KeyGen(key)
    d, v = cfg.d_model, cfg.vocab_size
    p: Params = {}
    if cfg.n_codebooks:
        p["embed"] = embed_init(kg(), (cfg.n_codebooks, v, d))
    else:
        p["embed"] = embed_init(kg(), (v, d))

    p["lead"] = [init_layer(kg, s, cfg) for s in cfg.lead]
    # per-pattern-position stacks: init each repeat independently, stack
    scan_params = []
    for pos, spec in enumerate(cfg.pattern):
        per_repeat = [init_layer(kg, spec, cfg) for _ in range(cfg.repeats)]
        scan_params.append(jax.tree.map(lambda *xs: jnp.stack(xs), *per_repeat))
    p["scan"] = scan_params
    p["tail"] = [init_layer(kg, s, cfg) for s in cfg.tail]
    p["final_norm"] = jnp.zeros((d,))
    if not cfg.tie_embeddings:
        if cfg.n_codebooks:
            p["head"] = dense_init(kg(), (cfg.n_codebooks, d, v), in_dim=d)
        else:
            p["head"] = dense_init(kg(), (d, v))
    if cfg.mtp:
        p["mtp"] = {
            "proj": dense_init(kg(), (2 * d, d)),
            "norm_h": jnp.zeros((d,)),
            "norm_e": jnp.zeros((d,)),
            "layer": init_layer(kg, LayerSpec("attn"), cfg),
            "final_norm": jnp.zeros((d,)),
        }
    dtype = jnp.dtype(cfg.dtype)
    return jax.tree.map(lambda w: w.astype(dtype), p)


def stacked_flags(params: Params) -> Params:
    """Pytree of bools marking scan-stacked leaves (for the compressor)."""
    flags = jax.tree.map(lambda _: False, params)
    flags["scan"] = jax.tree.map(lambda _: True, params["scan"])
    return flags


def count_params(params: Params) -> int:
    return sum(int(l.size) for l in jax.tree.leaves(params))


def init_caches(cfg: ModelConfig, batch: int, max_seq: int, dtype) -> Params:
    caches: Params = {
        "lead": [init_layer_cache(s, cfg, batch, max_seq, dtype) for s in cfg.lead],
        "tail": [init_layer_cache(s, cfg, batch, max_seq, dtype) for s in cfg.tail],
        "scan": [],
    }
    for spec in cfg.pattern:
        per = [init_layer_cache(spec, cfg, batch, max_seq, dtype)
               for _ in range(cfg.repeats)]
        caches["scan"].append(jax.tree.map(lambda *xs: jnp.stack(xs), *per))
    return caches


# ---------------------------------------------------------------- embed/head
# the vocabulary end (embedding, final norm, LM head; the loss adds its
# cross-entropy) carries one tag, HEAD_SCOPE, for the trace's split
HEAD_SCOPE = "model.head"


def _embed(params: Params, tokens: jax.Array, cfg: ModelConfig) -> jax.Array:
    with jax.named_scope(HEAD_SCOPE):
        if cfg.n_codebooks:
            # tokens (B, S, n_cb): sum codebook embeddings (MusicGen delay)
            embs = [params["embed"][cb][tokens[..., cb]]
                    for cb in range(cfg.n_codebooks)]
            return sum(embs)
        return params["embed"][tokens]


def _head(params: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    with jax.named_scope(HEAD_SCOPE):
        if cfg.tie_embeddings:
            if cfg.n_codebooks:
                return jnp.einsum("bsd,cvd->bscv", x, params["embed"])
            return x @ params["embed"].T
        if cfg.n_codebooks:
            return jnp.einsum("bsd,cdv->bscv", x, params["head"])
        return x @ params["head"]


# ---------------------------------------------------------------- forward
def apply_head(params: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Public head application (used by the chunked-CE loss path)."""
    return _head(params, x, cfg)


def forward(params: Params, tokens: jax.Array, cfg: ModelConfig, *,
            caches: Params | None = None, cache_index: jax.Array | None = None,
            cond: jax.Array | None = None, backend: str = "xla",
            remat_scan: bool = False, unroll_scan: bool = False,
            return_hidden: bool = False
            ) -> tuple[jax.Array, Params | None, dict[str, jax.Array]]:
    """Returns (logits, new_caches, aux).

    tokens: (B, S) int32 — or (B, S, n_codebooks) for multi-codebook models.
    cond:   (B, cond_len, D) stub frontend embeddings, prepended (train and
            prefill only; positions account for the prefix).
    """
    x = _embed(params, tokens, cfg)
    b, s = x.shape[0], x.shape[1]
    offset = 0
    if cond is not None and s > 1:
        x = jnp.concatenate([cond.astype(x.dtype), x], axis=1)
        offset = cond.shape[1]
        s = x.shape[1]

    if cache_index is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    else:
        positions = jnp.broadcast_to(
            (cache_index + offset)[None, None]
            if jnp.ndim(cache_index) == 0 else cache_index[:, None], (b, s))

    aux_total = jnp.zeros((), jnp.float32)
    new_caches: Params | None = None if caches is None else {
        "lead": [], "scan": [], "tail": []}

    # ---- lead (unscanned) -----------------------------------------------
    for i, spec in enumerate(cfg.lead):
        c = caches["lead"][i] if caches is not None else None
        x, nc, aux = layer_forward(params["lead"][i], x, spec, cfg,
                                   positions=positions, cache=c,
                                   cache_index=cache_index, backend=backend)
        aux_total += aux
        if new_caches is not None:
            new_caches["lead"].append(nc)

    # ---- scanned pattern ---------------------------------------------------
    if cfg.repeats > 0:
        specs = cfg.pattern

        def body(carry, xs):
            h, aux_acc = carry
            layer_ps, layer_cs = xs
            new_cs = []
            for pos, spec in enumerate(specs):
                c = None if layer_cs is None else layer_cs[pos]
                h, nc, aux = layer_forward(layer_ps[pos], h, spec, cfg,
                                           positions=positions, cache=c,
                                           cache_index=cache_index,
                                           backend=backend)
                aux_acc = aux_acc + aux
                new_cs.append(nc)
            ys = new_cs if caches is not None else None
            return (h, aux_acc), ys

        if remat_scan:
            body = jax.checkpoint(body)
        scan_caches = caches["scan"] if caches is not None else None
        if unroll_scan:
            # python-unrolled repeats: identical math; used by the dry-run
            # because XLA cost_analysis counts while-loop bodies only once
            # (DESIGN.md roofline notes) — unrolling restores exact FLOPs.
            outs = []
            carry = (x, aux_total)
            for r in range(cfg.repeats):
                xs_r = jax.tree.map(lambda t: t[r], (params["scan"], scan_caches))
                carry, ys = body(carry, xs_r)
                outs.append(ys)
            (x, aux_total) = carry
            scan_out = (jax.tree.map(lambda *ts: jnp.stack(ts), *outs)
                        if caches is not None else None)
        else:
            (x, aux_total), scan_out = jax.lax.scan(
                body, (x, aux_total), (params["scan"], scan_caches))
        if new_caches is not None:
            new_caches["scan"] = scan_out

    # ---- tail (unscanned) -------------------------------------------------
    for i, spec in enumerate(cfg.tail):
        c = caches["tail"][i] if caches is not None else None
        x, nc, aux = layer_forward(params["tail"][i], x, spec, cfg,
                                   positions=positions, cache=c,
                                   cache_index=cache_index, backend=backend)
        aux_total += aux
        if new_caches is not None:
            new_caches["tail"].append(nc)

    with jax.named_scope(HEAD_SCOPE):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if offset:
        x = x[:, offset:]
    if return_hidden:
        # chunked-CE path: caller fuses head matmul into the loss to avoid
        # materializing (B, S, V) logits (EXPERIMENTS.md §Perf)
        return x, new_caches, {"moe_aux": aux_total}
    logits = _head(params, x, cfg)

    aux_out: dict[str, jax.Array] = {"moe_aux": aux_total}

    # ---- MTP head (train only) --------------------------------------------
    if cfg.mtp and caches is None and tokens.ndim == 2 and tokens.shape[1] > 1:
        h_norm = rms_norm(x, params["mtp"]["norm_h"], cfg.norm_eps)
        e_next = rms_norm(_embed(params, tokens, cfg),
                          params["mtp"]["norm_e"], cfg.norm_eps)
        # combine h_t with emb(t_{t+1}): shift embeddings left by one
        e_shift = jnp.roll(e_next, -1, axis=1)
        h_mtp = jnp.concatenate([h_norm, e_shift], axis=-1) @ params["mtp"]["proj"]
        h_mtp, _, _ = layer_forward(params["mtp"]["layer"], h_mtp,
                                    LayerSpec("attn"), cfg,
                                    positions=positions, backend=backend)
        h_mtp = rms_norm(h_mtp, params["mtp"]["final_norm"], cfg.norm_eps)
        aux_out["mtp_logits"] = _head(params, h_mtp, cfg)

    return logits, new_caches, aux_out
