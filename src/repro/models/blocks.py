"""Decoder layers: (attn | mamba) mixer + optional (dense | MoE) FFN."""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig
from repro.models.attention import attn_forward, init_attn, init_attn_cache
from repro.models.common import KeyGen, rms_norm
from repro.models.mla import init_mla, init_mla_cache, mla_forward
from repro.models.mlp import init_mlp, mlp_forward
from repro.models.moe import init_moe, moe_forward
from repro.models.ssm import init_mamba, init_mamba_cache, mamba_forward

__all__ = ["init_layer", "layer_forward", "init_layer_cache", "has_ffn"]

Params = dict[str, Any]


def has_ffn(spec: LayerSpec, cfg: ModelConfig) -> bool:
    return spec.moe or cfg.d_ff > 0


def init_layer(kg: KeyGen, spec: LayerSpec, cfg: ModelConfig) -> Params:
    d = cfg.d_model
    p: Params = {"ln1": jnp.zeros((d,))}
    if spec.kind == "attn":
        p["mixer"] = init_mla(kg, cfg) if cfg.use_mla else init_attn(kg, cfg)
    else:
        p["mixer"] = init_mamba(kg, cfg)
    if has_ffn(spec, cfg):
        p["ln2"] = jnp.zeros((d,))
        p["ffn"] = init_moe(kg, cfg) if spec.moe else init_mlp(kg, d, cfg.d_ff)
    return p


def init_layer_cache(spec: LayerSpec, cfg: ModelConfig, batch: int,
                     max_seq: int, dtype) -> Params:
    if spec.kind == "mamba":
        return init_mamba_cache(cfg, batch, dtype)
    if cfg.use_mla:
        return init_mla_cache(cfg, batch, max_seq, dtype)
    # SWA layers only ever see `window` keys — cap the cache (memory win;
    # correctness preserved because decode positions use absolute indices
    # modulo nothing here: we keep the full buffer when window is None).
    return init_attn_cache(cfg, batch, max_seq, dtype)


def layer_forward(p: Params, x: jax.Array, spec: LayerSpec, cfg: ModelConfig, *,
                  positions: jax.Array, cache: Params | None = None,
                  cache_index: jax.Array | None = None,
                  backend: str = "xla"
                  ) -> tuple[jax.Array, Params | None, jax.Array]:
    """Pre-norm residual block. Returns (x, new_cache, moe_aux)."""
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    # sub-layer tags: the chip benchmark's trace reduction splits the
    # model's device time by them (they change HLO metadata only)
    with jax.named_scope("model.mixer"):
        if spec.kind == "attn":
            fwd = mla_forward if cfg.use_mla else attn_forward
            mix, new_cache = fwd(p["mixer"], h, spec, cfg, positions=positions,
                                 cache=cache, cache_index=cache_index,
                                 backend=backend)
        else:
            mix, new_cache = mamba_forward(p["mixer"], h, cfg, cache=cache,
                                           cache_index=cache_index)
    x = x + mix
    aux = jnp.zeros((), jnp.float32)
    if has_ffn(spec, cfg):
        h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
        with jax.named_scope("model.mlp"):
            if spec.moe:
                y, aux = moe_forward(p["ffn"], h2, cfg, cfg.mlp_act)
            else:
                y = mlp_forward(p["ffn"], h2, cfg.mlp_act)
        x = x + y
    return x, new_cache, aux
