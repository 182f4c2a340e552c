"""Mixture-of-Experts with capacity-table gather dispatch (dropless-ish).

Design goals: fixed shapes (jit/shard_map-safe), FLOPs proportional to
*active* tokens (so dry-run cost_analysis reflects real MoE compute, not
dense-all-experts waste), and expert-parallel sharding over the `model`
mesh axis (expert dim when divisible, else FFN dim).

Dispatch: assignments (token, expert-choice) are sorted by expert; each
assignment's rank within its expert group indexes a fixed (E, C) capacity
table (C = ceil(T·k/E · capacity_factor), 8-aligned). Overflow assignments
drop (standard capacity semantics); a sentinel row makes gathers/scatters
shape-safe. Router math in f32; probabilities renormalized over the top-k
(Mixtral-style; DeepSeek's sigmoid scoring noted as a simplification in
DESIGN.md).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.common import KeyGen, act_fn, dense_init
from repro.models.mlp import init_mlp, mlp_forward

__all__ = ["init_moe", "moe_forward", "moe_capacity"]

Params = dict[str, Any]


def moe_capacity(n_tokens: int, cfg: ModelConfig) -> int:
    c = int(n_tokens * cfg.experts_per_token / cfg.n_experts * cfg.capacity_factor)
    return max(8, -(-c // 8) * 8)


def init_moe(kg: KeyGen, cfg: ModelConfig) -> Params:
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff_expert
    p: Params = {
        "router": dense_init(kg(), (d, e)),
        "w_gate": dense_init(kg(), (e, d, f), in_dim=d),
        "w_up": dense_init(kg(), (e, d, f), in_dim=d),
        "w_down": dense_init(kg(), (e, f, d), in_dim=f),
    }
    if cfg.n_shared_experts:
        p["shared"] = init_mlp(kg, d, f * cfg.n_shared_experts)
    return p


def moe_forward(p: Params, x: jax.Array, cfg: ModelConfig,
                act: str = "silu") -> tuple[jax.Array, jax.Array]:
    """x (B, S, D) -> (y, aux_load_balance_loss).

    cfg.moe_impl:
      * "global"  — one capacity table over all B·S tokens. Simple, but under
        batch-sharded auto-SPMD the dispatch gather crosses data shards and
        XLA lowers it as full-capacity-tensor all-reduces (measured 43 GB/
        layer on mixtral prefill_32k — EXPERIMENTS.md §Perf).
      * "batched" — one capacity table per batch row (vmapped): the gather's
        batch dim is data-sharded so dispatch is shard-local, and the expert
        einsum reshards via the classic EP all-to-all of only routed tokens.
        Per-row capacity (S·k/E·cf) drops slightly differently; same
        expectation.
    """
    if cfg.moe_impl == "batched":
        b, s, d = x.shape
        t = s
        cap = moe_capacity(t, cfg)
        table, wtab, aux = jax.vmap(
            lambda xr: _dispatch_tables(p, xr, cfg, cap))(x)   # (B,E,C) each
        x_pad = jnp.concatenate([x, jnp.zeros((b, 1, d), x.dtype)], axis=1)
        table = _moe_constraint(table, cfg, batch_dim=0, expert_dim=1)
        wtab = _moe_constraint(wtab, cfg, batch_dim=0, expert_dim=1)
        xin = jax.vmap(lambda xp, tb: xp[tb])(x_pad, table)     # (B,E,C,D)
        xin = _moe_constraint(xin, cfg, batch_dim=0, expert_dim=1)
        g = act_fn(act)(jnp.einsum("becd,edf->becf", xin,
                                   p["w_gate"].astype(xin.dtype)))
        u = jnp.einsum("becd,edf->becf", xin, p["w_up"].astype(xin.dtype))
        y_e = jnp.einsum("becf,efd->becd", g * u,
                         p["w_down"].astype(xin.dtype))
        y_e = _moe_constraint(y_e, cfg, batch_dim=0, expert_dim=1)
        contrib = y_e.astype(jnp.float32) * wtab[..., None]

        def combine(tb, ct):
            yf = jnp.zeros((t + 1, d), jnp.float32)
            return yf.at[tb.reshape(-1)].add(ct.reshape(-1, d),
                                             mode="drop")[:t]

        y = jax.vmap(combine)(table, contrib)
        y = _moe_constraint(y, cfg, batch_dim=0).astype(x.dtype)
        if cfg.n_shared_experts:
            y = y + mlp_forward(p["shared"], x, act)
        return y, jnp.mean(aux)
    b, s, d = x.shape
    y, aux = _moe_tokens(p, x.reshape(b * s, d), cfg, act)
    return y.reshape(b, s, d), aux


def _moe_constraint(x: jax.Array, cfg: ModelConfig, *, batch_dim: int | None = None,
                    expert_dim: int | None = None):
    """Sharding hints for the MoE dispatch tensors (EXPERIMENTS.md §Perf:
    without them the auto-partitioner materializes/all-gathers the full
    (B, E, C, D) capacity tensor — measured 43 GB/layer on mixtral
    prefill_32k and 18.8 GB/layer on deepseek train_4k).

    Only mesh axes whose type is Auto in the ambient (possibly partial-
    manual) mesh are referenced: under the training shard_map the data axes
    are Manual (shapes already local) and only `model` is constrained."""
    if not cfg.moe_shard_hints:
        return x
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.shape:  # no mesh in scope
        return x
    shape = dict(mesh.shape)
    auto = {n for n, t in zip(mesh.axis_names, mesh.axis_types)
            if t == jax.sharding.AxisType.Auto}
    spec = [None] * x.ndim
    if (expert_dim is not None and "model" in auto
            and cfg.n_experts % shape.get("model", 1) == 0):
        spec[expert_dim] = "model"
    if batch_dim is not None:
        dp = tuple(a for a in ("pod", "data")
                   if a in auto and x.shape[batch_dim] % shape[a] == 0)
        if dp:
            spec[batch_dim] = dp if len(dp) > 1 else dp[0]
    if all(v is None for v in spec):
        return x
    return jax.lax.with_sharding_constraint(x, P(*spec))


def _dispatch_tables(p: Params, xf: jax.Array, cfg: ModelConfig, cap: int):
    """Routing for one flat token set xf (T, D): returns (table (E, cap),
    wtab (E, cap), aux) — the small tensors; callers do the heavy gather."""
    t, d = xf.shape
    e, k = cfg.n_experts, cfg.experts_per_token
    logits = (xf.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                    # (T, E)
    top_p, top_i = jax.lax.top_k(probs, k)                     # (T, k)
    weights = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    me = jnp.mean(probs, axis=0)
    hits = jnp.zeros((e,), jnp.float32).at[top_i.reshape(-1)].add(1.0)
    aux = e * jnp.sum(me * hits / (t * k))

    flat_e = top_i.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    counts = jnp.zeros((e,), jnp.int32).at[flat_e].add(1)
    starts = jnp.cumsum(counts) - counts
    rank = jnp.arange(t * k, dtype=jnp.int32) - starts[sorted_e]
    tok_of = (order // k).astype(jnp.int32)
    w_of = weights.reshape(-1)[order]

    table = jnp.full((e, cap), jnp.int32(t), jnp.int32)
    table = table.at[sorted_e, rank].set(tok_of, mode="drop")
    wtab = jnp.zeros((e, cap), jnp.float32)
    wtab = wtab.at[sorted_e, rank].set(w_of, mode="drop")
    return table, wtab, aux


def _moe_tokens(p: Params, xf: jax.Array, cfg: ModelConfig,
                act: str = "silu") -> tuple[jax.Array, jax.Array]:
    """Capacity-table MoE over a flat token set xf (T, D)."""
    t, d = xf.shape
    e, k = cfg.n_experts, cfg.experts_per_token
    cap = moe_capacity(t, cfg)

    logits = (xf.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                    # (T, E)
    top_p, top_i = jax.lax.top_k(probs, k)                     # (T, k)
    weights = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    # ---- load-balance aux loss (Switch-style) ---------------------------
    me = jnp.mean(probs, axis=0)                               # router mass
    hits = jnp.zeros((e,), jnp.float32).at[top_i.reshape(-1)].add(1.0)
    ce = hits / (t * k)                                        # dispatch frac
    aux = e * jnp.sum(me * ce)

    # ---- capacity-table dispatch ----------------------------------------
    flat_e = top_i.reshape(-1)                                 # (T*k,)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    counts = jnp.zeros((e,), jnp.int32).at[flat_e].add(1)
    starts = jnp.cumsum(counts) - counts
    rank = jnp.arange(t * k, dtype=jnp.int32) - starts[sorted_e]
    tok_of = (order // k).astype(jnp.int32)
    w_of = weights.reshape(-1)[order]

    sentinel = jnp.int32(t)
    table = jnp.full((e, cap), sentinel, jnp.int32)
    table = table.at[sorted_e, rank].set(tok_of, mode="drop")
    wtab = jnp.zeros((e, cap), jnp.float32)
    wtab = wtab.at[sorted_e, rank].set(w_of, mode="drop")

    xf_pad = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)], axis=0)
    table = _moe_constraint(table, cfg, expert_dim=0)
    wtab = _moe_constraint(wtab, cfg, expert_dim=0)
    xin = _moe_constraint(xf_pad[table], cfg, expert_dim=0)    # (E, C, D)

    # ---- expert FFN (active tokens only) --------------------------------
    g = act_fn(act)(jnp.einsum("ecd,edf->ecf", xin, p["w_gate"].astype(xin.dtype)))
    u = jnp.einsum("ecd,edf->ecf", xin, p["w_up"].astype(xin.dtype))
    y_e = _moe_constraint(
        jnp.einsum("ecf,efd->ecd", g * u, p["w_down"].astype(xin.dtype)),
        cfg, expert_dim=0)

    # ---- weighted combine ------------------------------------------------
    contrib = (y_e.astype(jnp.float32) * wtab[..., None]).reshape(-1, d)
    yf = jnp.zeros((t + 1, d), jnp.float32)
    yf = yf.at[table.reshape(-1)].add(contrib, mode="drop")
    y = yf[:t].astype(xf.dtype)

    if cfg.n_shared_experts:
        y = y + mlp_forward(p["shared"], xf, act)
    return y, aux
