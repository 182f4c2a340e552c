"""The distributed training step — where the paper meets the mesh.

``build_train_step`` composes  loss -> grad -> COMPRESSED gradient sync ->
optimizer  inside ``jax.shard_map`` whose *manual* axes are the
data-parallel ones (``pod``, ``data``) and whose ``model`` axis stays *auto*
(XLA partitions the tensor-parallel math). Manual DP is the point: the
gradient all-reduce is ours — the compressor's quantized collectives are
the only cross-DP traffic, exactly as in the paper's Algorithm 1.

Compressor state (error feedback E, warm-start Q) is *per-DP-worker* state:
stored with a leading ``n_dp`` dim sharded over the DP axes, so each worker
keeps its own E (never synchronized — the algorithm requires this), while
the inner dims inherit the model-axis sharding of the grads.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core import AxisComm, CompressorConfig, make_compressor
from repro.core.compressors import GradCompressor
from repro.core.lazy import STALE_NS
from repro.launch.sharding import assert_replicated, param_specs
from repro.models.model import init_params, stacked_flags
from repro.train.loss import lm_loss
from repro.train.optimizer import Optimizer

__all__ = ["build_train_step", "init_train_state", "make_model_compressor",
           "abstract_grads_of", "dp_axes_of", "broadcast_comp_state"]

PyTree = Any


def dp_axes_of(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def n_dp_of(mesh: Mesh) -> int:
    n = 1
    for a in dp_axes_of(mesh):
        n *= mesh.shape[a]
    return n


def broadcast_comp_state(state: PyTree, n_dp: int) -> PyTree:
    """Per-worker state: leading DP dim (initially identical everywhere)."""
    return jax.tree.map(lambda x: jnp.broadcast_to(x[None], (n_dp,) + x.shape),
                        state)


def abstract_grads_of(cfg: ModelConfig) -> tuple[PyTree, PyTree]:
    """(abstract grad pytree, stacked flags) for this model — what the
    compressor and the policy planner consume (no allocation)."""
    abstract = jax.eval_shape(lambda k: init_params(cfg, k),
                              jax.random.PRNGKey(0))
    return abstract, stacked_flags(abstract)


def make_model_compressor(cfg: ModelConfig, comp_cfg: CompressorConfig
                          ) -> GradCompressor:
    """Compressor bound to this model's grad pytree (abstract — no alloc)."""
    abstract, flags = abstract_grads_of(cfg)
    return make_compressor(comp_cfg, abstract, flags)


def init_train_state(cfg: ModelConfig, key: jax.Array, optimizer: Optimizer,
                     compressor: GradCompressor, n_dp: int) -> dict:
    params = init_params(cfg, key)
    return dict(
        params=params,
        opt=optimizer.init(params),
        comp=broadcast_comp_state(compressor.init_state(key), n_dp),
        step=jnp.zeros((), jnp.int32),
    )


def build_train_step(cfg: ModelConfig, mesh: Mesh, compressor: GradCompressor,
                     optimizer: Optimizer, *, backend: str = "xla",
                     remat_scan: bool = True, unroll_scan: bool = False,
                     loss_fn: Callable | None = None,
                     dp_axes: tuple[str, ...] | None = None,
                     head_chunk: int = 0, accum_steps: int = 1):
    """Returns (step_fn, state_shardings, batch_shardings).

    step_fn(state, batch) -> (state, metrics); shard_map'd but un-jitted —
    callers jit with the sharding builders (train loop) or lower (dry-run).

    ``accum_steps=k`` splits each worker's batch into k sequential
    microbatches (gradient accumulation): large global batches run on small
    meshes at 1/k the activation memory. The compressed sync fires ONCE per
    accumulated step, on the microbatch-mean gradient — exactly where the
    paper's Algorithm 1 places the quantized collective, so error feedback
    and wire bytes per optimizer step are unchanged. ``k=1`` is the
    unmodified single-pass path (bit-for-bit, regression-tested).
    """
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    dp = dp_axes_of(mesh) if dp_axes is None else tuple(dp_axes)
    # model-axis size for TP sharding: 1 if the model axis is consumed as DP
    tp_size = 1 if "model" in dp else mesh.shape["model"]
    loss_fn = loss_fn or functools.partial(lm_loss, cfg=cfg, backend=backend,
                                           remat_scan=remat_scan,
                                           unroll_scan=unroll_scan,
                                           head_chunk=head_chunk)

    def per_dp(state: dict, batch: dict[str, jax.Array]):
        params = state["params"]
        comp_local = jax.tree.map(lambda x: x[0], state["comp"])
        grad_fn = jax.value_and_grad(
            lambda p, b: loss_fn(p, b), has_aux=True)
        if accum_steps == 1:
            (loss, metrics), grads = grad_fn(params, batch)
            del loss
        else:
            def split(x):
                if x.shape[0] % accum_steps:
                    raise ValueError(
                        f"per-worker batch {x.shape[0]} not divisible by "
                        f"accum_steps={accum_steps}")
                return x.reshape((accum_steps, x.shape[0] // accum_steps)
                                 + x.shape[1:])

            def micro(acc, mb):
                (_, m), g = grad_fn(params, mb)
                acc = jax.tree.map(
                    lambda a, x: a + x.astype(jnp.float32), acc, g)
                return acc, m

            zero = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32),
                                params)
            g_sum, ms = jax.lax.scan(micro, zero, jax.tree.map(split, batch))
            # equal-size microbatches: mean of per-microbatch mean losses ==
            # the full-batch mean, so k only changes activation memory
            grads = jax.tree.map(
                lambda a, p: (a / accum_steps).astype(p.dtype), g_sum, params)
            metrics = jax.tree.map(lambda v: jnp.mean(v, axis=0), ms)
        comm = AxisComm(dp)
        grads, comp_local, rec = compressor.sync(grads, comp_local, comm)
        with jax.named_scope("train.optimizer"):
            new_params, new_opt = optimizer.update(grads, state["opt"], params)
        # tagged: the graph-lint shadow-collective rule allowlists these
        # scalar pmeans (they are telemetry, not wire the policy accounts)
        with jax.named_scope("train.metrics"):
            metrics = {k: jax.lax.pmean(v, dp) for k, v in metrics.items()}
        # EFFECTIVE accounting: static for eager compressors (a plain int,
        # same number every step), static + gate-weighted for lazily
        # aggregated groups (a traced scalar — skipped rounds report only
        # the decision sideband, so the logged trajectory shows the skips)
        metrics["wire_mb_per_step"] = jnp.asarray(
            rec.effective_bits() / 8e6, jnp.float32)
        # collective COUNT is the latency-side cost the fused codec phases
        # shrink (2 + n_raw per step when cfg.fuse_collectives) — surface it
        # next to the byte-side cost so both regressions show up in logs
        metrics["collectives_per_step"] = jnp.asarray(
            rec.effective_collectives(), jnp.float32)
        # server-wire downlink (the aggregate broadcast) — zero on the
        # symmetric wire, so the headline uplink figure is unchanged
        metrics["down_mb_per_step"] = jnp.asarray(
            rec.down_bits / 8e6, jnp.float32)
        new_state = dict(
            params=new_params, opt=new_opt,
            comp=jax.tree.map(lambda x: x[None], comp_local),
            step=state["step"] + 1,
        )
        return new_state, metrics

    rep = P()

    def step_fn(state: dict, batch):
        specs_state = jax.tree.map(lambda _: rep, state)
        specs_state["comp"] = jax.tree.map(lambda _: P(dp), state["comp"])
        specs_batch = jax.tree.map(lambda _: P(dp), batch)
        metric_specs = {k: rep for k in _metric_keys(cfg)}
        return jax.shard_map(per_dp, mesh=mesh,
                             in_specs=(specs_state, specs_batch),
                             out_specs=(specs_state, metric_specs),
                             axis_names=set(dp), check_vma=False)(state, batch)

    # ---- NamedShardings for jit / lower ----------------------------------
    abstract_params = jax.eval_shape(lambda k: init_params(cfg, k),
                                     jax.random.PRNGKey(0))
    flags = stacked_flags(abstract_params)
    if tp_size == 1:
        # pure-DP layout: no tensor parallelism — replicate every param
        pspecs = jax.tree.map(lambda x: P(*([None] * x.ndim)), abstract_params)
    else:
        pspecs = param_specs(abstract_params, flags, axis_size=tp_size, cfg=cfg)
    ns = lambda spec: NamedSharding(mesh, spec)

    def state_shardings(state_abstract: dict) -> dict:
        # compressor state: leading per-worker DP dim + the parameter's own
        # model-axis sharding on the inner dims (error feedback is
        # param-sized — without this, E would replicate over `model` and
        # dominate per-device memory at 70B+ scale).
        comp_inner = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape[1:], x.dtype), state_abstract["comp"])
        comp_specs = compressor.state_pspecs(comp_inner, pspecs, dp)
        # the lazy fire predicate dispatches lax.cond under the manual DP
        # axes; its only un-psummed input is the per-group staleness
        # counter, whose derived spec must replicate over the auto model
        # axis — a sharded counter could diverge the branch choice
        if STALE_NS in comp_specs:
            assert_replicated(comp_specs[STALE_NS], f"comp.{STALE_NS}")
        return dict(
            params=jax.tree.map(ns, pspecs),
            opt=jax.tree.map(lambda _: ns(P()), state_abstract["opt"]),
            comp=jax.tree.map(lambda spec: ns(P(dp, *spec)), comp_specs,
                              is_leaf=lambda x: isinstance(x, P)),
            step=ns(P()),
        )

    def batch_shardings(batch_abstract) -> dict:
        return jax.tree.map(
            lambda x: ns(P(dp, *([None] * (x.ndim - 1)))), batch_abstract)

    return step_fn, state_shardings, batch_shardings


def _metric_keys(cfg: ModelConfig) -> list[str]:
    keys = ["ce", "loss", "wire_mb_per_step", "collectives_per_step",
            "down_mb_per_step"]
    if cfg.n_experts:
        keys.append("moe_aux")
    if cfg.mtp:
        keys.append("mtp_ce")
    return keys
