"""Trace (config x policy x mesh) combinations to jaxpr / compiled HLO.

Everything here is abstract-shapes-only: ``jax.eval_shape`` builds the
state, ``jax.make_jaxpr`` / ``.lower().compile()`` never touch real
parameter memory, so the 671B config traces on a laptop.

Two levels:

* :func:`trace_sync_jaxpr` / :func:`trace_step_jaxpr` — the traced jaxpr,
  on a minimal mesh (collective *structure* — which ops, what operands,
  which cond branch — is mesh-shape independent at this level);
* :func:`compile_step_hlo` — the compiled SPMD module on a real (forced
  host-device) mesh, where partitioning, donation aliasing, and replica
  groups exist. Callers control the device count via ``XLA_FLAGS=
  --xla_force_host_platform_device_count=N`` before the first jax import
  (the lint CLI does this from its ``--mesh`` argument).
"""

from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs import INPUT_SHAPES
from repro.configs.base import ModelConfig
from repro.core import CompressorConfig
from repro.core.comm import AxisComm
from repro.core.compressors import GradCompressor
from repro.launch.inputs import input_specs
from repro.train.optimizer import sgd
from repro.train.step import (
    build_train_step,
    init_train_state,
    make_model_compressor,
    n_dp_of,
)

__all__ = [
    "abstract_comp_state",
    "compile_step_hlo",
    "trace_step_jaxpr",
    "trace_sync_jaxpr",
]


def abstract_comp_state(comp: GradCompressor) -> Any:
    """The compressor's threaded state, as ShapeDtypeStructs (no alloc)."""
    key = jax.ShapeDtypeStruct((2,), np.uint32)
    return jax.eval_shape(comp.init_state, key)


def trace_sync_jaxpr(
    comp: GradCompressor,
    abstract_grads: Any,
    axis_name: str = "data",
):
    """Jaxpr of ONE compressor sync under a single-device manual
    shard_map — the collective primitives are all present (nothing folds
    them away at trace time), so the inventory walker sees the exact
    per-round structure."""
    mesh = Mesh(np.array(jax.devices()[:1]), (axis_name,))
    state = abstract_comp_state(comp)

    def worker(grads, st):
        out, new_state, _rec = comp.sync(grads, st, AxisComm((axis_name,)))
        return out, new_state

    f = jax.shard_map(
        worker,
        mesh=mesh,
        in_specs=(P(), P()),
        out_specs=(P(), P()),
        axis_names={axis_name},
        check_vma=False,
    )
    return jax.make_jaxpr(f)(abstract_grads, state)


def _step_pieces(
    cfg: ModelConfig,
    comp_cfg: CompressorConfig,
    mesh: Mesh,
    shape_name: str,
):
    shape = INPUT_SHAPES[shape_name]
    if shape.mode != "train":
        raise ValueError(f"graph lint covers train shapes, got {shape_name!r}")
    compressor = make_model_compressor(cfg, comp_cfg)
    opt = sgd(1e-2)
    step_fn, state_sh, batch_sh = build_train_step(cfg, mesh, compressor, opt)
    state_abs = jax.eval_shape(
        lambda k: init_train_state(cfg, k, opt, compressor, n_dp_of(mesh)),
        jax.random.PRNGKey(0),
    )
    batch_abs = input_specs(cfg, shape)
    return compressor, step_fn, state_sh, batch_sh, state_abs, batch_abs


def trace_step_jaxpr(
    cfg: ModelConfig,
    comp_cfg: CompressorConfig,
    mesh: Mesh,
    shape_name: str = "train_4k",
):
    """(jaxpr, compressor) of the full train step on ``mesh``."""
    compressor, step_fn, _, _, state_abs, batch_abs = _step_pieces(
        cfg, comp_cfg, mesh, shape_name
    )
    with jax.set_mesh(mesh):
        jaxpr = jax.make_jaxpr(step_fn)(state_abs, batch_abs)
    return jaxpr, compressor


def compile_step_hlo(
    cfg: ModelConfig,
    comp_cfg: CompressorConfig,
    mesh: Mesh,
    shape_name: str = "train_4k",
    donate: bool = True,
) -> tuple[str, GradCompressor]:
    """(compiled HLO text, compressor) of the sharded, jitted train step —
    the same jit arrangement the launcher and ``launch/dryrun.py`` use
    (donation included, so the aliasing rule checks the real thing)."""
    compressor, step_fn, state_sh, batch_sh, state_abs, batch_abs = _step_pieces(
        cfg, comp_cfg, mesh, shape_name
    )
    with jax.set_mesh(mesh):
        st_sh = state_sh(state_abs)
        jitted = jax.jit(
            step_fn,
            in_shardings=(st_sh, batch_sh(batch_abs)),
            out_shardings=(st_sh, None),
            donate_argnums=(0,) if donate else (),
        )
        compiled = jitted.lower(state_abs, batch_abs).compile()
    return compiled.as_text(), compressor
