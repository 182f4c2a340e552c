"""Quickstart: LQ-SGD distributed training in ~40 lines.

Simulates an 8-device cluster on CPU (4-way data x 2-way tensor parallel),
trains a tiny Mixtral-family model with the paper's compressed gradient
all-reduce, and prints the wire savings.

    PYTHONPATH=src python examples/quickstart.py
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax

from repro.configs import get_config
from repro.core import CompressorConfig
from repro.data.synthetic import LMDataConfig, lm_batch
from repro.launch.mesh import make_mesh
from repro.train.optimizer import sgd
from repro.train.step import (build_train_step, init_train_state,
                              make_model_compressor, n_dp_of)


def main():
    mesh = make_mesh((4, 2), ("data", "model"))
    cfg = get_config("mixtral-8x7b", smoke=True)   # reduced 4-expert variant

    compressor = make_model_compressor(
        cfg, CompressorConfig(name="lq_sgd", rank=1, bits=8, alpha=10.0))
    optimizer = sgd(lr=0.05)
    step_fn, _, _ = build_train_step(cfg, mesh, compressor, optimizer,
                                     remat_scan=False)

    data = LMDataConfig(vocab_size=cfg.vocab_size, seq_len=64, batch=8)
    with jax.set_mesh(mesh):
        state = init_train_state(cfg, jax.random.PRNGKey(0), optimizer,
                                 compressor, n_dp_of(mesh))
        n_params = sum(x.size for x in jax.tree.leaves(state["params"]))
        print(f"model: {cfg.name}  params={n_params/1e6:.2f}M  "
              f"mesh=(data=4, model=2)")
        print(f"gradient wire/step: LQ-SGD {compressor.wire_bits_per_step()/8e6:.3f}MB"
              f" vs uncompressed {n_params*4/1e6:.1f}MB "
              f"({n_params*4*8/compressor.wire_bits_per_step():.0f}x smaller)")
        jstep = jax.jit(step_fn, donate_argnums=0)
        for step in range(20):
            state, metrics = jstep(state, lm_batch(data, step))
            if step % 5 == 0 or step == 19:
                print(f"step {step:3d}  loss={float(metrics['loss']):.4f}")


if __name__ == "__main__":
    main()
