"""End-to-end driver: train a ~100M-parameter LM for a few hundred steps
with LQ-SGD over a simulated 8-worker data-parallel cluster, checkpoint,
restore, and verify the loss curve + comm ledger.

    PYTHONPATH=src python examples/train_lm_e2e.py [--steps 200]

(~100M params on one CPU core: a few minutes with the default 200 steps of
batch 8 x seq 64; pass --steps 300+ and --seq 128 on beefier hosts.)
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import argparse
import time

import jax

from repro.checkpoint.io import restore
from repro.configs.base import ModelConfig, attn
from repro.core import CompressorConfig
from repro.data.synthetic import LMDataConfig, lm_batch
from repro.launch.mesh import make_mesh
from repro.train.optimizer import sgd
from repro.train.step import (build_train_step, init_train_state,
                              make_model_compressor, n_dp_of)
from repro.train.trainer import Trainer, TrainerConfig


def model_100m() -> ModelConfig:
    # ~101M params: 12L, d=768, GQA 12/4, ffn 2048, 32k vocab
    return ModelConfig(
        name="lm-100m", arch_type="dense", source="examples",
        d_model=768, vocab_size=32_000, pattern=(attn(),), repeats=12,
        n_heads=12, n_kv_heads=4, head_dim=64, d_ff=2048, dtype="float32")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--compressor", default="lq_sgd")
    ap.add_argument("--rank", type=int, default=2)
    args = ap.parse_args()

    mesh = make_mesh((4, 1), ("data", "model"))
    cfg = model_100m()
    comp = make_model_compressor(
        cfg, CompressorConfig(name=args.compressor, rank=args.rank, bits=8))
    opt = sgd(lr=0.003, momentum=0.9)
    step_fn, _, _ = build_train_step(cfg, mesh, comp, opt, remat_scan=False)
    data = LMDataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                        batch=args.batch)

    with jax.set_mesh(mesh):
        state = init_train_state(cfg, jax.random.PRNGKey(0), opt, comp,
                                 n_dp_of(mesh))
        n = sum(x.size for x in jax.tree.leaves(state["params"]))
        wire = comp.wire_bits_per_step() / 8e6
        print(f"params={n/1e6:.1f}M  workers=4  wire/step={wire:.2f}MB "
              f"(uncompressed {n*4/1e6:.0f}MB, {n*4/1e6/wire:.0f}x)")
        jstep = jax.jit(step_fn, donate_argnums=0)
        trainer = Trainer(jstep, lambda s: lm_batch(data, s),
                          TrainerConfig(steps=args.steps, log_every=20,
                                        ckpt_every=max(args.steps // 2, 1),
                                        ckpt_path="checkpoints/e2e.ckpt"))
        t0 = time.time()
        state = trainer.run(state)
        print(f"trained {args.steps} steps in {time.time()-t0:.0f}s; "
              f"loss {trainer.history[0]['loss']:.3f} -> "
              f"{trainer.history[-1]['loss']:.3f}")
        if args.steps >= 30:
            assert trainer.history[-1]["loss"] < trainer.history[0]["loss"]

        # checkpoint round-trip
        host = jax.tree.map(jax.device_get, state)
        like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), host)
        restored = restore("checkpoints/e2e.ckpt", like)
        print("checkpoint restore: ok (step",
              int(jax.tree.leaves(restored["step"])[0]), ")")


if __name__ == "__main__":
    main()
