"""Training launcher.

    # single-process CPU run with a simulated 8-device (4 data x 2 model) mesh:
    REPRO_DEVICES=8 PYTHONPATH=src python -m repro.launch.train \
        --arch mixtral-8x7b --smoke --compressor lq_sgd --rank 1 --bits 8 \
        --steps 50 --batch 8 --seq 64

On a real TPU cluster each host runs this module unmodified (jax picks up
the slice topology); the mesh flags select the production layout.

The step runs under the async runtime by default (prefetched batches,
deferred metric sync, background checkpoints — ``repro.train.runtime``);
``--runtime sync`` selects the reference loop. Either way the step is
jitted WITH the shardings ``build_train_step`` derives, so compressor
error feedback shards over (dp, model) instead of replicating.
"""
import os
if os.environ.get("REPRO_DEVICES"):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ["REPRO_DEVICES"])

import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.io import peek_step, restore as ckpt_restore
from repro.configs import get_config, list_archs
from repro.core import CompressorConfig
from repro.data.synthetic import LMDataConfig, lm_batch
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.train.optimizer import make_optimizer
from repro.train.runtime import (AsyncRunner, RuntimeConfig,
                                 build_sharded_step, run_schedule,
                                 sharded_init)
from repro.train.step import make_model_compressor
from repro.train.trainer import Trainer


def run(argv: list[str] | None = None) -> list[dict[str, float]]:
    """Parse ``argv`` (default: the command line), train, and return the
    runner's ``history``: one dict of metrics per logged step."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--optimizer", default="sgd", choices=["sgd", "adam"])
    ap.add_argument("--compressor", default="lq_sgd",
                    choices=["none", "topk", "qsgd", "powersgd", "lq_sgd"])
    ap.add_argument("--policy", default=None,
                    help="per-leaf policy: 'uniform' (default), 'auto' "
                         "(cost-model planner), or a spec string "
                         "'pattern=method:knob=v,...'; falls back to the "
                         "arch config's compression_policy hint")
    ap.add_argument("--error-budget", type=float, default=0.3,
                    help="auto-planner: max per-leaf error proxy")
    ap.add_argument("--warmup", type=int, default=0,
                    help="schedule: full-precision sync for the first W "
                         "steps (in-graph, no recompilation)")
    ap.add_argument("--decay", default=None,
                    help="schedule: piecewise rank/bit caps, e.g. "
                         "'200:rank=1,500:bits=4' (rebuilds at boundaries)")
    ap.add_argument("--lazy-thresh", type=float, default=0.0,
                    help="lazy aggregation: relative innovation threshold; "
                         "a method group whose accumulated update moved "
                         "less than this (vs its last fired round) skips "
                         "its collectives and reuses the cached aggregate "
                         "(0 = eager)")
    ap.add_argument("--max-stale", type=int, default=4,
                    help="lazy aggregation: max consecutive skipped rounds "
                         "before a fire is forced")
    ap.add_argument("--lazy-adaptive", type=float, default=0.0,
                    help="adaptive LAQ: cap on the drift-EMA threshold "
                         "scaling — thresholds ramp up (skips ramp up) as "
                         "the run converges, up to sqrt(cap) * lazy-thresh "
                         "(0 = fixed thresholds, otherwise >= 1)")
    ap.add_argument("--lazy-mode", default="elide",
                    choices=["elide", "gate"],
                    help="skip-round dispatch: 'elide' removes a skipped "
                         "round's collectives from the compiled graph via "
                         "lax.cond; 'gate' traces them every round and "
                         "discards skipped results (legacy baseline)")
    ap.add_argument("--rank", type=int, default=1)
    ap.add_argument("--bits", type=int, default=8)
    ap.add_argument("--alpha", type=float, default=10.0)
    ap.add_argument("--wire", default="symmetric",
                    choices=["symmetric", "server"],
                    help="wire topology: 'symmetric' all-reduce among "
                         "peers (the historical path) or 'server' — a "
                         "parameter-server round with per-worker "
                         "participation draws, weighted server-side "
                         "aggregation and per-worker lazy decisions")
    ap.add_argument("--participation", type=float, default=1.0,
                    help="server wire: each worker's independent "
                         "per-round upload probability (straggler "
                         "drop-out; 1.0 = everyone)")
    ap.add_argument("--agg", default="participation",
                    choices=["participation", "sparsity"],
                    help="server aggregation weighting: divide by the "
                         "participant count, or FedDropoutAvg per-element "
                         "nonzero masking ('sparsity')")
    ap.add_argument("--participation-seed", type=int, default=0)
    ap.add_argument("--noniid-alpha", type=float, default=0.0,
                    help="federated non-IID data: Dirichlet concentration "
                         "reshaping each DP worker's token prior (0 = "
                         "IID; smaller = more skew)")
    ap.add_argument("--wire-accounting", "--wire-mode",
                    dest="wire_accounting", default="allgather_codes",
                    choices=["allgather_codes", "psum_sim"],
                    help="wire modelling: exact packed code gather, or "
                         "the psum-simulated ring all-reduce (--wire-mode "
                         "is the pre-rename alias)")
    ap.add_argument("--codec", default=None,
                    help="wire codec override for lq_sgd leaves: 'log' "
                         "(deterministic), 'dlog' (dithered/DP), 'lrq' "
                         "(layered randomized); default picks by "
                         "--dp-epsilon")
    ap.add_argument("--dp-epsilon", type=float, default=0.0,
                    help="per-use DP budget per transmitted tensor; > 0 "
                         "calibrates dlog noise (see "
                         "repro.core.privacy.accounting)")
    ap.add_argument("--dp-delta", type=float, default=1e-5)
    ap.add_argument("--avg-mode", default="paper")
    ap.add_argument("--fuse", action="store_true")
    ap.add_argument("--comp-dtype", default="float32")
    ap.add_argument("--mesh", default=None,
                    help="e.g. '4x2' (data x model); default: all devices on data")
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--runtime", default="async", choices=["async", "sync"],
                    help="async: prefetch + deferred metric sync + "
                         "background checkpoints (repro.train.runtime); "
                         "sync: the reference loop")
    ap.add_argument("--microbatch", type=int, default=1,
                    help="gradient accumulation: split each step's batch "
                         "into k sequential microbatches; the compressed "
                         "sync fires once per accumulated step")
    ap.add_argument("--prefetch", type=int, default=2,
                    help="async runtime: device batches kept in flight")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--ckpt-path", default="checkpoints/state.ckpt")
    ap.add_argument("--resume", action="store_true",
                    help="restore from --ckpt-path and continue; schedule "
                         "phases already completed are skipped (their "
                         "warm-Q truncations are not re-applied)")
    args = ap.parse_args(argv)

    if args.production_mesh:
        mesh = make_production_mesh(multi_pod=args.multi_pod)
    elif args.mesh:
        d, m = (int(x) for x in args.mesh.split("x"))
        mesh = make_mesh((d, m), ("data", "model"))
    else:
        mesh = make_mesh((len(jax.devices()), 1), ("data", "model"))

    cfg = get_config(args.arch, smoke=args.smoke)
    from repro.core.policy import parse_decay_spec
    decay = parse_decay_spec(args.decay) if args.decay else ()
    comp_cfg = CompressorConfig(name=args.compressor, rank=args.rank,
                                bits=args.bits, alpha=args.alpha,
                                wire_accounting=args.wire_accounting,
                                avg_mode=args.avg_mode,
                                codec=args.codec,
                                dp_epsilon=args.dp_epsilon,
                                dp_delta=args.dp_delta,
                                fuse_collectives=args.fuse,
                                state_dtype=args.comp_dtype,
                                policy=args.policy or cfg.compression_policy,
                                error_budget=args.error_budget,
                                warmup_steps=args.warmup,
                                schedule_decay=decay,
                                lazy_thresh=args.lazy_thresh,
                                max_stale=args.max_stale,
                                lazy_adaptive=args.lazy_adaptive,
                                lazy_mode=args.lazy_mode,
                                topology=args.wire,
                                participation=args.participation,
                                agg=args.agg,
                                participation_seed=args.participation_seed)
    compressor = make_model_compressor(cfg, comp_cfg)
    if getattr(compressor, "plan_report", None):
        from repro.core.policy import format_plan_report
        print(format_plan_report(compressor.plan_report))
    optimizer = make_optimizer(args.optimizer, args.lr)

    data_cfg = LMDataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                            batch=args.batch, n_codebooks=cfg.n_codebooks,
                            noniid_alpha=args.noniid_alpha)
    n_dp = 1
    for a, s in mesh.shape.items():
        if a in ("pod", "data"):
            n_dp *= s

    def batch_fn(step: int):
        if args.noniid_alpha > 0:
            # federated data layout: DP worker c's rows come from client
            # c's skewed prior (batch rows shard over dp in order)
            if args.batch % n_dp:
                raise ValueError(f"--noniid-alpha needs --batch divisible "
                                 f"by the {n_dp} DP workers, got {args.batch}")
            per = dataclasses.replace(data_cfg, batch=args.batch // n_dp)
            chunks = [lm_batch(per, step, client=c) for c in range(n_dp)]
            b = {k: np.concatenate([ch[k] for ch in chunks])
                 for k in chunks[0]}
        else:
            b = lm_batch(data_cfg, step)
        if cfg.cond_len:
            # pure numpy (matches conditioning_stub's distribution): this
            # runs on the async runtime's prefetch thread, where eager jax
            # ops contend with the main thread on the dispatch locks — the
            # same reason lm_batch itself is numpy
            rng = np.random.default_rng(
                np.random.SeedSequence([data_cfg.seed, step, 1]))
            b["cond"] = (rng.standard_normal(
                (args.batch, cfg.cond_len, cfg.d_model)) * 0.02
                ).astype(jnp.dtype(cfg.dtype))
        return b

    with jax.set_mesh(mesh):
        def build(comp):
            return build_sharded_step(cfg, mesh, comp, optimizer,
                                      sample_batch=batch_fn(0),
                                      microbatch=args.microbatch,
                                      remat_scan=not args.smoke)

        comp0 = compressor
        if args.resume:
            if not os.path.exists(args.ckpt_path):
                raise FileNotFoundError(
                    f"--resume: no checkpoint at {args.ckpt_path!r} — "
                    "refusing to silently restart from scratch")
            # the checkpoint's q columns reflect the schedule phase that
            # PRODUCED the saved state — the phase of the last executed
            # step, step0-1, not step0: a save landing exactly on a decay
            # boundary holds the pre-boundary (un-truncated) q, and
            # run_schedule applies the boundary's adapt_state when it
            # enters the next phase
            step0 = peek_step(args.ckpt_path)
            if hasattr(compressor, "at_step"):
                comp0 = compressor.at_step(max(step0 - 1, 0))
            jstep, st_sh, _, state_abs = build(comp0)
            state = ckpt_restore(args.ckpt_path, state_abs, st_sh)
            print(f"# resumed at step {step0} from {args.ckpt_path}")
        else:
            jstep, st_sh, _, state_abs = build(comp0)
            state = sharded_init(cfg, jax.random.PRNGKey(0), optimizer,
                                 comp0, mesh, st_sh)
        lazy_note = ""
        if getattr(comp0, "lazy_groups", None):
            lazy_note = (f" expected(lazy)="
                         f"{comp0.expected_wire_bits_per_step()/8e6:.3f}MB")
        print(f"arch={cfg.name} params={sum(x.size for x in jax.tree.leaves(state['params']))/1e6:.1f}M "
              f"mesh={dict(mesh.shape)} compressor={args.compressor} "
              f"policy={comp_cfg.policy or 'uniform'} "
              f"runtime={args.runtime} microbatch={args.microbatch} "
              f"wire/step={comp0.wire_bits_per_step()/8e6:.3f}MB{lazy_note} "
              f"(uncompressed={sum(x.size for x in jax.tree.leaves(state['params']))*4/1e6:.1f}MB)")
        rcfg = RuntimeConfig(steps=args.steps, log_every=args.log_every,
                             ckpt_every=args.ckpt_every,
                             ckpt_path=args.ckpt_path,
                             microbatch=args.microbatch,
                             prefetch=args.prefetch)
        if args.runtime == "async":
            runner = AsyncRunner(jstep, batch_fn, rcfg)
        else:
            runner = Trainer(jstep, batch_fn, rcfg)

        def rebuild(comp_t, seg_start):
            js, sh, _, _ = build(comp_t)
            print(f"# schedule phase @step {seg_start}: "
                  f"wire/step={comp_t.wire_bits_per_step()/8e6:.3f}MB")
            return js, sh

        # ONE runner threads through every schedule phase (history and
        # wall-clock survive boundaries); completed phases are skipped on
        # resume — see repro.train.runtime.run_schedule
        run_schedule(runner, compressor, state, total_steps=args.steps,
                     rebuild=rebuild, initial=comp0)
    return runner.history


def main() -> None:
    use_compile_cache()
    run()


if __name__ == "__main__":
    main()
