import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any jax import (jax locks the device
# count at first init). Test meshes can shrink it via env var:
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ["REPRO_DRYRUN_DEVICES"])

# Multi-pod dry-run: lower + compile every (arch x input-shape x mesh)
# combination against the production mesh, and extract the roofline terms.
#
#     PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-1b \
#         --shape train_4k [--multi-pod] [--out results.json]
#
# Success criteria (assignment): ``.lower().compile()`` succeeds;
# ``memory_analysis()`` and ``cost_analysis()`` are printed and recorded.
# (No `from __future__` here: the XLA_FLAGS lines above must stay first.)

import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import INPUT_SHAPES, get_config, list_archs, shape_supported
from repro.configs.base import ModelConfig
from repro.core import CompressorConfig
from repro.launch.inputs import input_specs
from repro.launch.mesh import make_production_mesh
from repro.models.model import init_caches, init_params
from repro.roofline import hw
from repro.roofline.analysis import roofline_terms
from repro.roofline.flops_model import per_device_flops
from repro.serving.engine import (build_decode_step, build_prefill_step,
                                  serve_shardings)
from repro.train.optimizer import sgd
from repro.train.step import (build_train_step, init_train_state,
                              make_model_compressor, n_dp_of)


def _active_params(cfg: ModelConfig) -> int:
    """Parameter count with MoE experts scaled to the routed top-k."""
    abstract = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    flat = jax.tree_util.tree_flatten_with_path(abstract)[0]
    total = 0
    for kp, leaf in flat:
        path = jax.tree_util.keystr(kp)
        n = int(leaf.size)
        if any(w in path for w in ("w_gate", "w_up", "w_down")):
            n = int(n * cfg.experts_per_token / max(cfg.n_experts, 1))
        total += n
    return total


def _total_params(cfg: ModelConfig) -> int:
    abstract = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    return sum(int(l.size) for l in jax.tree.leaves(abstract))


def lower_one(arch: str, shape_name: str, *, multi_pod: bool = False,
              comp_cfg: CompressorConfig | None = None,
              backend: str = "xla", verbose: bool = True,
              dump_hlo: str | None = None, unroll: bool = False,
              perf_tag: str | None = None, dp_only: bool = False,
              moe_impl: str | None = None, moe_hints: bool = False,
              lint: bool = False) -> dict:
    """Lower + compile one combination; return the roofline record."""
    cfg = get_config(arch)
    if moe_impl and cfg.n_experts:
        cfg = dataclasses.replace(cfg, moe_impl=moe_impl)
    if moe_hints and cfg.n_experts:
        cfg = dataclasses.replace(cfg, moe_shard_hints=True)
    shape = INPUT_SHAPES[shape_name]
    if not shape_supported(arch, shape_name):
        return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                "status": "skipped",
                "reason": "full-attention arch: long_500k requires a "
                          "sub-quadratic path (DESIGN.md §5)"}
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    comp_cfg = comp_cfg or CompressorConfig(name="lq_sgd", rank=1, bits=8)
    t0 = time.time()

    with jax.set_mesh(mesh):
        if shape.mode == "train":
            compressor = make_model_compressor(cfg, comp_cfg)
            opt = sgd(1e-2)
            dp_axes = None
            if dp_only:
                dp_axes = tuple(a for a in mesh.axis_names)  # all axes = DP
            step_fn, state_sh, batch_sh = build_train_step(
                cfg, mesh, compressor, opt, backend=backend, remat_scan=True,
                unroll_scan=unroll, dp_axes=dp_axes)
            n_dp = chips if dp_only else n_dp_of(mesh)
            state_abs = jax.eval_shape(
                lambda k: init_train_state(cfg, k, opt, compressor, n_dp),
                jax.random.PRNGKey(0))
            batch_abs = input_specs(cfg, shape)
            st_sh = state_sh(state_abs)
            jitted = jax.jit(step_fn,
                             in_shardings=(st_sh, batch_sh(batch_abs)),
                             out_shardings=(st_sh, None),
                             donate_argnums=(0,))
            lowered = jitted.lower(state_abs, batch_abs)
            wire_bits = compressor.wire_bits_per_step()
        elif shape.mode == "prefill":
            p_sh, c_sh, t_sh = serve_shardings(cfg, mesh, shape.global_batch)
            fn = build_prefill_step(cfg, max_seq=shape.seq_len + cfg.cond_len,
                                    backend=backend, unroll_scan=unroll)
            params_abs = jax.eval_shape(lambda k: init_params(cfg, k),
                                        jax.random.PRNGKey(0))
            specs = input_specs(cfg, shape)
            args = [params_abs, specs["tokens"]]
            in_sh = [p_sh, t_sh]
            if "cond" in specs:
                from jax.sharding import NamedSharding, PartitionSpec as P
                dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
                args.append(specs["cond"])
                in_sh.append(NamedSharding(mesh, P(dp, None, None)))
            jitted = jax.jit(fn, in_shardings=tuple(in_sh))
            lowered = jitted.lower(*args)
            wire_bits = 0
        else:  # decode
            p_sh, c_sh, t_sh = serve_shardings(cfg, mesh, shape.global_batch)
            fn = build_decode_step(cfg, backend=backend, unroll_scan=unroll)
            params_abs = jax.eval_shape(lambda k: init_params(cfg, k),
                                        jax.random.PRNGKey(0))
            caches_abs = jax.eval_shape(
                lambda: init_caches(cfg, shape.global_batch, shape.seq_len,
                                    jnp.bfloat16))
            specs = input_specs(cfg, shape)
            jitted = jax.jit(fn, in_shardings=(p_sh, c_sh, t_sh, None),
                             donate_argnums=(1,))
            lowered = jitted.lower(params_abs, caches_abs, specs["tokens"],
                                   specs["index"])
            wire_bits = 0

        compiled = lowered.compile()

    t_compile = time.time() - t0
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    if dump_hlo:
        with open(dump_hlo, "w") as f:
            f.write(hlo)
    # Analytic per-device FLOPs (validated vs unrolled HLO; DESIGN.md
    # roofline notes: scanned cost_analysis counts while bodies once).
    if dp_only and shape.mode == "train":
        ndp, msize = chips, 1
    else:
        ndp, msize = n_dp_of(mesh), mesh.shape["model"]
    analytic_dev = per_device_flops(cfg, shape, ndp=ndp, msize=msize,
                                    remat=(shape.mode == "train"))
    rep = roofline_terms(cost, hlo, chips)
    hlo_flops_dev = rep.flops_per_device
    if not unroll:
        rep.flops_per_device = analytic_dev
        rep.__post_init__()  # recompute terms with corrected flops

    n_total = _total_params(cfg)
    n_active = _active_params(cfg)
    tokens = shape.global_batch * (shape.seq_len if shape.mode != "decode" else 1)
    # 6·N·D already counts fwd+bwd (train); inference is forward-only 2·N·D.
    mf = (6.0 if shape.mode == "train" else 2.0) * n_active * tokens
    flops_global = rep.flops_per_device * chips
    record = {
        "arch": arch, "shape": shape_name, "multi_pod": multi_pod,
        "status": "ok", "mode": shape.mode, "chips": chips,
        "unrolled": unroll, "perf_tag": perf_tag, "dp_only": dp_only,
        "compressor": dataclasses.asdict(comp_cfg),
        "compile_s": round(t_compile, 1),
        "params_total": n_total, "params_active": n_active,
        "tokens_per_step": tokens,
        "model_flops": mf,
        "hlo_flops_per_device_measured": hlo_flops_dev,
        "analytic_flops_per_device": analytic_dev,
        "hlo_flops_global": flops_global,
        "useful_flops_ratio": (mf / flops_global) if flops_global else None,
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_est_bytes": (mem.argument_size_in_bytes
                               + mem.output_size_in_bytes
                               + mem.temp_size_in_bytes
                               - mem.alias_size_in_bytes),
            "hbm_bytes_per_chip": hw.HBM_BYTES,
        },
        "compressor_wire_bits_per_step": wire_bits,
        **rep.as_dict(),
    }
    if lint and shape.mode == "train":
        # static verification leg: re-trace the step's jaxpr (minimal mesh,
        # abstract shapes) and lint it together with the just-compiled HLO
        # — no second compile, lint_step consumes the module text as-is
        from repro.analysis.lint import format_report, lint_step
        report = lint_step(cfg, comp_cfg, shape_name=shape_name, hlo_text=hlo,
                           target={"arch": arch, "compressor": comp_cfg.name})
        record["graph_lint"] = report.to_json()
        if verbose:
            print(format_report(report))
    if verbose:
        print(f"== {arch} x {shape_name} ({'2-pod' if multi_pod else '1-pod'}, "
              f"{chips} chips) compiled in {t_compile:.0f}s")
        print(f"   memory_analysis: args={mem.argument_size_in_bytes/1e9:.2f}GB "
              f"temp={mem.temp_size_in_bytes/1e9:.2f}GB "
              f"out={mem.output_size_in_bytes/1e9:.2f}GB  (per device)")
        print(f"   cost_analysis: flops/dev={rep.flops_per_device:.3e} "
              f"bytes/dev={rep.bytes_per_device:.3e}")
        print(f"   collectives: {rep.collectives.counts} "
              f"wire={rep.collectives.wire_bytes/1e6:.2f}MB/dev")
        print(f"   roofline: compute={rep.compute_s*1e3:.2f}ms "
              f"memory={rep.memory_s*1e3:.2f}ms "
              f"collective={rep.collective_s*1e3:.2f}ms "
              f"-> dominant: {rep.dominant}")
    return record


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True, choices=list_archs() + ["all"])
    ap.add_argument("--shape", required=True,
                    choices=sorted(INPUT_SHAPES) + ["all"])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default=None, help="JSON output path")
    ap.add_argument("--compressor", default="lq_sgd",
                    choices=["none", "sgd", "topk", "qsgd", "powersgd", "lq_sgd"])
    ap.add_argument("--policy", default=None,
                    help="per-leaf policy: 'uniform', 'auto' (cost-model "
                         "planner), or a spec string (README)")
    ap.add_argument("--error-budget", type=float, default=0.3,
                    help="auto-planner: max per-leaf error proxy")
    ap.add_argument("--warmup", type=int, default=0,
                    help="in-graph full-precision warm-up steps")
    ap.add_argument("--rank", type=int, default=1)
    ap.add_argument("--bits", type=int, default=8)
    # --wire here historically meant the ACCOUNTING mode while train.py's
    # --wire means topology (the PR-9 collision); canonical name now
    # matches CompressorConfig.wire_accounting, old spelling kept as alias
    ap.add_argument("--wire-accounting", "--wire", "--wire-mode",
                    dest="wire_accounting", default="allgather_codes",
                    choices=["allgather_codes", "psum_sim"])
    ap.add_argument("--avg-mode", default="paper",
                    choices=["paper", "dequant_then_mean"])
    ap.add_argument("--dump-hlo", default=None,
                    help="write compiled HLO text to this path")
    ap.add_argument("--unroll", action="store_true",
                    help="unroll the layer scan (exact cost_analysis FLOPs; "
                         "slower compile)")
    ap.add_argument("--perf-tag", default=None,
                    help="label this record as a §Perf hillclimb variant")
    ap.add_argument("--dp-only", action="store_true",
                    help="consume ALL mesh axes as data-parallel (no TP); "
                         "the compressor syncs over every axis")
    ap.add_argument("--moe-impl", default=None,
                    choices=["global", "batched"],
                    help="MoE dispatch strategy (perf iteration)")
    ap.add_argument("--moe-hints", action="store_true",
                    help="expert-dim sharding constraints (perf iteration)")
    ap.add_argument("--comp-dtype", default="float32",
                    choices=["float32", "bfloat16"],
                    help="error-feedback storage dtype (perf iteration)")
    ap.add_argument("--fuse", action="store_true",
                    help="fuse factor collectives: one int8 gather per "
                         "power-iteration phase (perf iteration)")
    ap.add_argument("--lint", action="store_true",
                    help="run the graph linter (repro.analysis) over each "
                         "compiled train step; findings fail the run")
    args = ap.parse_args()

    comp_cfg = CompressorConfig(name=args.compressor, rank=args.rank,
                                bits=args.bits,
                                wire_accounting=args.wire_accounting,
                                avg_mode=args.avg_mode,
                                state_dtype=args.comp_dtype,
                                fuse_collectives=args.fuse,
                                policy=args.policy,
                                error_budget=args.error_budget,
                                warmup_steps=args.warmup)
    archs = list_archs() if args.arch == "all" else [args.arch]
    shapes = sorted(INPUT_SHAPES) if args.shape == "all" else [args.shape]
    records = []
    for a in archs:
        for s in shapes:
            try:
                records.append(lower_one(a, s, multi_pod=args.multi_pod,
                                         comp_cfg=comp_cfg,
                                         dump_hlo=args.dump_hlo,
                                         unroll=args.unroll,
                                         perf_tag=args.perf_tag,
                                         dp_only=args.dp_only,
                                         moe_impl=args.moe_impl,
                                         moe_hints=args.moe_hints,
                                         lint=args.lint))
            except Exception as e:  # record failures: they are bugs to fix
                traceback.print_exc()
                records.append({"arch": a, "shape": s,
                                "multi_pod": args.multi_pod,
                                "status": "error", "error": repr(e)})
    if args.out:
        with open(args.out, "w") as f:
            json.dump(records, f, indent=1)
        print(f"wrote {args.out}")
    n_bad = sum(r["status"] == "error" for r in records)
    n_lint = sum(1 for r in records
                 if r.get("graph_lint") and not r["graph_lint"]["ok"])
    if n_bad or n_lint:
        raise SystemExit(f"{n_bad} combination(s) FAILED, "
                         f"{n_lint} with graph-lint findings")


if __name__ == "__main__":
    main()
