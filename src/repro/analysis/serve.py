"""Serve-graph lint: static rules over the compiled decode step.

The training linter (:mod:`repro.analysis.lint`) proves collective and
sharding invariants of the train step; this module does the same for the
serving hot path — the single-token decode step the scan driver runs
thousands of times per second. Three rules, reusing the shared
Finding/RuleResult/LintReport engine and the HLO text parser:

``serve-collective-allowlist``
    On a data-only mesh (model=1) decode is purely data-parallel and must
    launch ZERO collectives. On model>1 exactly two kinds are allowed:
    ``all-reduce`` (partial-softmax / sharded-matmul reductions when
    heads split over ``model``) and ``all-gather`` (the designed read of
    the seq-sharded cache — ``cache_specs`` splits the cache seq dim over
    ``model`` when heads don't divide, trading one gather per token for
    1/model per-device cache HBM). ``all-to-all`` / ``reduce-scatter`` /
    ``collective-permute`` above a per-token floor (two token-rows of the
    widest cache leaf — exempting index plumbing and the single-token
    append halo-exchange, same floor idea as the train linter's shadow
    ban) mean the decode sharding regressed into resharding the
    O(max_seq) cache every token.
``serve-donation-aliasing``
    Decode is compiled with donated caches; every cache array leaf
    (codes, scales, raw K/V, SSM state) must appear in the module
    header's ``input_output_alias`` — an unaliased leaf is a silent
    full-cache copy per token.
``serve-container-dtype``
    The entry computation's parameters carry exactly the cache's declared
    container dtypes: one ``s8`` parameter per packed-codes leaf, one
    ``f32`` per scale, ``bf16``/``f32`` for raw leaves. An implicit
    upcast at the jit boundary (e.g. codes arriving as f32) would silently
    multiply decode HBM traffic by 32/b while the accounting still
    reports quantized bytes.

CLI (used by the CI graph-lint matrix; pins the forced device count
before the first jax import, like ``repro.analysis.lint``)::

    PYTHONPATH=src python -m repro.analysis.serve --arch gemma3-1b \\
        --smoke --cache-bits 8 --mesh 2x1 --json
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

# on model>1 meshes, collectives outside the allowlist are exempt below
# a per-token floor (see _token_floor_bits); this is the static minimum
SMALL_COLLECTIVE_BITS = 1024

# model>1 decode may launch only these: softmax/matmul partial reductions
# and the designed seq-sharded cache read (see module docstring)
ALLOWED_KINDS = ("all-reduce", "all-gather")

_JAX_TO_HLO = {
    "int8": "s8",
    "int16": "s16",
    "int32": "s32",
    "uint32": "u32",
    "float32": "f32",
    "float64": "f64",
    "bfloat16": "bf16",
    "float16": "f16",
    "bool": "pred",
}


def _token_floor_bits(caches_abs, max_seq: int) -> int:
    """Exemption floor for non-allowlisted collectives: two token-rows of
    the widest cache leaf (stacked scan leaves are per-layer inside the
    compiled scan body, so their leading layer dim is divided out)."""
    import jax

    per_token = 0
    for kp, leaf in jax.tree_util.tree_flatten_with_path(caches_abs)[0]:
        bits = leaf.size * leaf.dtype.itemsize * 8
        if "'scan'" in jax.tree_util.keystr(kp):
            bits //= leaf.shape[0]
        per_token = max(per_token, bits // max_seq)
    return max(SMALL_COLLECTIVE_BITS, 2 * per_token)


def _cache_dtype_counts(caches_abs) -> dict[str, int]:
    """HLO-dtype histogram of the cache tree's array leaves."""
    import jax

    counts: dict[str, int] = {}
    for leaf in jax.tree_util.tree_leaves(caches_abs):
        d = _JAX_TO_HLO.get(str(leaf.dtype), str(leaf.dtype))
        counts[d] = counts.get(d, 0) + 1
    return counts


def lint_serve_step(
    cfg,
    mesh,
    *,
    cache_dtype=None,
    qcfg=None,
    batch: int = 2,
    max_seq: int = 32,
    donate: bool = True,
    target: dict | None = None,
):
    """Compile the sharded single-token decode step and lint it.

    Returns a :class:`repro.analysis.rules.LintReport` (same JSON shape as
    the train linter, so the CI matrix consumes both uniformly)."""
    import jax
    import jax.numpy as jnp

    from repro.analysis.hlo import parse_module, parse_type
    from repro.analysis.inventory import hlo_inventory
    from repro.analysis.rules import Finding, LintReport, RuleResult
    from repro.models.model import init_params
    from repro.serving.engine import (
        build_decode_step,
        init_serving_caches,
        serve_shardings,
    )

    if cache_dtype is None:
        cache_dtype = jnp.bfloat16
    t0 = time.time()
    key0 = jax.random.PRNGKey(0)
    params_abs = jax.eval_shape(lambda k: init_params(cfg, k), key0)
    caches_abs = jax.eval_shape(
        lambda: init_serving_caches(cfg, batch, max_seq, cache_dtype, qcfg)
    )
    tok_abs = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
    idx_abs = jax.ShapeDtypeStruct((batch,), jnp.int32)
    p_sh, c_sh, t_sh = serve_shardings(
        cfg, mesh, batch, cache_dtype=cache_dtype, qcfg=qcfg
    )
    decode = build_decode_step(cfg)
    with jax.set_mesh(mesh):
        jitted = jax.jit(
            decode,
            in_shardings=(p_sh, c_sh, t_sh, None),
            out_shardings=(None, c_sh),
            donate_argnums=(1,) if donate else (),
        )
        lowered = jitted.lower(params_abs, caches_abs, tok_abs, idx_abs)
        hlo_text = lowered.compile().as_text()
    module = parse_module(hlo_text)
    rows, conds = hlo_inventory(module)
    model_size = mesh.shape.get("model", 1)

    results: list[RuleResult] = []

    # ---- serve-collective-allowlist ------------------------------------
    rid = "serve-collective-allowlist"
    floor = _token_floor_bits(caches_abs, max_seq)
    findings: list[Finding] = []
    for r in rows:
        if model_size <= 1:
            msg = (
                f"{r.kind} of {r.bits} bits on a data-only mesh — decode "
                f"must be purely data-parallel"
            )
            findings.append(Finding(rid, r.tag or r.kind, msg))
        elif r.kind not in ALLOWED_KINDS and r.bits > floor:
            msg = (
                f"{r.kind} ({r.dtype}{list(r.shape)}, {r.bits} bits > "
                f"{floor}-bit token floor) — only "
                f"{'/'.join(ALLOWED_KINDS)} are expected in the decode step"
            )
            findings.append(Finding(rid, r.tag or r.kind, msg))
    results.append(
        RuleResult(
            rid,
            "hlo",
            "fail" if findings else "pass",
            findings,
            note=(
                f"{len(rows)} collective(s) on model={model_size}, "
                f"floor={floor}b"
            ),
        )
    )

    # ---- serve-donation-aliasing ---------------------------------------
    rid = "serve-donation-aliasing"
    n_cache = len(jax.tree_util.tree_leaves(caches_abs))
    if not donate:
        results.append(
            RuleResult(rid, "hlo", "pass", [], note="caller did not donate")
        )
    else:
        n_alias = len(module.input_output_alias)
        findings = []
        if n_alias < n_cache:
            msg = (
                f"{n_cache} cache leaves donated but only {n_alias} "
                f"output(s) aliased — the rest are copied every token"
            )
            findings.append(Finding(rid, "module header", msg))
        results.append(
            RuleResult(
                rid,
                "hlo",
                "fail" if findings else "pass",
                findings,
                note=f"{n_alias} aliased / {n_cache} cache leaves",
            )
        )

    # ---- serve-container-dtype -----------------------------------------
    rid = "serve-container-dtype"
    expected = _cache_dtype_counts(caches_abs)
    entry = module.computations[module.entry]
    got: dict[str, int] = {}
    for ins in entry.instructions:
        if ins.opcode == "parameter":
            for t in ins.result_types:
                d = parse_type(t)[0]
                got[d] = got.get(d, 0) + 1
    findings = []
    for d, n in sorted(expected.items()):
        if got.get(d, 0) < n:
            msg = (
                f"cache tree declares {n} {d} leaf(s) but the compiled "
                f"entry has only {got.get(d, 0)} {d} parameter(s) — a "
                f"container dtype was lost at the jit boundary"
            )
            findings.append(Finding(rid, f"entry parameters [{d}]", msg))
    got_note = ",".join(f"{d}:{n}" for d, n in sorted(got.items()))
    results.append(
        RuleResult(
            rid,
            "hlo",
            "fail" if findings else "pass",
            findings,
            note=f"entry params {got_note}",
        )
    )

    summary = {
        "hlo_collectives": len(rows),
        "hlo_conditionals": len(conds),
        "hlo_collective_kinds": sorted({r.kind for r in rows}),
        "aliased_outputs": len(module.input_output_alias),
        "cache_leaves": n_cache,
        "cache_dtypes": expected,
        "compile_s": round(time.time() - t0, 2),
    }
    return LintReport(target=dict(target or {}), results=results, summary=summary)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.serve",
        description="Static lint of the compiled serving decode step.",
    )
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--mesh", default="2x1", help="DATAxMODEL forced mesh")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--max-seq", type=int, default=32)
    ap.add_argument(
        "--cache-dtype",
        default="bfloat16",
        choices=("float32", "bfloat16", "float16"),
    )
    ap.add_argument("--cache-bits", type=int, default=0, choices=(0, 4, 8))
    ap.add_argument(
        "--cache-backend",
        default="jnp_ref",
        choices=("jnp_ref", "pallas"),
    )
    ap.add_argument("--no-donate", action="store_true")
    ap.add_argument("--json", action="store_true", dest="as_json")
    args = ap.parse_args(argv)

    from repro.analysis.lint import _parse_mesh, format_report

    try:
        dims, axes = _parse_mesh(args.mesh)
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    n_dev = 1
    for dim in dims:
        n_dev *= dim
    n_dev = int(os.environ.get("REPRO_DRYRUN_DEVICES") or n_dev)
    os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"

    import jax.numpy as jnp

    from repro.configs import get_config, list_archs
    from repro.launch.mesh import make_mesh
    from repro.serving.kv_cache import CacheQuantConfig

    if args.arch not in list_archs():
        print(f"error: unknown --arch {args.arch!r}", file=sys.stderr)
        return 2
    cfg = get_config(args.arch, smoke=args.smoke)
    qcfg = None
    if args.cache_bits:
        qcfg = CacheQuantConfig(bits=args.cache_bits, backend=args.cache_backend)
    dtypes = {
        "float32": jnp.float32,
        "bfloat16": jnp.bfloat16,
        "float16": jnp.float16,
    }
    mesh = make_mesh(dims, axes)
    target = {
        "arch": args.arch + ("[smoke]" if args.smoke else ""),
        "mesh": args.mesh,
        "cache": f"q{args.cache_bits}" if args.cache_bits else args.cache_dtype,
        "levels": ["hlo"],
        "mode": "serve-decode",
    }
    report = lint_serve_step(
        cfg,
        mesh,
        cache_dtype=dtypes[args.cache_dtype],
        qcfg=qcfg,
        batch=args.batch,
        max_seq=args.max_seq,
        donate=not args.no_donate,
        target=target,
    )
    if args.as_json:
        print(json.dumps(report.to_json(), indent=1))
    else:
        print(format_report(report))
    return 0 if report.ok else 1


if __name__ == "__main__":
    sys.exit(main())
