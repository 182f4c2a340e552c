"""Graph-lint config matrix — the static-analysis leg of CI.

Runs a lint CLI (subprocess per config: each needs its own
``--xla_force_host_platform_device_count``) over one config per
architecture family, and fails if ANY rule reports findings. Each matrix
entry names its lint module — ``repro.analysis.lint`` for the train step,
``repro.analysis.serve`` for the serving decode step; both emit the same
LintReport JSON:

  * ``dense_smoke``  — gemma3-1b smoke, lazy lq_sgd, jaxpr + compiled HLO
                       on a forced 2x1 host mesh (donation aliasing, the
                       compiled conditional, predicate slice);
  * ``moe_smoke``    — mixtral-8x7b smoke (MoE routing in the graph);
  * ``ssm_smoke``    — mamba2-370m smoke, lazy 4-bit QSGD (int8-packed
                       wire exercises dtype hygiene on the other codec);
  * ``server_wire``  — gemma3-1b smoke on the SERVER topology with
                       drop-out + per-worker laziness: payload
                       collectives unconditional, one contribution
                       gather per group, collective-free worker_gate
                       conds (the inverted containment invariant);
  * ``deepseek_671b``— the FULL deepseek-v3-671b config, jaxpr level
                       (abstract trace: ~10 s, no compile) under the
                       ``REPRO_DRYRUN_DEVICES`` override the dry-run
                       tooling uses. This is the static verification leg
                       of the 671B dry-run roadmap item;
  * ``serve_smoke_q8``— the compiled single-token decode step with a
                       quantized (q8) KV cache on a data-only mesh:
                       zero collectives, donated caches aliased, s8
                       codes at the jit boundary;
  * ``serve_smoke_mla``— decode on a model-parallel (1x2) mesh with the
                       MLA latent cache: collective allowlist under
                       seq-sharded cache reads.

Headline counts (collectives/step, payload bits, conditionals — all
deterministic static accounting) land in ``BENCH_graph_lint.json`` and the
``BENCH_history.jsonl`` trajectory via benchmarks/check_regression.py.

This file is formatter-clean (see [tool.ruff.format] in pyproject.toml).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

BENCH_JSON = "BENCH_graph_lint.json"

# (name, lint module, space-separated CLI args, extra env)
MATRIX = [
    (
        "dense_smoke",
        "repro.analysis.lint",
        "--arch gemma3-1b --smoke --compressor lq_sgd --lazy-thresh 0.05 --mesh 2x1",
        {},
    ),
    (
        "moe_smoke",
        "repro.analysis.lint",
        "--arch mixtral-8x7b --smoke --compressor lq_sgd --lazy-thresh 0.05 --mesh 2x1",
        {},
    ),
    (
        "ssm_smoke",
        "repro.analysis.lint",
        "--arch mamba2-370m --smoke --compressor qsgd --bits 4 --lazy-thresh 0.05 --mesh 2x1",
        {},
    ),
    (
        "server_wire",
        "repro.analysis.lint",
        "--arch gemma3-1b --smoke --compressor lq_sgd --lazy-thresh 0.05 "
        "--wire server --participation 0.5 --mesh 2x1",
        {},
    ),
    (
        "deepseek_671b",
        "repro.analysis.lint",
        "--arch deepseek-v3-671b --compressor lq_sgd --lazy-thresh 0.05 --level jaxpr",
        {"REPRO_DRYRUN_DEVICES": "2"},
    ),
    (
        "serve_smoke_q8",
        "repro.analysis.serve",
        "--arch gemma3-1b --smoke --cache-bits 8 --mesh 2x1",
        {},
    ),
    (
        "serve_smoke_mla",
        "repro.analysis.serve",
        "--arch deepseek-v3-671b --smoke --mesh 1x2",
        {},
    ),
]


def _lint_one(name, module, cli, env_extra):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.abspath("src")] + env.get("PYTHONPATH", "").split(os.pathsep)
    )
    env.update(env_extra)
    # a CPU lint by design: the parent may hold the accelerator
    env["JAX_PLATFORMS"] = "cpu"
    t0 = time.time()
    out = subprocess.run(
        [sys.executable, "-m", module, *cli.split(), "--json"],
        env=env,
        capture_output=True,
        text=True,
        timeout=900,
    )
    wall = time.time() - t0
    if out.returncode == 2 or not out.stdout.strip():
        raise RuntimeError(f"graph_lint/{name} could not run:\n{out.stderr[-2000:]}")
    return json.loads(out.stdout), wall


def bench(quick: bool = False) -> tuple[list[tuple[str, float, str]], dict]:
    """Shared benchmarks.run contract: (csv rows, payload)."""
    rows, configs, failures = [], [], []
    for name, module, cli, env_extra in MATRIX:
        report, wall = _lint_one(name, module, cli, env_extra)
        statuses = {r["id"]: r["status"] for r in report["rules"]}
        n_pass = sum(1 for s in statuses.values() if s == "pass")
        s = report["summary"]
        entry = {
            "name": name,
            "arch": report["target"].get("arch"),
            "ok": report["ok"],
            "levels": report["target"].get("levels"),
            "lint_s": round(wall, 1),
            # serve reports count compiled-HLO collectives instead of
            # jaxpr-level ones — same static-accounting gate either way
            "collectives_per_step": (
                s.get("jaxpr_collectives")
                if "jaxpr_collectives" in s
                else s.get("hlo_collectives")
            ),
            "payload_bits_fired": s.get("jaxpr_payload_bits_fired_round"),
            "conditionals": s.get("hlo_conditionals"),
            "rules": statuses,
        }
        configs.append(entry)
        rows.append(
            (
                f"graph_lint/{name}",
                wall * 1e6,
                f"ok={report['ok']} "
                f"collectives/step={entry['collectives_per_step']} "
                f"rules={n_pass}/{len(statuses)}",
            )
        )
        if not report["ok"]:
            findings = [
                f"{r['id']}: {f['location']}: {f['message']}"
                for r in report["rules"]
                for f in r["findings"]
            ]
            failures.append(f"{name}: " + "; ".join(findings[:5]))
    payload = {
        "bench": "graph_lint",
        "schema": 1,
        "quick": quick,
        "all_ok": not failures,
        "configs": configs,
    }
    if failures:
        raise RuntimeError("graph lint FINDINGS: " + " | ".join(failures))
    return rows, payload


if __name__ == "__main__":
    for name, us, derived in bench(quick=True)[0]:
        print(f"{name},{us:.1f},{derived}")
