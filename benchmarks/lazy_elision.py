"""Wall-clock proof of graph-level collective elision (repro.core.lazy +
the composite's ``lazy_mode``): eager vs gate vs elide on a REAL 8-device
mesh (``--xla_force_host_platform_device_count``), not the vmap simulator.

Three modes drive the same launcher-built, explicitly-sharded train step
at ``lazy_thresh=2.0, max_stale=8``:

  * ``eager``       — no gating machinery (``lazy_thresh=0``): every round
                      runs every collective.
  * ``lazy_gate``   — PR5 semantics: the group's collectives are traced
                      and EXECUTED every round, skipped rounds discard the
                      fresh aggregate via ``jnp.where``. Accounting says
                      "skipped", the interconnect disagrees.
  * ``lazy_elide``  — this PR: ``lax.cond`` dispatch, the compiled graph
                      only executes the group's all-gathers/pmaxes on
                      fired rounds (~1 in ``max_stale+1`` at this
                      threshold on stochastic gradients).

The timed region is a bare jitted-step loop over prebuilt device batches
(no runtime scheduling, no checkpoint IO — that delta is ``step_time``'s
job); modes alternate across repeats and report their best round. The
whole measurement runs in a subprocess so the 8-device XLA flag does not
leak into the driver process.

Merged into ``BENCH_step_time.json`` under the ``lazy_elision`` key
(shared ``benchmarks.run`` contract + BENCH_KEY).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

BENCH_JSON = "BENCH_step_time.json"
BENCH_KEY = "lazy_elision"

N_DEVICES = 8
LAZY_THRESH = 2.0
MAX_STALE = 8

_SUBPROC = textwrap.dedent("""
    import os, time, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%(devs)d"
    import jax
    import numpy as np
    from repro.configs.base import ModelConfig, attn
    from repro.core import CompressorConfig
    from repro.data.synthetic import LMDataConfig, lm_batch
    from repro.launch.mesh import make_mesh
    from repro.train.optimizer import sgd
    from repro.train.runtime import build_sharded_step, sharded_init
    from repro.train.step import make_model_compressor

    STEPS, REPEATS = %(steps)d, %(repeats)d
    BATCH, SEQ = 8, 32
    cfg = ModelConfig(name="bench-elide", arch_type="dense", source="bench",
                      d_model=64, vocab_size=128, pattern=(attn(),),
                      repeats=2, n_heads=4, n_kv_heads=2, head_dim=16,
                      d_ff=128, dtype="float32")
    mesh = make_mesh((%(devs)d, 1), ("data", "model"))
    opt = sgd(0.05)
    data = LMDataConfig(vocab_size=128, seq_len=SEQ, batch=BATCH)
    batches = [lm_batch(data, i) for i in range(STEPS)]

    def comp_cfg(mode):
        lazy = dict(lazy_thresh=%(thresh)s, max_stale=%(max_stale)d,
                    lazy_mode=mode) if mode else {}
        return CompressorConfig(name="lq_sgd", rank=1, bits=8,
                                fuse_collectives=True, **lazy)

    MODES = {"eager": None, "lazy_gate": "gate", "lazy_elide": "elide"}
    best, colls = {}, {}
    with jax.set_mesh(mesh):
        built = {}
        for name, mode in MODES.items():
            comp = make_model_compressor(cfg, comp_cfg(mode))
            jstep, st_sh, _, _ = build_sharded_step(
                cfg, mesh, comp, opt, sample_batch=batches[0],
                remat_scan=False)
            built[name] = (jstep, st_sh, comp)
        for _ in range(REPEATS):
            for name, (jstep, st_sh, comp) in built.items():
                state = sharded_init(cfg, jax.random.PRNGKey(0), opt, comp,
                                     mesh, st_sh)
                state, m = jstep(state, batches[0])  # compile + warm
                jax.block_until_ready(state)
                cs = []
                t0 = time.time()
                for b in batches[1:]:
                    state, m = jstep(state, b)
                    cs.append(m["collectives_per_step"])
                jax.block_until_ready(state)
                wall = time.time() - t0
                sps = (STEPS - 1) / wall
                if name not in best or sps > best[name]:
                    best[name] = sps
                colls[name] = float(np.mean(
                    [float(jax.device_get(c)) for c in cs]))
    print("RESULT" + json.dumps({"steps_per_s": best,
                                 "collectives_per_step": colls}))
""")


def bench(quick: bool = False) -> tuple[list[tuple[str, float, str]], dict]:
    """Shared benchmarks.run contract: (csv rows, merged payload)."""
    steps, repeats = (25, 2) if quick else (60, 3)
    src = _SUBPROC % {"devs": N_DEVICES, "steps": steps, "repeats": repeats,
                      "thresh": LAZY_THRESH, "max_stale": MAX_STALE}
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.abspath("src")] + env.get("PYTHONPATH", "").split(os.pathsep))
    # a CPU simulation by design: the parent may hold the accelerator
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", src], env=env,
                         capture_output=True, text=True, timeout=1800)
    if out.returncode != 0:
        raise RuntimeError(f"lazy_elision subprocess failed:\n"
                           f"{out.stderr[-2000:]}")
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT")][0]
    res = json.loads(line[len("RESULT"):])
    sps, colls = res["steps_per_s"], res["collectives_per_step"]

    rows = []
    for name in ("eager", "lazy_gate", "lazy_elide"):
        rows.append((f"lazy_elision/{name}", 1e6 / sps[name],
                     f"steps/s={sps[name]:.1f} "
                     f"collectives/step={colls[name]:.2f}"))
    vs_gate = sps["lazy_elide"] / sps["lazy_gate"]
    vs_eager = sps["lazy_elide"] / sps["eager"]
    rows.append(("lazy_elision/speedup", 0.0,
                 f"elide_vs_gate={vs_gate:.2f}x "
                 f"elide_vs_eager={vs_eager:.2f}x"))
    payload = {
        "bench": "lazy_elision", "schema": 1, "quick": quick,
        "devices": N_DEVICES, "mesh": f"{N_DEVICES}x1",
        "lazy_thresh": LAZY_THRESH, "max_stale": MAX_STALE,
        "steps": steps, "repeats": repeats,
        "steps_per_s": sps, "collectives_per_step": colls,
        "speedup_elide_vs_gate": vs_gate,
        "speedup_elide_vs_eager": vs_eager,
    }
    return rows, payload


if __name__ == "__main__":
    for name, us, derived in bench(quick=True)[0]:
        print(f"{name},{us:.1f},{derived}")
