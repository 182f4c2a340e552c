"""Async production runtime (repro.train.runtime):

  * the launcher-built step must CARRY the derived shardings (the launcher
    used to drop them — error feedback then replicated over `model`);
  * AsyncRunner == Trainer bit-for-bit on the same jitted step;
  * gradient accumulation: k=1 == no-accumulation bit-for-bit, k>1 within
    float tolerance of the full-batch step;
  * background checkpoints restore and continue; write errors surface;
  * schedule phases: one runner threads history/wall-clock through
    boundaries, and resume skips completed phases (no re-applied warm-Q
    truncation).
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.io import AsyncCheckpointer, restore as ckpt_restore
from repro.configs.base import ModelConfig, attn
from repro.core import CompressorConfig
from repro.data.synthetic import LMDataConfig, lm_batch
from repro.launch.mesh import make_mesh
from repro.train.optimizer import sgd
from repro.train.runtime import (AsyncRunner, RuntimeConfig, _SnapshotPacker,
                                 build_sharded_step, run_schedule,
                                 sharded_init)
from repro.train.step import (build_train_step, init_train_state,
                              make_model_compressor, n_dp_of)
from repro.train.trainer import Trainer, TrainerConfig


def _tiny_cfg():
    return ModelConfig(name="t", arch_type="dense", source="t", d_model=64,
                       vocab_size=128, pattern=(attn(),), repeats=2,
                       n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
                       dtype="float32")


def _setup(comp_cfg=None, batch=8, seq=32):
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = _tiny_cfg()
    comp = make_model_compressor(
        cfg, comp_cfg or CompressorConfig(name="lq_sgd", rank=2))
    opt = sgd(0.05)
    data = LMDataConfig(vocab_size=128, seq_len=seq, batch=batch)
    return mesh, cfg, comp, opt, (lambda i: lm_batch(data, i))


def _params_equal(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(jax.device_get(a)),
                               jax.tree.leaves(jax.device_get(b))))


# ------------------------------------------------------- sync == async ----
def test_async_runner_matches_trainer_bit_for_bit():
    mesh, cfg, comp, opt, bf = _setup()
    with jax.set_mesh(mesh):
        jstep, st_sh, _, _ = build_sharded_step(cfg, mesh, comp, opt,
                                                sample_batch=bf(0),
                                                remat_scan=False)
        s_sync = sharded_init(cfg, jax.random.PRNGKey(0), opt, comp, mesh,
                              st_sh)
        s_async = sharded_init(cfg, jax.random.PRNGKey(0), opt, comp, mesh,
                               st_sh)
        tr = Trainer(jstep, bf, TrainerConfig(steps=8, log_every=3,
                                              verbose=False))
        ar = AsyncRunner(jstep, bf, RuntimeConfig(steps=8, log_every=3,
                                                  verbose=False))
        f_sync = tr.run(s_sync)
        f_async = ar.run(s_async)
        assert _params_equal(f_sync["params"], f_async["params"])
        assert _params_equal(f_sync["comp"], f_async["comp"])
        # same history schema and logging grid
        assert [h["step"] for h in tr.history] == \
               [h["step"] for h in ar.history] == [0, 3, 6, 7]
        for h1, h2 in zip(tr.history, ar.history):
            assert h1["loss"] == h2["loss"]


# ------------------------------------------------ gradient accumulation ----
def test_microbatch_k1_equals_no_accumulation():
    mesh, cfg, comp, opt, bf = _setup()
    with jax.set_mesh(mesh):
        finals = {}
        for k in (None, 1, 4):
            if k is None:  # the pre-runtime path: un-sharded jit, no accum
                step_fn, _, _ = build_train_step(cfg, mesh, comp, opt,
                                                 remat_scan=False)
                jstep = jax.jit(step_fn, donate_argnums=0)
            else:
                jstep, _, _, _ = build_sharded_step(cfg, mesh, comp, opt,
                                                    sample_batch=bf(0),
                                                    microbatch=k,
                                                    remat_scan=False)
            state = init_train_state(cfg, jax.random.PRNGKey(0), opt, comp,
                                     n_dp_of(mesh))
            for i in range(5):
                state, m = jstep(state, bf(i))
            finals[k] = (jax.device_get(state["params"]), float(m["loss"]))
        # k=1 is literally the single-pass code path
        assert _params_equal(finals[None][0], finals[1][0])
        assert np.isfinite(finals[4][1])
        # k=4 averages the same per-microbatch means the full batch averages
        # — equal up to float reassociation across 5 steps
        for x, y in zip(jax.tree.leaves(finals[1][0]),
                        jax.tree.leaves(finals[4][0])):
            np.testing.assert_allclose(x, y, rtol=2e-3, atol=1e-5)


def test_microbatch_rejects_indivisible_batch():
    mesh, cfg, comp, opt, bf = _setup(batch=6)
    with jax.set_mesh(mesh):
        jstep, _, _, _ = build_sharded_step(cfg, mesh, comp, opt,
                                            sample_batch=bf(0), microbatch=4,
                                            remat_scan=False)
        state = init_train_state(cfg, jax.random.PRNGKey(0), opt, comp,
                                 n_dp_of(mesh))
        with pytest.raises(ValueError, match="not divisible"):
            jstep(state, bf(0))


# --------------------------------------------- background checkpointing ----
def _counting_async_runner(tmp_path, steps, ckpt_every=3):
    def step_fn(state, batch):
        return ({"w": state["w"] + batch, "step": state["step"] + 1},
                {"loss": jnp.float32(0.0)})

    cfg = RuntimeConfig(steps=steps, log_every=1000, ckpt_every=ckpt_every,
                        ckpt_path=str(tmp_path / "state.ckpt"),
                        verbose=False)
    return AsyncRunner(step_fn, lambda i: jnp.float32(1.0), cfg), cfg


def test_background_checkpoint_restores_and_continues(tmp_path):
    runner, cfg = _counting_async_runner(tmp_path, steps=8)
    state = runner.run({"w": jnp.float32(0.0),
                        "step": jnp.zeros((), jnp.int32)})
    assert int(state["step"]) == 8
    # the background saver drained before run() returned: the final
    # (off-grid) step is on disk
    restored = ckpt_restore(cfg.ckpt_path, jax.eval_shape(lambda: state))
    assert int(restored["step"]) == 8 and float(restored["w"]) == 8.0
    runner2, _ = _counting_async_runner(tmp_path, steps=12)
    final = runner2.run(restored)   # start derived from state["step"]
    assert int(final["step"]) == 12 and float(final["w"]) == 12.0


def test_async_checkpoint_write_error_surfaces(tmp_path):
    blocker = tmp_path / "blocker"
    blocker.write_text("not a directory")
    saver = AsyncCheckpointer(str(blocker / "state.ckpt"))
    try:
        saver.submit({"w": jnp.float32(1.0)})
        with pytest.raises(RuntimeError, match="checkpoint write"):
            saver.drain()
    finally:
        saver.close()


def test_snapshot_packer_is_donation_safe():
    state = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
             "b": jnp.ones((4,), jnp.float32),
             "n": jnp.asarray(3, jnp.int32)}
    packer = _SnapshotPacker(state)
    thunk = packer.snapshot(state)
    burn = jax.jit(lambda s: jax.tree.map(lambda x: x * 0, s),
                   donate_argnums=0)
    burned = burn(state)           # donates every buffer of `state`
    jax.block_until_ready(burned)
    host = thunk()
    assert host["a"].shape == (2, 3) and host["b"].shape == (4,)
    np.testing.assert_array_equal(host["a"],
                                  np.arange(6, dtype=np.float32).reshape(2, 3))
    np.testing.assert_array_equal(host["b"], np.ones(4, np.float32))
    assert int(host["n"]) == 3


def test_prefetch_error_propagates():
    def bad_batch(i):
        if i >= 2:
            raise RuntimeError("shard missing")
        return jnp.float32(1.0)

    runner = AsyncRunner(
        lambda s, b: ({"w": s["w"] + b, "step": s["step"] + 1}, {}),
        bad_batch, RuntimeConfig(steps=6, log_every=1000, verbose=False))
    with pytest.raises(RuntimeError, match="prefetch"):
        runner.run({"w": jnp.float32(0.0), "step": jnp.zeros((), jnp.int32)})


# ----------------------------------------------------- schedule phases ----
def _decay_setup():
    return _setup(CompressorConfig(name="lq_sgd", rank=4,
                                   schedule_decay=((4, 2, None),
                                                   (8, 1, None))))


def test_run_schedule_resume_mid_decay(tmp_path):
    """save -> restore -> resume past a decay boundary: completed phases
    are skipped (their warm-Q truncations are NOT re-applied), the entry
    phase reuses the restored compressor's graph, and later boundaries
    still fire."""
    mesh, cfg, comp, opt, bf = _decay_setup()
    ck = str(tmp_path / "s.ckpt")
    with jax.set_mesh(mesh):
        def build(c):
            return build_sharded_step(cfg, mesh, c, opt, sample_batch=bf(0),
                                      remat_scan=False)

        jstep, st_sh, _, _ = build(comp)
        state = sharded_init(cfg, jax.random.PRNGKey(0), opt, comp, mesh,
                             st_sh)
        calls = []

        def rebuild(c, seg):
            calls.append(seg)
            js, sh, _, _ = build(c)
            return js, sh

        runner = Trainer(jstep, bf, TrainerConfig(
            steps=6, log_every=100, ckpt_every=3, ckpt_path=ck,
            verbose=False))
        state = run_schedule(runner, comp, state, total_steps=6,
                             rebuild=rebuild)
        assert calls == [4]                      # one boundary crossed
        assert int(jax.device_get(state["step"])) == 6
        q_cols = {v.shape[-1]
                  for v in jax.device_get(state["comp"]["q"]).values()}
        assert q_cols == {2}                     # truncated at step 4

        # ---- resume: restore with the compressor at the saved step ------
        comp_r = comp.at_step(6)
        jstep2, st_sh2, _, st_abs2 = build(comp_r)
        restored = ckpt_restore(ck, st_abs2, st_sh2)
        assert int(jax.device_get(restored["step"])) == 6
        calls2 = []

        def rebuild2(c, seg):
            calls2.append(seg)
            js, sh, _, _ = build(c)
            return js, sh

        runner2 = Trainer(jstep2, bf, TrainerConfig(steps=6, log_every=100,
                                                    verbose=False))
        final = run_schedule(runner2, comp, restored, total_steps=12,
                             rebuild=rebuild2, initial=comp_r)
        # phase (0,4) skipped entirely; entry phase (4,8) needs NO rebuild
        # (comp_r already is that phase's compressor — the old loop would
        # have re-applied adapt_state here); boundary 8 fires once
        assert calls2 == [8]
        assert int(jax.device_get(final["step"])) == 12
        q_final = {v.shape[-1]
                   for v in jax.device_get(final["comp"]["q"]).values()}
        assert q_final == {1}


def test_resume_checkpoint_saved_exactly_on_boundary(tmp_path):
    """A save landing ON a decay boundary holds the PRE-boundary q (the
    truncation only happens when the next phase is entered): restore
    shapes must come from the phase of the last EXECUTED step (step-1),
    and run_schedule must then apply the boundary adaptation once."""
    mesh, cfg, comp, opt, bf = _decay_setup()
    ck = str(tmp_path / "s.ckpt")
    with jax.set_mesh(mesh):
        def build(c):
            return build_sharded_step(cfg, mesh, c, opt, sample_batch=bf(0),
                                      remat_scan=False)

        jstep, st_sh, _, _ = build(comp)
        state = sharded_init(cfg, jax.random.PRNGKey(0), opt, comp, mesh,
                             st_sh)
        # run EXACTLY to the first boundary (4): ckpt carries step=4 with
        # rank-4 q (phase (0,4) produced it; truncation not yet applied)
        runner = Trainer(jstep, bf, TrainerConfig(
            steps=4, log_every=100, ckpt_every=4, ckpt_path=ck,
            verbose=False))
        run_schedule(runner, comp, state, total_steps=4,
                     rebuild=lambda c, s: build(c)[:2])
        from repro.checkpoint.io import peek_step
        assert peek_step(ck) == 4
        # restore shapes for the phase of step0-1 = 3 (rank 4) — building
        # them for at_step(4) (rank 2) raises a shape mismatch (the old
        # launcher bug)
        comp_r = comp.at_step(3)
        jstep2, st_sh2, _, st_abs2 = build(comp_r)
        restored = ckpt_restore(ck, st_abs2, st_sh2)
        assert {v.shape[-1]
                for v in jax.device_get(restored["comp"]["q"]).values()} \
            == {4}
        calls = []

        def rebuild(c, seg):
            calls.append(seg)
            js, sh, _, _ = build(c)
            return js, sh

        runner2 = Trainer(jstep2, bf, TrainerConfig(steps=4, log_every=100,
                                                    verbose=False))
        final = run_schedule(runner2, comp, restored, total_steps=12,
                             rebuild=rebuild, initial=comp_r)
        # boundary 4's adaptation fires exactly once on entry, 8's once
        assert calls == [4, 8]
        assert int(jax.device_get(final["step"])) == 12
        assert {v.shape[-1]
                for v in jax.device_get(final["comp"]["q"]).values()} == {1}


def test_run_schedule_threads_one_runner_history(tmp_path):
    """Regression: the launcher built a fresh Trainer per schedule phase,
    so history was discarded and wall_s restarted at each boundary."""
    mesh, cfg, comp, opt, bf = _decay_setup()
    with jax.set_mesh(mesh):
        def build(c):
            return build_sharded_step(cfg, mesh, c, opt, sample_batch=bf(0),
                                      remat_scan=False)

        jstep, st_sh, _, _ = build(comp)
        state = sharded_init(cfg, jax.random.PRNGKey(0), opt, comp, mesh,
                             st_sh)
        runner = Trainer(jstep, bf, TrainerConfig(steps=6, log_every=2,
                                                  verbose=False))
        run_schedule(runner, comp, state, total_steps=6,
                     rebuild=lambda c, s: build(c)[:2])
        steps_logged = [h["step"] for h in runner.history]
        # history spans BOTH phases (0-3 and 4-5) in one list...
        assert steps_logged == [0, 2, 3, 4, 5]
        # ...and wall_s is monotone across the boundary (no reset to ~0)
        walls = [h["wall_s"] for h in runner.history]
        assert walls == sorted(walls)


def test_run_schedule_plain_compressor_passthrough():
    """No schedule attr (dedicated compressors): one phase, no rebuild."""
    mesh, cfg, comp, opt, bf = _setup()
    with jax.set_mesh(mesh):
        jstep, st_sh, _, _ = build_sharded_step(cfg, mesh, comp, opt,
                                                sample_batch=bf(0),
                                                remat_scan=False)
        state = sharded_init(cfg, jax.random.PRNGKey(0), opt, comp, mesh,
                             st_sh)
        runner = Trainer(jstep, bf, TrainerConfig(steps=3, log_every=100,
                                                  verbose=False))
        boom = lambda c, s: pytest.fail("rebuild must not fire")
        final = run_schedule(runner, comp, state, total_steps=3,
                             rebuild=boom)
        assert int(jax.device_get(final["step"])) == 3


# ------------------------------------------- launcher sharding (slow) ----
_SHARDING_SUBPROC = textwrap.dedent("""
    import os, tempfile
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, jax, jax.numpy as jnp
    import numpy as np
    from repro.configs.base import ModelConfig, attn
    from repro.core import CompressorConfig
    from repro.data.synthetic import LMDataConfig, lm_batch
    from repro.checkpoint.io import restore as ckpt_restore
    from repro.launch.mesh import make_mesh
    from repro.train.optimizer import sgd
    from repro.train.runtime import (AsyncRunner, RuntimeConfig,
                                     build_sharded_step, sharded_init)
    from repro.train.step import make_model_compressor
    from repro.train.trainer import Trainer, TrainerConfig

    # each spec entry as a list of mesh axis names: compared structurally,
    # since PartitionSpec's repr differs between jax releases
    axes = lambda s: [[] if e is None else list(e) if isinstance(e, tuple)
                      else [e] for e in s]
    cfg = ModelConfig(name="t", arch_type="dense", source="t", d_model=64,
                      vocab_size=128, pattern=(attn(),), repeats=2,
                      n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
                      dtype="float32")
    mesh = make_mesh((4, 2), ("data", "model"))
    comp = make_model_compressor(cfg, CompressorConfig(name="lq_sgd", rank=2))
    opt = sgd(0.05)
    data = LMDataConfig(vocab_size=128, seq_len=32, batch=8)
    bf = lambda i: lm_batch(data, i)
    out = {}
    with jax.set_mesh(mesh):
        # the exact path launch/train.py takes
        jstep, st_sh, b_sh, st_abs = build_sharded_step(
            cfg, mesh, comp, opt, sample_batch=bf(0), remat_scan=False)
        state = sharded_init(cfg, jax.random.PRNGKey(0), opt, comp, mesh,
                             st_sh)
        # state born on the mesh with the derived shardings
        out["init_err_specs"] = sorted(
            axes(v.sharding.spec) for v in state["comp"]["err"].values())
        ck_async = tempfile.mktemp()
        runner = AsyncRunner(jstep, bf,
                             RuntimeConfig(steps=3, log_every=100,
                                           ckpt_every=2, ckpt_path=ck_async,
                                           verbose=False))
        state = runner.run(state)
        # ...and still sharded AFTER launcher-built steps ran (this is the
        # regression: jax.jit without in/out_shardings placed everything
        # by default, replicating error feedback over `model`)
        out["step"] = int(jax.device_get(state["step"]))
        out["err_specs"] = sorted(
            axes(v.sharding.spec) for v in state["comp"]["err"].values())
        # background-saved checkpoint must bit-for-bit match the sync
        # trainer's (regression: the packed snapshot's mixed-sharding
        # concat partial-SUMMED over the model axis — counters doubled)
        ck_sync = tempfile.mktemp()
        st2 = sharded_init(cfg, jax.random.PRNGKey(0), opt, comp, mesh,
                           st_sh)
        Trainer(jstep, bf, TrainerConfig(steps=3, log_every=100,
                                         ckpt_every=2, ckpt_path=ck_sync,
                                         verbose=False)).run(st2)
        ra = jax.device_get(ckpt_restore(ck_async, st_abs))
        rs = jax.device_get(ckpt_restore(ck_sync, st_abs))
        out["ckpt_step"] = int(ra["step"])
        out["ckpt_match"] = all(
            np.array_equal(np.asarray(x), np.asarray(y))
            for x, y in zip(jax.tree.leaves(ra), jax.tree.leaves(rs)))
    print("RESULT" + json.dumps(out))
""")


@pytest.mark.slow
def test_launcher_step_carries_derived_shardings():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", _SHARDING_SUBPROC], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    payload = [l for l in out.stdout.splitlines() if l.startswith("RESULT")]
    assert payload, out.stdout
    res = json.loads(payload[0][len("RESULT"):])
    assert res["step"] == 3
    assert res["ckpt_step"] == 3 and res["ckpt_match"]
    for specs in (res["init_err_specs"], res["err_specs"]):
        # every error-feedback leaf leads with the per-worker DP dim...
        assert specs and all(s[0] == ["data"] for s in specs), specs
        # ...and at least one (embed/head-sized) leaf shards its inner
        # dims over the model axis instead of replicating
        assert any("model" in e for s in specs for e in s), specs
