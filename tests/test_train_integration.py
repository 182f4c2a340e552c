"""Integration: the full distributed compressed train step on a real
multi-device mesh (subprocess with 8 host devices), plus loss/optimizer/
checkpoint units that run in-process."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.io import restore as ckpt_restore
from repro.configs.base import ModelConfig, attn
from repro.models.model import init_params
from repro.train.loss import lm_loss
from repro.train.optimizer import adam, sgd
from repro.train.trainer import Trainer, TrainerConfig


def _tiny_cfg():
    return ModelConfig(name="t", arch_type="dense", source="t", d_model=64,
                       vocab_size=128, pattern=(attn(),), repeats=2,
                       n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
                       dtype="float32")


def test_lm_loss_matches_manual_ce():
    cfg = _tiny_cfg()
    p = init_params(cfg, jax.random.PRNGKey(0))
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 128)
    loss, metrics = lm_loss(p, {"tokens": tok}, cfg=cfg)
    assert np.isfinite(float(loss))
    # manual next-token CE over positions 0..s-2 (last target masked)
    from repro.models.model import forward
    logits, _, _ = forward(p, tok, cfg)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    nll = -jnp.take_along_axis(logp[:, :-1], tok[:, 1:, None], -1)[..., 0]
    np.testing.assert_allclose(float(loss), float(jnp.mean(nll)), rtol=1e-5)


def test_sgd_momentum_and_adam_shapes():
    p = {"w": jnp.ones((4, 4)), "b": jnp.zeros((4,))}
    g = jax.tree.map(jnp.ones_like, p)
    for opt in (sgd(0.1), sgd(0.1, momentum=0.9), adam(1e-3)):
        st = opt.init(p)
        p2, st2 = opt.update(g, st, p)
        assert jax.tree_util.tree_structure(p2) == jax.tree_util.tree_structure(p)
        assert float(p2["w"][0, 0]) < 1.0


def test_adam_converges_quadratic():
    opt = adam(0.1)
    p = {"x": jnp.array(5.0)}
    st = opt.init(p)
    for _ in range(200):
        g = {"x": 2 * p["x"]}
        p, st = opt.update(g, st, p)
    assert abs(float(p["x"])) < 0.05


def _counting_trainer(tmp_path, steps):
    """Toy state machine: w accumulates the batch (always 1.0), step counts
    completed steps — so w == step == number of step_fn invocations."""
    def step_fn(state, batch):
        new = {"w": state["w"] + batch, "step": state["step"] + 1}
        return new, {"loss": jnp.float32(0.0)}

    cfg = TrainerConfig(steps=steps, log_every=1000, ckpt_every=3,
                        ckpt_path=str(tmp_path / "state.ckpt"))
    return Trainer(step_fn, lambda i: jnp.float32(1.0), cfg), cfg


def test_trainer_saves_final_step(tmp_path):
    """Regression: the final step was never saved when (steps-1) was off
    the ckpt_every grid — an 8-step run with ckpt_every=3 (final loop
    index 7, off-grid) left its newest checkpoint at loop index 6,
    losing the last update."""
    trainer, cfg = _counting_trainer(tmp_path, steps=8)
    state = trainer.run({"w": jnp.float32(0.0), "step": jnp.zeros((), jnp.int32)})
    assert int(state["step"]) == 8
    restored = ckpt_restore(cfg.ckpt_path, jax.eval_shape(lambda: state))
    assert int(restored["step"]) == 8          # not 7 (the last grid save)
    assert float(restored["w"]) == 8.0


def test_trainer_resume_round_trip(tmp_path):
    """save -> restore -> continue: run() derives start_step from the
    restored state["step"], so no step is repeated or skipped."""
    trainer, cfg = _counting_trainer(tmp_path, steps=5)
    state0 = {"w": jnp.float32(0.0), "step": jnp.zeros((), jnp.int32)}
    state = trainer.run(state0)
    restored = ckpt_restore(cfg.ckpt_path, jax.eval_shape(lambda: state))
    trainer2, _ = _counting_trainer(tmp_path, steps=9)
    final = trainer2.run(restored)             # start_step derived: 5
    assert int(final["step"]) == 9
    assert float(final["w"]) == 9.0            # 4 more steps, none repeated
    # explicit start_step still wins over the derived one
    trainer3, _ = _counting_trainer(tmp_path, steps=9)
    again = trainer3.run(restored, start_step=8)
    assert int(again["step"]) == 6 and float(again["w"]) == 6.0


_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, jax, jax.numpy as jnp
    from repro.configs.base import ModelConfig, attn
    from repro.core import CompressorConfig
    from repro.data.synthetic import LMDataConfig, lm_batch
    from repro.launch.mesh import make_mesh
    from repro.train.optimizer import sgd
    from repro.train.step import (build_train_step, init_train_state,
                                  make_model_compressor, n_dp_of)

    cfg = ModelConfig(name="t", arch_type="dense", source="t", d_model=64,
                      vocab_size=128, pattern=(attn(),), repeats=2,
                      n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
                      dtype="float32")
    results = {}
    for comp_name in ["none", "lq_sgd"]:
        mesh = make_mesh((4, 2), ("data", "model"))
        comp = make_model_compressor(cfg, CompressorConfig(name=comp_name, rank=2))
        opt = sgd(0.05)
        step_fn, st_sh, b_sh = build_train_step(cfg, mesh, comp, opt,
                                                remat_scan=False)
        data = LMDataConfig(vocab_size=128, seq_len=32, batch=8)
        with jax.set_mesh(mesh):
            state = init_train_state(cfg, jax.random.PRNGKey(0), opt, comp,
                                     n_dp_of(mesh))
            jstep = jax.jit(step_fn, donate_argnums=0)
            losses = []
            for i in range(12):
                state, m = jstep(state, lm_batch(data, i))
                losses.append(float(m["loss"]))
            # params replicated across DP after sync? fetch and check one leaf
            w = jax.device_get(state["params"]["embed"])
            results[comp_name] = {"losses": losses,
                                  "wire_mb": float(m["wire_mb_per_step"]),
                                  "finite": bool(jnp.isfinite(jnp.asarray(losses)).all())}
    print("RESULT" + json.dumps(results))
""")


@pytest.mark.slow
def test_distributed_step_8_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", _SUBPROC], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    payload = [l for l in out.stdout.splitlines() if l.startswith("RESULT")]
    assert payload, out.stdout
    res = json.loads(payload[0][len("RESULT"):])
    for name, r in res.items():
        assert r["finite"]
        assert r["losses"][-1] < r["losses"][0], (name, r["losses"])
    # LQ-SGD moves far fewer bytes than uncompressed
    assert res["lq_sgd"]["wire_mb"] < res["none"]["wire_mb"] / 20
