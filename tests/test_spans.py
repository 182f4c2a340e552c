"""Named spans and scopes inside the program:

  * ``AsyncRunner`` times every host span (``runtime.*``) into ``span_s``,
    and ``host_s`` is exactly the sum of its blocking spans;
  * the spans leave the runner's history equal to ``Trainer``'s;
  * the compiled step carries every device tag (``model.*``,
    ``train.optimizer``, ``lowrank.*``, ``codec.*``) in its ``op_name``s,
    and the tags change nothing but that metadata;
  * no new tag is read as one of the chip benchmark's layer tags.
"""
import contextlib
import importlib
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, attn, mamba
from repro.core import CompressorConfig
from repro.core.codec import DECODE_SCOPE, ENCODE_SCOPE
from repro.core.powersgd import ORTH_SCOPE, POWER_SCOPE
from repro.data.synthetic import LMDataConfig, lm_batch
from repro.launch.mesh import make_mesh
from repro.models.model import HEAD_SCOPE
from repro.train.optimizer import sgd
from repro.train.runtime import (BLOCKING_SPANS, SPANS, AsyncRunner,
                                 RuntimeConfig, build_sharded_step)
from repro.train.step import make_model_compressor
from repro.train.trainer import Trainer, TrainerConfig

ROOT = Path(__file__).resolve().parents[1]
DEVICE_TAGS = ("model.mixer", "model.mlp", HEAD_SCOPE, "train.optimizer",
               POWER_SCOPE, ORTH_SCOPE, ENCODE_SCOPE, DECODE_SCOPE)


def _toy():
    """A jitted step on a small state, and its batches."""
    @jax.jit
    def step(state, batch):
        w = state["w"] * 0.5 + batch["x"].sum(0)
        return {"w": w, "step": state["step"] + 1}, {"loss": jnp.sum(w * w)}

    def batch_fn(i):
        return {"x": np.full((2, 4), float(i), np.float32)}

    state = {"w": jnp.ones((4,), jnp.float32), "step": jnp.zeros((), jnp.int32)}
    return step, batch_fn, state


def test_runner_times_every_span(tmp_path):
    step, bf, state = _toy()
    cfg = RuntimeConfig(steps=6, log_every=2, verbose=False, ckpt_every=3,
                        ckpt_path=str(tmp_path / "s.ckpt"))
    runner = AsyncRunner(step, bf, cfg)
    runner.run(state)
    assert set(runner.span_s) == set(SPANS)
    assert all(runner.span_s[name] > 0 for name in SPANS), runner.span_s
    assert all(name.startswith("runtime.") for name in SPANS)


def test_host_s_is_the_blocking_spans_sum():
    step, bf, state = _toy()
    runner = AsyncRunner(step, bf, RuntimeConfig(steps=5, log_every=1,
                                                 verbose=False))
    runner.run(state)
    runner.cfg.steps = 9
    runner.run(state, start_step=5)
    assert set(BLOCKING_SPANS) < set(SPANS)
    assert "runtime.dispatch" not in BLOCKING_SPANS
    assert "runtime.batch_build" not in BLOCKING_SPANS
    assert runner.host_s == sum(runner.span_s[n] for n in BLOCKING_SPANS)
    assert runner.host_s > 0 and runner.span_s["runtime.dispatch"] > 0


def test_spanned_runner_history_equals_trainer():
    step, bf, state = _toy()
    tr = Trainer(step, bf, TrainerConfig(steps=7, log_every=3, verbose=False))
    ar = AsyncRunner(step, bf, RuntimeConfig(steps=7, log_every=3,
                                             verbose=False))
    end_sync, end_async = tr.run(dict(state)), ar.run(dict(state))
    assert np.array_equal(np.asarray(end_sync["w"]), np.asarray(end_async["w"]))
    drop = lambda h: [{k: v for k, v in m.items() if k != "wall_s"} for m in h]
    assert drop(tr.history) == drop(ar.history)
    assert [m["step"] for m in ar.history] == [0, 3, 6]


# ------------------------------------------------------------ device tags
def _hybrid_hlo() -> str:
    """The compiled step of a tiny hybrid model (attention, SSD and MLP
    layers; LQ-SGD at rank 2), as text."""
    cfg = ModelConfig(name="t", arch_type="hybrid", source="t", d_model=64,
                      vocab_size=128, pattern=(attn(), mamba()), repeats=2,
                      n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
                      ssm_state=16, ssm_head_dim=16, ssm_chunk=16,
                      dtype="bfloat16")
    mesh = make_mesh((1, 1), ("data", "model"))
    comp = make_model_compressor(cfg, CompressorConfig(name="lq_sgd", rank=2))
    batch = lm_batch(LMDataConfig(vocab_size=128, seq_len=32, batch=4), 0)
    with jax.set_mesh(mesh):
        step, st_sh, _, st_abs = build_sharded_step(
            cfg, mesh, comp, sgd(0.05), sample_batch=batch, remat_scan=True)
        state = jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            st_abs, st_sh)
        shapes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch)
        return step.lower(state, shapes).compile().as_text()


def _chipbench(module: str):
    """A module of the chip benchmark, which lives beside the package."""
    sys.path.insert(0, str(ROOT))
    try:
        return importlib.import_module(f"chipbench.{module}")
    finally:
        sys.path.remove(str(ROOT))


def test_compiled_step_carries_tags_and_nothing_else(monkeypatch):
    tagged = _hybrid_hlo()
    op_names = re.findall(r'op_name="([^"]*)"', tagged)
    for tag in DEVICE_TAGS:
        assert any(tag in n for n in op_names), tag
    # backward ops name their pass; the compressor's tags nest in comp.*
    assert any("transpose(" in n and "model.mixer" in n for n in op_names)
    assert all("comp." in n for n in op_names
               if POWER_SCOPE in n or ORTH_SCOPE in n)

    scope = jax.named_scope
    monkeypatch.setattr(
        jax, "named_scope",
        lambda name: (contextlib.nullcontext() if name in DEVICE_TAGS
                      else scope(name)))
    plain = _hybrid_hlo()
    assert not any(t in plain for t in DEVICE_TAGS)
    strip = _chipbench("splits").strip_metadata
    assert strip(tagged) == strip(plain)


def test_new_tags_are_not_benchmark_layer_tags():
    trace = _chipbench("trace")
    for tag in DEVICE_TAGS + SPANS:
        assert not any(prefix in tag for prefix, _ in trace.SCOPES), tag
    # under the compressor's scope a phase tag stays the compressor's
    for tag in (POWER_SCOPE, ORTH_SCOPE, ENCODE_SCOPE, DECODE_SCOPE):
        assert trace._scope_of(f"jit(f)/comp.lq_sgd.eager/{tag}/dot") == (
            "compress")
    for tag in ("model.mixer", "model.mlp", HEAD_SCOPE, "train.optimizer"):
        assert trace._scope_of(f"jit(f)/transpose(jvp())/{tag}/dot") == "model"


def test_spans_record_on_the_profiler_clock(tmp_path):
    """Each span is a profiler annotation: a trace taken around a run
    holds it by name, the prefetch thread's included."""
    step, bf, state = _toy()
    runner = AsyncRunner(step, bf, RuntimeConfig(
        steps=4, log_every=1, verbose=False, ckpt_every=2,
        ckpt_path=str(tmp_path / "s.ckpt")))
    with jax.profiler.trace(str(tmp_path / "trace")):
        runner.run(state)
    (pb,) = (tmp_path / "trace").rglob("*.xplane.pb")
    planes = jax.profiler.ProfileData.from_file(str(pb)).planes
    names = {e.name for plane in planes if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events}
    assert set(SPANS) <= names
