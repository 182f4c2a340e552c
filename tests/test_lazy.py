"""Lazy aggregation (repro.core.lazy + the composite's lazy groups):

  * ``lazy_thresh=0`` composite is BIT-FOR-BIT the eager composite across
    all four methods, fused and unfused (no gating machinery built);
  * skip rounds reuse the cached aggregate and freeze compressor state;
    ``max_stale`` forces a fire; warm-up forces fires;
  * effective accounting: fired round == ``wire_bits_per_step()``, skip
    round == the decision sideband (64 bits/leaf + a 32-bit group
    force-vote slot) with ONE collective;
  * the auto-planner's ``p_fire`` cost model and the policy-spec knobs;
  * skip-state leaves stay sharded on a 4x2 mesh AFTER launcher-built
    steps run (subprocess, slow) — the lazy namespaces are param-shaped
    and must mirror the parameter's model-axis sharding like ``err``.

Collective semantics via ``jax.vmap(axis_name=...)`` — the same named-axis
code path the production shard_map runs (see test_compressors.py).
"""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (AxisComm, CompositeCompressor, CompressorConfig,
                        LeafPolicy, make_compressor, p_fire, plan_auto)
from repro.core.lazy import (DECISION_BITS_PER_GROUP, DECISION_BITS_PER_LEAF,
                             OUT_NS, REF_NS, STALE_NS, staleness_err)
from repro.core.policy import parse_policy_spec

from conftest import broadcast_state

N = 4


def _grads(key, n=N):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w": jax.random.normal(k1, (n, 64, 32)),
        "b": jax.random.normal(k2, (n, 32)),
        "scan": jax.random.normal(k3, (n, 3, 48, 16)),
    }


def _abstract(grads):
    return {k: jax.ShapeDtypeStruct(v.shape[1:], v.dtype)
            for k, v in grads.items()}


STACKED = {"w": False, "b": False, "scan": True}


def _run(comp, grads, steps=1, state=None):
    """Returns (outs, state, per-step [(eff_bits, eff_colls)])."""
    if state is None:
        state = broadcast_state(comp.init_state(jax.random.PRNGKey(42)), N)

    def worker(g, st):
        out, st2, rec = comp.sync(g, st, AxisComm(("data",)))
        return (out, st2,
                jnp.asarray(rec.effective_bits(), jnp.float32),
                jnp.asarray(rec.effective_collectives(), jnp.float32))

    wf = jax.jit(jax.vmap(worker, axis_name="data"))
    out, hist = None, []
    for _ in range(steps):
        out, state, eb, ec = wf(grads, state)
        hist.append((float(eb[0]), float(ec[0])))
    return out, state, hist


def _lazy_policies(method, thresh, max_stale, n=3):
    return [LeafPolicy(method=method, rank=2, topk_ratio=0.1,
                       lazy_thresh=thresh, max_stale=max_stale)] * n


# --------------------------------------------------------------------------
# satellite: thresh=0 is bit-for-bit eager, all methods, fused + unfused
# --------------------------------------------------------------------------

@pytest.mark.parametrize("fuse", [False, True])
@pytest.mark.parametrize("name", ["topk", "qsgd", "powersgd", "lq_sgd"])
def test_lazy_thresh_zero_bit_for_bit_eager(name, fuse):
    grads = _grads(jax.random.PRNGKey(0))
    cfg = CompressorConfig(name=name, rank=2, bits=8, topk_ratio=0.1,
                           fuse_collectives=fuse)
    eager = CompositeCompressor(cfg, _abstract(grads), STACKED,
                                policies=_lazy_policies(name, 0.0, 4))
    ded = make_compressor(cfg, _abstract(grads), STACKED)
    # no gating machinery at thresh=0: state and accounting are untouched
    assert eager.lazy_groups == {}
    st = eager.init_state(jax.random.PRNGKey(0))
    assert not any(ns in st for ns in (OUT_NS, REF_NS, STALE_NS))
    assert eager.decision_bits_per_step() == 0
    assert eager.wire_bits_per_step() == ded.wire_bits_per_step()
    assert eager.expected_wire_bits_per_step() == eager.wire_bits_per_step()
    out_e, st_e, _ = _run(eager, grads, steps=3)
    out_d, st_d, _ = _run(ded, grads, steps=3)
    for a, b in zip(jax.tree.leaves(out_e), jax.tree.leaves(out_d)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), name


# --------------------------------------------------------------------------
# skip semantics + the staleness cap
# --------------------------------------------------------------------------

def test_max_stale_forces_fire_pattern():
    """A never-voting threshold forces the pure staleness schedule: fire
    at round 0 (counter born at the cap), then exactly max_stale skips."""
    grads = _grads(jax.random.PRNGKey(1))
    cfg = CompressorConfig(name="lq_sgd", rank=2, fuse_collectives=True)
    comp = CompositeCompressor(cfg, _abstract(grads), STACKED,
                               policies=_lazy_policies("lq_sgd", 1e6, 2))
    _, st, hist = _run(comp, grads, steps=7)
    fired_bits = comp.wire_bits_per_step()
    side = comp.decision_bits_per_step()
    assert side == DECISION_BITS_PER_LEAF * 3 + DECISION_BITS_PER_GROUP
    want = [fired_bits, side, side, fired_bits, side, side, fired_bits]
    assert [b for b, _ in hist] == want
    # a skipped round runs exactly ONE collective (the decision psum)
    assert all(c == 1.0 for (b, c), w in zip(hist, want) if w == side)
    assert int(np.asarray(st[STALE_NS]["lq_sgd"])[0]) == 0  # just fired


def test_skip_reuses_cached_aggregate_and_freezes_state():
    grads = _grads(jax.random.PRNGKey(2))
    cfg = CompressorConfig(name="lq_sgd", rank=2)
    comp = CompositeCompressor(cfg, _abstract(grads), STACKED,
                               policies=_lazy_policies("lq_sgd", 1e6, 3))
    out0, st0, _ = _run(comp, grads, steps=1)
    # feed DIFFERENT grads on the skip round: output must be the round-0
    # aggregate and err/q must not move (the gradient is not banked)
    grads2 = _grads(jax.random.PRNGKey(99))
    out1, st1, _ = _run(comp, grads2, steps=1, state=st0)
    for a, b in zip(jax.tree.leaves(out0), jax.tree.leaves(out1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for ns in ("err", "q", OUT_NS, REF_NS):
        for k in st0[ns]:
            np.testing.assert_array_equal(np.asarray(st0[ns][k]),
                                          np.asarray(st1[ns][k]))
    assert int(np.asarray(st1[STALE_NS]["lq_sgd"])[0]) == 1
    # identical grads in a fired eager run differ from the stale reuse
    assert int(np.asarray(st1["step"])[0]) == 2  # composite step still runs


def test_small_innovation_skips_large_fires():
    """The actual LAQ criterion: resending near-identical gradients skips
    (innovation ~ 0), a genuinely new gradient fires."""
    grads = _grads(jax.random.PRNGKey(3))
    cfg = CompressorConfig(name="powersgd", rank=2)
    comp = CompositeCompressor(cfg, _abstract(grads), STACKED,
                               policies=_lazy_policies("powersgd", 0.5, 50))
    _, st, hist = _run(comp, grads, steps=3)
    fired = comp.wire_bits_per_step()
    side = comp.decision_bits_per_step()
    # round 0 fires (born stale); identical grads after that -> skips
    assert [b for b, _ in hist] == [fired, side, side]
    # an orthogonal gradient (innovation >> thresh^2 * norm) fires
    grads2 = _grads(jax.random.PRNGKey(77))
    _, _, hist2 = _run(comp, grads2, steps=1, state=st)
    assert hist2[0][0] == fired


def test_workers_agree_under_lazy():
    grads = _grads(jax.random.PRNGKey(4))
    cfg = CompressorConfig(name="lq_sgd", rank=2, fuse_collectives=True)
    comp = CompositeCompressor(cfg, _abstract(grads), STACKED,
                               policies=_lazy_policies("lq_sgd", 1.5, 4))
    out, _, _ = _run(comp, grads, steps=4)
    for leaf in jax.tree.leaves(out):
        for i in range(1, N):
            np.testing.assert_allclose(np.asarray(leaf[0]),
                                       np.asarray(leaf[i]), atol=1e-5)


def test_mixed_eager_and_lazy_leaves_split_groups():
    """Within one method group, only the lazy subset gates; eager leaves
    keep full-rate syncing in their own phase set."""
    grads = _grads(jax.random.PRNGKey(5))
    cfg = CompressorConfig(name="lq_sgd", rank=2)
    # flatten order: b, scan, w — only 'scan' is lazy
    pol = LeafPolicy(method="lq_sgd", rank=2)
    lazy_pol = dataclasses.replace(pol, lazy_thresh=1e6, max_stale=2)
    comp = CompositeCompressor(cfg, _abstract(grads), STACKED,
                               policies=[pol, lazy_pol, pol])
    assert comp.lazy_groups == {"lq_sgd": [1]}
    _, _, hist = _run(comp, grads, steps=2)
    h = comp.handlers["lq_sgd"]
    eager_bits = sum(h.leaf_wire_bits(comp.plans[i]) for i in (0, 2))
    lazy_bits = h.leaf_wire_bits(comp.plans[1])
    side = DECISION_BITS_PER_LEAF + DECISION_BITS_PER_GROUP
    assert hist[0][0] == eager_bits + lazy_bits + side
    assert hist[1][0] == eager_bits + side  # scan skipped, others synced
    assert comp.wire_bits_per_step() == eager_bits + lazy_bits + side


def test_warmup_forces_fire():
    """While the in-graph warm-up is selecting the exact fp32 mean, the
    lazy gate must fire every round: the cached aggregate keeps tracking
    the compressed stream so the first post-warm skip reuses fresh state,
    and error feedback stays zeroed as in the eager warm-up."""
    grads = _grads(jax.random.PRNGKey(6))
    from repro.core import PolicySchedule
    cfg = CompressorConfig(name="lq_sgd", rank=2)
    comp = CompositeCompressor(cfg, _abstract(grads), STACKED,
                               policies=_lazy_policies("lq_sgd", 1e6, 50),
                               schedule=PolicySchedule(warmup_steps=2))
    _, st, hist = _run(comp, grads, steps=3)
    fired = comp.wire_bits_per_step()
    side = comp.decision_bits_per_step()
    # warm rounds 0,1 fire (forced); round 2 resumes the lazy schedule
    assert [b for b, _ in hist] == [fired, fired, side]


def test_schedule_decay_preserves_lazy_knobs():
    grads = _grads(jax.random.PRNGKey(7))
    from repro.core import PolicySchedule
    cfg = CompressorConfig(name="lq_sgd", rank=4)
    comp = CompositeCompressor(
        cfg, _abstract(grads), STACKED,
        policies=_lazy_policies("lq_sgd", 1.5, 4),
        schedule=PolicySchedule(decay=((10, 1, None),)))
    c10 = comp.at_step(10)
    assert c10 is not comp
    assert all(p.lazy_thresh == 1.5 and p.max_stale == 4
               for p in c10.policies)
    assert c10.lazy_groups == comp.lazy_groups
    # adapt_state truncates q and carries the lazy namespaces through
    _, st, _ = _run(comp, grads, steps=1)
    st10 = c10.adapt_state(st)
    assert set(st10) >= {OUT_NS, REF_NS, STALE_NS}


# --------------------------------------------------------------------------
# config / spec / planner plumbing
# --------------------------------------------------------------------------

def test_policy_validation():
    with pytest.raises(ValueError, match="lazy_thresh"):
        LeafPolicy(method="lq_sgd", lazy_thresh=-1.0)
    with pytest.raises(ValueError, match="max_stale"):
        LeafPolicy(method="lq_sgd", lazy_thresh=0.5, max_stale=0)


def test_make_compressor_routes_lazy_to_composite():
    abstract = _abstract(_grads(jax.random.PRNGKey(8)))
    cfg = CompressorConfig(name="lq_sgd", lazy_thresh=1.5, max_stale=4)
    comp = make_compressor(cfg, abstract, STACKED)
    assert isinstance(comp, CompositeCompressor)
    assert comp.lazy_groups  # uniform policy carries the lazy knobs
    assert all(p.lazy_thresh == 1.5 for p in comp.policies)


def test_policy_spec_lazy_knobs():
    rules = parse_policy_spec(
        "scan=lq_sgd:rank=2:lazy_thresh=1.5:max_stale=8,*=lq_sgd")
    assert rules[0][1].lazy_thresh == 1.5
    assert rules[0][1].max_stale == 8
    assert rules[1][1].lazy_thresh == 0.0


def test_p_fire_model():
    assert p_fire(0.0, 4) == 1.0
    # monotone: higher threshold -> lower fire probability...
    assert p_fire(0.5, 8) >= p_fire(1.0, 8) >= p_fire(2.0, 8)
    # ...floored by the staleness cap
    assert p_fire(100.0, 4) == pytest.approx(1 / 5)
    assert staleness_err(0.0, 4) == 0.0
    assert staleness_err(2.0, 8) > staleness_err(0.5, 8)


def test_auto_planner_trades_wire_for_staleness():
    abstract = _abstract(_grads(jax.random.PRNGKey(9)))
    cfg = CompressorConfig(name="lq_sgd", lazy_thresh=2.0, max_stale=8,
                           policy="auto", error_budget=0.5)
    pols, report = plan_auto(abstract, STACKED, cfg=cfg)
    assert any(p.lazy_thresh > 0 for p in pols)  # lazy variants won leaves
    comp = CompositeCompressor(cfg, abstract, STACKED, policies=pols)
    # report wire (fired round + sideband share) matches the composite
    assert sum(r["wire_bits"] for r in report) == comp.wire_bits_per_step()
    # the expectation the cost model optimized is below the fired figure
    assert comp.expected_wire_bits_per_step() < comp.wire_bits_per_step()
    # eager planning is unchanged by the lazy code path
    pols0, _ = plan_auto(abstract, STACKED,
                         cfg=dataclasses.replace(cfg, lazy_thresh=0.0))
    assert all(p.lazy_thresh == 0 for p in pols0)


def test_wire_bits_by_method_includes_sideband():
    grads = _grads(jax.random.PRNGKey(10))
    cfg = CompressorConfig(name="lq_sgd", rank=2)
    comp = CompositeCompressor(cfg, _abstract(grads), STACKED,
                               policies=_lazy_policies("lq_sgd", 1.5, 4))
    by_method = comp.wire_bits_by_method()
    assert sum(by_method.values()) == comp.wire_bits_per_step()


# --------------------------------------------------------------------------
# satellite: skip-state leaves stay sharded on a 4x2 mesh (slow)
# --------------------------------------------------------------------------

_LAZY_SHARDING_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, jax, jax.numpy as jnp
    from repro.configs.base import ModelConfig, attn
    from repro.core import CompressorConfig
    from repro.data.synthetic import LMDataConfig, lm_batch
    from repro.launch.mesh import make_mesh
    from repro.train.optimizer import sgd
    from repro.train.runtime import (AsyncRunner, RuntimeConfig,
                                     build_sharded_step, sharded_init)
    from repro.train.step import make_model_compressor

    # each spec entry as a list of mesh axis names: compared structurally,
    # since PartitionSpec's repr differs between jax releases
    axes = lambda s: [[] if e is None else list(e) if isinstance(e, tuple)
                      else [e] for e in s]
    cfg = ModelConfig(name="t", arch_type="dense", source="t", d_model=64,
                      vocab_size=128, pattern=(attn(),), repeats=2,
                      n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
                      dtype="float32")
    mesh = make_mesh((4, 2), ("data", "model"))
    comp = make_model_compressor(
        cfg, CompressorConfig(name="lq_sgd", rank=2, lazy_thresh=1.5,
                              max_stale=4))
    assert comp.lazy_groups, "uniform lazy config must gate every group"
    opt = sgd(0.05)
    data = LMDataConfig(vocab_size=128, seq_len=32, batch=8)
    bf = lambda i: lm_batch(data, i)
    out = {}
    with jax.set_mesh(mesh):
        jstep, st_sh, b_sh, st_abs = build_sharded_step(
            cfg, mesh, comp, opt, sample_batch=bf(0), remat_scan=False)
        state = sharded_init(cfg, jax.random.PRNGKey(0), opt, comp, mesh,
                             st_sh)
        runner = AsyncRunner(jstep, bf, RuntimeConfig(steps=3, log_every=100,
                                                      verbose=False))
        state = runner.run(state)
        out["step"] = int(jax.device_get(state["step"]))
        for ns in ("lazy_out", "lazy_ref"):
            out[ns] = sorted(
                axes(v.sharding.spec) for v in state["comp"][ns].values())
        out["stale"] = sorted(
            axes(v.sharding.spec) for v in state["comp"]["lazy_stale"].values())
    print("RESULT" + json.dumps(out))
""")


@pytest.mark.slow
def test_lazy_state_stays_sharded_after_launcher_steps():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", _LAZY_SHARDING_SUBPROC],
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    payload = [l for l in out.stdout.splitlines() if l.startswith("RESULT")]
    assert payload, out.stdout
    res = json.loads(payload[0][len("RESULT"):])
    assert res["step"] == 3
    for ns in ("lazy_out", "lazy_ref"):
        specs = res[ns]
        # every skip-state leaf leads with the per-worker DP dim...
        assert specs and all(s[0] == ["data"] for s in specs), (ns, specs)
        # ...and at least one (embed/head-sized) leaf shards its inner
        # dims over the model axis instead of replicating
        assert any("model" in e for s in specs for e in s), (ns, specs)
    # the per-group staleness counters replicate (scalars)
    assert all("model" not in e for s in res["stale"] for e in s), res["stale"]
