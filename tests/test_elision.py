"""Graph-level collective elision for lazy aggregation (PR: lax.cond skip
branches) + adaptive LAQ thresholds.

What is being proven, layer by layer:

  * jaxpr: under shard_map the decision psum is UNCONDITIONAL at the body's
    top level, while every group collective (all-gather, scale pmax/psum)
    lives ONLY inside ``lax.cond``'s true (fire) branch — the skip branch
    traces zero collectives. ``lazy_mode="gate"`` traces no cond at all.
  * semantics: gate and elide modes are bit-for-bit identical across skip
    and fire rounds; an always-firing lazy composite (tiny threshold +
    adaptive cap engaged) is bit-for-bit the eager composite for all four
    methods, fused and unfused.
  * adaptive LAQ: the drift-EMA threshold scaling ramps the skip rate as a
    synthetic run converges, where fixed thresholds hold a steady rate.
  * system (slow, subprocess, 8 devices): the compiled HLO of a
    launcher-built 4x2-mesh train step keeps the ``conditional`` with the
    group's all-gathers only in its fire branch, and per-worker skip state
    (stale counters, cached aggregates) stays identical across the data
    axis after real async-runtime steps — the predicate never diverged.

Equivalence tests use ``jax.vmap(axis_name=...)``; under vmap a batched
predicate lowers cond to a select over BOTH branches, which is exactly
gate-mode semantics — so vmap exercises equivalence, and the shard_map
jaxpr/HLO tests exercise the actual elision.
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
from jax.sharding import PartitionSpec as P

from repro.analysis.inventory import jaxpr_inventory
from repro.analysis.trace import trace_sync_jaxpr
from repro.core import (AxisComm, CompositeCompressor, CompressorConfig,
                        LeafPolicy)
from repro.core.lazy import (EMA_NS, ema_update, group_adaptive_cap,
                             tau_scale2)
from repro.launch.sharding import assert_replicated

from conftest import broadcast_state

N = 4


def _grads(key, n=None):
    k1, k2, k3 = jax.random.split(key, 3)
    lead = () if n is None else (n,)
    return {
        "w": jax.random.normal(k1, lead + (64, 32)),
        "b": jax.random.normal(k2, lead + (32,)),
        "scan": jax.random.normal(k3, lead + (3, 48, 16)),
    }


def _abstract(grads):
    return {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
            for k, v in grads.items()}


STACKED = {"w": False, "b": False, "scan": True}


def _lazy_policies(method, thresh, max_stale, adaptive=0.0, n=3):
    return [LeafPolicy(method=method, rank=2, topk_ratio=0.1,
                       lazy_thresh=thresh, max_stale=max_stale,
                       lazy_adaptive=adaptive)] * n


def _composite(method, thresh, max_stale, *, fuse=False, mode="elide",
               adaptive=0.0, grads=None):
    grads = grads if grads is not None else _grads(jax.random.PRNGKey(0))
    cfg = CompressorConfig(name=method, rank=2, bits=8, topk_ratio=0.1,
                           fuse_collectives=fuse, lazy_mode=mode)
    return CompositeCompressor(cfg, _abstract(grads), STACKED,
                               policies=_lazy_policies(method, thresh,
                                                       max_stale, adaptive))


def _run(comp, grads, steps=1, state=None):
    """vmap N-worker harness; returns (outs, state, [(bits, colls)])."""
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


# --------------------------------------------------------------------------
# jaxpr: collectives live only where they should (via the graph linter's
# collective inventory — repro.analysis owns the jaxpr/HLO parsers now)
# --------------------------------------------------------------------------

def _inventory(comp, grads):
    """(rows, cond sites) of one sync's jaxpr, via the shared extractor."""
    return jaxpr_inventory(trace_sync_jaxpr(comp, _abstract(grads)))


@pytest.mark.parametrize("fuse", [False, True])
@pytest.mark.parametrize("method", ["topk", "qsgd", "powersgd", "lq_sgd"])
def test_group_collectives_only_in_fire_branch(method, fuse):
    grads = _grads(jax.random.PRNGKey(0))
    comp = _composite(method, 1.5, 4, fuse=fuse, grads=grads)
    rows, conds = _inventory(comp, grads)

    assert len(conds) == 1  # one lazy group -> one dispatch point

    # outside the cond: exactly the fused decision psum, nothing else
    outside = [r.kind for r in rows if r.cond is None]
    assert outside == ["psum"], (method, fuse, outside)
    assert rows[0].tagged("lazy.decision") or outside != ["psum"]

    # branches[0] is the false (skip) branch, branches[1] the fire branch
    skip_colls = conds[0].branch_kinds(0)
    fire_colls = conds[0].branch_kinds(1)
    assert skip_colls == [], (method, fuse, skip_colls)
    assert "all_gather" in fire_colls, (method, fuse, fire_colls)
    if method in ("qsgd", "lq_sgd"):  # quantizers also sync their scales
        assert "pmax" in fire_colls, (method, fuse, fire_colls)


def test_gate_mode_traces_no_cond():
    grads = _grads(jax.random.PRNGKey(0))
    comp = _composite("lq_sgd", 1.5, 4, fuse=True, mode="gate", grads=grads)
    rows, conds = _inventory(comp, grads)
    assert conds == []
    # the gate traces the group collectives unconditionally
    assert "all_gather" in [r.kind for r in rows]


def test_adaptive_scaling_adds_no_collectives():
    """The drift EMA must stay collective-free: it reads only the psum'd
    decision stats and the already-uniform selected aggregate."""
    grads = _grads(jax.random.PRNGKey(0))
    comp = _composite("lq_sgd", 1.5, 4, fuse=True, adaptive=4.0, grads=grads)
    rows, _ = _inventory(comp, grads)
    assert [r.kind for r in rows if r.cond is None] == ["psum"]


def test_lazy_mode_validation():
    with pytest.raises(ValueError, match="lazy_mode"):
        _composite("lq_sgd", 1.5, 4, mode="bogus")
    with pytest.raises(ValueError, match="lazy_adaptive"):
        LeafPolicy(method="lq_sgd", lazy_thresh=1.0, lazy_adaptive=0.5)


# --------------------------------------------------------------------------
# semantics: gate == elide, always-firing lazy == eager
# --------------------------------------------------------------------------

@pytest.mark.parametrize("fuse", [False, True])
@pytest.mark.parametrize("method", ["topk", "qsgd", "powersgd", "lq_sgd"])
def test_gate_and_elide_bitwise_identical(method, fuse):
    """Across fire AND skip rounds (identical grads re-fed -> skips after
    round 0) the two dispatch modes agree on every output and state leaf."""
    grads = _grads(jax.random.PRNGKey(1))
    ce = _composite(method, 1.5, 2, fuse=fuse, mode="elide", grads=grads)
    cg = _composite(method, 1.5, 2, fuse=fuse, mode="gate", grads=grads)
    gb = broadcast_state(grads, N)
    out_e, st_e, h_e = _run(ce, gb, steps=5)
    out_g, st_g, h_g = _run(cg, gb, steps=5)
    assert h_e == h_g  # same fire pattern, same effective accounting
    for a, b in zip(jax.tree.leaves(out_e), jax.tree.leaves(out_g)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(st_e), jax.tree.leaves(st_g)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("fuse", [False, True])
@pytest.mark.parametrize("method", ["topk", "qsgd", "powersgd", "lq_sgd"])
def test_always_firing_adaptive_matches_eager(method, fuse):
    """A tiny threshold with the adaptive cap engaged fires every round on
    fresh gradients — through the cond path — and must be bit-for-bit the
    eager (thresh=0) composite."""
    grads0 = _grads(jax.random.PRNGKey(2))
    lazy = _composite(method, 1e-9, 1000, fuse=fuse, adaptive=4.0,
                      grads=grads0)
    eager = _composite(method, 0.0, 4, fuse=fuse, grads=grads0)
    assert lazy.lazy_groups and not eager.lazy_groups
    st_l = st_e = None
    for t in range(3):
        g = broadcast_state(_grads(jax.random.PRNGKey(10 + t)), N)
        out_l, st_l, h_l = _run(lazy, g, state=st_l)
        out_e, st_e, _ = _run(eager, g, state=st_e)
        assert h_l[0][0] > lazy.decision_bits_per_step()  # it fired
        for a, b in zip(jax.tree.leaves(out_l), jax.tree.leaves(out_e)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # shared compressor state also never diverged
    for ns in set(st_e) & {"err", "q"}:
        for k in st_e[ns]:
            np.testing.assert_array_equal(np.asarray(st_e[ns][k]),
                                          np.asarray(st_l[ns][k]))


# --------------------------------------------------------------------------
# adaptive LAQ: unit behaviour + the skip-rate ramp
# --------------------------------------------------------------------------

def test_adaptive_helpers():
    zero = jnp.zeros((2,), jnp.float32)
    # cold state scales by 1.0 (never BELOW 1: adaptive only tightens skips)
    assert float(tau_scale2(zero, 8.0)) == 1.0
    ema = jnp.asarray([1.0, 4.0], jnp.float32)
    assert float(tau_scale2(ema, 8.0)) == pytest.approx(4.0)
    assert float(tau_scale2(ema, 2.0)) == 2.0  # capped
    # first fired round latches the EMA; later rounds smooth; skips freeze
    e1 = ema_update(zero, jnp.float32(10.0), jnp.bool_(True))
    assert e1.tolist() == [10.0, 10.0]
    e2 = ema_update(e1, jnp.float32(0.0), jnp.bool_(True))
    assert e2[0] == pytest.approx(9.0) and e2[1] == 10.0  # beta=0.9, peak holds
    e3 = ema_update(e2, jnp.float32(555.0), jnp.bool_(False))
    np.testing.assert_array_equal(np.asarray(e3), np.asarray(e2))


def test_group_adaptive_cap_is_min_of_engaged_leaves():
    pols = [LeafPolicy(method="lq_sgd", lazy_thresh=1.0, lazy_adaptive=8.0),
            LeafPolicy(method="lq_sgd", lazy_thresh=1.0, lazy_adaptive=2.0),
            LeafPolicy(method="lq_sgd", lazy_thresh=1.0)]
    plans = [dataclasses.replace(dataclasses.replace(p)) for p in pols]

    class _P:  # group_adaptive_cap only reads .policy
        def __init__(self, p):
            self.policy = p

    assert group_adaptive_cap([_P(p) for p in pols], [0, 1]) == 2.0
    assert group_adaptive_cap([_P(p) for p in pols], [2]) == 0.0
    del plans


def test_adaptive_state_namespace_lifecycle():
    grads = _grads(jax.random.PRNGKey(3))
    comp = _composite("lq_sgd", 1e6, 3, fuse=True, adaptive=4.0, grads=grads)
    st0 = comp.init_state(jax.random.PRNGKey(0))
    assert EMA_NS in st0 and st0[EMA_NS]["lq_sgd"].shape == (2,)
    gb = broadcast_state(grads, N)
    _, st1, h = _run(comp, gb, steps=2)
    # round 0 fires (born stale) -> EMA latched; round 1 skips -> frozen
    ema = np.asarray(st1[EMA_NS]["lq_sgd"])[0]
    assert ema[0] > 0 and ema[1] >= ema[0]
    # a fixed-threshold composite builds no EMA state
    fixed = _composite("lq_sgd", 1e6, 3, fuse=True, grads=grads)
    assert EMA_NS not in fixed.init_state(jax.random.PRNGKey(0))


def test_adaptive_skip_rate_ramps_as_run_converges():
    """Shrinking gradients leave the scale-free LAQ criterion's fire rate
    flat under fixed thresholds — the adaptive drift EMA is what converts
    convergence into extra skips, monotonically and within the cap."""
    rounds, window = 60, 20

    def fires(comp):
        st, fired = None, []
        side = comp.decision_bits_per_step()
        for t in range(rounds):
            # fresh directions, geometrically shrinking magnitude: the
            # relative innovation stays >= ~2 every round (always above a
            # fixed tau^2 = 0.3), while the absolute drift decays
            g = jax.tree.map(lambda a, t=t: a * 0.93 ** t,
                             _grads(jax.random.PRNGKey(100 + t)))
            _, st, h = _run(comp, broadcast_state(g, N), state=st)
            fired.append(h[0][0] > side)
        return [sum(fired[i:i + window])
                for i in range(0, rounds, window)]

    adaptive = fires(_composite("lq_sgd", 0.55, 8, fuse=True, adaptive=16.0))
    fixed = fires(_composite("lq_sgd", 0.55, 8, fuse=True))
    # adaptive: fire count per window ramps DOWN as the run converges
    assert adaptive[0] > adaptive[-1], (adaptive, fixed)
    assert sorted(adaptive, reverse=True) == adaptive, adaptive
    # and skips strictly more than the fixed-threshold baseline overall
    assert sum(adaptive) < sum(fixed), (adaptive, fixed)
    # max_stale still bounds staleness: >= 1 fire per (max_stale+1) rounds
    assert adaptive[-1] >= window // 9, adaptive


# --------------------------------------------------------------------------
# launcher-layer guard
# --------------------------------------------------------------------------

def test_assert_replicated():
    assert_replicated([P(), P(None, None)], "ok")
    assert_replicated({"a": P()}, "ok")
    with pytest.raises(AssertionError, match="comp.lazy_stale"):
        assert_replicated([P(), P("model")], "comp.lazy_stale")


# --------------------------------------------------------------------------
# system proof (slow): compiled HLO + predicate uniformity on a 4x2 mesh
# --------------------------------------------------------------------------

_ELISION_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, jax, numpy as np
    from repro.analysis.hlo import parse_module
    from repro.analysis.inventory import hlo_inventory
    from repro.configs.base import ModelConfig, attn
    from repro.core import CompressorConfig
    from repro.data.synthetic import LMDataConfig, lm_batch
    from repro.launch.mesh import make_mesh
    from repro.train.optimizer import sgd
    from repro.train.runtime import (AsyncRunner, RuntimeConfig,
                                     build_sharded_step, sharded_init)
    from repro.train.step import make_model_compressor

    cfg = ModelConfig(name="t", arch_type="dense", source="t", d_model=64,
                      vocab_size=128, pattern=(attn(),), repeats=2,
                      n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
                      dtype="float32")
    mesh = make_mesh((4, 2), ("data", "model"))
    comp = make_model_compressor(
        cfg, CompressorConfig(name="lq_sgd", rank=2, fuse_collectives=True,
                              lazy_thresh=2.0, max_stale=8))
    opt = sgd(0.05)
    data = LMDataConfig(vocab_size=128, seq_len=32, batch=8)
    bf = lambda i: lm_batch(data, i)
    out = {}
    with jax.set_mesh(mesh):
        jstep, st_sh, b_sh, st_abs = build_sharded_step(
            cfg, mesh, comp, opt, sample_batch=bf(0), remat_scan=False)
        state = sharded_init(cfg, jax.random.PRNGKey(0), opt, comp, mesh,
                             st_sh)
        hlo = jstep.lower(state, bf(0)).compile().as_text()

        # the graph linter's inventory: conditional sites with per-branch
        # collective rows, plus every collective's enclosing branch
        rows, conds = hlo_inventory(parse_module(hlo))
        out["n_conditionals"] = len(conds)
        out["branch_collectives"] = [[len(b) for b in c.branches]
                                     for c in conds]
        out["outside_all_reduce"] = sum(
            1 for r in rows if r.kind == "all-reduce" and r.cond is None)

        runner = AsyncRunner(jstep, bf, RuntimeConfig(steps=4, log_every=100,
                                                      verbose=False))
        state = runner.run(state)
        out["step"] = int(jax.device_get(state["step"]))
        # lazy_out (cached aggregate) and lazy_stale (decision-driven
        # counter) must agree across workers — they only advance on the
        # worker-uniform predicate. lazy_ref is per-worker LOCAL state
        # (each worker's own last-fired input; pspec sharded over dp) and
        # is legitimately non-uniform.
        uniform = {}
        for ns in ("lazy_out", "lazy_stale"):
            ok = True
            for k, v in state["comp"][ns].items():
                a = np.asarray(jax.device_get(v))
                ok &= all(np.array_equal(a[0], a[i])
                          for i in range(1, a.shape[0]))
            uniform[ns] = bool(ok)
        out["uniform"] = uniform
    print("RESULT" + json.dumps(out))
""")


@pytest.mark.slow
def test_compiled_elision_and_uniformity_on_mesh():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", _ELISION_SUBPROC],
                         env=env, capture_output=True, text=True,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    payload = [l for l in out.stdout.splitlines() if l.startswith("RESULT")]
    assert payload, out.stdout
    res = json.loads(payload[0][len("RESULT"):])
    # the cond survived compilation (not flattened into a select)
    assert res["n_conditionals"] >= 1, res
    # one branch holds ALL the group's collectives, the other holds none
    for skip_n, fire_n in res["branch_collectives"]:
        lo, hi = sorted((skip_n, fire_n))
        assert lo == 0 and hi >= 1, res["branch_collectives"]
    # the decision all-reduce stays unconditional in the calling computation
    assert res["outside_all_reduce"] >= 1, res
    # 4 async launcher steps; skip state never diverged across workers
    assert res["step"] == 4
    assert all(res["uniform"].values()), res["uniform"]
