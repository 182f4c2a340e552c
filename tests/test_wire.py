"""The wire abstraction (repro.core.wire) and the server topology:

  * ``ServerWire`` at full participation is BIT-FOR-BIT the symmetric
    wire across all four methods, fused and unfused (acceptance bar for
    the refactor — the abstraction costs nothing on the default path);
  * participation-weighted and FedDropoutAvg sparsity aggregation math,
    the per-round participation draw, and the prepare()-before-weights
    charging contract;
  * the server lazy path: per-worker fire/skip with value-space
    substitution — worker-uniform aggregates, per-worker staleness
    counters that reset on CONTRIBUTION, frozen error feedback for
    absent workers, and the 32-bit/group decision sideband accounting;
  * routing/validation plumbing (``make_compressor`` topology checks,
    no ``lazy_out`` cache in server mode);
  * server state stays correctly sharded on a 4x2 mesh after
    launcher-built steps run (subprocess, slow).

Collective semantics via ``jax.vmap(axis_name=...)`` — the same
named-axis code path the production shard_map runs.
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

from repro.core import (AxisComm, CompositeCompressor, CompressorConfig,
                        LeafPolicy, ServerWire, SymmetricWire, as_wire,
                        make_compressor)
from repro.core.comm import CommRecord
from repro.core.lazy import (OUT_NS, REF_NS, SERVER_DECISION_BITS_PER_GROUP,
                             STALE_NS)

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


def _run(comp, grads_fn, steps=1, state=None):
    """Per-step grads via ``grads_fn(t)``; returns
    (last outs, state, [(eff_bits, eff_colls, down_bits)])."""
    if state is None:
        state = broadcast_state(comp.init_state(jax.random.PRNGKey(42)), N)

    def worker(g, st):
        out, st2, rec = comp.sync(g, st, AxisComm(("data",)))
        return (out, st2,
                jnp.asarray(rec.effective_bits(), jnp.float32),
                jnp.asarray(rec.effective_collectives(), jnp.float32),
                jnp.asarray(rec.down_bits, jnp.float32))

    wf = jax.jit(jax.vmap(worker, axis_name="data"))
    out, hist = None, []
    for t in range(steps):
        out, state, eb, ec, db = wf(grads_fn(t), state)
        hist.append((float(eb[0]), float(ec[0]), float(db[0])))
    return out, state, hist


def _expected_flags(seed, step, n, p):
    """Replicates ServerWire.active() outside the trace: fold step then
    the worker's axis index into the seed key."""
    base = jax.random.fold_in(jax.random.PRNGKey(seed),
                              jnp.asarray(step, jnp.int32))
    return np.array([bool(jax.random.bernoulli(
        jax.random.fold_in(base, i), p)) for i in range(n)])


# --------------------------------------------------------------------------
# acceptance bar: full participation == symmetric, bit for bit
# --------------------------------------------------------------------------

@pytest.mark.parametrize("fuse", [False, True])
@pytest.mark.parametrize("name", ["topk", "qsgd", "powersgd", "lq_sgd"])
def test_server_full_participation_bit_for_bit(name, fuse):
    grads = _grads(jax.random.PRNGKey(0))
    kw = dict(rank=2, bits=8, topk_ratio=0.1, fuse_collectives=fuse)
    sym = make_compressor(CompressorConfig(name=name, **kw),
                          _abstract(grads), STACKED)
    srv = make_compressor(CompressorConfig(name=name, topology="server", **kw),
                          _abstract(grads), STACKED)
    out_s, st_s, hist_s = _run(sym, lambda t: grads, steps=3)
    out_v, st_v, hist_v = _run(srv, lambda t: grads, steps=3)
    for a, b in zip(jax.tree.leaves((out_s, st_s)),
                    jax.tree.leaves((out_v, st_v))):
        assert np.array_equal(np.asarray(a), np.asarray(b)), name
    # uplink identical; the server round additionally books the broadcast
    assert [h[0] for h in hist_s] == [h[0] for h in hist_v]
    assert all(h[2] == 0 for h in hist_s)
    assert all(h[2] > 0 for h in hist_v)


def test_server_lazy_always_fire_matches_eager_composite():
    """With a vanishing threshold every worker contributes every round, so
    the value-space substitution path must reduce to the eager composite
    (up to the 32-bit decision sideband in the accounting)."""
    cfg = CompressorConfig(name="lq_sgd", rank=2)
    pols = [LeafPolicy(method="lq_sgd", rank=2, lazy_thresh=1e-12,
                       max_stale=1000)] * 3
    abstract = _abstract(_grads(jax.random.PRNGKey(1)))
    eager = CompositeCompressor(cfg, abstract, STACKED,
                                policies=[LeafPolicy(method="lq_sgd",
                                                     rank=2)] * 3)
    import dataclasses
    srv = CompositeCompressor(dataclasses.replace(cfg, topology="server"),
                              abstract, STACKED, policies=pols)
    gf = lambda t: _grads(jax.random.PRNGKey(100 + t))
    out_e, _, hist_e = _run(eager, gf, steps=3)
    out_v, _, hist_v = _run(srv, gf, steps=3)
    for a, b in zip(jax.tree.leaves(out_e), jax.tree.leaves(out_v)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    side = SERVER_DECISION_BITS_PER_GROUP
    assert [h[0] for h in hist_v] == [h[0] + side for h in hist_e]


# --------------------------------------------------------------------------
# aggregation math + participation draw
# --------------------------------------------------------------------------

def test_participation_weighted_average_and_pmean():
    n, p, seed, step = N, 0.6, 3, 7
    x = np.arange(1.0, n + 1, dtype=np.float32)
    flags = _expected_flags(seed, step, n, p)
    assert 0 < flags.sum() < n  # seed chosen so both cases appear

    def worker(xi):
        rec = CommRecord()
        w = ServerWire(("data",), participation=p, seed=seed, step=step)
        w.prepare(rec)
        return (w.average(w.all_gather(xi)), w.pmean(xi), w.active(),
                jnp.asarray(rec.bits_sent, jnp.float32))

    avg, pm, act, bits = jax.vmap(worker, axis_name="data")(jnp.asarray(x))
    np.testing.assert_array_equal(np.asarray(act), flags)
    want = (x * flags).sum() / max(flags.sum(), 1.0)
    np.testing.assert_allclose(np.asarray(avg), want, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(pm), want, rtol=1e-6)
    assert np.all(np.asarray(bits) == 32)  # the flag sideband, charged once


def test_sparsity_agg_counts_nonzero_contributions():
    """FedDropoutAvg weighting: each element divides by its own nonzero
    count, so sparse (TopK) uploads don't dilute each other."""
    w = ServerWire(("data",), participation=1.0, agg="sparsity")
    stacked = jnp.asarray([[1.0, 0.0, 0.0], [3.0, 4.0, 0.0]])
    np.testing.assert_allclose(np.asarray(w.average(stacked)),
                               [2.0, 4.0, 0.0])
    # dense input degrades to the plain mean
    dense = jnp.asarray([[1.0, 2.0], [3.0, 6.0]])
    np.testing.assert_allclose(np.asarray(w.average(dense)), [2.0, 4.0])


def test_weights_require_prepare():
    w = ServerWire(("data",), participation=0.5)
    with pytest.raises(RuntimeError, match="prepare"):
        w.weights()
    # full participation needs no sideband: weights is a None fast path
    assert ServerWire(("data",), participation=1.0).weights() is None


def test_wire_validation_and_routing():
    with pytest.raises(ValueError, match="participation"):
        ServerWire(("data",), participation=0.0)
    with pytest.raises(ValueError, match="participation"):
        ServerWire(("data",), participation=1.5)
    with pytest.raises(ValueError, match="agg"):
        ServerWire(("data",), agg="mean")
    with pytest.raises(ValueError, match="topology"):
        as_wire(AxisComm(("data",)), topology="ring")
    # an existing wire passes through unchanged (no double-wrap)
    w = SymmetricWire(("data",))
    assert as_wire(w, topology="server") is w
    with pytest.raises(ValueError, match="topology"):
        make_compressor(CompressorConfig(name="qsgd", topology="ring"),
                        _abstract(_grads(jax.random.PRNGKey(2))), STACKED)
    # drop-out needs the composite (step counter + per-worker freezing)
    comp = make_compressor(
        CompressorConfig(name="qsgd", topology="server", participation=0.5),
        _abstract(_grads(jax.random.PRNGKey(2))), STACKED)
    assert isinstance(comp, CompositeCompressor)


# --------------------------------------------------------------------------
# server lazy path: per-worker staleness + frozen state
# --------------------------------------------------------------------------

def _server_lazy_comp(participation, thresh=1e-12, max_stale=1000, seed=0):
    cfg = CompressorConfig(name="lq_sgd", rank=2, topology="server",
                           participation=participation,
                           participation_seed=seed)
    pols = [LeafPolicy(method="lq_sgd", rank=2, lazy_thresh=thresh,
                       max_stale=max_stale)] * 3
    abstract = _abstract(_grads(jax.random.PRNGKey(3)))
    return CompositeCompressor(cfg, abstract, STACKED, policies=pols)


def test_per_worker_staleness_tracks_participation():
    p, seed, steps = 0.5, 0, 4
    comp = _server_lazy_comp(p, seed=seed)
    # fire always votes yes (tiny thresh, huge cap): contrib == active,
    # so the counter is exactly "rounds since last participation"
    gf = lambda t: _grads(jax.random.PRNGKey(200 + t))
    out, st, _ = _run(comp, gf, steps=steps)
    stale = np.full(N, 1000.0)
    for t in range(steps):
        flags = _expected_flags(seed, t, N, p)
        stale = np.where(flags, 0.0, stale + 1)
    np.testing.assert_array_equal(
        np.asarray(st[STALE_NS]["lq_sgd"]).reshape(-1), stale)
    # the aggregate every worker applies is identical (server broadcast)
    for leaf in jax.tree.leaves(out):
        for i in range(1, N):
            np.testing.assert_array_equal(np.asarray(leaf[0]),
                                          np.asarray(leaf[i]))


def test_dropout_freezes_absent_workers_error_feedback():
    p, seed = 0.5, 0
    flags = _expected_flags(seed, 0, N, p)
    assert 0 < flags.sum() < N
    comp = _server_lazy_comp(p, seed=seed)
    _, st, _ = _run(comp, lambda t: _grads(jax.random.PRNGKey(300)), steps=1)
    for k, e in st["err"].items():
        e = np.asarray(e)
        moved = np.array([np.any(e[i] != 0) for i in range(N)])
        # absent workers' err stays at init (zero); contributors bank the
        # quantization residual, which is nonzero for these shapes
        np.testing.assert_array_equal(moved, flags), k


def test_server_decision_sideband_accounting():
    """Never-voting threshold + staleness cap: the fire pattern is the
    symmetric one, but the sideband is one 32-bit flag gather per group
    and a skipped round still runs every payload collective."""
    comp = _server_lazy_comp(1.0, thresh=1e6, max_stale=3)
    assert comp.decision_bits_per_step() == SERVER_DECISION_BITS_PER_GROUP
    gf = lambda t: _grads(jax.random.PRNGKey(400))
    _, _, hist = _run(comp, gf, steps=5)
    fired = comp.wire_bits_per_step()
    side = SERVER_DECISION_BITS_PER_GROUP
    assert [b for b, _, _ in hist] == [fired, side, side, side, fired]
    # collective COUNT does not drop on skips — elision is value-space
    assert len({c for _, c, _ in hist}) == 1
    # drop-out scales the expected payload figure down
    half = _server_lazy_comp(0.5)
    assert half.expected_wire_bits_per_step() < half.wire_bits_per_step()


def test_server_init_state_has_no_aggregate_cache():
    comp = _server_lazy_comp(0.5)
    st = comp.init_state(jax.random.PRNGKey(0))
    assert OUT_NS not in st  # no shared cache: substitution is per worker
    assert REF_NS in st and STALE_NS in st


# --------------------------------------------------------------------------
# satellite: server state stays sharded on a 4x2 mesh (slow)
# --------------------------------------------------------------------------

_SERVER_SHARDING_SUBPROC = textwrap.dedent("""
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
                              max_stale=4, topology="server",
                              participation=0.5))
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
        out["has_out_ns"] = "lazy_out" in state["comp"]
        out["lazy_ref"] = sorted(
            axes(v.sharding.spec) for v in state["comp"]["lazy_ref"].values())
        out["stale"] = sorted(
            axes(v.sharding.spec) for v in state["comp"]["lazy_stale"].values())
    print("RESULT" + json.dumps(out))
""")


@pytest.mark.slow
def test_server_state_stays_sharded_after_launcher_steps():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", _SERVER_SHARDING_SUBPROC],
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    payload = [l for l in out.stdout.splitlines() if l.startswith("RESULT")]
    assert payload, out.stdout
    res = json.loads(payload[0][len("RESULT"):])
    assert res["step"] == 3
    assert not res["has_out_ns"]  # server mode keeps no aggregate cache
    specs = res["lazy_ref"]
    # reference grads lead with the per-worker DP dim and at least one
    # (embed/head-sized) leaf shards its inner dims over the model axis
    assert specs and all(s[0] == ["data"] for s in specs), specs
    assert any("model" in e for s in specs for e in s), specs
    # per-worker staleness counters: DP dim only, replicated over model
    assert all("model" not in e for s in res["stale"] for e in s), res["stale"]
