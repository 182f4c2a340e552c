"""Model-stack tests: SSD oracle, MoE invariants, prefill/decode equivalence
across every layer family, multimodal paths, ResNet-18."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # clean env: seeded-sweep fallback, see the shim
    from _hypothesis_compat import given, settings, strategies as st

from repro.configs.base import ModelConfig, attn, mamba
from repro.models.model import (forward, init_caches, init_params,
                                stacked_flags)
from repro.models.moe import moe_capacity, moe_forward, init_moe
from repro.models.common import KeyGen
from repro.models.resnet import init_resnet18, resnet18_forward, resnet18_param_count
from repro.models.ssm import ssd_chunked, ssd_naive


# ------------------------------------------------------------------ SSD
class TestSSD:
    @pytest.mark.parametrize("chunk", [1, 4, 16, 37, 64])
    def test_chunked_matches_naive(self, chunk):
        b, s, h, p, n = 2, 37, 3, 8, 16
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        x = jax.random.normal(ks[0], (b, s, h, p))
        a = -jnp.abs(jax.random.normal(ks[1], (b, s, h))) * 0.1
        bm = jax.random.normal(ks[2], (b, s, h, n))
        cm = jax.random.normal(ks[3], (b, s, h, n))
        y0, h0 = ssd_naive(x, a, bm, cm)
        y1, h1 = ssd_chunked(x, a, bm, cm, chunk)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), atol=3e-5)
        np.testing.assert_allclose(np.asarray(h1), np.asarray(h0), atol=3e-5)

    def test_initial_state(self):
        b, s, h, p, n = 1, 16, 2, 4, 8
        ks = jax.random.split(jax.random.PRNGKey(1), 5)
        x = jax.random.normal(ks[0], (b, s, h, p))
        a = -jnp.abs(jax.random.normal(ks[1], (b, s, h))) * 0.2
        bm = jax.random.normal(ks[2], (b, s, h, n))
        cm = jax.random.normal(ks[3], (b, s, h, n))
        h0 = jax.random.normal(ks[4], (b, h, p, n))
        y_ref, hT_ref = ssd_naive(x, a, bm, cm, h0)
        y, hT = ssd_chunked(x, a, bm, cm, 8, h0)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=3e-5)
        np.testing.assert_allclose(np.asarray(hT), np.asarray(hT_ref), atol=3e-5)

    @settings(max_examples=20, deadline=None)
    @given(s=st.integers(1, 48), chunk=st.integers(1, 32), seed=st.integers(0, 99))
    def test_property_chunk_invariance(self, s, chunk, seed):
        b, h, p, n = 1, 2, 4, 4
        ks = jax.random.split(jax.random.PRNGKey(seed), 4)
        x = jax.random.normal(ks[0], (b, s, h, p))
        a = -jnp.abs(jax.random.normal(ks[1], (b, s, h))) * 0.3
        bm = jax.random.normal(ks[2], (b, s, h, n))
        cm = jax.random.normal(ks[3], (b, s, h, n))
        y0, _ = ssd_naive(x, a, bm, cm)
        y1, _ = ssd_chunked(x, a, bm, cm, chunk)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), atol=5e-5)

    @pytest.mark.parametrize("chunk", [6, 5])
    @pytest.mark.parametrize("with_h0", [False, True])
    @pytest.mark.parametrize("groups", [1, 2, 4])
    def test_grouped_bc_matches_per_head(self, groups, with_h0, chunk):
        """B and C per group, (B,S,G,N): y, the final state and the
        gradients of x, a, B and C equal the same call with B and C
        repeated to heads, and the oracle on the repeated inputs."""
        b, s, h, p, n = 2, 18, 4, 8, 16
        ks = jax.random.split(jax.random.PRNGKey(groups), 7)
        x = jax.random.normal(ks[0], (b, s, h, p))
        a = -jnp.abs(jax.random.normal(ks[1], (b, s, h))) * 0.2
        bm = jax.random.normal(ks[2], (b, s, groups, n))
        cm = jax.random.normal(ks[3], (b, s, groups, n))
        h0 = jax.random.normal(ks[4], (b, h, p, n)) if with_h0 else None
        gy = jax.random.normal(ks[5], (b, s, h, p))
        gh = jax.random.normal(ks[6], (b, h, p, n))

        def rep(t):
            return jnp.repeat(t, h // groups, axis=2)

        def outputs_and_grads(ssd):
            def f(*args):
                y, hT = ssd(*args)
                return jnp.vdot(y, gy) + jnp.vdot(hT, gh), (y, hT)

            grad = jax.grad(f, argnums=(0, 1, 2, 3), has_aux=True)
            return jax.jit(grad)(x, a, bm, cm)

        got = outputs_and_grads(
            lambda x, a, bm, cm: ssd_chunked(x, a, bm, cm, chunk, h0))
        per_head = outputs_and_grads(
            lambda x, a, bm, cm: ssd_chunked(x, a, rep(bm), rep(cm), chunk, h0))
        oracle = outputs_and_grads(
            lambda x, a, bm, cm: ssd_naive(x, a, rep(bm), rep(cm), h0))
        for want in (per_head, oracle):
            jax.tree.map(
                lambda u, v: np.testing.assert_allclose(
                    np.asarray(u), np.asarray(v), rtol=1e-5, atol=1e-4),
                got, want)

    @pytest.mark.parametrize("groups", [1, 2, 8])
    def test_grouped_decode_matches_prefill(self, groups):
        """The decode step with grouped B and C continues a prefill as the
        full sequence's last position does."""
        cfg = ModelConfig(name="ssm-groups", arch_type="ssm", source="t",
                          d_model=64, vocab_size=96, dtype="float32",
                          pattern=(mamba(),), repeats=2, d_ff=0, ssm_state=16,
                          ssm_head_dim=16, ssm_chunk=8, ssm_groups=groups)
        assert cfg.ssm_heads == 8
        _pd_check(cfg)


# ------------------------------------------------------------------ MoE
class TestMoE:
    def _cfg(self, **kw):
        base = dict(name="moe", arch_type="moe", source="t", d_model=32,
                    vocab_size=64, n_experts=4, experts_per_token=2,
                    d_ff_expert=16, dtype="float32")
        base.update(kw)
        return ModelConfig(**base)

    def test_capacity_alignment(self):
        cfg = self._cfg()
        assert moe_capacity(64, cfg) % 8 == 0
        assert moe_capacity(1, cfg) >= 8

    def test_high_capacity_no_drop_equals_dense_mixture(self):
        """With capacity >> tokens, MoE output equals the explicit per-token
        weighted sum of its experts (dense oracle)."""
        cfg = self._cfg(capacity_factor=16.0)
        p = init_moe(KeyGen(jax.random.PRNGKey(0)), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 32))
        y, aux = moe_forward(p, x, cfg)
        # dense oracle
        xf = x.reshape(-1, 32)
        logits = xf @ p["router"]
        probs = jax.nn.softmax(logits, -1)
        top_p, top_i = jax.lax.top_k(probs, 2)
        w = top_p / top_p.sum(-1, keepdims=True)
        outs = []
        for e in range(4):
            g = jax.nn.silu(xf @ p["w_gate"][e]) * (xf @ p["w_up"][e])
            outs.append(g @ p["w_down"][e])
        dense = jnp.stack(outs, 1)  # (T, E, D)
        want = jnp.einsum("tk,tkd->td", w,
                          jnp.take_along_axis(dense, top_i[..., None], axis=1))
        np.testing.assert_allclose(np.asarray(y.reshape(-1, 32)),
                                   np.asarray(want), atol=1e-4)

    def test_aux_loss_uniform_router_is_one(self):
        """Perfectly uniform routing gives aux approx 1 (Switch normalization)."""
        cfg = self._cfg(capacity_factor=8.0)
        p = init_moe(KeyGen(jax.random.PRNGKey(0)), cfg)
        p["router"] = jnp.zeros_like(p["router"])  # uniform probs
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32))
        _, aux = moe_forward(p, x, cfg)
        assert abs(float(aux) - 1.0) < 0.3


# --------------------------------------------------- prefill/decode equiv
def _pd_check(cfg, seq=16, atol=5e-5):
    tok_shape = (2, seq, cfg.n_codebooks) if cfg.n_codebooks else (2, seq)
    tok = jax.random.randint(jax.random.PRNGKey(1), tok_shape, 0, cfg.vocab_size)
    p = init_params(cfg, jax.random.PRNGKey(2))
    caches = init_caches(cfg, 2, seq * 2, jnp.float32)
    lp, c2, _ = forward(p, tok, cfg, caches=caches)
    lt, _, _ = forward(p, tok, cfg)
    np.testing.assert_allclose(np.asarray(lp), np.asarray(lt), atol=atol)
    if cfg.n_codebooks:
        nxt = jnp.argmax(lp[:, -1:], -1).astype(jnp.int32)  # (B,1,n_cb)
    else:
        nxt = jnp.argmax(lp[:, -1:], -1).astype(jnp.int32)
    ld, _, _ = forward(p, nxt, cfg, caches=c2, cache_index=jnp.int32(seq))
    lf, _, _ = forward(p, jnp.concatenate([tok, nxt], 1), cfg)
    np.testing.assert_allclose(np.asarray(ld[:, 0]), np.asarray(lf[:, -1]),
                               atol=atol)


FAMILIES = {
    "dense-gqa": dict(arch_type="dense", pattern=(attn(),), repeats=3,
                      n_heads=4, n_kv_heads=2, head_dim=16, d_ff=64),
    "dense-mqa-bias": dict(arch_type="dense", pattern=(attn(),), repeats=2,
                           n_heads=4, n_kv_heads=1, head_dim=16, d_ff=64,
                           qkv_bias=True),
    "dense-qknorm": dict(arch_type="vlm", pattern=(attn(),), repeats=2,
                         n_heads=4, n_kv_heads=2, head_dim=16, d_ff=64,
                         qk_norm=True),
    "swa-localglobal": dict(arch_type="dense",
                            pattern=(attn(window=8), attn(window=8), attn()),
                            repeats=2, n_heads=4, n_kv_heads=1, head_dim=16,
                            d_ff=64),
    "ssm": dict(arch_type="ssm", pattern=(mamba(),), repeats=3, d_ff=0,
                ssm_state=16, ssm_head_dim=16, ssm_chunk=8),
    "moe": dict(arch_type="moe", pattern=(attn(moe=True),), repeats=2,
                n_heads=4, n_kv_heads=2, head_dim=16, d_ff=64, n_experts=4,
                experts_per_token=2, d_ff_expert=32, capacity_factor=16.0),
    "hybrid": dict(arch_type="hybrid",
                   pattern=(mamba(), mamba(moe=True), attn(), mamba(moe=True)),
                   repeats=2, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=64,
                   n_experts=4, experts_per_token=2, d_ff_expert=32,
                   capacity_factor=16.0, ssm_state=16, ssm_head_dim=16,
                   ssm_chunk=8),
    "mla": dict(arch_type="moe", pattern=(attn(moe=True),), repeats=2,
                lead=(attn(),), n_heads=4, use_mla=True, q_lora_rank=32,
                kv_lora_rank=16, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
                d_ff=64, n_experts=4, experts_per_token=2, d_ff_expert=32,
                n_shared_experts=1, capacity_factor=16.0),
    "audio-codebooks": dict(arch_type="audio", pattern=(attn(),), repeats=2,
                            n_heads=4, n_kv_heads=4, head_dim=16, d_ff=64,
                            n_codebooks=4),
}


@pytest.mark.parametrize("fam", sorted(FAMILIES))
def test_prefill_decode_equivalence(fam):
    kw = dict(name=fam, source="t", d_model=64, vocab_size=96, dtype="float32")
    kw.update(FAMILIES[fam])
    _pd_check(ModelConfig(**kw))


def test_tail_and_lead_layers():
    cfg = ModelConfig(name="glt", arch_type="dense", source="t", d_model=64,
                      vocab_size=96, pattern=(attn(window=8),), repeats=2,
                      lead=(attn(),), tail=(attn(window=8), attn(window=8)),
                      n_heads=4, n_kv_heads=1, head_dim=16, d_ff=64,
                      dtype="float32")
    assert cfg.n_layers == 5
    _pd_check(cfg)


def test_stacked_flags_match_structure():
    cfg = ModelConfig(name="sf", arch_type="dense", source="t", d_model=32,
                      vocab_size=64, pattern=(attn(),), repeats=2, n_heads=2,
                      n_kv_heads=2, head_dim=16, d_ff=32, dtype="float32")
    p = init_params(cfg, jax.random.PRNGKey(0))
    f = stacked_flags(p)
    assert jax.tree_util.tree_structure(p) == jax.tree_util.tree_structure(f)
    assert all(jax.tree.leaves(f["scan"]))
    assert not any(jax.tree.leaves({"e": f["embed"], "n": f["final_norm"]}))
    # stacked leaves really have leading dim == repeats
    for leaf in jax.tree.leaves(p["scan"]):
        assert leaf.shape[0] == 2


def test_mtp_head_train_only():
    cfg = ModelConfig(name="mtp", arch_type="dense", source="t", d_model=32,
                      vocab_size=64, pattern=(attn(),), repeats=2, n_heads=2,
                      n_kv_heads=2, head_dim=16, d_ff=32, mtp=True,
                      dtype="float32")
    p = init_params(cfg, jax.random.PRNGKey(0))
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 64)
    _, _, aux = forward(p, tok, cfg)
    assert "mtp_logits" in aux and aux["mtp_logits"].shape == (2, 8, 64)
    caches = init_caches(cfg, 2, 16, jnp.float32)
    _, _, aux_p = forward(p, tok, cfg, caches=caches)
    assert "mtp_logits" not in aux_p


def test_conditioning_prefix():
    cfg = ModelConfig(name="cond", arch_type="audio", source="t", d_model=32,
                      vocab_size=64, pattern=(attn(),), repeats=2, n_heads=2,
                      n_kv_heads=2, head_dim=16, d_ff=32, n_codebooks=2,
                      cond_len=4, dtype="float32")
    p = init_params(cfg, jax.random.PRNGKey(0))
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 8, 2), 0, 64)
    cond = jax.random.normal(jax.random.PRNGKey(2), (2, 4, 32)) * 0.02
    logits, _, _ = forward(p, tok, cfg, cond=cond)
    assert logits.shape == (2, 8, 2, 64)  # prefix stripped
    l2, _, _ = forward(p, tok, cfg)       # without cond: different result
    assert float(jnp.max(jnp.abs(logits - l2))) > 1e-6


def test_no_nans_bf16():
    cfg = ModelConfig(name="bf", arch_type="dense", source="t", d_model=64,
                      vocab_size=96, pattern=(attn(),), repeats=2, n_heads=4,
                      n_kv_heads=2, head_dim=16, d_ff=64, dtype="bfloat16")
    p = init_params(cfg, jax.random.PRNGKey(0))
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 96)
    logits, _, _ = forward(p, tok, cfg)
    assert logits.dtype == jnp.bfloat16
    assert not bool(jnp.any(jnp.isnan(logits.astype(jnp.float32))))


# ------------------------------------------------------------------ resnet
def test_resnet18():
    p = init_resnet18(jax.random.PRNGKey(0))
    # the canonical ResNet-18 parameter count (CIFAR stem)
    assert abs(resnet18_param_count(p) - 11_173_962) < 20_000
    out = resnet18_forward(p, jnp.ones((2, 32, 32, 3)))
    assert out.shape == (2, 10)
    assert not bool(jnp.any(jnp.isnan(out)))


def test_resnet18_grads_flow():
    p = init_resnet18(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, 32, 3))
    y = jnp.array([0, 1, 2, 3])

    def loss(p):
        logits = resnet18_forward(p, x)
        return jnp.mean(-jax.nn.log_softmax(logits)[jnp.arange(4), y])

    g = jax.grad(loss)(p)
    norms = [float(jnp.linalg.norm(l)) for l in jax.tree.leaves(g)]
    assert all(np.isfinite(norms))
    assert max(norms) > 0
