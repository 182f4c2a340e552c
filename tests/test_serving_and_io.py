"""Serving engine + checkpoint + data pipeline tests."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.io import restore, save
from repro.configs import get_config
from repro.configs.base import ModelConfig, attn
from repro.data.synthetic import (ImageDataConfig, LMDataConfig,
                                  class_templates, image_batch, lm_batch)
from repro.models.model import forward, init_caches, init_params
from repro.serving.engine import (build_decode_step, build_prefill_step,
                                  greedy_sample, temperature_sample)


# ------------------------------------------------------------------ serving
def test_prefill_then_decode_matches_full_forward():
    cfg = get_config("granite-20b", smoke=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab_size)
    prefill = jax.jit(build_prefill_step(cfg, 24, cache_dtype=jnp.float32))
    decode = jax.jit(build_decode_step(cfg))
    logits, caches = prefill(params, tok)
    nxt = greedy_sample(logits)
    seq = [nxt]
    for i in range(4):
        logits, caches = decode(params, caches, seq[-1], jnp.int32(12 + i))
        seq.append(greedy_sample(logits))
    # oracle: full forward over the generated prefix (greedy => deterministic)
    full = jnp.concatenate([tok] + seq[:-1], axis=1)
    ref_logits, _, _ = forward(params, full, cfg)
    np.testing.assert_array_equal(np.asarray(greedy_sample(ref_logits[:, -1:])),
                                  np.asarray(seq[-1]))


def test_decode_respects_sliding_window():
    """A windowed layer must ignore keys beyond the window during decode."""
    cfg = ModelConfig(name="w", arch_type="dense", source="t", d_model=64,
                      vocab_size=64, pattern=(attn(window=4),), repeats=2,
                      n_heads=4, n_kv_heads=2, head_dim=16, d_ff=64,
                      dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    tok = jax.random.randint(jax.random.PRNGKey(1), (1, 10), 0, 64)
    caches = init_caches(cfg, 1, 16, jnp.float32)
    _, caches, _ = forward(params, tok, cfg, caches=caches)
    # corrupt cache entries OUTSIDE the window of position 10 (j <= 6)
    def poison(c):
        return c.at[:, :, :5, :].set(999.0) if c.ndim == 4 else c
    caches_p = jax.tree.map(lambda x: poison(x) if x.ndim >= 4 else x, caches)
    nxt = jnp.zeros((1, 1), jnp.int32)
    a, _, _ = forward(params, nxt, cfg, caches=caches, cache_index=jnp.int32(10))
    b, _, _ = forward(params, nxt, cfg, caches=caches_p, cache_index=jnp.int32(10))
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_sampling():
    logits = jnp.array([[[0.0, 10.0, 0.0]]])
    assert int(greedy_sample(logits)[0, 0]) == 1
    s = temperature_sample(jax.random.PRNGKey(0), logits, 1.0)
    assert s.shape == (1, 1)
    assert int(temperature_sample(jax.random.PRNGKey(0), logits, 0.0)[0, 0]) == 1


# --------------------------------------------------------------- checkpoint
def test_checkpoint_roundtrip_mixed_dtypes():
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "nest": {"b": jnp.ones((4,), jnp.bfloat16),
                     "c": jnp.array(3, jnp.int32)}}
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "x.ckpt")
        nbytes = save(path, tree)
        assert nbytes > 0
        like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
        back = restore(path, like)
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))


def test_checkpoint_shape_mismatch_raises():
    tree = {"a": jnp.ones((2, 2))}
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "x.ckpt")
        save(path, tree)
        bad = {"a": jax.ShapeDtypeStruct((3, 2), jnp.float32)}
        with pytest.raises(ValueError):
            restore(path, bad)
        with pytest.raises(KeyError):
            restore(path, {"zzz": jax.ShapeDtypeStruct((2, 2), jnp.float32)})


# --------------------------------------------------------------------- data
def test_lm_batch_deterministic_and_learnable():
    cfg = LMDataConfig(vocab_size=64, seq_len=32, batch=4, period=8)
    b1, b2 = lm_batch(cfg, 5), lm_batch(cfg, 5)
    np.testing.assert_array_equal(np.asarray(b1["tokens"]), np.asarray(b2["tokens"]))
    assert not np.array_equal(np.asarray(lm_batch(cfg, 6)["tokens"]),
                              np.asarray(b1["tokens"]))
    # periodic structure: most positions repeat at lag `period`
    t = np.asarray(b1["tokens"])
    agree = np.mean(t[:, 8:] == t[:, :-8])
    assert agree > 0.6


def test_image_batch_class_structure():
    cfg = ImageDataConfig(batch=64, hw=8, noise=0.1)
    b = image_batch(cfg, 0)
    assert b["images"].shape == (64, 8, 8, 3)
    tmpl = class_templates(cfg)
    # each image is closer to its own class template than to others (mostly)
    diff = (b["images"][:, None] - tmpl[None]) ** 2
    d = jnp.sum(diff, axis=(2, 3, 4))
    pred = jnp.argmin(d, axis=1)
    assert float(jnp.mean(pred == b["labels"])) > 0.9


def test_codebook_batch():
    cfg = LMDataConfig(vocab_size=32, seq_len=16, batch=2, n_codebooks=4)
    b = lm_batch(cfg, 0)
    assert b["tokens"].shape == (2, 16, 4)


# ------------------------------------------------------- quantized KV cache
@pytest.mark.parametrize("bits", [4, 8])
@pytest.mark.parametrize("shape", [(2, 2, 16, 8),     # (B, Hkv, S, hd)
                                   (3, 2, 2, 16, 8),  # stacked scan leaf
                                   (2, 1, 11, 7)])    # odd S and odd d
def test_quantize_kv_roundtrip(bits, shape):
    from repro.serving.kv_cache import dequantize_kv, quantize_kv, row_bytes

    x = jax.random.normal(jax.random.PRNGKey(0), shape, jnp.float32)
    q = quantize_kv(x, bits)
    assert q.codes.dtype == jnp.int8
    assert q.codes.shape == shape[:-1] + (row_bytes(shape[-1], bits),)
    assert q.scale.shape == shape[:-1] + (1,)
    y = np.asarray(dequantize_kv(q))
    # log-quant per-value error bound: levels grow with bits
    tol = 0.16 if bits == 4 else 0.012
    scale = np.asarray(q.scale)
    np.testing.assert_allclose(y, np.asarray(x), atol=tol * scale.max())


@pytest.mark.parametrize("bits", [4, 8])
@pytest.mark.parametrize("shape", [(2, 2, 16, 8), (2, 1, 11, 7)])
def test_quantize_kv_backends_byte_identical(bits, shape):
    """Pallas (interpret on the CPU) and jnp_ref must produce the same BYTES,
    so accounting and parity transfer to the TPU path unchanged."""
    from repro.serving.kv_cache import dequantize_kv, quantize_kv

    x = jax.random.normal(jax.random.PRNGKey(1), shape, jnp.float32)
    qj = quantize_kv(x, bits, backend="jnp_ref")
    qp = quantize_kv(x, bits, backend="pallas")
    np.testing.assert_array_equal(np.asarray(qj.codes), np.asarray(qp.codes))
    np.testing.assert_array_equal(np.asarray(qj.scale), np.asarray(qp.scale))
    # dequant-on-read: the Pallas row kernel equals the jnp reference
    np.testing.assert_allclose(np.asarray(dequantize_kv(qp)),
                               np.asarray(dequantize_kv(qj)),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("bits", [4, 8])
def test_cache_bytes_match_wire_accounting(bits):
    from repro.serving.engine import init_serving_caches
    from repro.serving.kv_cache import (CacheQuantConfig,
                                        cache_bytes_per_token,
                                        cache_bytes_per_token_accounting)

    cfg = get_config("gemma3-1b", smoke=True)
    caches = init_serving_caches(cfg, 2, 32, jnp.bfloat16,
                                 CacheQuantConfig(bits=bits))
    measured = cache_bytes_per_token(caches, 2, 32)
    accounted = cache_bytes_per_token_accounting(caches, 2, 32)
    assert measured == pytest.approx(accounted, rel=1e-9)


def test_prefill_decode_quantized_vs_bf16():
    """Single-step decode logits from a quantized cache stay within the
    documented tolerance band of the bf16 cache (q8 tight, q4 loose —
    mirrored in benchmarks/serve_throughput.py PARITY_REL)."""
    from repro.serving.engine import init_serving_caches  # noqa: F401
    from repro.serving.kv_cache import CacheQuantConfig

    cfg = get_config("gemma3-1b", smoke=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                             cfg.vocab_size)
    decode = jax.jit(build_decode_step(cfg))
    steps = {}
    for name, qcfg in [("bf16", None),
                       ("q8", CacheQuantConfig(bits=8)),
                       ("q4", CacheQuantConfig(bits=4))]:
        prefill = jax.jit(build_prefill_step(cfg, 24,
                                             cache_dtype=jnp.bfloat16,
                                             qcfg=qcfg))
        logits, caches = prefill(params, tok)
        lg, _ = decode(params, caches, greedy_sample(logits), jnp.int32(12))
        steps[name] = np.asarray(lg[:, -1, :], np.float32)
    ref = np.max(np.abs(steps["bf16"]))
    assert np.max(np.abs(steps["q8"] - steps["bf16"])) / ref <= 0.05
    assert np.max(np.abs(steps["q4"] - steps["bf16"])) / ref <= 0.75


def test_generate_fn_matches_host_loop():
    """The on-device lax.scan driver must reproduce the per-token host
    loop token-for-token under greedy sampling."""
    from repro.serving.engine import build_generate_fn

    cfg = get_config("gemma3-1b", smoke=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab_size)
    prefill = jax.jit(build_prefill_step(cfg, 24, cache_dtype=jnp.bfloat16))
    decode = jax.jit(build_decode_step(cfg))
    logits, caches = prefill(params, tok)
    first = greedy_sample(logits)

    host_caches, host = caches, [first]
    for i in range(6):
        lg, host_caches = decode(params, host_caches, host[-1],
                                 jnp.int32(8 + i))
        host.append(greedy_sample(lg))
    host_toks = np.asarray(jnp.concatenate(host[1:], axis=1))

    generate = jax.jit(build_generate_fn(cfg), static_argnums=5)
    _, _, _, sampled = generate(params, caches, first, jnp.int32(8),
                                jax.random.PRNGKey(0), 6)
    np.testing.assert_array_equal(np.asarray(sampled), host_toks)


def test_vector_cache_index_matches_scalar():
    """decode_attend takes per-request positions; a constant vector index
    must equal the scalar path exactly."""
    cfg = get_config("gemma3-1b", smoke=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab_size)
    prefill = jax.jit(build_prefill_step(cfg, 16, cache_dtype=jnp.float32))
    decode = jax.jit(build_decode_step(cfg))
    logits, caches = prefill(params, tok)
    nxt = greedy_sample(logits)
    a, _ = decode(params, caches, nxt, jnp.int32(8))
    b, _ = decode(params, caches, nxt, jnp.full((2,), 8, jnp.int32))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_block_pool_accounting():
    from repro.serving.kv_cache import BlockPool

    pool = BlockPool(n_blocks=4, block_tokens=16)
    assert pool.blocks_for(1) == 1 and pool.blocks_for(17) == 2
    assert pool.can_alloc(64) and not pool.can_alloc(65)
    got = pool.alloc(owner=7, n_tokens=33)
    assert len(got) == 3 and not pool.can_alloc(32)
    with pytest.raises(RuntimeError):
        pool.alloc(owner=8, n_tokens=32)
    pool.release(7)
    assert pool.can_alloc(64)


def test_continuous_scheduler_matches_fixed_batch():
    """Staggered requests drained through fewer slots reproduce the
    fixed-batch greedy reference per request (bf16 cache => exact)."""
    from repro.serving.scheduler import ContinuousScheduler, Request

    cfg = get_config("gemma3-1b", smoke=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=n, dtype=np.int32)
               for n in (5, 9, 12, 7, 10)]
    max_new = 6

    sched = ContinuousScheduler(cfg, params, slots=2, max_seq=32,
                                cache_dtype=jnp.bfloat16, decode_chunk=3)
    got = sched.run([Request(uid=i, prompt=p, max_new=max_new)
                     for i, p in enumerate(prompts)])

    decode = jax.jit(build_decode_step(cfg))
    for i, p in enumerate(prompts):
        prefill = jax.jit(build_prefill_step(cfg, 32,
                                             cache_dtype=jnp.bfloat16))
        logits, caches = prefill(params, p[None, :].astype(np.int32))
        ref, cur = [], greedy_sample(logits)
        for t in range(max_new):
            ref.append(int(cur[0, 0]))
            if t + 1 < max_new:
                lg, caches = decode(params, caches, cur,
                                    jnp.int32(len(p) + t))
                cur = greedy_sample(lg)
        assert got[i] == ref, f"request {i} diverged"


def test_continuous_scheduler_quantized_cache_runs():
    from repro.serving.kv_cache import CacheQuantConfig
    from repro.serving.scheduler import ContinuousScheduler, Request

    cfg = get_config("gemma3-1b", smoke=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    reqs = [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab_size, size=6,
                                        dtype=np.int32),
                    max_new=4)
            for i in range(3)]
    sched = ContinuousScheduler(cfg, params, slots=2, max_seq=32,
                                qcfg=CacheQuantConfig(bits=8))
    got = sched.run(reqs)
    assert sorted(got) == [0, 1, 2]
    assert all(len(v) == 4 for v in got.values())


def test_scheduler_rejects_pad_unsafe_configs():
    from repro.serving.scheduler import ContinuousScheduler

    cfg = get_config("mamba2-370m", smoke=True)
    params = None  # constructor validates the spec before touching params
    with pytest.raises(ValueError, match="attention-only"):
        ContinuousScheduler(cfg, params, slots=2, max_seq=32)


def test_serve_graph_lint_rules():
    """In-process serve lint on a 1x1 mesh: zero collectives, donated
    cache leaves all aliased, s8 codes survive the jit boundary."""
    from repro.analysis.serve import lint_serve_step
    from repro.launch.mesh import make_mesh
    from repro.serving.kv_cache import CacheQuantConfig

    cfg = get_config("gemma3-1b", smoke=True)
    mesh = make_mesh((1, 1), ("data", "model"))
    report = lint_serve_step(cfg, mesh, qcfg=CacheQuantConfig(bits=8),
                             batch=2, max_seq=16)
    assert report.ok, report.to_json()
    assert {r.rule for r in report.results} == {
        "serve-collective-allowlist", "serve-donation-aliasing",
        "serve-container-dtype"}
    assert report.summary["hlo_collectives"] == 0
    assert report.summary["cache_dtypes"].get("s8", 0) > 0
    assert report.summary["aliased_outputs"] >= report.summary["cache_leaves"]
