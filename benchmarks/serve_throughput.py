"""Serving throughput: quantized KV cache + on-device decode vs the old loop.

Four cache/driver variants at equal batch on the gemma3-1b smoke config:

  * ``fp32_loop`` — the pre-PR baseline verbatim: fp32 cache, one jitted
    decode dispatch per token from a Python loop (launch/serve.py's old
    hot path);
  * ``bf16``      — bf16 cache, the on-device ``lax.scan`` driver
    (``build_generate_fn``: sample -> append -> decode without a host
    round-trip, donated caches);
  * ``q8`` / ``q4`` — log-quant KV cache (codes + per-row scales,
    ``serving/kv_cache.py``) under the same scan driver.

Per variant: tokens/sec, cache bytes/token MEASURED from the live arrays
vs ACCOUNTED from the training-wire ``packed_wire_bits`` formula (+32-bit
scale sideband per row) — the gate hard-fails if they disagree beyond 2% —
concurrent-request capacity at a fixed HBM budget, single-step decode
logits parity vs the bf16 cache, and a leakage row: SSIM/PSNR of the
dequantized cached K against the raw fp32 activations, reusing the GIA
harness scoring (``core/privacy/ssim.py``). The leakage numbers are
*representation fidelity* — an upper bound on what any inversion attack
can recover from the stored cache, not a full attack; lower SSIM at q4
means the cache itself retains measurably less invertible signal.

Timing note: quantized variants time the ``jnp_ref`` codec backend — the
Pallas kernels run in interpret mode on the CPU (a semantics emulator, not a
CPU fast path) and are asserted byte-identical to jnp_ref in the test
suite, so bytes/accounting here transfer to the TPU path unchanged.

Parity tolerances (documented, enforced by the gate and mirrored in
tests/test_serving_and_io.py): single-step decode logits vs the bf16
cache within rel 0.05 for q8, rel 0.75 for q4 (4-bit log-quant carries
~14% per-value cache error; greedy trajectories may diverge after the
first few tokens, which is inherent to 4-bit, not a codec bug).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

BENCH_JSON = "BENCH_serve.json"

HBM_BUDGET_GIB = 16.0  # capacity row: requests fitting in this HBM
SPEEDUP_TARGET = 1.3  # q8 scan driver vs fp32 per-token loop
ACCOUNTING_TOL = 0.02  # measured vs wire-accounted bytes/token
PARITY_REL = {"fp32_loop": 0.05, "q8": 0.05, "q4": 0.75}


def _variants():
    from repro.serving.kv_cache import CacheQuantConfig

    return [
        ("fp32_loop", jnp.float32, None),
        ("bf16", jnp.bfloat16, None),
        ("q8", jnp.bfloat16, CacheQuantConfig(bits=8, backend="jnp_ref")),
        ("q4", jnp.bfloat16, CacheQuantConfig(bits=4, backend="jnp_ref")),
    ]


def bench(quick: bool = False) -> tuple[list[tuple[str, float, str]], dict]:
    from repro.configs import get_config
    from repro.core.privacy.ssim import psnr, ssim
    from repro.models.model import init_params
    from repro.serving.engine import (
        build_decode_step,
        build_generate_fn,
        build_prefill_step,
        greedy_sample,
    )
    from repro.serving.kv_cache import (
        cache_bytes_per_token,
        cache_bytes_per_token_accounting,
        dequantize_kv,
        quantize_kv,
    )

    cfg = get_config("gemma3-1b", smoke=True)
    b, prompt, gen = (4, 16, 24) if quick else (8, 32, 64)
    max_seq = prompt + gen
    params = init_params(cfg, jax.random.PRNGKey(0))
    key1 = jax.random.PRNGKey(1)
    tokens = jax.random.randint(key1, (b, prompt), 0, cfg.vocab_size)
    key2 = jax.random.PRNGKey(2)
    generate = jax.jit(build_generate_fn(cfg), static_argnums=5, donate_argnums=1)

    def copy_tree(t):
        return jax.tree.map(lambda x: x.copy(), t)

    def one_step_logits(caches, dtype_caches_decode):
        """One decode step at idx=prompt from this variant's prefill."""
        logits, _ = dtype_caches_decode(
            params, copy_tree(caches), first, jnp.int32(prompt)
        )
        return logits[:, -1, :].astype(jnp.float32)

    rows, variants = [], []
    bf16_step = None
    first = None
    for name, cache_dtype, qcfg in _variants():
        prefill = jax.jit(
            build_prefill_step(cfg, max_seq, cache_dtype=cache_dtype, qcfg=qcfg)
        )
        decode = jax.jit(build_decode_step(cfg))
        logits, caches = prefill(params, tokens)
        if first is None:
            first = greedy_sample(logits)

        # ---- tokens/sec ------------------------------------------------
        if name == "fp32_loop":
            dec = jax.jit(build_decode_step(cfg), donate_argnums=1)
            work = copy_tree(caches)
            lg, work = dec(params, work, first, jnp.int32(prompt))
            jax.block_until_ready(lg)  # compile outside the clock
            work, tok = copy_tree(caches), first
            t0 = time.perf_counter()
            for i in range(gen):
                lg, work = dec(params, work, tok, jnp.int32(prompt + i))
                tok = greedy_sample(lg)
            jax.block_until_ready(tok)
            dt = time.perf_counter() - t0
        else:
            work = copy_tree(caches)
            out = generate(params, work, first, jnp.int32(prompt), key2, gen)
            jax.block_until_ready(out[3])  # compile outside the clock
            work = copy_tree(caches)
            t0 = time.perf_counter()
            out = generate(params, work, first, jnp.int32(prompt), key2, gen)
            jax.block_until_ready(out[3])
            dt = time.perf_counter() - t0
        tps = b * gen / dt

        # ---- bytes/token: measured vs wire accounting ------------------
        measured = cache_bytes_per_token(caches, b, max_seq)
        accounted = cache_bytes_per_token_accounting(caches, b, max_seq)
        ratio = measured / accounted
        per_request = accounted * max_seq
        capacity = int(HBM_BUDGET_GIB * 2**30 // per_request)

        # ---- single-step logits parity vs the bf16 cache ---------------
        step = one_step_logits(caches, decode)
        if name == "bf16":
            bf16_step = step
            maxdiff = rel = 0.0
        else:
            ref = bf16_step if bf16_step is not None else step
            maxdiff = float(jnp.max(jnp.abs(step - ref)))
            rel = maxdiff / float(jnp.max(jnp.abs(ref)))
        variants.append(
            {
                "name": name,
                "tokens_per_sec": round(tps, 1),
                "cache_bytes_per_token": round(measured, 3),
                "accounted_bytes_per_token": round(accounted, 3),
                "accounting_ratio": round(ratio, 5),
                "capacity_requests_at_budget_hbm": capacity,
                "logits_maxdiff_vs_bf16": round(maxdiff, 5),
                "logits_rel_vs_bf16": round(rel, 5),
            }
        )
        derived = (
            f"tok/s={tps:.0f} bytes/tok={measured:.1f} "
            f"capacity@{HBM_BUDGET_GIB:.0f}GiB={capacity}"
        )
        rows.append((f"serve/{name}", dt / (b * gen) * 1e6, derived))

    # bf16 runs second; fp32_loop's parity was computed against itself —
    # recompute it against the real bf16 reference
    fp32 = variants[0]
    pre32 = jax.jit(build_prefill_step(cfg, max_seq, cache_dtype=jnp.float32))
    _, c32 = pre32(params, tokens)
    step32 = one_step_logits(c32, jax.jit(build_decode_step(cfg)))
    d32 = float(jnp.max(jnp.abs(step32 - bf16_step)))
    fp32["logits_maxdiff_vs_bf16"] = round(d32, 5)
    fp32["logits_rel_vs_bf16"] = round(d32 / float(jnp.max(jnp.abs(bf16_step))), 5)

    # ---- leakage: SSIM/PSNR of the stored-cache representation ---------
    flat = jax.tree_util.tree_flatten_with_path(c32)[0]
    k_leaf = next(x for kp, x in flat if "'k'" in jax.tree_util.keystr(kp))
    if k_leaf.ndim == 5:  # stacked scan leaf: layer 0
        k_leaf = k_leaf[0]
    img = k_leaf.astype(jnp.float32).transpose(0, 2, 3, 1)  # (B, S, hd, Hkv)
    leakage = []
    for name, bits in [("bf16", 0), ("q8", 8), ("q4", 4)]:
        if bits:
            recon = dequantize_kv(quantize_kv(k_leaf, bits)).transpose(0, 2, 3, 1)
        else:
            bf = k_leaf.astype(jnp.bfloat16)
            recon = bf.astype(jnp.float32).transpose(0, 2, 3, 1)
        leakage.append(
            {
                "name": name,
                "ssim": round(float(ssim(img, recon)), 4),
                "psnr_db": round(float(psnr(img, recon)), 2),
            }
        )
        lk = leakage[-1]
        derived = f"ssim={lk['ssim']} psnr={lk['psnr_db']}dB"
        rows.append((f"serve/leakage_{name}", 0.0, derived))

    # ---- acceptance gate ----------------------------------------------
    by = {v["name"]: v for v in variants}
    speedup = by["q8"]["tokens_per_sec"] / by["fp32_loop"]["tokens_per_sec"]
    accounting_ok = all(
        abs(v["accounting_ratio"] - 1.0) <= ACCOUNTING_TOL for v in variants
    )
    parity_ok = all(by[n]["logits_rel_vs_bf16"] <= t for n, t in PARITY_REL.items())
    gate = {
        "q8_speedup_vs_fp32_loop": round(speedup, 3),
        "speedup_target": SPEEDUP_TARGET,
        "speedup_ok": speedup >= SPEEDUP_TARGET,
        "accounting_tol": ACCOUNTING_TOL,
        "accounting_ok": accounting_ok,
        "parity_rel_tol": PARITY_REL,
        "parity_ok": parity_ok,
        "passed": accounting_ok and parity_ok,
    }
    g_derived = (
        f"q8_speedup={speedup:.2f}x accounting_ok={accounting_ok} "
        f"parity_ok={parity_ok}"
    )
    rows.append(("serve/gate", 0.0, g_derived))
    payload = {
        "bench": "serve",
        "schema": 1,
        "quick": quick,
        "config": {
            "arch": "gemma3-1b",
            "smoke": True,
            "batch": b,
            "prompt_len": prompt,
            "gen": gen,
            "max_seq": max_seq,
            "hbm_budget_gib": HBM_BUDGET_GIB,
            "timing_backend": "jnp_ref",
        },
        "variants": variants,
        "leakage": leakage,
        "gate": gate,
    }
    return rows, payload


if __name__ == "__main__":
    for name, us, derived in bench(quick=True)[0]:
        print(f"{name},{us:.1f},{derived}")
