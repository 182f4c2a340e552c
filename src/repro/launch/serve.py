"""Serving launcher: quantized KV cache + on-device decode, two schedulers.

    REPRO_DEVICES=8 PYTHONPATH=src python -m repro.launch.serve \
        --arch gemma3-1b --smoke --batch 4 --prompt-len 32 --gen 16 \
        --cache-bits 8 --cache-dtype bfloat16 --scheduler continuous

``--scheduler fixed`` runs the classic batched prefill + one on-device
``lax.scan`` decode chunk (all requests same length); ``continuous`` runs
the paged admit/decode/retire loop (per-request lengths, slot reuse).
``--cache-bits 4|8`` stores the KV cache as log-quant codes + per-row
scales (``repro.serving.kv_cache``); 0 keeps the raw ``--cache-dtype``.
"""
import os
if os.environ.get("REPRO_DEVICES"):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ["REPRO_DEVICES"])

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, list_archs
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.models.model import init_params
from repro.models.multimodal import codec_tokens_stub, conditioning_stub, vq_tokens_stub
from repro.serving.engine import (build_generate_fn, build_prefill_step,
                                  greedy_sample)
from repro.serving.kv_cache import (CacheQuantConfig, cache_bytes_per_token,
                                    tree_is_quantized)
from repro.serving.scheduler import ContinuousScheduler, Request

CACHE_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
                "float16": jnp.float16}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4,
                    help="decode slots (fixed: batch; continuous: grid size)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--requests", type=int, default=None,
                    help="continuous only: total requests (default 2x batch)")
    ap.add_argument("--mesh", default=None)
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--cache-dtype", default="bfloat16",
                    choices=sorted(CACHE_DTYPES))
    ap.add_argument("--cache-bits", type=int, default=0, choices=(0, 4, 8),
                    help="log-quant the KV cache (0 = raw --cache-dtype)")
    ap.add_argument("--cache-backend", default="pallas",
                    choices=("jnp_ref", "pallas"))
    ap.add_argument("--scheduler", default="fixed",
                    choices=("fixed", "continuous"))
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()
    use_compile_cache()

    if args.production_mesh:
        mesh = make_production_mesh()
    elif args.mesh:
        d, m = (int(x) for x in args.mesh.split("x"))
        mesh = make_mesh((d, m), ("data", "model"))
    else:
        mesh = make_mesh((1, len(jax.devices())), ("data", "model"))

    cfg = get_config(args.arch, smoke=args.smoke)
    cache_dtype = CACHE_DTYPES[args.cache_dtype]
    qcfg = (CacheQuantConfig(bits=args.cache_bits, backend=args.cache_backend)
            if args.cache_bits else None)
    max_seq = args.prompt_len + args.gen + cfg.cond_len
    key = jax.random.PRNGKey(0)
    if cfg.n_codebooks:
        tokens = codec_tokens_stub(key, args.batch, args.prompt_len, cfg)
    elif cfg.arch_type == "vlm":
        tokens = vq_tokens_stub(key, args.batch, args.prompt_len, cfg)
    else:
        tokens = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                    cfg.vocab_size)
    cond = (conditioning_stub(key, args.batch, cfg) if cfg.cond_len else None)

    with jax.set_mesh(mesh):
        params = init_params(cfg, jax.random.PRNGKey(1))

        if args.scheduler == "continuous":
            if cond is not None or cfg.n_codebooks:
                raise SystemExit("--scheduler continuous supports plain "
                                 "token LMs only")
            sched = ContinuousScheduler(
                cfg, params, slots=args.batch, max_seq=max_seq,
                cache_dtype=cache_dtype, qcfg=qcfg,
                temperature=args.temperature)
            n_req = args.requests or 2 * args.batch
            rng = np.random.default_rng(0)
            reqs = [Request(uid=i,
                            prompt=rng.integers(0, cfg.vocab_size,
                                                size=args.prompt_len,
                                                dtype=np.int32),
                            max_new=args.gen) for i in range(n_req)]
            t0 = time.time()
            done = sched.run(reqs)
            dt = time.time() - t0
            total = sum(len(v) for v in done.values())
            print(f"continuous: {n_req} requests x {args.gen} tokens through "
                  f"{args.batch} slots in {dt:.2f}s "
                  f"({total / max(dt, 1e-9):.1f} tok/s, {sched.steps} chunks)")
            bpt = cache_bytes_per_token(sched.caches, args.batch, max_seq)
            print(f"cache: quantized={tree_is_quantized(sched.caches)} "
                  f"{bpt:.1f} bytes/token")
            print("sample token ids:", done[0][:16])
            return

        # ---- fixed batch: batched prefill + one on-device decode chunk ----
        prefill = jax.jit(build_prefill_step(cfg, max_seq,
                                             cache_dtype=cache_dtype,
                                             qcfg=qcfg))
        generate = jax.jit(build_generate_fn(cfg,
                                             temperature=args.temperature),
                           static_argnums=5, donate_argnums=1)

        t0 = time.time()
        if cond is not None:
            logits, caches = prefill(params, tokens, cond)
        else:
            logits, caches = prefill(params, tokens)
        jax.block_until_ready(logits)
        print(f"prefill {tokens.shape} in {time.time()-t0:.2f}s "
              f"(cache quantized={tree_is_quantized(caches)}, "
              f"{cache_bytes_per_token(caches, args.batch, max_seq):.1f} "
              f"bytes/token)")

        first = greedy_sample(logits)
        idx = args.prompt_len + cfg.cond_len
        t0 = time.time()
        if cfg.n_codebooks:
            # multi-codebook logits need per-codebook sampling; keep the
            # host loop for this (niche) path
            from repro.serving.engine import build_decode_step
            decode = jax.jit(build_decode_step(cfg), donate_argnums=1)
            out = [first]
            for i in range(args.gen - 1):
                logits, caches = decode(params, caches, out[-1],
                                        jnp.int32(idx + i))
                out.append(greedy_sample(logits))
            toks = jnp.concatenate(out, axis=1)
        else:
            caches, _, _, sampled = generate(params, caches, first,
                                             jnp.int32(idx),
                                             jax.random.PRNGKey(2),
                                             args.gen - 1)
            toks = jnp.concatenate([first, sampled], axis=1)
        jax.block_until_ready(toks)
        dt = time.time() - t0
        print(f"decoded {args.gen} tokens/seq x {args.batch} seqs in {dt:.2f}s "
              f"({args.gen * args.batch / max(dt, 1e-9):.1f} tok/s)")
        print("sample token ids:", jax.device_get(toks[0])[:16].tolist())


if __name__ == "__main__":
    main()
