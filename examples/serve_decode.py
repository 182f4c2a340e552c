"""Serving example: quantized KV cache + continuous batching end to end
on a simulated (2 data x 4 model) mesh — gemma3-family reduced config
with its 5:1 local:global sliding-window pattern.

Three stages, each building on the last:

  1. fixed batch, bf16 cache, the on-device ``lax.scan`` decode driver
     (one dispatch per chunk instead of one per token);
  2. the same driver over a log-quantized (q8) cache — codes + per-row
     scales packed exactly like the training wire, ~4x less cache HBM;
  3. continuous batching: staggered requests admitted/retired through a
     fixed slot grid with paged block accounting.

    PYTHONPATH=src python examples/serve_decode.py
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models.model import init_params
from repro.serving.engine import (build_generate_fn, build_prefill_step,
                                  greedy_sample)
from repro.serving.kv_cache import (CacheQuantConfig, cache_bytes_per_token,
                                    tree_is_quantized)
from repro.serving.scheduler import ContinuousScheduler, Request


def main():
    mesh = make_mesh((2, 4), ("data", "model"))
    cfg = get_config("gemma3-1b", smoke=True)
    batch, prompt_len, gen = 4, 32, 24
    max_seq = prompt_len + gen

    with jax.set_mesh(mesh):
        params = init_params(cfg, jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1),
                                    (batch, prompt_len), 0, cfg.vocab_size)

        # -- 1+2: fixed batch, bf16 then q8 cache, scan decode driver ----
        for label, qcfg in [("bf16", None),
                            ("q8", CacheQuantConfig(bits=8))]:
            prefill = jax.jit(build_prefill_step(cfg, max_seq,
                                                 cache_dtype=jnp.bfloat16,
                                                 qcfg=qcfg))
            generate = jax.jit(build_generate_fn(cfg), static_argnums=5,
                               donate_argnums=1)
            t0 = time.time()
            logits, caches = prefill(params, tokens)
            jax.block_until_ready(logits)
            bpt = cache_bytes_per_token(caches, batch, max_seq)
            print(f"[{label}] prefill {batch}x{prompt_len} in "
                  f"{time.time()-t0:.2f}s — cache "
                  f"quantized={tree_is_quantized(caches)}, "
                  f"{bpt:.1f} bytes/token")
            first = greedy_sample(logits)
            t0 = time.time()
            _, _, _, sampled = generate(params, caches, first,
                                        jnp.int32(prompt_len),
                                        jax.random.PRNGKey(2), gen - 1)
            seq = jnp.concatenate([first, sampled], axis=1)
            jax.block_until_ready(seq)
            dt = time.time() - t0
            print(f"[{label}] decode {gen}x{batch} tokens in {dt:.2f}s "
                  f"({gen * batch / dt:.1f} tok/s, one dispatch per chunk)")
            assert int(seq.min()) >= 0 and int(seq.max()) < cfg.vocab_size

        # -- 3: continuous batching over staggered requests ---------------
        rng = np.random.default_rng(0)
        reqs = [Request(uid=i,
                        prompt=rng.integers(0, cfg.vocab_size,
                                            size=int(n), dtype=np.int32),
                        max_new=8)
                for i, n in enumerate((9, 17, 12, 25, 7, 14))]
        sched = ContinuousScheduler(cfg, params, slots=2, max_seq=max_seq,
                                    qcfg=CacheQuantConfig(bits=8))
        t0 = time.time()
        done = sched.run(reqs)
        dt = time.time() - t0
        total = sum(len(v) for v in done.values())
        print(f"[continuous] {len(reqs)} staggered requests through 2 slots "
              f"in {dt:.2f}s ({total / dt:.1f} tok/s, {sched.steps} chunks)")
        for uid in sorted(done):
            print(f"  request {uid}: {done[uid]}")
        assert sorted(done) == list(range(len(reqs)))
        print("ok")


if __name__ == "__main__":
    main()
