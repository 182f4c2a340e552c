"""Bring-up check: the LQ-SGD training main path on the TPU.

    python3 chip_smoke.py            # one chip: (a) train, (b) codec
    python3 chip_smoke.py --chips 4  # four chips: (a) with LQ-SGD and with
                                     # no compression, plus placement checks

(a) trains full-width mamba2-370m for 20 steps through the launcher,
``repro.launch.train.run``, with rank-1 8-bit LQ-SGD at 8 sequences of
2048 tokens per chip, all chips on the ``data`` axis. Losses must be finite
and fall, and the measured ``wire_mb_per_step`` must equal the compressor's
static ``wire_bits_per_step()``.

(b) runs ``LogQuantCodec(backend="pallas")`` against ``backend="jnp_ref"``
at bits 8 and 4, at the factor and raw-leaf shapes that step hands the
codec. Codes and wire bytes must be identical, and the compiled programs
must hold the kernels (``tpu_custom_call``), not an interpreter.

With ``--chips 4`` only (a) runs, once with LQ-SGD and once with
``--compressor none``; every error-feedback leaf must span four devices and
peak memory must agree across devices within 20%.

One process drives every chip. The last line of stdout is a JSON object
naming the device, printed only when every check passed. Without a TPU, or
run without the repository around it, the script exits non-zero.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "mamba2-370m"
SMOKE = False  # tests rehearse on the CPU with the reduced config
SEQ = 2048
BATCH_PER_CHIP = 8
STEPS = 20
BITS = 8
RANK = 1
MEMORY_SPREAD = 1.2  # max / min peak bytes across devices


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileStats:
    """Counts persistent-cache hits and misses and sums XLA compile time
    from JAX's monitoring events while the context is open."""

    _EVENTS = {
        "/jax/compilation_cache/cache_hits": "hits",
        "/jax/compilation_cache/cache_misses": "misses",
    }
    _COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.hits = self.misses = 0
        self.compile_s = 0.0

    def _event(self, event: str, **kwargs) -> None:
        name = self._EVENTS.get(event)
        if name:
            setattr(self, name, getattr(self, name) + 1)

    def _duration(self, event: str, secs: float, **kwargs) -> None:
        if event == self._COMPILE:
            self.compile_s += secs

    def __enter__(self) -> "CompileStats":
        import jax

        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        return self

    def __exit__(self, *exc) -> None:
        import jax

        jax.monitoring.unregister_event_listener(self._event)
        jax.monitoring.unregister_event_duration_listener(self._duration)


def _compressor(name: str):
    from repro.configs import get_config
    from repro.core import CompressorConfig
    from repro.train.step import make_model_compressor

    return make_model_compressor(
        get_config(ARCH, smoke=SMOKE),
        CompressorConfig(name=name, rank=RANK, bits=BITS),
    )


def _peak_bytes() -> list[int]:
    import jax

    return [d.memory_stats()["peak_bytes_in_use"] for d in jax.devices()]


def train_phase(compressor: str, chips: int, stats: CompileStats) -> dict:
    """Phase (a): train through the launcher and check what it reports."""
    import jax

    from repro.launch import train

    argv = [
        "--arch", ARCH,
        "--compressor", compressor,
        "--rank", str(RANK),
        "--bits", str(BITS),
        "--batch", str(BATCH_PER_CHIP * chips),
        "--seq", str(SEQ),
        "--steps", str(STEPS),
        "--log-every", "1",
    ]  # fmt: skip
    argv += ["--smoke"] if SMOKE else []
    tag = f"train[{compressor}]"
    print(f"# {tag}: repro.launch.train {' '.join(argv)}", flush=True)
    compile_before = stats.compile_s
    t0 = time.perf_counter()
    history = train.run(argv)  # ends fetching the last step's metrics
    run_s = time.perf_counter() - t0

    steps = [h["step"] for h in history]
    check(steps == list(range(STEPS)), f"{tag}: logged steps {steps}")
    losses = [h["loss"] for h in history]
    print(f"{tag}: losses {losses}")
    check(all(math.isfinite(v) for v in losses), f"{tag}: non-finite loss")
    k = max(1, STEPS // 4)
    check(
        losses[-1] < losses[0] and sum(losses[-k:]) < sum(losses[:k]),
        f"{tag}: loss did not fall",
    )

    static_mb = _compressor(compressor).wire_bits_per_step() / 8e6
    wire = sorted({h["wire_mb_per_step"] for h in history})
    print(f"{tag}: wire_mb_per_step {wire} static {static_mb}")
    # the metric is float32: equal to the static count up to its rounding
    check(
        all(abs(w - static_mb) <= 1e-6 * static_mb for w in wire),
        f"{tag}: wire {wire} != static accounting {static_mb}",
    )

    # wall_s is taken when a step's metrics reach the host, i.e. once the
    # step has finished on the device; step 0 carries the compilation
    first_s = history[0]["wall_s"]
    step_s = (history[-1]["wall_s"] - first_s) / (STEPS - 1)
    print(
        f"{tag}: first_step_s {first_s} (trace + compile + step 0) "
        f"step_s {step_s} (steps 1-{STEPS - 1}) run_s {run_s} "
        f"xla_compile_s {stats.compile_s - compile_before} (every program "
        "compiled in the phase, state init included)"
    )
    print(f"{tag}: peak_bytes_in_use {_peak_bytes()}", flush=True)
    print(f"{tag}: device 0 memory_stats {jax.devices()[0].memory_stats()}")
    return {"losses": losses, "wire_mb": wire[0], "step_s": step_s}


def codec_shapes(compressor) -> list[tuple[int, ...]]:
    """Shapes the step hands the codec: per low-rank leaf its P (n, r) and
    Q (m, r) factors, stacked over layers where the leaf is, and each raw
    leaf whole. The plans come from ``jax.eval_shape`` of the gradients."""
    shapes = set()
    for pl in compressor.plans:
        if pl.route == "lowrank":
            n, m = pl.mat_shape
            lead = pl.shape[:1] if pl.stacked else ()
            shapes.update({lead + (n, pl.eff_rank), lead + (m, pl.eff_rank)})
        else:
            shapes.add(tuple(pl.shape))
    return sorted(shapes)


def codec_phase(shapes: list[tuple[int, ...]]) -> None:
    """Phase (b): Pallas codec == jnp reference, byte for byte."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.codec import LogQuantCodec

    on_tpu = jax.default_backend() == "tpu"
    for bits in (8, 4):
        ref = LogQuantCodec(bits=bits, backend="jnp_ref")
        pal = LogQuantCodec(bits=bits, backend="pallas")
        for i, shape in enumerate(shapes):
            tag = f"codec[bits={bits} shape={shape}]"
            x = jax.random.normal(jax.random.PRNGKey(i), shape, jnp.float32)
            xn = x / jnp.max(jnp.abs(x))
            encode = jax.jit(pal.encode).lower(xn).compile()
            codes_ref = np.asarray(jax.jit(ref.codes)(xn))
            codes_pal = np.asarray(jax.jit(pal.codes)(xn))
            wire_ref = np.asarray(jax.jit(ref.encode)(xn))
            wire_pal = np.asarray(encode(xn))
            n_codes = int(np.sum(codes_ref != codes_pal))
            check(n_codes == 0, f"{tag}: {n_codes} codes differ")
            check(
                wire_ref.dtype == wire_pal.dtype
                and wire_ref.tobytes() == wire_pal.tobytes(),
                f"{tag}: wire bytes differ",
            )
            c = jnp.asarray(codes_ref, jnp.float32)
            expand = jax.jit(pal.expand).lower(c).compile()
            diff = np.asarray(expand(c)) - np.asarray(jax.jit(ref.expand)(c))
            dv = float(np.max(np.abs(diff)))
            check(dv <= 1e-6, f"{tag}: expanded values differ by {dv}")
            if on_tpu:
                for name, prog in (("encode", encode), ("expand", expand)):
                    check(
                        "tpu_custom_call" in prog.as_text(),
                        f"{tag}: no kernel in the compiled {name}",
                    )
            print(
                f"{tag}: codes and {wire_pal.nbytes} wire bytes identical, "
                f"max |value diff| {dv}, kernels compiled: {on_tpu}",
                flush=True,
            )


def placement_check(chips: int) -> None:
    """Error feedback of the launcher's own sharded step spans ``chips``
    devices, one DP worker's slice on each."""
    import jax

    from repro.configs import get_config
    from repro.data.synthetic import LMDataConfig, lm_batch
    from repro.launch.mesh import make_mesh
    from repro.train.optimizer import make_optimizer
    from repro.train.runtime import build_sharded_step, sharded_init

    cfg = get_config(ARCH, smoke=SMOKE)
    mesh = make_mesh((chips, 1), ("data", "model"))
    comp = _compressor("lq_sgd")
    opt = make_optimizer("sgd", 0.05)
    data = LMDataConfig(
        vocab_size=cfg.vocab_size, seq_len=SEQ, batch=BATCH_PER_CHIP * chips
    )
    batch = lm_batch(data, 0)
    with jax.set_mesh(mesh):
        jstep, st_sh, _, _ = build_sharded_step(
            cfg, mesh, comp, opt, sample_batch=batch, remat_scan=not SMOKE
        )
        state = sharded_init(cfg, jax.random.PRNGKey(0), opt, comp, mesh, st_sh)
        state, _ = jstep(state, batch)
    err = state["comp"]["err"]
    check(bool(err), "no error-feedback state")
    for name, leaf in err.items():
        shards = leaf.addressable_shards
        devices = {s.device for s in shards}
        check(
            len(shards) == chips
            and len(devices) == chips
            and all(s.data.shape[0] == leaf.shape[0] // chips for s in shards),
            f"err[{name}] {leaf.shape}: {len(shards)} shards on "
            f"{len(devices)} devices",
        )
    print(
        f"placement: {len(err)} error-feedback leaves, each in {chips} "
        f"shards on {chips} distinct devices",
        flush=True,
    )


def run(chips: int) -> None:
    import jax

    with CompileStats() as stats:
        if chips == 1:
            train_phase("lq_sgd", 1, stats)
            codec_phase(codec_shapes(_compressor("lq_sgd")))
        else:
            lq = train_phase("lq_sgd", chips, stats)
            dense = train_phase("none", chips, stats)
            ratio = lq["wire_mb"] / dense["wire_mb"]
            print(
                f"wire: lq_sgd {lq['wire_mb']} MB/step, none "
                f"{dense['wire_mb']} MB/step, ratio {ratio}"
            )
            check(ratio < 0.01, f"LQ-SGD wire is {ratio} of uncompressed")
            placement_check(chips)
            peaks = _peak_bytes()
            check(
                max(peaks) <= MEMORY_SPREAD * min(peaks),
                f"peak memory unbalanced across devices: {peaks}",
            )
        print(
            f"compile cache: {jax.config.jax_compilation_cache_dir} "
            f"hits {stats.hits} misses {stats.misses} "
            f"backend_compile_s {stats.compile_s}",
            flush=True,
        )


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--chips",
        type=int,
        default=1,
        choices=(1, 4),
        help="1: train + codec phases; 4: data-parallel LQ-SGD against no "
        "compression",
    )
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro" / "launch" / "train.py").is_file():
        print(f"chip_smoke: no repro package under {ROOT / 'src'}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" or len(devices) != args.chips:
        print(
            f"chip_smoke: want {args.chips} TPU device(s), JAX found "
            f"{len(devices)} {dev.platform}",
            file=sys.stderr,
        )
        return 2
    try:
        run(args.chips)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
