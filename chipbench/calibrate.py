"""Readings that the limits of a cell's correctness check are set from.

    python3 chipbench/calibrate.py --workload <name> --seeds 12 --faulty 3

In one process, at the cell's own size, on its own chips, without a
measured window:

* sound runs: the program's first steps against the reference, for
  ``--seeds`` seeds (the lower reading of each number is their largest);
* the control: the reference computed with every matmul operand rounded to
  float8 (e4m3), the step below the configuration's bfloat16, put in the
  program's place, for the first ``--faulty`` seeds;
* faults planted in the program, for the same seeds: half of each worker's
  batch left out (half of the positions where a worker holds one row),
  and on several chips the exchange between them left out. A step that
  returns its state unchanged reads 1 on ``change_gap`` by construction
  and needs no run;
* witnesses (``--kinds witness``, best with ``--seed-list``): on each seed,
  the reference with every matmul in bfloat16 (the program's compute type)
  against the float32 reference, and, after all seeds, the program built
  with float32 parameters against the reference from float32 weights.
  They tell rounding from a fault where a sound seed reads high.

Prints one JSON line per reading and a summary line last.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
SEEDS = 7_000_000_001  # first calibration seed; the runs' seeds differ


def half_batch(cell_cls):
    """The cell's program fed half of each worker's rows (or positions)."""

    class HalfBatch(cell_cls):
        def batch(self, seed, step):
            tok = super().batch(seed, step)["tokens"]
            w, s = self.chips, tok.shape[1]
            rows = tok.reshape(w, -1, s)
            if rows.shape[1] > 1:
                rows = rows[:, : rows.shape[1] // 2]
            else:
                rows = rows[:, :, : s // 2]
            return {"tokens": rows.reshape(-1, rows.shape[-1])}

    return HalfBatch


@contextlib.contextmanager
def no_exchange():
    """Every collective of the compressor returns the worker's own value."""
    from repro.core.comm import AxisComm

    saved = {k: getattr(AxisComm, k) for k in ("all_gather", "pmax", "pmean", "psum")}
    AxisComm.all_gather = lambda self, x: x[None]
    AxisComm.pmax = AxisComm.pmean = AxisComm.psum = lambda self, x: x
    try:
        yield
    finally:
        for k, v in saved.items():
            setattr(AxisComm, k, v)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--faulty", type=int, default=3)
    ap.add_argument(
        "--kinds",
        default="sound,control,faults",
        help="which readings to take: any of sound, control, faults, witness",
    )
    ap.add_argument(
        "--seed-list",
        default="",
        help="comma-separated seeds to read instead of the calibration seeds",
    )
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    import jax.numpy as jnp

    from chipbench import check
    from chipbench.run import Cell, load_cell
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    spec = load_cell(args.workload)
    chips = spec["cell"]["chips"]
    devices = jax.devices()[:chips]
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"calibrate: needs {chips} TPU chip(s), found {devices}", file=sys.stderr)
        return 2
    lr = spec["traffic"]["lr"]
    cell = Cell(spec, devices)
    half = half_batch(Cell)(spec, devices)
    out: dict[str, list] = {"sound": [], "control": [], "half_batch": []}
    out |= {"bf16_reference": [], "f32_program": []}
    if chips > 1:
        out["no_exchange"] = []
        with no_exchange():
            lonely = Cell(spec, devices)

    names = cell.leaf_names()

    def emit(kind, seed, values, secs, got, ref):
        out[kind].append(values)
        line = {"kind": kind, "seed": seed, "seconds": secs, **values}
        line["not_compared"] = check.details(got, ref, names)
        line["norms"] = {k: [got[k], ref[k]] for k in ("losses", "step1", "stepK")}
        print(json.dumps(line), flush=True)

    def program(c, seed):
        _, state, losses, snaps, _ = c.start(seed)
        del state
        jax.clear_caches()  # free the step's reserved memory for the reference
        return c.program(seed, losses, snaps)

    kinds = set(args.kinds.split(","))
    if args.seed_list:
        seeds = [int(x) for x in args.seed_list.split(",")]
    else:
        seeds = [SEEDS + i for i in range(args.seeds)]
    for i, seed in enumerate(seeds):
        if i >= args.faulty and not kinds & {"sound", "witness"}:
            break
        t0 = time.perf_counter()
        ref = cell.reference(seed)
        t_ref = time.perf_counter() - t0
        if "sound" in kinds:
            got = program(cell, seed)
            emit("sound", seed, check.readings(got, ref, lr), t_ref, got, ref)
        if "witness" in kinds:
            t0 = time.perf_counter()
            low = cell.reference(seed, low=jnp.bfloat16)
            secs = time.perf_counter() - t0
            emit("bf16_reference", seed, check.readings(low, ref, lr), secs, low, ref)
        if i >= args.faulty:
            continue
        if "control" in kinds:
            t0 = time.perf_counter()
            low = cell.reference(seed, low=jnp.float8_e4m3fn)
            secs = time.perf_counter() - t0
            emit("control", seed, check.readings(low, ref, lr), secs, low, ref)
        if "faults" not in kinds:
            continue
        t0 = time.perf_counter()
        got = program(half, seed)
        secs = time.perf_counter() - t0
        emit("half_batch", seed, check.readings(got, ref, lr), secs, got, ref)
        if chips > 1:
            t0 = time.perf_counter()
            with no_exchange():
                got = program(lonely, seed)
            secs = time.perf_counter() - t0
            emit("no_exchange", seed, check.readings(got, ref, lr), secs, got, ref)
    if "witness" in kinds:
        del cell, half
        jax.clear_caches()
        model = dict(spec["config"]["model"], dtype="float32")
        f32 = Cell({**spec, "config": {**spec["config"], "model": model}}, devices)
        for seed in seeds:
            t0 = time.perf_counter()
            try:
                ref = f32.reference(seed)
                got = program(f32, seed)
            except Exception as e:  # e.g. float32 state over the chip's memory
                print(json.dumps({"kind": "f32_program", "seed": seed,
                                  "error": repr(e)[:2000]}), flush=True)  # fmt: skip
                break
            secs = time.perf_counter() - t0
            emit("f32_program", seed, check.readings(got, ref, lr), secs, got, ref)
    summary = {"kind": "summary", "seconds": time.perf_counter() - T_START}
    for kind, rows in out.items():
        if rows:
            faulty = kind in ("control", "half_batch", "no_exchange")
            agg = min if faulty else max
            summary[kind] = {k: agg(r[k] for r in rows) for k in rows[0]}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
