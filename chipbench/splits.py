"""Splits of a traced step by the program's own ``jax.named_scope`` tags.

``chipbench/trace.py`` reduces a trace to the benchmark's buckets: the
model, the compressor, the metrics and the collectives. The program tags
finer work inside them, and this module reads those tags by one open rule,
with no table of names: every part of an HLO ``op_name`` of the form
``<word>.<word>`` is a tag (``model.mixer``, ``ssd.cb``, ``lowrank.orth``,
``codec.encode``), also where JAX wraps it, as in ``jvp(model.head)`` or
``transpose(jvp(model.mlp))``; ``comp.lq_sgd.eager``, with three words, is
a bucket's scope and no tag. An instruction's tags, outermost first, are
its chain, and a part is named by its chain's path: ``model.mixer`` and,
inside it, ``model.mixer/ssd.cb``.

Each op keeps the bucket ``chipbench.trace.scope_map`` gives it. Within
it, the op's instructions (itself and those it calls) of that bucket's
scope vote with the bytes of their results (``by_bytes``, so that a fusion
goes to the phase that makes most of its data; ``by_count`` gives one vote
each), and the op goes to the winning chain, or to ``other`` where none of
them carries a tag. Parts hold the parts nested in them, so the parts
without a ``/`` and ``other`` add up to their bucket. The backward pass is
the ops whose instructions are mostly under JAX's ``transpose(``, and its
remat recompute those mostly under ``rematted_computation``.

    python3 chipbench/splits.py --workload <cell> --seed <n> [--out <dir>]

runs one traced run of the cell (``chipbench.run.run_cell``) and prints its
result line with a ``splits`` object added: the parts by count, the ops
whose instructions carry more than one chain, and what a runtime span
costs. With ``--out`` it also writes there the compiled step
(``step.hlo.txt.gz``), the step with its source metadata stripped
(``step.stripped.hlo``, for a comparison of two commits' programs) and the
trace in plain form with its spans (``trace.json.gz``).
"""

from __future__ import annotations

import collections
import gzip
import json
import os
import re
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import trace as tr  # noqa: E402
from chipbench.hlo import parse_module, parse_type  # noqa: E402

TAG = re.compile(r"(?:^|[/(])([A-Za-z_]\w*\.[A-Za-z_]\w*)(?=$|[/)])")
BUCKETS = ("model", "compress")
BACKWARD = "transpose("
REMAT = "rematted_computation"
METADATA_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def tags(op_name: str) -> tuple[str, ...]:
    """The ``<word>.<word>`` tags of an ``op_name``, outermost first, each
    once."""
    return tuple(dict.fromkeys(TAG.findall(op_name)))


def _result_bits(ins) -> int:
    return sum(parse_type(t)[2] for t in ins.result_types)


def sub_map(hlo_text: str, by_bytes: bool = True) -> dict[str, dict]:
    """instruction name -> the chain it goes to in the model and in the
    compressor bucket (None: ``other``), whether it is backward or remat
    recompute, and the chains its instructions carry (``phases``)."""
    mod = parse_module(hlo_text)
    memo: dict[str, collections.Counter] = {}

    def own(ins) -> collections.Counter:
        # votes as (bucket, chain) -> weight; "" counts every instruction
        c: collections.Counter = collections.Counter()
        if not ins.op_name:
            return c
        w = _result_bits(ins) if by_bytes else 1
        c[""] += w
        c[BACKWARD] += w if BACKWARD in ins.op_name else 0
        c[REMAT] += w if REMAT in ins.op_name else 0
        chain = tags(ins.op_name)
        if chain:
            c[(tr._scope_of(ins.op_name), chain)] += w
        return c

    def called(ins) -> collections.Counter:
        c: collections.Counter = collections.Counter()
        for callee in dict.fromkeys(ins.callees):
            if callee in mod.computations:
                c.update(computation(callee))
        return c

    def computation(name: str) -> collections.Counter:
        if name not in memo:
            memo[name] = collections.Counter()  # a cycle reads as empty
            c: collections.Counter = collections.Counter()
            for ins in mod.computations[name].instructions:
                c.update(own(ins))
                c.update(called(ins))
            memo[name] = c
        return memo[name]

    out = {}
    for ins in mod.instructions():
        votes = own(ins) + called(ins)
        entry: dict = {}
        chains = [(k, w) for k, w in votes.items() if isinstance(k, tuple)]
        for bucket in BUCKETS:
            mine = {chain: w for (b, chain), w in chains if b == bucket}
            entry[bucket] = max(mine, key=mine.get) if mine else None
        entry["phases"] = sorted({"/".join(chain) for (_, chain), _ in chains})
        total = votes[""]
        entry["backward"] = 2 * votes[BACKWARD] > total
        entry["remat"] = 2 * votes[REMAT] > total
        out[ins.name] = entry
    return out


def op_ms(trace: dict, scopes: dict, steps: int) -> dict[str, tuple[str, float]]:
    """instruction name -> (bucket, milliseconds per step) of the model's and
    the compressor's ops, collectives left out, averaged over devices as
    ``chipbench.trace.reduce_trace`` averages: each bucket's ops add up to
    its ``model_ms`` or ``compress_ms``."""
    lo, hi = trace["window"]
    n = len(trace["devices"])
    out: dict[str, list] = {}
    for dev in trace["devices"].values():
        for name, a, b in tr._clip(tr._innermost(dev["ops"]), lo, hi):
            instr = tr._instr_name(name)
            scope, opcode = scopes.get(instr, ("model", "unknown"))
            if tr._is_collective(opcode) or scope not in ("model", "compress"):
                continue
            out.setdefault(instr, [scope, 0.0])[1] += (b - a) / n / 1e6 / steps
    return {k: (scope, ms) for k, (scope, ms) in out.items()}


_OTHER = {"model": None, "compress": None, "phases": []}


def split_trace(ops: dict, subs: dict) -> dict:
    """Milliseconds per step of each part of the model and compressor
    buckets, by path (``other`` for the untagged), and of the model's
    backward pass (``bwd``) and its remat recompute (``bwd_remat``)."""
    out: dict = {b: collections.Counter() for b in BUCKETS}
    out |= {"bwd": 0.0, "bwd_remat": 0.0}
    for instr, (scope, ms) in ops.items():
        sub = subs.get(instr, _OTHER)
        chain = sub[scope]
        if chain is None:
            out[scope]["other"] += ms
        for i in range(len(chain or ())):
            out[scope]["/".join(chain[: i + 1])] += ms
        if scope == "model" and sub.get("backward"):
            out["bwd"] += ms
            if sub.get("remat"):
                out["bwd_remat"] += ms
    for b in BUCKETS:
        out[b] = dict(sorted(out[b].items()))
    return out


def mixed_ops(ops: dict, subs: dict, by_count: dict, min_ms: float = 1.0):
    """The ops of at least ``min_ms`` per step whose instructions carry more
    than one chain: [instruction, bucket, ms, part by bytes, part by count,
    chains], longest first."""

    def path(chain) -> str:
        return "other" if chain is None else "/".join(chain)

    out = []
    for instr, (scope, ms) in ops.items():
        sub = subs.get(instr, _OTHER)
        if ms >= min_ms and len(sub["phases"]) > 1:
            count = by_count.get(instr, _OTHER)[scope]
            out.append([instr, scope, ms, path(sub[scope]), path(count), sub["phases"]])
    return sorted(out, key=lambda r: -r[2])


def strip_metadata(hlo_text: str) -> str:
    """The module without its source metadata: no instruction ``metadata``
    and no stack-frame tables (which name files and lines)."""
    out, skip = [], False
    for line in hlo_text.splitlines():
        if line in METADATA_TABLES:
            skip = True
        elif skip:
            skip = bool(line.strip())
        else:
            out.append(re.sub(r",?\s*metadata=\{[^}]*\}", "", line))
    return "\n".join(out) + "\n"


def span_cost_us(n: int = 20000) -> dict[str, float]:
    """Microseconds one ``AsyncRunner`` span costs, with the profiler off
    and on (an empty body; JAX must already hold its device). Empty where
    the program's runner has no spans."""
    import tempfile

    import jax

    from repro.train.runtime import AsyncRunner, RuntimeConfig

    runner = AsyncRunner(None, None, RuntimeConfig(steps=0, verbose=False))
    span = getattr(runner, "_span", None)
    if span is None:
        return {}

    def per_span() -> float:
        t0 = time.perf_counter()
        for _ in range(n):
            with span("runtime.dispatch"):
                pass
        return 1e6 * (time.perf_counter() - t0) / n

    off = per_span()
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            on = per_span()
        finally:
            jax.profiler.stop_trace()
    return {"off": off, "on": on}


def measure(spec: dict, *, seed: int, seconds: float, require_tpu=True):
    """One traced run of the cell ``spec`` (``chipbench.run.run_cell``):
    its result line with a ``splits`` object added, and what it read: the
    compiled step (``hlo``) and the trace in plain form, with its spans."""
    from chipbench import run

    seen: dict = {}
    result = run.run_cell(
        spec,
        seed=seed,
        seconds=seconds,
        trace=True,
        t_start=run.T_START,
        require_tpu=require_tpu,
        seen=seen,
    )
    ops = op_ms(seen["trace"], tr.scope_map(seen["hlo"]), result["attempted"])
    subs, by_count = sub_map(seen["hlo"]), sub_map(seen["hlo"], by_bytes=False)
    result["splits"] = {
        "by_count": split_trace(ops, by_count),
        "mixed_ops": mixed_ops(ops, subs, by_count),
        "span_cost_us": span_cost_us(),
    }
    return result, seen


def main(argv: list[str] | None = None) -> int:
    import argparse

    from chipbench import run

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", default=None, help="directory for step and trace")
    args = ap.parse_args(argv)
    try:
        result, seen = measure(
            run.load_cell(args.workload), seed=args.seed, seconds=args.seconds
        )
    except run.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    if args.out:
        out = Path(args.out)
        out.mkdir(parents=True, exist_ok=True)
        (out / "step.stripped.hlo").write_text(strip_metadata(seen["hlo"]))
        with gzip.open(out / "step.hlo.txt.gz", "wt") as f:
            f.write(seen["hlo"])
        with gzip.open(out / "trace.json.gz", "wt") as f:
            json.dump(seen["trace"], f)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
