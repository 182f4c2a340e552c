"""Splits of a traced step by the program's own spans and scopes.

``chipbench/trace.py`` reduces a trace to the benchmark's layers: the model,
the compressor, the metrics and the collectives. The program tags finer
work inside them, and this module reads those tags:

- device ``jax.named_scope`` tags, in the compiled step's HLO ``op_name``s:
  ``model.mixer``, ``model.mlp``, ``model.head`` and ``train.optimizer``
  inside the model bucket, and the backward pass by ``transpose(`` (its
  remat recompute by ``rematted_computation``); ``lowrank.power``,
  ``lowrank.orth`` and ``codec.*`` inside the compressor bucket;
- host spans, ``jax.profiler.TraceAnnotation``s named ``runtime.*`` by
  ``repro.train.runtime.AsyncRunner``, on every ``/host:CPU`` line (the
  prefetch thread's included), on the clock of the device's ops.

Each op keeps the bucket ``chipbench.trace.scope_map`` gives it, so a
bucket's parts add up to the bucket. Within it, an op goes to the tag whose
instructions make the most bytes of results, and to ``other`` without one;
``by_count`` gives one vote per instruction instead, which reads a fusion
of a pass over a whole gradient with the decode of a small factor as the
decode. An idle gap of the device is named after the innermost
``runtime.*`` span that holds its midpoint, or by ``chipbench.trace``'s
rule where no such span holds it.

    python3 chipbench/splits.py --workload <cell> --seed <n> [--out <dir>]

runs one traced run of the cell (``chipbench.run.run_cell``) and prints its
result line with a ``splits`` object added. With ``--out`` it also writes
there the compiled step (``step.hlo.txt.gz``), the step with its source
metadata stripped (``step.stripped.hlo``, for a comparison of two commits'
programs) and the trace in plain form with its spans (``trace.json.gz``).
A commit whose program carries no tags reads all of its time as ``other``
and no spans.
"""

from __future__ import annotations

import collections
import glob
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

SPAN_PREFIX = "runtime."
MODEL_TAGS = (
    ("model.mixer", "mixer"),
    ("model.mlp", "mlp"),
    ("model.head", "head"),
    ("train.optimizer", "optimizer"),
)
COMPRESS_TAGS = (
    ("lowrank.orth", "orth"),
    ("lowrank.power", "power"),
    ("codec.", "codec"),
)
BACKWARD = "transpose("
REMAT = "rematted_computation"
METADATA_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def _tag(op_name: str, tags) -> str | None:
    for tag, part in tags:
        if tag in op_name:
            return part
    return None


def _result_bits(ins) -> int:
    return sum(parse_type(t)[2] for t in ins.result_types)


def sub_map(hlo_text: str, by_bytes: bool = True) -> dict[str, dict]:
    """instruction name -> its part in the model and in the compressor
    bucket, whether it is backward or remat recompute, and the parts its
    instructions carry (``phases``).

    Each of its instructions (itself and those it calls) that carries an
    ``op_name`` votes: with the bytes of its result (``by_bytes``, so that a
    fusion goes to the phase that makes most of its data), or one each."""
    mod = parse_module(hlo_text)

    def members(ins, seen: set) -> list:
        out = [ins] if ins.op_name else []
        for callee in ins.callees:
            if callee in seen or callee not in mod.computations:
                continue
            seen.add(callee)
            for sub in mod.computations[callee].instructions:
                out += members(sub, seen)
        return out

    out = {}
    for ins in mod.instructions():
        votes = [
            (m.op_name, _result_bits(m) if by_bytes else 1)
            for m in members(ins, set())
        ]
        total = sum(w for _, w in votes)
        entry: dict = {"phases": []}
        for key, tags in (("model", MODEL_TAGS), ("compress", COMPRESS_TAGS)):
            parts: collections.Counter = collections.Counter()
            for name, w in votes:
                part = _tag(name, tags)
                if part is not None:
                    parts[part] += w
            entry[key] = max(parts, key=parts.get) if parts else "other"
            entry["phases"] += sorted(parts)
        for key, tag in (("backward", BACKWARD), ("remat", REMAT)):
            entry[key] = 2 * sum(w for name, w in votes if tag in name) > total
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


_OTHER = {"model": "other", "compress": "other", "phases": []}


def split_trace(ops: dict, subs: dict) -> dict:
    """Milliseconds per step of each part of the model and compressor
    buckets, and of the model's backward pass and its remat recompute."""
    parts = {
        "model": [p for _, p in MODEL_TAGS] + ["other", "bwd", "bwd_remat"],
        "compress": [p for _, p in COMPRESS_TAGS] + ["other"],
    }
    out = {key: dict.fromkeys(names, 0.0) for key, names in parts.items()}
    for instr, (scope, ms) in ops.items():
        sub = subs.get(instr, _OTHER)
        out[scope][sub[scope]] += ms
        if scope == "model" and sub.get("backward"):
            out["model"]["bwd"] += ms
            if sub.get("remat"):
                out["model"]["bwd_remat"] += ms
    return out


def mixed_ops(ops: dict, subs: dict, by_count: dict, min_ms: float = 1.0):
    """The ops of at least ``min_ms`` per step whose instructions carry more
    than one part's tag: [instruction, bucket, ms, part by bytes, part by
    count, parts], longest first."""
    out = []
    for instr, (scope, ms) in ops.items():
        sub = subs.get(instr, _OTHER)
        if ms >= min_ms and len(sub["phases"]) > 1:
            count = by_count.get(instr, _OTHER)[scope]
            out.append([instr, scope, ms, sub[scope], count, sub["phases"]])
    return sorted(out, key=lambda r: -r[2])


def load_spans(trace_dir: str) -> list:
    """The ``runtime.*`` events of every ``/host:CPU`` line of the trace
    under ``trace_dir``, as ``[name, start_ns, dur_ns]``."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append([e.name, e.start_ns, e.duration_ns])
    return sorted(spans, key=lambda e: e[1])


def span_ms(spans: list, window, steps: int) -> dict[str, float]:
    """Milliseconds per step of each span within the window, and the
    number of spans per step."""
    ms: collections.Counter = collections.Counter()
    count = 0
    for name, a, b in tr._clip(spans, *window):
        ms[name] += (b - a) / 1e6 / steps
        count += 1
    return {**dict(sorted(ms.items())), "spans_per_step": count / steps}


def _doing(spans: list, host: list, t: float) -> str:
    """The innermost ``runtime.*`` span that holds time t, else the
    innermost main-thread span (``chipbench.trace``'s rule)."""
    held = [(dur, name) for name, start, dur in spans if start <= t <= start + dur]
    return min(held)[1] if held else tr._host_doing(host, t)


def idle_gaps(trace: dict, spans: list) -> list:
    """The device's idle gaps in the window, named by ``_doing``: the ten
    largest names' seconds, as ``reduce_trace``'s ``idle_gaps`` gives them."""
    lo, hi = trace["window"]
    host = sorted(trace.get("host", []), key=lambda e: e[1])
    gaps = []
    for dev in trace["devices"].values():
        ops = tr._clip(tr._innermost(dev["ops"]), lo, hi)
        busy = tr._union([(a, b) for _, a, b in ops])
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, a, b))
    gaps.sort(reverse=True)
    names: collections.Counter = collections.Counter()
    n = len(trace["devices"])
    for length, a, b in gaps[:200]:  # the rest are gaps between ops
        names[_doing(spans, host, (a + b) / 2)] += length / n
    return [[k, v / 1e9] for k, v in names.most_common(10)]


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
    compiled step (``hlo``), the trace in plain form and its spans."""
    import jax

    from chipbench import run

    # the compile cache's key leaves source metadata out by default, so a
    # step compiled by another commit would come back with its op_names
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    seen: dict = {}
    load, scope_map = tr.load_xplane, tr.scope_map

    def load_xplane(trace_dir, window_name):
        # the step is compiled by now: the reference's programs that follow
        # may come from the cache as they are
        jax.config.update("jax_compilation_cache_include_metadata_in_key", False)
        seen["spans"] = load_spans(trace_dir)
        seen["trace"] = load(trace_dir, window_name)
        return seen["trace"]

    def see_scopes(hlo_text):
        seen["hlo"] = hlo_text
        return scope_map(hlo_text)

    # run_cell reads the trace through these two; they are put back after
    tr.load_xplane, tr.scope_map = load_xplane, see_scopes
    try:
        result = run.run_cell(
            spec,
            seed=seed,
            seconds=seconds,
            trace=True,
            t_start=run.T_START,
            require_tpu=require_tpu,
        )
    finally:
        tr.load_xplane, tr.scope_map = load, scope_map
        jax.config.update("jax_compilation_cache_include_metadata_in_key", False)
    trace, spans, steps = seen["trace"], seen["spans"], result["attempted"]
    ops = op_ms(trace, scope_map(seen["hlo"]), steps)
    subs, by_count = sub_map(seen["hlo"]), sub_map(seen["hlo"], by_bytes=False)
    result["splits"] = {
        **split_trace(ops, subs),
        "by_count": split_trace(ops, by_count),
        "mixed_ops": mixed_ops(ops, subs, by_count),
        "span_ms": span_ms(spans, trace["window"], steps),
        "idle_gaps": idle_gaps(trace, spans),
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
            json.dump({**seen["trace"], "spans": seen["spans"]}, f)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
