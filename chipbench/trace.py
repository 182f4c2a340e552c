"""From a profiler trace to per-layer device times.

A trace is first brought to one plain form, which tests can hold as JSON:

    {"window": [start_ns, end_ns],          # the host's span of the window
     "devices": {"0": {"ops": [[name, start_ns, dur_ns], ...],
                       "async": [[name, start_ns, dur_ns], ...]}, ...},
     "host": [[name, start_ns, dur_ns], ...],  # the main thread's spans
     "spans": [[name, start_ns, dur_ns], ...]}  # runtime.* spans, any thread

``ops`` is the device's "XLA Ops" line: one event per executed HLO
instruction, run one after another on the TensorCore. ``async`` is its
"Async XLA Ops" line: an asynchronous copy or collective from its start to
its done. Times are on the profiler's common clock, so the host's window
and the device's ops can be compared.

Each op is attributed to a layer through the compiled step's HLO text: the
event names the instruction, and the instruction's ``op_name`` metadata
carries the ``jax.named_scope`` tags of the code that made it. A fusion
that holds any instruction of the compressor is the compressor's; any other
takes its own tags, or without them the tags most of its fused instructions
carry. Events that hold other events (a ``while`` around its body's ops)
are left out: only the innermost ops count as busy or as a layer's time.
An idle gap of the device is named after the innermost ``runtime.*`` span
(``repro.train.runtime.AsyncRunner``'s, on any host thread) that holds its
midpoint, else after the innermost main-thread span.
"""

from __future__ import annotations

import collections
import glob
import re

from chipbench.hlo import parse_module

__all__ = [
    "COLLECTIVES",
    "load_xplane",
    "scope_map",
    "reduce_trace",
]

# the step's layer tags (jax.named_scope in train/step.py and core/)
SCOPES = (
    ("comp.", "compress"),
    ("lazy.", "compress"),
    ("wire.", "compress"),
    ("train.metrics", "metrics"),
)
COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "collective-permute",
    "all-to-all",
    "collective-broadcast",
)
SPAN_PREFIX = "runtime."
_SUFFIX = re.compile(r"\.\d+$")


def _scope_of(op_name: str | None) -> str | None:
    if not op_name:
        return None
    for tag, scope in SCOPES:
        if tag in op_name:
            return scope
    return "model"


def _is_collective(opcode: str) -> bool:
    return any(opcode.startswith(c) for c in COLLECTIVES)


def scope_map(hlo_text: str) -> dict[str, tuple[str, str]]:
    """instruction name -> (layer scope, opcode) for one compiled module."""
    mod = parse_module(hlo_text)
    out: dict[str, tuple[str, str]] = {}

    def votes(comp: str, seen: set) -> collections.Counter:
        c: collections.Counter = collections.Counter()
        if comp in seen or comp not in mod.computations:
            return c
        seen.add(comp)
        for ins in mod.computations[comp].instructions:
            s = _scope_of(ins.op_name)
            if s is not None:
                c[s] += 1
            for callee in ins.callees:
                c.update(votes(callee, seen))
        return c

    for ins in mod.instructions():
        c = collections.Counter()
        for callee in ins.callees:
            c.update(votes(callee, set()))
        own = _scope_of(ins.op_name)
        if own == "compress" or c["compress"]:
            # XLA fuses the compressor's passes with the optimizer update and
            # the gradient's last cast: an op that does any compressor work
            # is the compressor's, so compress_ms holds all of that work
            scope = "compress"
        elif own is not None:
            scope = own
        else:
            scope = c.most_common(1)[0][0] if c else "model"
        opcode = ins.opcode
        if opcode in ("async-start", "async-done", "async-update"):
            # an async wrapper names the collective it runs in its callee
            for callee in ins.callees:
                for sub in mod.computations.get(callee, ()).instructions:
                    if _is_collective(sub.opcode):
                        opcode = sub.opcode + opcode[len("async") :]
        out[ins.name] = (scope, opcode)
    return out


def _instr_name(event_name: str) -> str:
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def _minus(a_iv, b_iv) -> float:
    """Length of the union ``a_iv`` not covered by the union ``b_iv``."""
    total = 0.0
    j = 0
    for a, b in a_iv:
        covered = 0.0
        while j < len(b_iv) and b_iv[j][1] <= a:
            j += 1
        k = j
        while k < len(b_iv) and b_iv[k][0] < b:
            covered += min(b, b_iv[k][1]) - max(a, b_iv[k][0])
            k += 1
        total += (b - a) - covered
    return total


def _innermost(events):
    """The events that hold no other event of the same line."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    holds = [False] * len(evs)
    stack: list[int] = []
    for i, (_, start, dur) in enumerate(evs):
        while stack and evs[stack[-1]][1] + evs[stack[-1]][2] <= start:
            stack.pop()
        if stack and start + dur <= evs[stack[-1]][1] + evs[stack[-1]][2]:
            holds[stack[-1]] = True
        stack.append(i)
    return [e for e, h in zip(evs, holds) if not h]


def _clip(events, lo, hi):
    out = []
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append((name, a, b))
    return out


def reduce_trace(trace: dict, scopes: dict[str, tuple[str, str]], steps: int):
    """Per-layer device times of the traced window, averaged over devices.

    Returns seconds for ``busy_s`` and ``window_s``, a percentage for
    ``idle_pct``, milliseconds per step for the layers, and the ten
    longest device-op groups and idle gaps for the breakdown.
    """
    lo, hi = trace["window"]
    window = hi - lo
    per_dev = []
    op_groups: collections.Counter = collections.Counter()
    gaps_all = []
    host = sorted(trace.get("host", []), key=lambda e: e[1])
    spans = trace.get("spans", [])
    for dev in trace["devices"].values():
        ops = _clip(_innermost(dev["ops"]), lo, hi)
        asyncs = _clip(dev.get("async", []), lo, hi)
        busy = _union([(a, b) for _, a, b in ops])
        layer = collections.Counter()
        coll_iv, comp_iv = [], []
        for name, a, b in ops:
            scope, opcode = scopes.get(_instr_name(name), ("model", "unknown"))
            if _is_collective(opcode):
                layer["collective"] += b - a
                coll_iv.append((a, b))
            else:
                layer[scope] += b - a
                comp_iv.append((a, b))
            op_groups[f"{scope}:{_SUFFIX.sub('', _instr_name(name))}"] += b - a
        for name, a, b in asyncs:
            _, opcode = scopes.get(_instr_name(name), ("model", "unknown"))
            if _is_collective(opcode):
                coll_iv.append((a, b))
        coll_u = _union(coll_iv)
        per_dev.append(
            {
                "busy": _length(busy),
                "collective_span": _length(coll_u),
                "collective_exposed": _minus(coll_u, _union(comp_iv)),
                **layer,
            }
        )
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps_all.append((b - a, a, b))
    n = len(per_dev)

    def mean(key):
        return sum(d.get(key, 0.0) for d in per_dev) / n

    busy_ns = mean("busy")
    gaps_all.sort(reverse=True)
    gap_names: collections.Counter = collections.Counter()
    for length, a, b in gaps_all[:200]:  # the rest are gaps between ops
        gap_names[_host_doing(host, (a + b) / 2, spans)] += length / n
    return {
        "busy_s": busy_ns / 1e9,
        "window_s": window / 1e9,
        "idle_pct": 100.0 * (1.0 - busy_ns / window),
        "model_ms": mean("model") / 1e6 / steps,
        "metrics_ms": mean("metrics") / 1e6 / steps,
        "compress_ms": mean("compress") / 1e6 / steps,
        "collective_ms": max(mean("collective"), mean("collective_span")) / 1e6 / steps,
        "collective_exposed_ms": mean("collective_exposed") / 1e6 / steps,
        "has_collectives": mean("collective_span") > 0,
        "device_ops": [[k, v / 1e9 / n] for k, v in op_groups.most_common(10)],
        "idle_gaps": [[k, v / 1e9] for k, v in gap_names.most_common(10)],
    }


def _host_doing(host: list, t: float, spans: list = ()) -> str:
    """The innermost ``runtime.*`` span that holds time t, else the
    innermost main-thread span that does."""
    held = [(dur, name) for name, start, dur in spans if start <= t <= start + dur]
    if held:
        return min(held)[1]
    best = None
    for name, start, dur in host:
        if start > t:
            break
        if start + dur >= t and (best is None or dur < best[1]):
            best = (name, dur)
    return best[0] if best else "host: no span"


def _spans(planes) -> list:
    """The ``runtime.*`` events of every ``/host:CPU`` line, by start."""
    spans = []
    for plane in planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append([e.name, e.start_ns, e.duration_ns])
    return sorted(spans, key=lambda e: e[1])


def _xplane(trace_dir: str):
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    return ProfileData.from_file(path)


def load_xplane(trace_dir: str, window_name: str) -> dict:
    """Read the ``.xplane.pb`` under ``trace_dir`` into the plain form."""
    pd = _xplane(trace_dir)
    devices: dict[str, dict] = {}
    host: list = []
    window = None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = devices.setdefault(plane.name.rsplit(":", 1)[1], {})
            for line in plane.lines:
                key = {"XLA Ops": "ops", "Async XLA Ops": "async"}.get(line.name)
                if key:
                    dev[key] = [
                        [e.name, e.start_ns, e.duration_ns] for e in line.events
                    ]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name == window_name:
                        window = [e.start_ns, e.start_ns + e.duration_ns]
                    if line.name.startswith("python3") or line.name.startswith("main"):
                        host.append([e.name, e.start_ns, e.duration_ns])
    if window is None or not devices:
        raise RuntimeError(f"trace in {trace_dir} has no window span or no TPU plane")
    spans = _spans(pd.planes)
    return {"window": window, "devices": devices, "host": host, "spans": spans}
