"""Operation and byte counts from shapes, kept with the benchmark.

``model_flops_per_token`` follows ``repro.roofline.flops_model`` with one
change: a training token costs the forward pass three times (forward plus a
backward of twice its cost), never the four of full rematerialisation,
because recomputed operations do not count towards MFU. Matmuls count 2·M·N·K.
The forward count of each layer comes from a counter found by name: one
module per kind under ``chipbench/counts/``, ``<kind>.py`` with
``fwd(model, spec, seq_len) -> float``. A layer adds its mixer's count by
``spec["kind"]`` and its FFN's, ``moe`` where ``spec["moe"]``, else ``mlp``
where the model has ``d_ff``; the head adds 2·d_model·vocab_size. A new
kind of layer is a new file there.

``compress_cost`` is the least work of one rank-r LQ-SGD sync (paper
Algorithm 1), whatever implements it. Per low-rank matrix instance (n, m):
P = (G+E)Q, Q' = (G+E)^T P̂ and E' = G+E - P̂Q'^T depend on each other
through a global orthonormalisation and a global scale, so the algorithm
reads G and E three times and writes E once: 3·n·m·(g+e) + n·m·e bytes,
with g and e the gradient's and the error feedback's bytes per element.
The reconstruction P̂Q'^T need never be stored: it can go straight into the
optimizer's update. The factors add 16·(n+m)·r bytes and the three matmuls
6·n·m·r FLOPs plus 2·n·m for the two elementwise passes. A leaf that is
synced whole (a norm, a bias) is read once.
"""

from __future__ import annotations

import functools
import importlib.util
import math
from pathlib import Path

__all__ = ["layer_specs", "model_flops_per_token", "compress_cost", "leaf_route"]


def layer_specs(model: dict) -> list[dict]:
    """The layer stack ``lead + pattern * repeats + tail`` of a config file."""
    return (
        list(model.get("lead", []))
        + list(model["pattern"]) * model["repeats"]
        + list(model.get("tail", []))
    )


COUNTS = Path(__file__).resolve().parent / "counts"


@functools.cache
def _counter(path: Path):
    if not path.is_file():
        raise ValueError(f"no FLOP counter for layer kind {path.stem!r}: {path}")
    spec = importlib.util.spec_from_file_location(f"counts_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.fwd


def model_flops_per_token(model: dict, seq_len: int, counts: Path = COUNTS) -> float:
    """Forward + backward FLOPs per trained token, no recompute."""
    fwd = 0.0
    for spec in layer_specs(model):
        kinds = [spec["kind"]]
        if spec.get("moe"):
            kinds.append("moe")
        elif model.get("d_ff", 0) > 0:
            kinds.append("mlp")
        for kind in kinds:
            fwd += _counter(counts / f"{kind}.py")(model, spec, seq_len)
    fwd += 2 * model["d_model"] * model["vocab_size"]
    return 3 * fwd


def leaf_route(shape: tuple[int, ...], stacked: bool, rank: int, min_numel: int):
    """``(n, m, r)`` for a leaf the low-rank path compresses, else None.

    The routing rule of the paper's PowerSGD baseline: a leaf whose every
    instance is a matrix (collapsed to ``(prod(leading), last)``), that
    holds at least ``min_numel`` values, and for which rank r is smaller
    than the matrix; everything else is synced whole.
    """
    inst = shape[1:] if stacked else shape
    if len(inst) < 2 or math.prod(shape) < min_numel:
        return None
    n, m = math.prod(inst[:-1]), inst[-1]
    r = min(rank, n, m)
    return (n, m, r) if n * m > r * (n + m) else None


def compress_cost(
    leaves: list[tuple[tuple[int, ...], bool]],
    *,
    rank: int,
    grad_bytes: int,
    err_bytes: int,
    min_numel: int = 1024,
) -> tuple[float, float]:
    """(bytes, FLOPs) of one sync of ``leaves`` = [(shape, stacked), ...]."""
    total_bytes = 0.0
    total_flops = 0.0
    for shape, stacked in leaves:
        route = leaf_route(shape, stacked, rank, min_numel)
        if route is None:
            total_bytes += grad_bytes * math.prod(shape)
            continue
        n, m, r = route
        inst = shape[0] if stacked else 1
        nm = n * m
        total_bytes += inst * (
            3 * nm * (grad_bytes + err_bytes) + nm * err_bytes + 16 * (n + m) * r
        )
        total_flops += inst * (6 * nm * r + 2 * nm)
    return total_bytes, total_flops
