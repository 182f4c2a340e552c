"""The comparison that decides ``correct`` for a training cell.

Set-up drives the compiled step from the seed through its first K steps
(the window's own runner, batches and state); the reference follows the same
K steps from the same seed. ``readings`` gives five numbers; a cell compares
those that its ``chipbench/limits/<workload>.json`` lists, each against its
limit there:

* ``loss_gap``: the largest |loss_prog - loss_ref| / |loss_ref| over the
  first two steps (before and after the first synced update);
* ``loss1_gap``: the same for the first step alone, the forward pass from
  the seed's weights, with no compressor in it;
* ``grad_gap``: the gradient as the optimizer got it in step 1, worked out
  from the parameters after it, (w0 - w1) / lr. Per leaf, the gap between
  the program's norm and the reference's, over the larger of the
  reference's norm of that leaf and of the median leaf; the worst leaf;
* ``grad_mean_gap``: the mean of those per-leaf gaps over the leaves;
* ``change_gap``: the same gap for the parameters' change over the K steps,
  w_K - w0, of the median leaf, leaving out leaves whose reference gradient
  is under a thousandth of the median leaf's (they move by rounding alone).

Why a cell compares what it does, and from which readings each limit was
set, is in PERF.md §2. The later steps' losses and the worst leaf's change
are not compared: at the cells' learning rate a few seeds' losses jump
within three steps, and there those gaps grow by an order of magnitude on
some seeds while the first steps and the median leaf stay put. ``details``
gives them for the record.
"""

from __future__ import annotations

import math
import statistics

__all__ = ["readings", "details", "judge"]


def _gaps(prog: list[float], ref: list[float], keep: list[bool]) -> list[float]:
    med = statistics.median([r for r, k in zip(ref, keep) if k])
    out = []
    for p, r, k in zip(prog, ref, keep):
        if k:
            den = max(r, med)
            gap = abs(p - r) / den if den > 0 else abs(p - r)
            out.append(gap if math.isfinite(p) else math.inf)
    return out


def _loss_gaps(prog: dict, ref: dict) -> list[float]:
    return [
        abs(p - r) / abs(r) if math.isfinite(p) else math.inf
        for p, r in zip(prog["losses"], ref["losses"])
    ]


def _moves(ref: dict) -> list[bool]:
    g_med = statistics.median(ref["step1"])
    return [g >= 1e-3 * g_med for g in ref["step1"]]


def readings(prog: dict, ref: dict, lr: float) -> dict[str, float]:
    """``prog`` and ``ref`` each hold ``losses`` (per step), ``step1``
    (per-leaf ‖w1 - w0‖) and ``stepK`` (per-leaf ‖wK - w0‖)."""
    every = [True] * len(ref["step1"])
    g_prog = [v / lr for v in prog["step1"]]
    g_ref = [v / lr for v in ref["step1"]]
    grad = _gaps(g_prog, g_ref, every)
    change = _gaps(prog["stepK"], ref["stepK"], _moves(ref))
    loss = _loss_gaps(prog, ref)
    return {
        "loss_gap": max(loss[:2]),
        "loss1_gap": loss[0],
        "grad_gap": max(grad),
        "grad_mean_gap": statistics.fmean(grad),
        "change_gap": statistics.median(change),
    }


def details(prog: dict, ref: dict, names: list[str]) -> dict:
    """What is not compared: every step's loss gap, the worst leaf's change
    gap, and which leaves read worst."""
    change = _gaps(prog["stepK"], ref["stepK"], [True] * len(names))
    grad = _gaps(prog["step1"], ref["step1"], [True] * len(names))
    return {
        "loss_gaps": _loss_gaps(prog, ref),
        "change_worst": max(change),
        "change_worst_leaf": names[change.index(max(change))],
        "grad_worst_leaf": names[grad.index(max(grad))],
    }


def judge(values: dict[str, float], limits: dict[str, float]) -> bool:
    """Correct when every compared number is within its limit."""
    return all(values[k] <= limits[k] for k in limits)
