"""Tests of the chip benchmark's yardstick, on the CPU.

    JAX_PLATFORMS=cpu python3 -m pytest -q chipbench/tests

They check the benchmark's data layout, its FLOP and byte counts and peaks
table, the trace reduction on a trace recorded on a v5e chip, and that the
correctness check passes a sound run and fails its control and the faults
a training cell can have, at sizes a CPU holds.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import check, flops, peaks  # noqa: E402
from chipbench import trace as tr  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
DATA = ROOT / "chipbench" / "testdata"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
ONE_LINE = re.compile(r"^[^\n\t]{1,200}$")


def _config(name: str) -> dict:
    return json.loads((ROOT / "chipbench" / "configs" / f"{name}.json").read_text())


# ------------------------------------------------------------ data layout
def test_benchmark_keys_and_command():
    assert set(BENCH) == {
        "command",
        "paths",
        "run_seconds",
        "configs",
        "workloads",
        "end_to_end",
        "per_layer",
    }
    assert BENCH["paths"] == ["chipbench"]
    assert BENCH["command"][1] == "chipbench/run.py"
    assert (ROOT / BENCH["command"][1]).is_file()
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_config_resolves_to_its_file(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(cfg["name"]) and ONE_LINE.match(cfg["why"])
    assert cfg["file"] == f"chipbench/configs/{cfg['name']}.json"
    body = _config(cfg["name"])
    assert body["name"] == cfg["name"] == body["model"]["name"]
    assert body["source"] == cfg["source"]
    assert body["reduced"] == cfg["reduced"]
    for key in cfg["reduced"]:
        assert NAME.match(key) and key in body["model"] and key in body["published"]
    assert (ROOT / "chipbench" / "reference" / f"{body['reference']}.py").is_file()
    assert any(w["config"] == cfg["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_workload_resolves_to_its_files(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    for key in ("name", "config", "traffic"):
        assert NAME.match(cell[key])
    assert ONE_LINE.match(cell["why"]) and cell["chips"] in (1, 4)
    assert cell["name"] == f"{cell['config']}.{cell['traffic']}"
    base = ROOT / "chipbench"
    assert (base / "configs" / f"{cell['config']}.json").is_file()
    traffic = json.loads((base / "traffic" / f"{cell['traffic']}.json").read_text())
    limits = json.loads((base / "limits" / f"{cell['name']}.json").read_text())
    readings = {"loss_gap", "loss1_gap", "grad_gap", "grad_mean_gap", "change_gap"}
    assert limits and set(limits) <= readings
    assert traffic["check_steps"] >= 2
    e2e = [m["name"] for m in BENCH["end_to_end"] if _covers(m, cell)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(_covers(m, cell) for m in BENCH["per_layer"])


def _covers(metric, cell):
    return cell["name"] in metric.get("workloads", [cell["name"]])


def test_pairs_are_unique_and_four_chip_cells_are_few():
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    names = [x["name"] for k in ("configs", "workloads") for x in BENCH[k]]
    names += [m["name"] for k in ("end_to_end", "per_layer") for m in BENCH[k]]
    assert len(set(names)) == len(names)
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)


@pytest.mark.parametrize(
    "metric",
    BENCH["end_to_end"] + BENCH["per_layer"],
    ids=lambda m: m["name"],
)
def test_metric_names_units_and_readers(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(metric.get("workloads", [])) <= cells
    if metric in BENCH["end_to_end"]:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
        return
    assert metric["source"] in (
        "device_trace",
        "program_span",
        "program_counter",
        "host_clock",
    )
    assert ONE_LINE.match(metric["layer"])
    assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
    reader = ROOT / "chipbench" / "metrics" / f"{metric['name']}.py"
    assert "def read(ctx)" in reader.read_text()


def test_layers_share_one_spelling():
    text = (ROOT / "PERF.md").read_text()
    for m in BENCH["per_layer"]:
        assert m["layer"] in text, m["layer"]


# ------------------------------------------------------------ counts and peaks
def test_model_flops_per_token_against_hand_counts():
    mamba = _config("mamba2-370m")["model"]
    # per layer: in_proj 2*1024*4384, out_proj 2*2048*1024, conv 2*4*2304,
    # SSD 2*(256*128 + 32*(256*64 + 2*64*128)), C·Bᵀ once for its one B/C
    # group and the rest per head; head 2*1024*50280; x3
    ssd = 2 * (256 * 128 + 32 * (256 * 64 + 2 * 64 * 128))
    layer = 2 * 1024 * 4384 + 2 * 2048 * 1024 + 2 * 4 * 2304 + ssd
    hand = 3 * (48 * layer + 2 * 1024 * 50280)
    assert flops.model_flops_per_token(mamba, 2048) == hand
    assert hand == pytest.approx(2.52e9, rel=1e-3)
    nemo = dict(_config("mistral-nemo-12b-l2")["model"], repeats=4)
    # per layer: q, k, v, o projections, QK^T and PV over S/2 = 2048 keys,
    # SwiGLU 3 * 2*5120*14336; head 2*5120*16384; x3
    proj = 2 * 5120 * 4096 * 2 + 2 * 2 * 5120 * 1024
    layer = proj + 2 * 2 * 2048 * 32 * 128 + 6 * 5120 * 14336
    hand = 3 * (4 * layer + 2 * 5120 * 16384)
    assert flops.model_flops_per_token(nemo, 4096) == hand
    assert hand == pytest.approx(7.45e9, rel=2e-3)


def test_compress_cost_of_a_small_leaf_set():
    leaves = [((3, 8, 16), True), ((16,), False), ((40, 10), False), ((4, 4), False)]
    got_bytes, got_flops = flops.compress_cost(
        leaves, rank=2, grad_bytes=2, err_bytes=4, min_numel=16
    )
    # (3, 8, 16) stacked: 3 instances of 8 x 16 at r 2; (40, 10): one at r 2;
    # (16,) and (4, 4) are synced whole (a vector; r(n+m) = 16 = n*m)
    inst = [(3, 8, 16), (1, 40, 10)]
    want_b = sum(k * (3 * n * m * 6 + n * m * 4 + 16 * (n + m) * 2) for k, n, m in inst)
    want_b += 2 * 16 + 2 * 16
    want_f = sum(k * (6 * n * m * 2 + 2 * n * m) for k, n, m in inst)
    assert (got_bytes, got_flops) == (want_b, want_f)


def test_unknown_device_kind_is_an_error():
    assert peaks.peaks_for("TPU v5 lite").bf16_flops == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v99")


# ------------------------------------------------------------ trace reduction
def test_reduction_of_a_hand_made_trace():
    hlo = """HloModule m

%fused_computation.1 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %a = f32[4]{0} add(%p, %p), metadata={op_name="jit(s)/comp.lq_sgd.eager/add"}
}

ENTRY %main.2 (x: f32[4]) -> f32[4] {
  %x = f32[4]{0:T(256)} parameter(0)
  %dot.3 = f32[4]{0:T(256)} multiply(%x, %x), metadata={op_name="jit(s)/jvp()/mul"}
  %fusion.4 = f32[4]{0:T(256)} fusion(%dot.3), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(s)/convert_element_type"}
  %all-gather.5 = f32[8]{0} all-gather(%fusion.4), dimensions={0}
  %while.6 = f32[4]{0} while(%x), condition=%c, body=%b
  ROOT %m.7 = f32[4]{0} multiply(%all-gather.5, %x), metadata={op_name="jit(s)/train.metrics/mul"}
}
"""
    scopes = tr.scope_map(hlo)
    assert scopes["fusion.4"] == ("compress", "fusion")
    assert scopes["dot.3"] == ("model", "multiply")
    ops = [
        ["%while.6 = f32[4]", 0, 100],  # holds the next two: not counted
        ["%dot.3 = f32[4]", 10, 30],
        ["%fusion.4 = f32[4]", 40, 20],
        ["%all-gather.5 = f32[8]", 70, 20],
        ["%m.7 = f32[4]", 120, 10],
    ]
    trace = {
        "window": [0, 200],
        "devices": {"0": {"ops": ops, "async": []}},
        "host": [["wait", 130, 70]],
    }
    got = tr.reduce_trace(trace, scopes, steps=1)
    assert got["busy_s"] == pytest.approx(80e-9)
    assert got["idle_pct"] == pytest.approx(60.0)
    assert got["model_ms"] == pytest.approx(30e-6)
    assert got["compress_ms"] == pytest.approx(20e-6)
    assert got["metrics_ms"] == pytest.approx(10e-6)
    assert got["collective_ms"] == pytest.approx(20e-6)
    assert got["collective_exposed_ms"] == pytest.approx(20e-6)
    assert got["idle_gaps"][0] == ["wait", pytest.approx(70e-9)]


RECORDED = sorted(p.name[6:-5] for p in DATA.glob("trace_*.json") if "." not in p.name[6:-5])


@pytest.mark.parametrize("name", RECORDED)
def test_reduction_of_a_recorded_chip_trace(name):
    """A slice of a profiled window of a cell on v5e, around its compressor
    (and, on four chips, its collectives), with the step's HLO excerpt."""
    trace = json.loads((DATA / f"trace_{name}.json").read_text())
    scopes = tr.scope_map((DATA / f"step_{name}.hlo.txt").read_text())
    got = tr.reduce_trace(trace, scopes, steps=1)
    want = json.loads((DATA / f"trace_{name}.expected.json").read_text())
    for key, value in want.items():
        assert got[key] == pytest.approx(value, rel=1e-9), key
    assert got["compress_ms"] > 0 and got["model_ms"] > 0
    assert got["busy_s"] <= got["window_s"]
    assert got["collective_exposed_ms"] <= got["collective_ms"]
    assert got["has_collectives"] == (got["collective_ms"] > 0)


# ------------------------------------------------------------ correctness check
def _smoke_spec(workload: str, **traffic) -> dict:
    """The cell at a size a CPU holds: the program's smoke sizes for its
    architecture, the cell's traffic at a short sequence, the cell's limits."""
    sys.path[:0] = [str(ROOT / "src")]
    from chipbench.run import load_cell
    from repro.configs import get_config

    spec = load_cell(workload)
    arch = spec["config"]["arch"]
    model = json.loads(json.dumps(dataclasses.asdict(get_config(arch, smoke=True))))
    spec["config"] = {**spec["config"], "name": model["name"], "model": model}
    spec["traffic"] = {**spec["traffic"], "seq_len": 64, **traffic}
    return spec


CELLS = [w["name"] for w in BENCH["workloads"] if w["chips"] == 1]


def _run(spec, wrap_step=None):
    from chipbench.run import run_cell

    return run_cell(
        spec, seed=2**31 + 77, seconds=0.5, trace=False, t_start=0.0,
        require_tpu=False, wrap_step=wrap_step,
    )  # fmt: skip


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload):
    res = _run(_smoke_spec(workload, batch_per_chip=2))
    assert res["correct"], res["checks"]
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "checks"


def _unchanged(step):
    def same_state(state, batch):
        import jax

        keep = jax.tree.map(lambda x: x.copy(), state)
        return keep, step(state, batch)[1]

    return same_state


def _half_rows(step):
    def half(state, batch):
        tok = batch["tokens"]
        return step(state, {"tokens": tok[: tok.shape[0] // 2]})

    return half


@pytest.mark.parametrize("fault", [_unchanged, _half_rows], ids=["unchanged", "half"])
@pytest.mark.parametrize("workload", CELLS)
def test_faults_fail_the_check(workload, fault):
    res = _run(_smoke_spec(workload, batch_per_chip=2), wrap_step=fault)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("workload", CELLS)
def test_control_fails_the_check(workload):
    import jax
    import jax.numpy as jnp

    from chipbench.run import Cell

    spec = _smoke_spec(workload, batch_per_chip=2)
    cell = Cell(spec, jax.devices()[:1])
    ref = cell.reference(11)
    low = cell.reference(11, low=jnp.float8_e4m3fn)
    values = check.readings(low, ref, spec["traffic"]["lr"])
    assert not check.judge(values, spec["limits"]), values


@pytest.mark.parametrize("scale", [1.0, 1e-6], ids=["unit", "tiny"])
def test_control_matmul_is_low_precision_forward_and_backward(scale):
    """The control's matmul rounds its operands forward and the cotangent
    that meets it backward, each with its own scale: its gradient differs
    from float32's by fp8 rounding, however small the cotangent, and is
    never flushed to zero."""
    import jax
    import jax.numpy as jnp

    from chipbench.run import _module

    lm = _module(ROOT / "chipbench" / "reference" / "lm.py")
    ka, kb, kw = jax.random.split(jax.random.PRNGKey(0), 3)
    a = jax.random.normal(ka, (64, 32))
    b = jax.random.normal(kb, (32, 16))
    w = scale * jax.random.normal(kw, (64, 16))

    def f(a, b, low):
        return jnp.sum(lm.mm("nd,de->ne", a, b, low) * w)

    for argnum in (0, 1):
        g32 = jax.grad(f, argnum)(a, b, None)
        g8 = jax.grad(f, argnum)(a, b, jnp.float8_e4m3fn)
        rel = float(jnp.linalg.norm(g8 - g32) / jnp.linalg.norm(g32))
        assert 0.005 < rel < 0.2, (argnum, rel)
    y32, y8 = f(a, b, None), f(a, b, jnp.float8_e4m3fn)
    assert 0 < abs(float(y8 - y32)) < 0.2 * float(jnp.sum(jnp.abs(w))) + 1e-30


_NO_EXCHANGE = """
import json, sys
sys.path[:0] = [{root!r}, {src!r}]
from chipbench.tests.test_chipbench import _smoke_spec, _run
from chipbench.calibrate import no_exchange
spec = _smoke_spec({workload!r}, batch_per_chip=2)
spec["cell"] = dict(spec["cell"], chips=4)
sound = _run(spec)
with no_exchange():
    lonely = _run(spec)
print(json.dumps([sound["correct"], lonely["correct"], lonely["checks"]]))
"""


FOUR_CHIP_CELLS = [w["name"] for w in BENCH["workloads"] if w["chips"] == 4]


@pytest.mark.parametrize("workload", FOUR_CHIP_CELLS or CELLS[:1])
def test_exchange_left_out_fails_the_check(workload):
    """A cell's program over four workers (four CPU devices): sound, it
    passes the check; with the compressor's collectives left out, each
    worker applies its own update and it fails."""
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
    )
    code = _NO_EXCHANGE.format(
        root=str(ROOT), src=str(ROOT / "src"), workload=workload
    )
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True,
        timeout=900, check=True,
    ).stdout  # fmt: skip
    sound, lonely, checks = json.loads(out.strip().splitlines()[-1])
    assert sound and not lonely, checks


def test_limits_are_finite_and_positive():
    for w in BENCH["workloads"]:
        limits = json.loads(
            (ROOT / "chipbench" / "limits" / f"{w['name']}.json").read_text()
        )
        assert all(0 < v < math.inf for v in limits.values())
