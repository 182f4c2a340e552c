"""A new configuration is added to the benchmark as files alone, on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest -q chipbench/tests

Each test adds, in a temporary directory, what a configuration would bring
(its file with a cut layer stack, a FLOP counter for a new kind of layer,
a leaf init, a tag and the reader of its part) and edits nothing under
``chipbench/``.
"""

from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import flops, splits, weights  # noqa: E402
from chipbench import trace as tr  # noqa: E402
from chipbench.run import _module, model_config  # noqa: E402


def _registered(arch: str) -> dict:
    from repro.configs import get_config

    return json.loads(json.dumps(dataclasses.asdict(get_config(arch))))


# ------------------------------------------------------------ the layer stack
def _jamba_cut() -> dict:
    """Part of jamba-v0.1-52b's period of 8 as a stage's file: its first
    two layers unscanned, the next three scanned once, one more after."""
    model = _registered("jamba-v0.1-52b")
    period = model["pattern"]
    model |= {
        "name": "jamba-stage",
        "lead": period[:2],
        "pattern": period[2:5],
        "repeats": 1,
        "tail": period[5:6],
    }
    return {"arch": "jamba-v0.1-52b", "model": model}


def test_a_cut_layer_stack_resolves_and_round_trips(tmp_path):
    from repro.configs.base import attn, mamba

    path = tmp_path / "jamba-stage.json"
    path.write_text(json.dumps(_jamba_cut()))
    config = json.loads(path.read_text())
    cfg = model_config(config)
    assert cfg.layers == (
        mamba(),
        mamba(moe=True),
        mamba(),
        mamba(moe=True),
        attn(),
        mamba(moe=True),
    )
    ran = json.loads(json.dumps(dataclasses.asdict(cfg)))
    assert ran == config["model"]
    assert flops.layer_specs(config["model"]) == [
        dataclasses.asdict(s) for s in cfg.layers
    ]


def test_a_file_may_leave_out_a_field_at_the_programs_default():
    config = _jamba_cut()
    del config["model"]["moe_shard_hints"]  # False, the program's default
    for spec in config["model"]["pattern"]:
        del spec["rope_theta"]  # None, the default
    assert model_config(config).layers[2].rope_theta is None
    del config["model"]["n_experts"]  # 16 in the registry; the default is 0
    with pytest.raises(ValueError, match="n_experts"):
        model_config(config)


def test_a_stack_the_program_cannot_run_is_refused():
    model = _registered("mamba2-370m")
    model["tail"] = [dict(model["pattern"][0], kind="attn")]  # no heads
    with pytest.raises(AssertionError):
        model_config({"arch": "mamba2-370m", "model": model})
    config = _jamba_cut()
    config["model"]["pattern"][0]["experts"] = 4  # no such LayerSpec field
    with pytest.raises(TypeError, match="experts"):
        model_config(config)


# ------------------------------------------------------------ FLOP counters
def test_a_counter_module_is_found_by_its_kind(tmp_path):
    counts = tmp_path / "counts"
    counts.mkdir()
    (counts / "ffn.py").write_text(
        "def fwd(model, spec, seq_len):\n"
        "    return 2 * 3 * model['d_model'] * model['d_ff_expert'] + seq_len\n"
    )
    model = {
        "d_model": 4,
        "vocab_size": 10,
        "d_ff_expert": 5,
        "lead": [{"kind": "ffn", "moe": False}],
        "pattern": [{"kind": "ffn", "moe": False}],
        "repeats": 2,
    }
    layer = 2 * 3 * 4 * 5 + 8
    want = 3 * (3 * layer + 2 * 4 * 10)
    assert flops.model_flops_per_token(model, 8, counts=counts) == want
    model["pattern"][0]["moe"] = True
    with pytest.raises(ValueError, match=r"'moe'.*moe\.py"):
        flops.model_flops_per_token(model, 8, counts=counts)


def test_a_missing_counter_is_named_in_the_error():
    model = _registered("jamba-v0.1-52b")  # MoE layers: no moe counter yet
    with pytest.raises(ValueError, match=r"chipbench/counts/moe\.py"):
        flops.model_flops_per_token(model, 4096)


def test_the_ssd_counter_takes_the_inner_width_the_model_gives():
    count = _module(ROOT / "chipbench" / "counts" / "mamba.py").fwd
    model = _registered("mamba2-370m")
    derived = count(model, {"kind": "mamba"}, 2048)
    assert count({**model, "ssm_d_inner": 0}, {"kind": "mamba"}, 2048) == derived
    # 64 heads of 64 at d_model 1024: the heads' width, not ssm_expand's
    wide = count({**model, "ssm_d_inner": 4096}, {"kind": "mamba"}, 2048)
    d, di, n, p, q = 1024, 4096, 128, 64, 256
    h = di // p
    want = 2 * d * (2 * di + 2 * n + h) + 2 * di * d + 2 * 4 * (di + 2 * n)
    assert wide == want + 2 * (q * n + h * (q * p + 2 * p * n))


# ------------------------------------------------------------ leaf inits
def _abstract():
    import jax
    import jax.numpy as jnp

    return {
        "layer": {
            "router_bias": jax.ShapeDtypeStruct((8,), jnp.float32),
            "router": jax.ShapeDtypeStruct((16, 8), jnp.bfloat16),
        }
    }


def test_an_init_entry_gives_a_vector_its_rule():
    import numpy as np

    params = weights.init_params(2**31 + 5, _abstract(), init={"router_bias": "zeros"})
    assert np.all(np.asarray(params["layer"]["router_bias"]) == 0)
    assert params["layer"]["router_bias"].dtype == np.float32
    router = np.asarray(params["layer"]["router"], np.float32)
    assert 0.1 < router.std() < 0.5  # N(0, 1/16) by the matrix rule
    ones = weights.init_params(3, _abstract(), init={"router_bias": "ones"})
    assert np.all(np.asarray(ones["layer"]["router_bias"]) == 1)


def test_an_unknown_vector_without_an_entry_is_an_error():
    with pytest.raises(ValueError, match="no init rule for leaf 'router_bias'"):
        weights.init_params(3, _abstract())
    with pytest.raises(ValueError, match="not in"):
        weights.init_params(3, _abstract(), init={"router_bias": "uniform"})
    with pytest.raises(ValueError, match="its own init rule"):
        weights.init_params(3, _abstract(), init={"A_log": "zeros"})
    with pytest.raises(ValueError, match="needs a matrix"):
        weights.init_params(3, _abstract(), init={"router_bias": "fan_in"})


# ------------------------------------------------------------ tags and readers
HLO = """HloModule m

%fused_computation.1 (p: f32[64]) -> f32[64] {
  %p = f32[64]{0} parameter(0)
  %a = f32[64]{0} multiply(%p, %p), metadata={op_name="jit(s)/transpose(jvp())/model.mixer/foo.bar/mul"}
  ROOT %b = f32[64]{0} add(%a, %p), metadata={op_name="jit(s)/transpose(jvp())/model.mixer/foo.bar/add"}
}

ENTRY %main.4 (x: f32[64]) -> f32[64] {
  %x = f32[64]{0} parameter(0)
  %mix.1 = f32[64]{0} multiply(%x, %x), metadata={op_name="jit(s)/jvp()/model.mixer/mul"}
  %fusion.2 = f32[64]{0} fusion(%mix.1), kind=kLoop, calls=%fused_computation.1
  ROOT %head.3 = f32[64]{0} multiply(%fusion.2, %x), metadata={op_name="jit(s)/jvp(model.head)/mul"}
}
"""
OPS = [["%mix.1 = f32[64]", 0, 40], ["%fusion.2 = f32[64]", 40, 25]]
OPS += [["%head.3 = f32[64]", 65, 10]]


def test_a_new_tag_is_a_part_inside_its_enclosing_part(tmp_path):
    trace = {"window": [0, 100], "devices": {"0": {"ops": OPS, "async": []}}}
    scopes = tr.scope_map(HLO)
    parts = splits.split_trace(splits.op_ms(trace, scopes, 1), splits.sub_map(HLO))
    model = parts["model"]
    assert model["model.mixer/foo.bar"] == pytest.approx(25e-6)
    assert model["model.mixer"] == pytest.approx(65e-6)  # foo.bar's ops in it
    assert model["model.head"] == pytest.approx(10e-6)
    assert parts["bwd"] == pytest.approx(25e-6)
    whole = tr.reduce_trace(trace, scopes, steps=1)["model_ms"]
    top = sum(ms for name, ms in model.items() if "/" not in name)
    assert top == pytest.approx(whole)
    # and its reader is a file of its own, found by the metric's name
    reader = tmp_path / "foo_bar_ms.py"
    reader.write_text(
        "def read(ctx):\n"
        "    return ctx['parts']['model'].get('model.mixer/foo.bar')\n"
    )
    assert _module(reader).read({"parts": parts}) == pytest.approx(25e-6)
