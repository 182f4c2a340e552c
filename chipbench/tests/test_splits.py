"""Tests of ``chipbench.splits``, the program-tag splits of a traced step,
on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest -q chipbench/tests
"""

from __future__ import annotations

import json
import sys
import threading
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import splits  # noqa: E402
from chipbench import trace as tr  # noqa: E402

DATA = ROOT / "chipbench" / "testdata"

HLO = """HloModule m

FileNames
1 "a/step.py"

%fused_computation.1 (p: f32[64]) -> f32[64] {
  %p = f32[64]{0} parameter(0)
  %d = f32[2]{0} slice(%p), slice={[0:2]}, metadata={op_name="jit(s)/comp.lq_sgd.eager/codec.decode/mul"}
  %x = f32[2]{0} exponential(%d), metadata={op_name="jit(s)/comp.lq_sgd.eager/codec.decode/exp"}
  %e = f32[64]{0} subtract(%p, %p), metadata={op_name="jit(s)/comp.lq_sgd.eager/lowrank.power/sub"}
  ROOT %w = f32[64]{0} multiply(%e, %p), metadata={op_name="jit(s)/train.optimizer/mul"}
}

%fused_computation.2 (q: f32[4]) -> f32[4] {
  %q = f32[4]{0} parameter(0)
  %r = f32[4]{0} multiply(%q, %q), metadata={op_name="jit(s)/transpose(jvp())/model.mlp/mul"}
  ROOT %s = f32[4]{0} add(%r, %q), metadata={op_name="jit(s)/transpose(jvp())/while/body/checkpoint/rematted_computation/model.mixer/add"}
}

ENTRY %main.9 (x: f32[4]) -> f32[4] {
  %x = f32[4]{0:T(256)} parameter(0)
  %emb.1 = f32[4]{0} multiply(%x, %x), metadata={op_name="jit(s)/jvp()/model.head/mul"}
  %mix.2 = f32[4]{0} multiply(%emb.1, %x), metadata={op_name="jit(s)/jvp()/while/body/model.mixer/mul"}
  %fusion.3 = f32[4]{0} fusion(%mix.2), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(s)/transpose(jvp())/model.mlp/mul"}
  %orth.4 = f32[4]{0} divide(%fusion.3, %x), metadata={op_name="jit(s)/comp.lq_sgd.eager/lowrank.orth/div"}
  %enc.5 = f32[4]{0} multiply(%orth.4, %x), metadata={op_name="jit(s)/comp.lq_sgd.eager/codec.encode/mul"}
  %all-gather.6 = f32[8]{0} all-gather(%enc.5), dimensions={0}, metadata={op_name="jit(s)/comp.lq_sgd.eager/all_gather"}
  %fusion.7 = f32[64]{0} fusion(%enc.5), kind=kLoop, calls=%fused_computation.1
  %norm.8 = f32[4]{0} multiply(%x, %x), metadata={op_name="jit(s)/jvp()/rms_norm/mul"}
  ROOT %met.9 = f32[4]{0} multiply(%fusion.7, %x), metadata={op_name="jit(s)/train.metrics/mul"}
}
"""

# [op, start, duration] in ns on one device: the window is [0, 300]
OPS = [
    ["%emb.1 = f32[4]", 0, 10],
    ["%mix.2 = f32[4]", 10, 40],
    ["%fusion.3 = f32[4]", 50, 30],
    ["%orth.4 = f32[4]", 80, 5],
    ["%enc.5 = f32[4]", 85, 15],
    ["%all-gather.6 = f32[8]", 100, 20],
    ["%fusion.7 = f32[4]", 120, 25],
    ["%norm.8 = f32[4]", 145, 5],
    ["%met.9 = f32[4]", 150, 10],
    # idle 160..240 (inside the dispatch span), then 250..300 (no span)
    ["%mix.2 = f32[4]", 240, 10],
]
HOST = [["$runtime.py:300 run", 0, 300], ["$api.py:2894 device_get", 245, 55]]
SPANS = [
    ["runtime.prefetch_wait", 150, 5],
    ["runtime.dispatch", 155, 90],
    ["runtime.batch_build", 20, 100],
]


def _trace():
    return {
        "window": [0, 300],
        "devices": {"0": {"ops": OPS, "async": []}},
        "host": HOST,
    }


def _top(parts: dict) -> float:
    """The sum of a bucket's outermost parts and ``other``."""
    return sum(ms for name, ms in parts.items() if "/" not in name)


def test_parts_of_a_hand_made_trace_add_up_to_their_buckets():
    """The open tag rule gives the parts the fixed tables of tags gave
    (``mixer``, ``mlp``, ``head``, ``optimizer``; ``orth``, ``power``,
    ``codec``), under the tags' own names."""
    scopes = tr.scope_map(HLO)
    whole = tr.reduce_trace(_trace(), scopes, steps=1)
    ops = splits.op_ms(_trace(), scopes, steps=1)
    got = splits.split_trace(ops, splits.sub_map(HLO))
    model, comp = got["model"], got["compress"]
    assert model["model.head"] == pytest.approx(10e-6)
    assert model["model.mixer"] == pytest.approx(50e-6)
    assert model["model.mlp"] == pytest.approx(30e-6)  # 2 mlp, 1 mixer instruction
    assert model["other"] == pytest.approx(5e-6)
    assert "train.optimizer" not in model  # fused into the compressor's pass
    assert got["bwd"] == pytest.approx(30e-6)
    assert got["bwd_remat"] == 0.0  # 1 of its 3 instructions, not most
    assert comp["lowrank.orth"] == pytest.approx(5e-6)
    assert comp["codec.encode"] == pytest.approx(15e-6)  # the gather is not in it
    assert "codec.decode" not in comp
    assert comp["lowrank.power"] == pytest.approx(25e-6)
    assert _top(model) == pytest.approx(whole["model_ms"])
    assert _top(comp) == pytest.approx(whole["compress_ms"])
    assert got["bwd"] <= whole["model_ms"]
    by_count = splits.split_trace(ops, splits.sub_map(HLO, by_bytes=False))
    assert by_count["compress"]["codec.decode"] == pytest.approx(25e-6)  # 2 decodes
    assert "lowrank.power" not in by_count["compress"]
    assert _top(by_count["compress"]) == pytest.approx(whole["compress_ms"])


def test_tags_are_read_through_jax_wrappers():
    assert splits.tags("jit(f)/transpose(jvp(model.head))/mul") == ("model.head",)
    assert splits.tags("jit(s)/comp.lq_sgd.eager/codec.decode/mul") == (
        "codec.decode",
    )
    remat = "jit(s)/transpose(jvp())/checkpoint/rematted_computation/"
    assert splits.tags(remat + "model.mixer/ssd.cb/dot_general") == (
        "model.mixer",
        "ssd.cb",
    )
    assert splits.tags("jit(s)/jvp(model.mlp)/while/body/model.mlp/add") == (
        "model.mlp",
    )
    assert splits.tags("jit(step_fn)/vmap(jit(norm))/lnm,lnr->lmr") == ()


def test_sub_map_votes_and_passes():
    subs = splits.sub_map(HLO)
    assert subs["fusion.7"]["compress"] == ("lowrank.power",)  # 64 of 68 words
    assert subs["fusion.7"]["model"] == ("train.optimizer",)
    assert subs["fusion.7"]["phases"] == [
        "codec.decode",
        "lowrank.power",
        "train.optimizer",
    ]
    by_count = splits.sub_map(HLO, by_bytes=False)
    assert by_count["fusion.7"]["compress"] == ("codec.decode",)
    assert subs["fusion.3"]["backward"] and not subs["fusion.3"]["remat"]
    assert not subs["mix.2"]["backward"]
    assert subs["norm.8"]["model"] is None
    assert subs["all-gather.6"]["compress"] is None


def test_mixed_ops_name_where_each_was_counted():
    scopes = tr.scope_map(HLO)
    ops = splits.op_ms(_trace(), scopes, steps=1)
    by_count = splits.sub_map(HLO, by_bytes=False)
    got = splits.mixed_ops(ops, splits.sub_map(HLO), by_count, min_ms=0.0)
    assert [r[0] for r in got] == ["fusion.3", "fusion.7"]  # longest first
    assert got[0][1:5] == ["model", pytest.approx(30e-6), "model.mlp", "model.mlp"]
    instr, bucket, ms, part, count, phases = got[1]
    assert (bucket, part, count) == ("compress", "lowrank.power", "codec.decode")
    assert ms == pytest.approx(25e-6)
    assert phases == ["codec.decode", "lowrank.power", "train.optimizer"]
    assert splits.mixed_ops(ops, splits.sub_map(HLO), by_count) == []  # < 1 ms


def test_idle_gaps_take_the_runtime_span_that_holds_them():
    scopes = tr.scope_map(HLO)
    spanned = {**_trace(), "spans": SPANS}
    gaps = dict(tr.reduce_trace(spanned, scopes, steps=1)["idle_gaps"])
    assert gaps["runtime.dispatch"] == pytest.approx(80e-9)
    # no runtime span holds the last gap: the main thread's innermost span
    assert gaps["$api.py:2894 device_get"] == pytest.approx(50e-9)
    # without spans the main thread's innermost span names every gap
    plain = dict(tr.reduce_trace(_trace(), scopes, steps=1)["idle_gaps"])
    assert plain["$runtime.py:300 run"] == pytest.approx(80e-9)
    assert "runtime.dispatch" not in plain


def test_span_ms_per_step_within_the_window():
    from chipbench.run import span_ms

    before = {"runtime.dispatch": 1.0, "runtime.drain": 0.5}
    after = {"runtime.dispatch": 1.09, "runtime.drain": 0.52, "runtime.x": 0.004}
    got = span_ms(before, after, steps=2)
    assert got["runtime.dispatch"] == pytest.approx(45.0)
    assert got["runtime.drain"] == pytest.approx(10.0)
    assert got["runtime.x"] == pytest.approx(2.0)  # a span new in the window


def test_recorded_chip_trace_reads_as_before():
    """The recorded trace of a program without the tags: its expectation
    holds unchanged and, as under the fixed tables of tags, no op has a
    part and all of each bucket is ``other``."""
    trace = json.loads((DATA / "trace_m1.json").read_text())
    hlo = (DATA / "step_m1.hlo.txt").read_text()
    scopes = tr.scope_map(hlo)
    whole = tr.reduce_trace(trace, scopes, steps=1)
    want = json.loads((DATA / "trace_m1.expected.json").read_text())
    for key, value in want.items():
        assert whole[key] == pytest.approx(value, rel=1e-9), key
    got = splits.split_trace(splits.op_ms(trace, scopes, 1), splits.sub_map(hlo))
    assert got["model"] == {"other": pytest.approx(whole["model_ms"], rel=1e-9)}
    assert got["compress"] == {"other": pytest.approx(whole["compress_ms"], rel=1e-9)}
    assert 0 < got["bwd"] <= whole["model_ms"]  # the slice's transpose( ops
    assert whole["idle_gaps"] == tr.reduce_trace({**trace, "spans": []}, scopes, 1)[
        "idle_gaps"
    ]


def test_strip_metadata_keeps_the_program():
    other = HLO.replace('"a/step.py"', '"b/step.py"').replace("model.mixer", "x")
    assert other != HLO
    assert splits.strip_metadata(other) == splits.strip_metadata(HLO)
    assert "metadata" not in splits.strip_metadata(HLO)
    assert "fusion(%enc.5)" in splits.strip_metadata(HLO)
    changed = HLO.replace("divide(%fusion.3", "multiply(%fusion.3")
    assert splits.strip_metadata(changed) != splits.strip_metadata(HLO)


def test_load_spans_reads_every_host_thread(tmp_path):
    import jax

    def annotate(name):
        with jax.profiler.TraceAnnotation(name):
            pass

    with jax.profiler.trace(str(tmp_path)):
        annotate("runtime.dispatch")
        annotate("not.a.span")
        t = threading.Thread(target=annotate, args=("runtime.batch_build",))
        t.start()
        t.join(timeout=30)
    assert not t.is_alive()
    names = [name for name, _, _ in tr._spans(tr._xplane(str(tmp_path)).planes)]
    assert sorted(names) == ["runtime.batch_build", "runtime.dispatch"]


def test_main_writes_the_step_and_trace(tmp_path, monkeypatch, capsys):
    import gzip

    seen = {"hlo": HLO, "trace": {**_trace(), "spans": SPANS}}
    monkeypatch.setattr(splits, "measure", lambda spec, **kw: ({"ok": 1}, seen))
    cell = json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"][0]
    argv = ["--workload", cell["name"], "--seed", "5", "--out", str(tmp_path)]
    assert splits.main(argv) == 0
    assert json.loads(capsys.readouterr().out.splitlines()[-1]) == {"ok": 1}
    stripped = (tmp_path / "step.stripped.hlo").read_text()
    assert stripped == splits.strip_metadata(HLO)
    with gzip.open(tmp_path / "step.hlo.txt.gz", "rt") as f:
        assert f.read() == HLO
    with gzip.open(tmp_path / "trace.json.gz", "rt") as f:
        assert json.load(f) == {**_trace(), "spans": SPANS}
