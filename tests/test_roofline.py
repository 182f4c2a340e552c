"""Roofline machinery: HLO collective parsing + analytic FLOPs validation."""
import jax
import jax.numpy as jnp

from repro.configs import INPUT_SHAPES, get_config
from repro.roofline import hw
from repro.roofline.analysis import RooflineReport, parse_collectives
from repro.roofline.flops_model import per_device_flops

HLO_SAMPLE = """
  %ar = f32[16,1024]{1,0} all-reduce(%x), replica_groups={}
  %ag.1 = bf16[4,128,256]{2,1,0} all-gather(%y), dimensions={0}
  %aa = s8[1000]{0} all-to-all(%z)
  %rs = f32[64]{0} reduce-scatter(%w)
  %cp-start = (f32[8]{0}) collective-permute-start(%v)
  %notacoll = f32[2,2]{1,0} add(%a, %b)
"""


def test_parse_collectives_counts_and_bytes():
    st = parse_collectives(HLO_SAMPLE)
    assert st.counts == {"all-reduce": 1, "all-gather": 1, "all-to-all": 1,
                         "reduce-scatter": 1, "collective-permute": 1}
    assert st.out_bytes["all-reduce"] == 16 * 1024 * 4
    assert st.out_bytes["all-gather"] == 4 * 128 * 256 * 2
    assert st.out_bytes["all-to-all"] == 1000
    # ring model: AR counts 2x
    assert st.wire_bytes >= st.total_out()


def test_parse_ignores_done_ops():
    txt = "%x = f32[8]{0} all-reduce-start(%a)\n%y = f32[8]{0} all-reduce-done(%x)"
    st = parse_collectives(txt)
    assert st.counts["all-reduce"] == 1


def test_roofline_terms_dominance():
    rep = RooflineReport(flops_per_device=hw.PEAK_FLOPS_BF16,  # 1 s compute
                         bytes_per_device=hw.HBM_BW / 10,      # 0.1 s
                         collectives=parse_collectives(""), chips=256)
    assert rep.dominant == "compute"
    assert abs(rep.compute_s - 1.0) < 1e-9
    d = rep.as_dict()
    assert d["dominant"] == "compute" and d["chips"] == 256


def test_cost_analysis_is_per_device():
    """The empirical fact the roofline math relies on."""
    mesh = jax.make_mesh((1,), ("model",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    with jax.set_mesh(mesh):
        m, k, n = 256, 256, 256
        low = jax.jit(lambda a, b: a @ b).lower(
            jax.ShapeDtypeStruct((m, k), jnp.float32),
            jax.ShapeDtypeStruct((k, n), jnp.float32))
        cost = low.compile().cost_analysis()
        assert abs(cost["flops"] - 2 * m * k * n) / (2 * m * k * n) < 0.01


def test_analytic_flops_vs_unrolled_gemma3():
    """The analytic model matched the UNROLLED 256-chip HLO within ~1%
    (measured in the dry-run: 9.063e13 flops/device). Pin it within 15% so
    model changes that break the accounting fail loudly."""
    cfg = get_config("gemma3-1b")
    f = per_device_flops(cfg, INPUT_SHAPES["train_4k"], ndp=16, msize=16,
                         remat=True)
    assert abs(f - 9.063e13) / 9.063e13 < 0.15


def test_analytic_flops_scaling_sanity():
    cfg = get_config("qwen2-72b")
    tr = per_device_flops(cfg, INPUT_SHAPES["train_4k"], ndp=16, msize=16)
    pf = per_device_flops(cfg, INPUT_SHAPES["prefill_32k"], ndp=16, msize=16)
    de = per_device_flops(cfg, INPUT_SHAPES["decode_32k"], ndp=16, msize=16)
    assert tr > pf > de                      # train > prefill >> decode
    # doubling DP halves per-device flops
    tr2 = per_device_flops(cfg, INPUT_SHAPES["train_4k"], ndp=32, msize=16)
    assert abs(tr2 - tr / 2) / tr < 0.01


def test_moe_flops_scale_with_topk_not_experts():
    ds = get_config("deepseek-v3-671b")
    f = per_device_flops(ds, INPUT_SHAPES["train_4k"], ndp=16, msize=16)
    # 671B total / 37B active: flops must reflect ACTIVE params
    # upper bound: 4x remat * 6 * 40B * tokens/dev / msize-ish
    tokens_dev = 256 * 4096 / 16
    assert f < 4 * 6 * 60e9 * tokens_dev / 4   # way below dense-all-experts
