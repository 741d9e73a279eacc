"""Roofline machinery: collective parsing + analytic model counts."""

import numpy as np

from repro.configs import INPUT_SHAPES, get_config
from repro.launch import analysis as AN

HLO = """
ENTRY %main {
  %p0 = bf16[16,4096,896]{2,1,0} parameter(0)
  %ag = bf16[16,4096,896]{2,1,0} all-gather(bf16[16,256,896]{2,1,0} %p0), dimensions={1}
  %ar = f32[8,1024]{1,0} all-reduce(f32[8,1024]{1,0} %x), to_apply=%sum
  %rs = bf16[8,128]{1,0} reduce-scatter(bf16[8,2048]{1,0} %y), dimensions={1}
  %cp = u32[4]{0} collective-permute(u32[4]{0} %z), source_target_pairs={{0,1}}
  %misc = f32[2]{0} add(f32[2]{0} %a, f32[2]{0} %b)
}
"""


def test_parse_collectives():
    st = AN.parse_collectives(HLO)
    assert st.counts == {"all-reduce": 1, "all-gather": 1,
                         "reduce-scatter": 1, "collective-permute": 1}
    # all-reduce ≈ 2×operand
    assert st.wire_bytes["all-reduce"] == 2 * 8 * 1024 * 4
    # all-gather ≈ result − operand
    assert st.wire_bytes["all-gather"] == (16 * 4096 * 896 - 16 * 256 * 896) * 2
    # reduce-scatter ≈ operand
    assert st.wire_bytes["reduce-scatter"] == 8 * 2048 * 2
    assert st.wire_bytes["collective-permute"] == 4 * 4


def test_active_params_moe_vs_dense():
    # Kimi K2 as published: MLA (101.1 M a layer), a dense layer (FFN
    # 18,432), 60 MoE layers of 384 experts, 8 routed and 1 shared a token
    # (44.0 M each), a 384-wide router, an untied 163,840-row vocabulary:
    # 1.0263e12 in all, 3.287e10 active a token
    kimi = get_config("kimi_k2_1t_a32b")
    total, active = AN.active_params(kimi)
    assert 1.02e12 < total < 1.04e12
    assert 3.1e10 < active < 3.4e10
    qwen = get_config("qwen2_0p5b")
    t2, a2 = AN.active_params(qwen)
    assert t2 == a2                       # dense: all params active
    assert 4.2e8 < t2 < 6e8


def test_model_flops_modes():
    cfg = get_config("qwen2_0p5b")
    tr = AN.model_flops(cfg, INPUT_SHAPES["train_4k"])
    pf = AN.model_flops(cfg, INPUT_SHAPES["prefill_32k"])
    dc = AN.model_flops(cfg, INPUT_SHAPES["decode_32k"])
    assert tr / pf == (6 * 256 * 4096) / (2 * 32 * 32768)
    assert dc < pf < tr


def test_roofline_dominant_term():
    r = AN.Roofline("a", "s", "m", 256, hlo_flops=1e18, hlo_bytes=1e12,
                    wire_bytes_per_chip=1e9, model_flops=5e17).finalize()
    assert r.dominant == "compute"
    assert 0 < r.useful_flops_frac <= 1
    r2 = AN.Roofline("a", "s", "m", 256, hlo_flops=1e15, hlo_bytes=1e12,
                     wire_bytes_per_chip=1e12, model_flops=5e14).finalize()
    assert r2.dominant == "collective"


def test_scan_interior_correction_positive_for_long_seq():
    cfg = get_config("qwen2_0p5b")
    fl, by = AN.scan_interior_correction(cfg, INPUT_SHAPES["prefill_32k"])
    assert fl > 0 and by > 0
    fl_d, by_d = AN.scan_interior_correction(cfg, INPUT_SHAPES["decode_32k"])
    assert fl_d == 0 and by_d == 0        # decode has no chunk scans
