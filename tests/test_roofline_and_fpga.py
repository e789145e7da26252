"""Roofline parser + the paper's analytic FPGA model (Eq. 1/2, Fig. 1,
Table 2 reproduction checks)."""
import pytest

from repro.core import fpga_model as F
from repro.roofline import analysis

HLO = """
HloModule test
  %ar = f32[256,1024]{1,0} all-reduce(f32[256,1024]{1,0} %x), replica_groups={{0,1,2,3}}, to_apply=%sum
  %ag = bf16[64,2048]{1,0} all-gather(bf16[64,128]{1,0} %y), replica_groups=[16,16]<=[256], dimensions={1}
  %rs = f32[16,64]{1,0} reduce-scatter(f32[16,1024]{1,0} %z), replica_groups=[1,16]<=[16], dimensions={1}
  %cp = bf16[8,128]{1,0} collective-permute(bf16[8,128]{1,0} %w), source_target_pairs={{0,1},{1,0}}
  %a2a = f32[32,32]{1,0} all-to-all(f32[32,32]{1,0} %v), replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}
"""


def test_collective_parser():
    colls = analysis.parse_collectives(HLO)
    by = {c.op: c for c in colls}
    assert len(colls) == 5
    ar = by["all-reduce"]
    assert ar.result_bytes == 256 * 1024 * 4 and ar.group_size == 4
    assert ar.link_bytes == pytest.approx(2 * 3 / 4 * 256 * 1024 * 4)
    ag = by["all-gather"]
    assert ag.group_size == 16
    assert ag.link_bytes == pytest.approx(15 / 16 * 64 * 2048 * 2)
    rs = by["reduce-scatter"]
    assert rs.link_bytes == pytest.approx(15 * 16 * 64 * 4)
    cp = by["collective-permute"]
    assert cp.link_bytes == 8 * 128 * 2
    a2a = by["all-to-all"]
    assert a2a.link_bytes == pytest.approx(7 / 8 * 32 * 32 * 4)


def test_roofline_terms_and_dominance():
    cost = {"flops": 197e12 * 0.5, "bytes accessed": 819e9 * 2.0}
    terms = analysis.roofline_terms(cost, HLO)
    assert terms["compute_s"] == pytest.approx(0.5)
    assert terms["memory_s"] == pytest.approx(2.0)
    assert analysis.dominant(terms) == "memory"


def test_unknown_device_kind_has_no_peaks():
    """Peaks come from the table for the device kind, never a default."""
    assert analysis.peaks("TPU v5 lite")["hbm_bw"] == 819e9
    with pytest.raises(ValueError, match="no roofline peaks"):
        analysis.roofline_terms({"flops": 1.0}, HLO, device_kind="cpu")


def test_model_flops():
    assert analysis.model_flops("train", 1e9, 8, 1024) == 6e9 * 8 * 1024
    assert analysis.model_flops("decode", 1e9, 128, 4096) == 2e9 * 128


# ---------------------------------------------------------------------------
# the paper's FPGA claims
# ---------------------------------------------------------------------------

def test_eq1_dsp_peak():
    # Eq (1) at the paper's 333 MHz, 4-bit packing p=4, all 9024 DSPs
    peak = F.dsp_peak_ops(F.U280, bits=4)
    assert peak == pytest.approx(4 * 9024 * 2 * 333e6)


def test_lutmul_peak_exceeds_dsp_peak():
    """The headline claim: LUT-based multiplication raises the roofline."""
    for overhead in (1.0, 2.0, 3.24):     # 3.24 = Fig.6 measured overhead
        lut_peak = F.lutmul_peak_ops(F.U280, bits=4, lut_overhead=overhead)
        dsp_peak = F.dsp_peak_ops(F.U280, bits=4)
        assert lut_peak > dsp_peak, overhead


def test_fig1_ridge_points():
    r = F.roofline(F.U280, bits=4, frac=1 / 64)
    assert r["lutmul_peak_ops"] > r["dsp_peak_ops"]
    # both rooflines meet bandwidth at their ridge intensity
    for kind in ("dsp", "lutmul"):
        ridge = r[f"{kind}_ridge_intensity"]
        at = r[f"{kind}_attainable"](ridge)
        assert at == pytest.approx(r[f"{kind}_peak_ops"], rel=1e-6)
        assert r[f"{kind}_attainable"](ridge / 10) == pytest.approx(
            r[f"{kind}_peak_ops"] / 10, rel=1e-6)


def test_folding_respects_budget_and_balances():
    from repro.models.mobilenet import MobileNetConfig, fpga_layer_table
    layers = fpga_layer_table(MobileNetConfig())
    res = F.balance_folding(layers, lut_budget=500_000, freq_hz=333e6,
                            lut_overhead=2.0, full_parallel_prefix=15)
    assert res["total_luts"] <= 500_000
    assert res["fps"] > 0
    # bottleneck stage defines fps
    assert res["fps"] == pytest.approx(333e6 / res["bottleneck_cycles"])


def test_mobilenet_macs_match_paper_ops():
    """Paper Table 2: 978.6 GOPS at 1627 FPS -> ~0.6 GOPs/frame.  Our layer
    table must reproduce MobileNetV2's MAC count (~300M MACs)."""
    from repro.models.mobilenet import MobileNetConfig, fpga_layer_table
    layers = fpga_layer_table(MobileNetConfig())
    macs = sum(lyr.macs for lyr in layers)
    assert 280e6 < macs < 330e6, macs / 1e6
