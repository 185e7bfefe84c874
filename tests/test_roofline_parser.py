"""Roofline extraction: collective-byte parser + term arithmetic."""

import pytest

from repro.launch.roofline import (
    RooflineTerms,
    chip_peaks,
    collective_bytes_from_hlo,
)

V5E = "TPU v5 lite"

HLO = """
ENTRY %main {
  %p0 = bf16[16,512]{1,0} parameter(0)
  %ag = bf16[256,512]{1,0} all-gather(bf16[16,512]{1,0} %p0), dimensions={0}
  %ar = f32[1024]{0} all-reduce(f32[1024]{0} %x), to_apply=%add
  %rs = f32[64,32]{1,0} reduce-scatter(f32[1024,32]{1,0} %y), dimensions={0}
  %cp = bf16[8,128]{1,0} collective-permute(bf16[8,128]{1,0} %z)
  %a2a = (f32[4,4]{1,0}, f32[4,4]{1,0}) all-to-all(f32[4,4]{1,0} %q, f32[4,4]{1,0} %r)
  %ags = bf16[32,16]{1,0} all-gather-start(bf16[2,16]{1,0} %w)
}
"""


def test_collective_parser_counts_and_bytes():
    res = collective_bytes_from_hlo(HLO)
    by = res["bytes_by_type"]
    assert by["all-gather"] == 256 * 512 * 2 + 32 * 16 * 2
    assert by["all-reduce"] == 1024 * 4
    assert by["reduce-scatter"] == 64 * 32 * 4
    assert by["collective-permute"] == 8 * 128 * 2
    assert by["all-to-all"] == 2 * 4 * 4 * 4
    assert res["counts_by_type"]["all-gather"] == 2
    assert res["total_bytes"] == sum(by.values())


def test_roofline_terms_and_bottleneck():
    pk = chip_peaks(V5E)
    t = RooflineTerms(
        arch="a", shape="s", mesh="m", chips=256,
        hlo_flops=256 * pk.bf16_flops,       # exactly 1 s of compute
        hlo_bytes=256 * pk.hbm_bw * 0.5,     # 0.5 s of HBM
        collective_bytes=pk.ici_link_bw * 0.25,  # 0.25 s of ICI
        model_flops=128 * pk.bf16_flops,
        device_kind=V5E,
    ).finalize()
    assert t.compute_s == pytest.approx(1.0)
    assert t.memory_s == pytest.approx(0.5)
    assert t.collective_s == pytest.approx(0.25)
    assert t.bottleneck == "compute"
    assert t.useful_ratio == pytest.approx(0.5)


def test_chip_peaks_table_has_no_default():
    """v5e carries its published peaks; an unknown chip is an error."""
    pk = chip_peaks(V5E)
    assert (pk.bf16_flops, pk.hbm_bw, pk.hbm_bytes) == (197e12, 819e9, 16e9)
    with pytest.raises(KeyError, match="no published peaks"):
        chip_peaks("TPU v9 imaginary")
    t = RooflineTerms(
        arch="a", shape="s", mesh="m", chips=1, hlo_flops=1.0,
        hlo_bytes=1.0, collective_bytes=0.0, model_flops=1.0,
        device_kind="cpu",
    )
    with pytest.raises(KeyError):
        t.finalize()
