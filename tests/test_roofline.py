"""Roofline extraction: HLO collective parser + term math."""
import pytest

from repro.launch.mesh import peaks
from repro.launch.roofline import (collective_bytes, roofline_terms,
                                   _shape_bytes)


HLO = """
HloModule test
  %ag = bf16[16,1024]{1,0} all-gather(bf16[1,1024]{1,0} %x), dimensions={0}
  %ar = f32[256,64]{1,0} all-reduce(f32[256,64]{1,0} %y), to_apply=%add
  %rs = f32[2,8]{1,0} reduce-scatter(f32[16,8]{1,0} %z), dimensions={0}
  %a2a = (f32[4,4]{1,0}, f32[4,4]{1,0}) all-to-all(f32[4,4]{1,0} %a, f32[4,4]{1,0} %b)
  %cp = u32[128]{0} collective-permute(u32[128]{0} %c), source_target_pairs={{0,1}}
  %dot = f32[8,8]{1,0} dot(f32[8,8]{1,0} %p, f32[8,8]{1,0} %q)
"""


def test_shape_bytes():
    assert _shape_bytes("bf16[16,1024]") == 16 * 1024 * 2
    assert _shape_bytes("(f32[4,4], f32[4,4])") == 2 * 16 * 4
    assert _shape_bytes("u32[128]") == 512
    assert _shape_bytes("f32[]") == 4


def test_collective_parser():
    c = collective_bytes(HLO)
    assert c["all-gather"] == 16 * 1024 * 2
    assert c["all-reduce"] == 256 * 64 * 4
    assert c["reduce-scatter"] == 2 * 8 * 4
    assert c["all-to-all"] == 2 * 16 * 4
    assert c["collective-permute"] == 128 * 4


def test_dot_not_counted():
    c = collective_bytes(HLO)
    expected = (16 * 1024 * 2 + 256 * 64 * 4 + 2 * 8 * 4 + 2 * 16 * 4
                + 128 * 4)
    assert sum(c.values()) == expected        # exactly the collectives


def test_roofline_terms():
    pk = peaks("TPU v5 lite")
    r = roofline_terms(pk["peak_flops"], pk["hbm_bw"],
                       {"all-gather": pk["ici_bw"], "all-reduce": 0,
                        "reduce-scatter": 0, "all-to-all": 0,
                        "collective-permute": 0})
    assert abs(r["compute_s"] - 1.0) < 1e-9
    assert abs(r["memory_s"] - 1.0) < 1e-9
    assert abs(r["collective_s"] - 1.0) < 1e-9
    assert r["roofline_fraction"] == pytest.approx(1.0)


def test_allreduce_double_counted():
    r = roofline_terms(0, 0, {"all-gather": 0, "all-reduce": 200e9,
                              "reduce-scatter": 0, "all-to-all": 0,
                              "collective-permute": 0})
    assert abs(r["collective_s"] - 2.0) < 1e-9


def test_dominant_label():
    r = roofline_terms(1e15, 1e9, {"all-gather": 0, "all-reduce": 0,
                                   "reduce-scatter": 0, "all-to-all": 0,
                                   "collective-permute": 0})
    assert r["dominant"] == "compute_s"


def test_unknown_device_kind_has_no_peaks():
    with pytest.raises(ValueError, match="no published peaks"):
        roofline_terms(1.0, 1.0, {}, device_kind="cpu")
