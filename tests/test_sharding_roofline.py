"""Roofline analysis unit tests: collective bytes parsed from HLO text
and the three roofline terms."""

import numpy as np

from repro.roofline.analysis import collective_bytes_from_hlo, roofline_terms


HLO_SAMPLE = """
  %ag = bf16[16,512,7168]{2,1,0} all-gather(%p0), channel_id=1, replica_groups={{0,1,2,3}}, dimensions={0}
  %ar = f32[1024]{0} all-reduce(%x), replica_groups=[32,16]<=[512], to_apply=%add
  %rs = f32[64,128]{1,0} reduce-scatter(%y), replica_groups={{0,1}}, dimensions={0}
  %cp = f32[8,8]{1,0} collective-permute(%z), source_target_pairs={{0,1}}
  %ags = (bf16[4,4]{1,0}, bf16[4,4]{1,0}) all-gather-start(%q), replica_groups={{0,1,2,3}}
  %agd = bf16[4,4]{1,0} all-gather-done(%ags)
"""


def test_collective_parse():
    out = collective_bytes_from_hlo(HLO_SAMPLE)
    by = out["by_op"]
    ag = 16 * 512 * 7168 * 2 * (3 / 4)          # (G-1)/G * result
    ar = 1024 * 4 * 2 * (15 / 16)               # 2(G-1)/G, G=16 (iota)
    rs = 64 * 128 * 4 * 1                       # (G-1), G=2
    cp = 8 * 8 * 4
    ags = 2 * (4 * 4 * 2) * (3 / 4)             # tuple result, started op
    assert np.isclose(by["all-gather"], ag + ags)
    assert np.isclose(by["all-reduce"], ar)
    assert np.isclose(by["reduce-scatter"], rs)
    assert np.isclose(by["collective-permute"], cp)
    assert out["count"] == 5                     # -done not counted


def test_roofline_terms_dominant():
    cost = {"flops": 197e12, "bytes accessed": 819e9 * 2}
    coll = {"total": 50e9 * 0.5}
    r = roofline_terms(cost, coll)
    assert abs(r["compute_s"] - 1.0) < 1e-9
    assert abs(r["memory_s"] - 2.0) < 1e-9
    assert abs(r["collective_s"] - 0.5) < 1e-9
    assert r["dominant"] == "memory_s"

