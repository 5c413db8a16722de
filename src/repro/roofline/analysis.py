"""Three-term roofline from the compiled (SPMD-partitioned, per-device) HLO.

  compute    = flops_per_device / peak_flops          (MXU-bound time)
  memory     = bytes_per_device / hbm_bw              (HBM-bound time)
  collective = ici_bytes_per_device / link_bw         (ICI-bound time)

flops / bytes come from ``compiled.cost_analysis()`` (per-device, since the
compiled module is the per-device SPMD program). Collective bytes are NOT in
cost_analysis: we parse the optimized HLO text and sum wire bytes per
collective with ring-algorithm multipliers over the op's replica-group size G:

  all-gather         (G-1)/G * result_bytes
  all-reduce       2*(G-1)/G * result_bytes
  reduce-scatter     (G-1)   * result_bytes     (operand = G * result)
  all-to-all         (G-1)/G * result_bytes
  collective-permute          result_bytes

Hardware model (TPU v5e): 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI.
"""

from __future__ import annotations

import re

HW = {
    "peak_flops": 197e12,    # bf16 / chip
    "hbm_bw": 819e9,         # bytes/s / chip
    "ici_bw": 50e9,          # bytes/s / link
}

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s*(?P<result>[^=]*?)\s*"
    r"(?P<op>all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(?P<start>-start)?\(")
_SHAPE_RE = re.compile(r"([a-z]+[0-9]*(?:e[0-9a-z]+)?)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([0-9, ]*)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(result_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(result_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d.strip():
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        ids = [x for x in m.group(1).split(",") if x.strip()]
        return max(1, len(ids))
    m = _GROUPS_IOTA_RE.search(line)
    if m:  # iota form [n_groups, group_size]<=[total]
        return max(1, int(m.group(2)))
    return 1


def _wire_multiplier(op: str, g: int) -> float:
    if op == "collective-permute":     # pairs, not groups: always moves data
        return 1.0
    if g <= 1:
        return 0.0
    if op == "all-gather":
        return (g - 1) / g
    if op == "all-reduce":
        return 2 * (g - 1) / g
    if op == "reduce-scatter":
        return float(g - 1)
    if op == "all-to-all":
        return (g - 1) / g
    if op == "collective-permute":
        return 1.0
    return 1.0


def collective_bytes_from_hlo(hlo_text: str) -> dict:
    """-> {"total": wire bytes/device, "by_op": {...}, "count": int}."""
    by_op: dict[str, float] = {}
    count = 0
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        op = m.group("op")
        result = m.group("result")
        g = _group_size(line)
        wire = _shape_bytes(result) * _wire_multiplier(op, g)
        by_op[op] = by_op.get(op, 0.0) + wire
        count += 1
    return {"total": sum(by_op.values()), "by_op": by_op, "count": count}


def roofline_terms(cost: dict, coll: dict, *, hw: dict = HW) -> dict:
    """Seconds per step for each roofline term + the dominant one."""
    flops = float(cost.get("flops", 0.0))
    bytes_hbm = float(cost.get("bytes accessed", 0.0))
    bytes_ici = float(coll["total"])
    terms = {
        "compute_s": flops / hw["peak_flops"],
        "memory_s": bytes_hbm / hw["hbm_bw"],
        "collective_s": bytes_ici / hw["ici_bw"],
    }
    dominant = max(terms, key=terms.get)
    bound = max(terms.values())
    total = sum(terms.values())
    return {**terms, "dominant": dominant,
            "flops_per_dev": flops, "hbm_bytes_per_dev": bytes_hbm,
            "ici_bytes_per_dev": bytes_ici,
            # fraction of ideal: if perfectly overlapped, step time = max term
            "overlap_roofline_frac": bound / total if total > 0 else 0.0}

