"""Collective-traffic extraction from optimized HLO text + 3-term roofline.

cost_analysis() gives HLO FLOPs and bytes but NOT collective traffic; we
parse the compiled module text and account every all-reduce / all-gather /
reduce-scatter / all-to-all / collective-permute.

Accounting (per device, ring algorithm):
  all-reduce       2 * size * (G-1)/G      (reduce-scatter + all-gather)
  all-gather       out_size * (G-1)/G
  reduce-scatter   in_size  * (G-1)/G
  all-to-all       size * (G-1)/G
  collective-permute  size
plus the raw operand-size sum (the assignment's simpler metric) — both are
reported; the time term uses the ring wire bytes.

Hardware constants: :data:`PEAKS`, keyed by ``device_kind`` as JAX
reports it. A kind that is not in the table is an error, not a default.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Tuple

# Per-chip peaks. TPU v5e ("TPU v5 lite"): Google Cloud documentation,
# "TPU v5e" — 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s of ICI per
# chip, i.e. 50 GB/s on each of its four links.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "link_bw": 50e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    """The peak table row for ``device_kind``; raises for an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peaks for device kind {device_kind!r}; "
                         f"known: {sorted(PEAKS)}") from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_OP_RE = re.compile(
    r"=\s+(\([^=]*?\)|\S+)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(",
)
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return 1


@dataclasses.dataclass
class CollectiveStats:
    ops: List[Dict]
    operand_bytes: int           # assignment metric: sum of operand sizes
    wire_bytes: int              # ring-model bytes per device

    def by_kind(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for op in self.ops:
            out[op["kind"]] = out.get(op["kind"], 0) + op["wire_bytes"]
        return out


def parse_collectives(hlo_text: str) -> CollectiveStats:
    ops = []
    operand_total = 0
    wire_total = 0
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        out_shape, kind = m.group(1), m.group(2)
        out_bytes = _shape_bytes(out_shape)
        g = max(_group_size(line), 1)
        if kind == "all-reduce":
            operand = out_bytes
            wire = int(2 * out_bytes * (g - 1) / g)
        elif kind == "all-gather":
            operand = out_bytes // g
            wire = int(out_bytes * (g - 1) / g)
        elif kind == "reduce-scatter":
            operand = out_bytes * g
            wire = int(operand * (g - 1) / g)
        elif kind == "all-to-all":
            operand = out_bytes
            wire = int(out_bytes * (g - 1) / g)
        else:  # collective-permute
            operand = out_bytes
            wire = out_bytes
        ops.append({"kind": kind, "bytes": out_bytes, "group": g,
                    "operand_bytes": operand, "wire_bytes": wire})
        operand_total += operand
        wire_total += wire
    return CollectiveStats(ops, operand_total, wire_total)


def roofline_terms(flops_per_device: float, hbm_bytes_per_device: float,
                   wire_bytes_per_device: float,
                   device_kind: str) -> Dict[str, float]:
    """Three per-device time terms (seconds) on ``device_kind``'s peaks +
    the dominant bottleneck."""
    pk = peaks(device_kind)
    t_compute = flops_per_device / pk["flops"]
    t_memory = hbm_bytes_per_device / pk["hbm_bw"]
    t_collective = wire_bytes_per_device / pk["link_bw"]
    terms = {"compute_s": t_compute, "memory_s": t_memory,
             "collective_s": t_collective}
    dom = max(terms, key=terms.get)
    terms["bottleneck"] = dom.replace("_s", "")
    # Roofline fraction: useful-compute time over the max term (how close the
    # dominant resource is to being the only cost).
    tmax = max(t_compute, t_memory, t_collective)
    terms["compute_fraction_of_bound"] = t_compute / tmax if tmax > 0 else 0.0
    return terms
