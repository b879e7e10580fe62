"""Roofline terms from a compiled XLA artifact (no hardware required).

Sources:
  * `compiled.cost_analysis()` — HLO FLOPs + bytes accessed. Verified to be
    **per-device** (post-SPMD-partitioning) on this JAX version, so the terms
    below are per-chip without further division.
  * `compiled.as_text()`     — per-device HLO; collective bytes are parsed by
    summing operand sizes of all-gather / all-reduce / reduce-scatter /
    all-to-all / collective-permute with per-op wire-byte formulas.

Terms (seconds, per chip):
    compute    = flops / PEAK_FLOPS_BF16
    memory     = bytes_accessed / HBM_BW
    collective = wire_bytes / ICI_BW_PER_LINK
"""
from __future__ import annotations

import dataclasses
import re

from repro.roofline import hw

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
    "token": 0, "opaque": 0,
}

_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")

_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)


def _shape_bytes(shape_str: str) -> int:
    """Total bytes of all array shapes in an HLO type string (handles tuples)."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _group_size(line: str, default: int) -> int:
    """Parse replica_groups= in either explicit or iota form."""
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=", line)
    if m:  # iota form [G,S]<=[...]: G groups of size S
        return max(int(m.group(2)), 1)
    m = re.search(r"replica_groups=\{\{([0-9, ]+)\}", line)
    if m:
        return max(len(m.group(1).split(",")), 1)
    return default


@dataclasses.dataclass
class CollectiveStats:
    bytes_by_kind: dict[str, int]
    count_by_kind: dict[str, int]

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_kind.values())


def collective_stats(hlo_text: str, world: int) -> CollectiveStats:
    """Per-device wire bytes for every collective in (post-SPMD) HLO text.

    Formulas (ring algorithms, per device):
      all-gather      (n-1)/n * output_bytes
      reduce-scatter  (n-1)/n * input_bytes
      all-reduce      2 (n-1)/n * input_bytes
      all-to-all      (n-1)/n * input_bytes
      collective-permute  input_bytes
    `*-start` ops are counted, their `*-done` twins skipped.
    """
    bytes_by = {k: 0 for k in _COLLECTIVES}
    count_by = {k: 0 for k in _COLLECTIVES}
    for raw in hlo_text.splitlines():
        line = raw.strip()
        # match "<shape> opname(" occurrences, skip -done ops
        m = re.match(r"(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.*?)\s+([a-z\-]+)(?:-start)?\(",
                     line)
        if not m:
            continue
        shape_str, op = m.group(1), m.group(2)
        if op.endswith("-done"):
            continue
        kind = next((k for k in _COLLECTIVES if op == k), None)
        if kind is None:
            continue
        n = _group_size(line, world)
        size = _shape_bytes(shape_str)
        if kind == "all-gather":
            wire = size * (n - 1) // max(n, 1)
        elif kind == "reduce-scatter":
            # result shape is the scattered (small) shape; wire ~ result*(n-1)
            wire = size * (n - 1)
        elif kind == "all-reduce":
            wire = 2 * size * (n - 1) // max(n, 1)
        elif kind == "all-to-all":
            wire = size * (n - 1) // max(n, 1)
        else:  # collective-permute
            wire = size
        bytes_by[kind] += wire
        count_by[kind] += 1
    return CollectiveStats(bytes_by_kind=bytes_by, count_by_kind=count_by)


@dataclasses.dataclass
class Roofline:
    flops: float                 # per device (scan-aware)
    hbm_bytes: float             # per device (scan-aware estimate)
    wire_bytes: float            # per device (scan-aware)
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    collectives: dict[str, float]
    collective_counts: dict[str, float]
    xla_flops: float             # raw cost_analysis (undercounts while loops)
    xla_bytes: float
    while_trips: list[int]

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


def cost_bytes(cost: dict) -> float:
    if "bytes accessed" in cost:
        return float(cost["bytes accessed"])
    return float(sum(v for k, v in cost.items() if k.startswith("bytes accessed")))


def roofline(cost: dict, hlo_text: str, world: int) -> Roofline:
    """Three-term roofline. FLOPs/bytes come from the scan-aware HLO walker
    (`hlo_cost`) because `cost_analysis()` counts while bodies once; the raw
    cost_analysis numbers are kept as a cross-check."""
    from repro.roofline import hlo_cost

    hc = hlo_cost.analyze_hlo(hlo_text, world)
    flops = hc.flops
    mem = hc.hbm_bytes
    compute_s = flops / hw.PEAK_FLOPS_BF16
    memory_s = mem / hw.HBM_BW
    collective_s = hc.wire_bytes / hw.ICI_BW_PER_LINK
    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    dominant = max(terms, key=terms.get)
    return Roofline(
        flops=flops, hbm_bytes=mem, wire_bytes=hc.wire_bytes,
        compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
        dominant=dominant, collectives=hc.collective_bytes,
        collective_counts=hc.collective_counts,
        xla_flops=float(cost.get("flops", 0.0)),
        xla_bytes=cost_bytes(cost),
        while_trips=hc.while_trip_counts)


def packing_report(pack_spec) -> dict:
    """Padding overhead of a packed-gossip layout (`core.packing.PackSpec`).

    The packed engine pads each per-dtype flat buffer up to a
    (block_rows x 128)-element tile multiple; every padded byte is shipped
    over ICI d times per round and read by every fused reduction pass, so
    the overhead fraction is a direct multiplier on the gossip roofline
    terms. Smoke-sized models pad heavily (a tile is 128 KiB of f32); real
    architectures should sit well under 1%.
    """
    payload = int(pack_spec.payload_bytes)
    padded = int(pack_spec.padded_bytes)
    return {
        "n_leaves": pack_spec.n_leaves,
        "n_buffers": pack_spec.n_buffers,
        "payload_bytes": payload,
        "padded_bytes": padded,
        "pad_overhead": (padded / payload - 1.0) if payload else 0.0,
    }


def model_flops_train(n_active_params: int, n_tokens: int) -> float:
    """6 N D — fwd (2ND) + bwd (4ND)."""
    return 6.0 * n_active_params * n_tokens


def model_flops_decode(n_active_params: int, batch: int) -> float:
    return 2.0 * n_active_params * batch


def model_flops_prefill(n_active_params: int, n_tokens: int) -> float:
    return 2.0 * n_active_params * n_tokens
