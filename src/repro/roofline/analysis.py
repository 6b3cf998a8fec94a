"""Roofline-term derivation from compiled dry-run artifacts.

Three terms per (arch × shape × mesh), in seconds:

  compute    = HLO_FLOPs      / (chips × 197e12  bf16 FLOP/s)
  memory     = HLO_bytes      / (chips × 819e9   B/s HBM)
  collective = coll_bytes     / (chips × n_links × 50e9 B/s ICI)

``cost_analysis()`` supplies FLOPs and bytes for the whole SPMD module
(per-device program × chips is how XLA reports post-partitioning — we
normalize per chip).  Collective bytes are NOT in cost_analysis: we parse
the optimized HLO (``compiled.as_text()``) and sum, for every all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute, the bytes a
device moves over the wire:

  all-reduce       2·(g-1)/g · result     (ring)
  all-gather       (g-1)/g · result       (result = gathered buffer)
  reduce-scatter   (g-1)/g · operand      (operand = g × result)
  all-to-all       (g-1)/g · result
  collective-permute  result

with g = replica-group size parsed from ``replica_groups``.
"""

from __future__ import annotations

import dataclasses
import re

# Published per-chip peaks keyed by ``jax.Device.device_kind``.  Source:
# Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM at
# 819 GB/s).  A device kind missing here has no prediction: callers get
# None, never another chip's numbers.
DEVICE_PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bw": 819e9,
                    "hbm_bytes": 16e9},
}


def device_peaks(device_kind: str) -> dict | None:
    """The published peaks of one chip kind, or None if not tabled."""
    return DEVICE_PEAKS.get(device_kind)


# The dry-run analysis models a v5e slice (assignment-specified).
PEAK_FLOPS = DEVICE_PEAKS["TPU v5 lite"]["flops_bf16"]   # bf16 / chip
HBM_BW = DEVICE_PEAKS["TPU v5 lite"]["hbm_bw"]           # B/s / chip
ICI_BW = 50e9            # B/s / link
ICI_LINKS = 4            # usable links per chip on a 2D torus (x± / y±)

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:\w+\[[\d,]*\](?:\{[^}]*\})?))\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(", )
_GROUPS_ITOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class CollectiveStats:
    bytes_by_kind: dict
    count_by_kind: dict
    wire_bytes: float  # per-device bytes moved over ICI

    @property
    def total_bytes(self) -> float:
        return float(sum(self.bytes_by_kind.values()))


def parse_collectives(hlo_text: str) -> CollectiveStats:
    bytes_by_kind: dict[str, float] = {}
    count_by_kind: dict[str, int] = {}
    wire = 0.0
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        shape_txt, kind = m.group(1), m.group(2)
        result_bytes = _shape_bytes(shape_txt)
        g = _group_size(line)
        frac = (g - 1) / g if g > 1 else 0.0
        if kind == "all-reduce":
            w = 2.0 * frac * result_bytes
        elif kind == "all-gather":
            w = frac * result_bytes
        elif kind == "reduce-scatter":
            w = frac * result_bytes * g  # operand = g × result
        elif kind == "all-to-all":
            w = frac * result_bytes
        else:  # collective-permute
            w = float(result_bytes)
        bytes_by_kind[kind] = bytes_by_kind.get(kind, 0.0) + result_bytes
        count_by_kind[kind] = count_by_kind.get(kind, 0) + 1
        wire += w
    return CollectiveStats(bytes_by_kind, count_by_kind, wire)


def _group_size(line: str) -> int:
    m = _GROUPS_ITOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len([x for x in m.group(1).split(",") if x.strip() != ""])
    return 1


@dataclasses.dataclass
class RooflineTerms:
    flops: float        # per-device (XLA cost_analysis reports the SPMD program)
    hbm_bytes: float    # per-device
    wire_bytes: float   # per-device ICI traffic
    chips: int
    model_flops: float = 0.0  # GLOBAL useful flops (6·N·D)

    @property
    def t_compute(self) -> float:
        return self.flops / PEAK_FLOPS

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.wire_bytes / (ICI_LINKS * ICI_BW)

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def step_time(self) -> float:
        """No-overlap roofline estimate (upper bound on achievable)."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_flops_ratio(self) -> float:
        total = self.flops * self.chips
        return self.model_flops / total if total else 0.0

    @property
    def mfu_upper_bound(self) -> float:
        """Model-flops utilization at the roofline step time."""
        denom = self.step_time * self.chips * PEAK_FLOPS
        return self.model_flops / denom if denom else 0.0

    def to_dict(self) -> dict:
        return {
            "flops": self.flops, "hbm_bytes": self.hbm_bytes,
            "wire_bytes": self.wire_bytes, "chips": self.chips,
            "model_flops": self.model_flops,
            "t_compute_s": self.t_compute, "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "useful_flops_ratio": self.useful_flops_ratio,
            "mfu_upper_bound": self.mfu_upper_bound,
        }


def terms_from_compiled(compiled, chips: int, model_flops: float,
                        hlo_text: str | None = None) -> RooflineTerms:
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    flops = float(cost.get("flops", 0.0))
    hbm = float(cost.get("bytes accessed", 0.0))
    text = hlo_text if hlo_text is not None else compiled.as_text()
    coll = parse_collectives(text)
    return RooflineTerms(
        flops=flops, hbm_bytes=hbm, wire_bytes=coll.wire_bytes,
        chips=chips, model_flops=model_flops,
    ), coll
