"""Roofline accounting from compiled dry-run artifacts.

Per-chip peaks come from :data:`PEAKS`, keyed by ``jax.Device.device_kind``;
a device kind missing from the table is an error, never a default.

Conventions:
  * XLA's post-SPMD module is per-device, so cost_analysis flops/bytes are
    per-device; the roofline terms below therefore divide by per-chip peaks
    directly (equivalent to global/(chips × peak) for balanced shards).
  * Collective traffic is parsed from the optimized HLO text: for each
    all-gather / all-reduce / reduce-scatter / all-to-all /
    collective-permute we take the *result* shape (per-device) and the
    replica-group size N, and charge ring-algorithm bytes per chip:
        all-gather       (N-1)/N × result
        all-reduce       2 (N-1)/N × result
        reduce-scatter   (N-1) × result        (operand = N × result)
        all-to-all       (N-1)/N × result
        collective-permute   1 × result
  * The collective term assumes one ICI link per direction (conservative;
    a 2D torus can stripe across 2–3 links — noted in EXPERIMENTS.md).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict

from ..configs.base import ArchConfig, ShapeSpec

@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops: float            # dense bf16 FLOP/s
    hbm_bw: float           # HBM bytes/s
    ici_bw: float           # bytes/s over one inter-chip link


#: per-chip peaks by ``device_kind``.  TPU v5e (reported as "TPU v5 lite"):
#: 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s ICI over 4 links (Google
#: Cloud documentation, "TPU v5e").
PEAKS: Dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """The peaks of ``device_kind``; raises for a kind not in the table."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak figures for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None

_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8, "c64": 8,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLL_RE = re.compile(
    r"=\s*(?P<result>\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^\s]*)\s+"
    r"(?P<op>all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\(")
_ARRAY_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9,\s]*)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _array_bytes(text: str) -> int:
    total = 0
    for dt, dims in _ARRAY_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d.strip():
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_LIST_RE.search(line)
    if m:
        ids = [x for x in m.group(1).split(",") if x.strip()]
        return max(1, len(ids))
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        # replica_groups=[G,N]<=[...]  → G groups of N
        return max(1, int(m.group(2)))
    return 2      # conservative default


_RING_FACTOR = {
    "all-gather": lambda n: (n - 1) / n,
    "all-reduce": lambda n: 2 * (n - 1) / n,
    "reduce-scatter": lambda n: float(n - 1),
    "all-to-all": lambda n: (n - 1) / n,
    "collective-permute": lambda n: 1.0,
}


def parse_collectives(hlo_text: str) -> Dict[str, float]:
    """Per-chip collective bytes by op type (+ 'total')."""
    out: Dict[str, float] = {k: 0.0 for k in _RING_FACTOR}
    count: Dict[str, int] = {k: 0 for k in _RING_FACTOR}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        op = m.group("op")
        nbytes = _array_bytes(m.group("result"))
        n = _group_size(line)
        out[op] += nbytes * _RING_FACTOR[op](n)
        count[op] += 1
    out["total"] = sum(out[k] for k in _RING_FACTOR)
    for k, c in count.items():
        out[f"n_{k}"] = c
    return out


def model_flops(cfg: ArchConfig, shape: ShapeSpec) -> float:
    """MODEL_FLOPS = 6·N·D (train) / 2·N·D (prefill/decode), N active."""
    n = cfg.active_params() if cfg.is_moe else cfg.total_params()
    if shape.mode == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.mode == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    return 2.0 * n * shape.global_batch          # decode: one token per seq


@dataclasses.dataclass
class RooflineTerms:
    compute_s: float
    memory_s: float
    collective_s: float
    flops_per_chip: float
    bytes_per_chip: float
    coll_bytes_per_chip: float
    n_chips: int
    model_flops_total: float

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_ratio(self) -> float:
        hlo_global = self.flops_per_chip * self.n_chips
        return self.model_flops_total / hlo_global if hlo_global else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Fraction of the compute roofline achieved if the dominant term
        were the wall clock: compute_s / bound_s."""
        return self.compute_s / self.bound_s if self.bound_s else 0.0

    def to_dict(self) -> Dict:
        return {**dataclasses.asdict(self), "dominant": self.dominant,
                "bound_s": self.bound_s,
                "useful_flops_ratio": self.useful_flops_ratio,
                "roofline_fraction": self.roofline_fraction}


def roofline(flops_per_chip: float, bytes_per_chip: float,
             coll_bytes_per_chip: float, n_chips: int,
             model_flops_total: float, *,
             device_kind: str) -> RooflineTerms:
    peaks = chip_peaks(device_kind)
    return RooflineTerms(
        compute_s=flops_per_chip / peaks.flops,
        memory_s=bytes_per_chip / peaks.hbm_bw,
        collective_s=coll_bytes_per_chip / peaks.ici_bw,
        flops_per_chip=flops_per_chip,
        bytes_per_chip=bytes_per_chip,
        coll_bytes_per_chip=coll_bytes_per_chip,
        n_chips=n_chips,
        model_flops_total=model_flops_total)
