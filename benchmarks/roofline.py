"""Three-term roofline from compiled dry-run artifacts (DESIGN.md §6).

Hardware model: the published peaks of the target chip, looked up by
device_kind in repro.kernels.peaks (an unknown device is an error).

Terms (seconds, per device — cost_analysis is per-device post-SPMD):
    compute    = HLO flops / peak bf16 flop/s
    memory     = HLO bytes accessed / HBM bytes/s
    collective = sum over collective ops of wire-bytes / ICI bytes/s
      ring formulas on per-device shapes from the partitioned module:
        all-gather      (g-1)/g * result_bytes
        reduce-scatter  (g-1)   * result_bytes   (= (g-1)/g * input)
        all-reduce      2 (g-1)/g * result_bytes
        all-to-all      (g-1)/g * result_bytes
        collective-permute  result_bytes
"""
from __future__ import annotations

import dataclasses
import re

from repro.kernels.peaks import Peaks, peaks  # noqa: F401  (re-exported)

# the chip (device_kind) the dry run's placeholder meshes stand for
DRYRUN_TARGET = "TPU v5 lite"

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s*(\([^)]*\)|\S+)\s+"
    r"(all-gather-start|all-gather|all-reduce-start|all-reduce|"
    r"reduce-scatter|all-to-all|collective-permute-start|collective-permute)"
    r"\(", )

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")

_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(shape_str: str) -> int:
    """Total bytes of possibly-tuple HLO shape text; tuples take the LAST
    element (the destination buffer of -start ops)."""
    matches = _SHAPE_RE.findall(shape_str)
    if not matches:
        return 0
    dt, dims = matches[-1]
    if dt not in _DTYPE_BYTES:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


def _group_size(line: str) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))          # [n_groups, group_size]<=[...]
    m = _GROUPS_BRACE_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return 1


@dataclasses.dataclass
class CollectiveStats:
    counts: dict
    wire_bytes: float          # per device
    by_op: dict                # op -> wire bytes


def parse_collectives(hlo_text: str) -> CollectiveStats:
    counts: dict = {}
    by_op: dict = {}
    total = 0.0
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        shape_str, op = m.group(1), m.group(2)
        op = op.replace("-start", "")
        g = _group_size(line)
        if g <= 1:
            continue
        rb = _shape_bytes(shape_str)
        if op == "all-gather":
            wire = rb * (g - 1) / g
        elif op == "reduce-scatter":
            wire = rb * (g - 1)
        elif op == "all-reduce":
            wire = 2 * rb * (g - 1) / g
        elif op == "all-to-all":
            wire = rb * (g - 1) / g
        else:  # collective-permute
            wire = rb
        counts[op] = counts.get(op, 0) + 1
        by_op[op] = by_op.get(op, 0.0) + wire
        total += wire
    return CollectiveStats(counts=counts, wire_bytes=total, by_op=by_op)


# ---------------------------------------------------------------------------
# fusion-aware HBM-traffic estimate
# ---------------------------------------------------------------------------

# ops that materialize buffers on TPU too (fusion boundaries); everything
# else (standalone elementwise, plus the copies/transposes/pads/iotas the
# CPU backend inserts for layout but a TPU pipeline folds into neighbours)
# is assumed fused away — the CPU backend's sparse fusion makes raw
# `bytes accessed` an op-level overcount.
_MATERIALIZING = (
    "dot", "convolution", "fusion", "reduce", "reduce-window", "gather",
    "scatter", "dynamic-slice", "dynamic-update-slice", "sort", "rng",
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+((?:\([^=]*?\)|[\w\[\],{}\/ ]+?))\s+"
    r"([\w\-]+)\((.*)$")

_OPERAND_RE = re.compile(r"%([\w.\-]+)")


def parse_memory_traffic(hlo_text: str) -> float:
    """Estimate per-device HBM bytes under TPU-like fusion: sum operand +
    result bytes over materializing ops only (dots, reduces, gathers,
    collectives, existing fusions...), skipping standalone elementwise ops
    that a TPU pipeline would fuse into neighbours."""
    shapes: dict = {}
    entries = []
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, shape_str, op, rest = m.groups()
        shapes[name] = _shape_bytes(shape_str)
        entries.append((name, op, rest))
    total = 0.0
    for name, op, rest in entries:
        base = op.replace("-start", "").replace("-done", "")
        if base not in _MATERIALIZING:
            continue
        if op.endswith("-done"):
            continue
        total += shapes.get(name, 0)
        # operand list terminates at "), " metadata; good enough to scan
        # the full tail for %refs that have known shapes.
        for ref in _OPERAND_RE.findall(rest.split("metadata=")[0]):
            total += shapes.get(ref, 0)
    return total


@dataclasses.dataclass
class Roofline:
    compute_s: float
    memory_s: float             # fusion-aware estimate (the scored term)
    collective_s: float
    memory_upper_s: float       # raw op-level bytes / bw (upper bound)
    flops: float
    bytes_accessed: float       # raw op-level (CPU-backend fusion)
    fused_bytes: float          # materializing-ops-only estimate
    wire_bytes: float
    model_flops: float          # analytic useful flops per device
    flops_ratio: float          # model_flops / hlo flops
    peak_flops: float           # the target chip's bf16 peak

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_s(self) -> float:
        """Roofline step-time estimate = max of the three terms."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def roofline_fraction(self) -> float:
        """Useful-compute fraction of the roofline-limited step: how close
        the step is to spending all its time on model flops at peak."""
        ideal = self.model_flops / self.peak_flops
        return ideal / self.step_s if self.step_s > 0 else 0.0

    def to_dict(self) -> dict:
        return {**dataclasses.asdict(self),
                "dominant": self.dominant,
                "step_s": self.step_s,
                "roofline_fraction": self.roofline_fraction}


def model_flops_per_device(cfg, shape_kind: str, tokens: int,
                           n_devices: int) -> float:
    """Analytic 'useful' flops: 6ND train / 2ND per generated-or-prefilled
    token (MoE: active params)."""
    n = cfg.active_param_count()
    mult = 6.0 if shape_kind == "train" else 2.0
    return mult * n * tokens / n_devices


def build_roofline(cfg, shape_kind: str, tokens: int, n_devices: int,
                   flops: float, bytes_accessed: float,
                   colls: CollectiveStats, fused_bytes: float,
                   pk: Peaks) -> Roofline:
    mf = (model_flops_per_device(cfg, shape_kind, tokens, n_devices)
          if shape_kind != "he_agg" else 0.0)
    if bytes_accessed:
        fused_bytes = min(fused_bytes, bytes_accessed)
    return Roofline(
        compute_s=flops / pk.bf16_flops,
        memory_s=fused_bytes / pk.hbm_bytes_per_s,
        collective_s=colls.wire_bytes / pk.ici_bytes_per_s,
        memory_upper_s=bytes_accessed / pk.hbm_bytes_per_s,
        flops=flops,
        bytes_accessed=bytes_accessed,
        fused_bytes=fused_bytes,
        wire_bytes=colls.wire_bytes,
        model_flops=mf,
        flops_ratio=mf / flops if flops else 0.0,
        peak_flops=pk.bf16_flops,
    )
