"""Roofline-term extraction from compiled dry-run artifacts.

Three terms per (arch x shape x mesh), in seconds, from the published
peaks of the target chip (`PEAKS`, keyed by `jax.Device.device_kind`):

    compute    = HLO_FLOPs / (chips * bf16 FLOP/s)        [MXU peak]
    memory     = HLO_bytes / (chips * HBM bytes/s)        [HBM]
    collective = collective_bytes / ICI link bytes/s      [per-link ICI]

`compiled.cost_analysis()` supplies FLOPs / bytes-accessed of the
SPMD-partitioned per-device module (multiplied back to chip count where
the analysis is per-device).  Collective bytes are NOT in cost_analysis:
we parse the post-optimization per-device HLO and sum the result-shape
bytes of every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute op (methodology note: result bytes upper-bound ring
wire bytes for all-gather/all-reduce and under-count reduce-scatter by
1/n — recorded per-op-type so the table stays auditable).

MODEL_FLOPS uses 6*N*D (dense) or 6*N_active*D (MoE) for train cells and
2*N*D for inference cells; the ratio MODEL_FLOPS / HLO_FLOPs exposes
remat / redundancy waste.
"""

from __future__ import annotations

import dataclasses
import json
import re
from typing import Any


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published peaks of one chip."""

    bf16_flops: float          # FLOP/s
    hbm_bytes: float           # capacity
    hbm_bw: float              # bytes/s
    ici_link_bw: float         # bytes/s per ICI link


# Keyed by `jax.Device.device_kind`.  Source: Google Cloud documentation,
# "TPU v5e" (system architecture): 197 TFLOP/s bf16, 16 GB of HBM at
# 819 GB/s, 1,600 Gbit/s of ICI per chip, taken here over 4 links.
PEAKS: dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(
        bf16_flops=197e12, hbm_bytes=16e9, hbm_bw=819e9,
        ici_link_bw=1600e9 / 8 / 4,
    ),
}

# The dry-run compiles for a described v5e chip.
DRYRUN_DEVICE_KIND = "TPU v5 lite"


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of `device_kind`; a chip without published peaks raises."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None


_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes_from_hlo(hlo_text: str) -> dict[str, Any]:
    """Sum result-shape bytes per collective op type (per-device HLO)."""
    per_type: dict[str, int] = {c: 0 for c in _COLLECTIVES}
    counts: dict[str, int] = {c: 0 for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        ls = line.strip()
        if not ls.startswith("%") and " = " not in ls:
            continue
        for cname in _COLLECTIVES:
            # match the op invocation, e.g. "= bf16[...] all-gather(" or
            # "all-gather-start("; skip -done ops (same bytes as -start).
            if f" {cname}(" in ls or f" {cname}-start(" in ls:
                head = ls.split(f" {cname}")[0]
                shapes = _SHAPE_RE.findall(head)
                total = sum(_shape_bytes(d, s) for d, s in shapes)
                per_type[cname] += total
                counts[cname] += 1
                break
    return {
        "bytes_by_type": per_type,
        "counts_by_type": counts,
        "total_bytes": sum(per_type.values()),
    }


@dataclasses.dataclass
class RooflineTerms:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float            # whole-job FLOPs (per-device x chips)
    hlo_bytes: float            # whole-job HBM bytes
    collective_bytes: float     # per-device collective result bytes
    model_flops: float
    device_kind: str            # key of `PEAKS`
    compute_s: float = 0.0
    memory_s: float = 0.0
    collective_s: float = 0.0
    bottleneck: str = ""
    useful_ratio: float = 0.0
    per_device_bytes: float = 0.0
    collective_detail: dict = dataclasses.field(default_factory=dict)
    memory_analysis: dict = dataclasses.field(default_factory=dict)

    def finalize(self) -> "RooflineTerms":
        peaks = chip_peaks(self.device_kind)
        self.compute_s = self.hlo_flops / (self.chips * peaks.bf16_flops)
        self.memory_s = self.hlo_bytes / (self.chips * peaks.hbm_bw)
        # collective bytes parsed from the per-device module already;
        # each device drives its own links.
        self.collective_s = self.collective_bytes / peaks.ici_link_bw
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        self.bottleneck = max(terms, key=terms.get)
        self.useful_ratio = (
            self.model_flops / self.hlo_flops if self.hlo_flops else 0.0
        )
        return self

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


def model_flops(cfg, spec, tokens: int) -> float:
    """6*N_active*D for training, 2*N_active*D for inference steps."""
    n_active = cfg.active_param_count()
    mult = 6.0 if spec.kind == "train" else 2.0
    return mult * n_active * tokens


def summarize_cost_analysis(cost: Any) -> dict[str, float]:
    if cost is None:
        return {}
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    out = {}
    for k, v in dict(cost).items():
        if isinstance(v, (int, float)):
            out[k] = float(v)
    return out


def summarize_memory_analysis(mem: Any) -> dict[str, float]:
    if mem is None:
        return {}
    keys = (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "alias_size_in_bytes",
        "generated_code_size_in_bytes",
        "peak_memory_in_bytes",
    )
    out = {}
    for k in keys:
        v = getattr(mem, k, None)
        if v is not None:
            try:
                out[k] = float(v)
            except (TypeError, ValueError):
                pass
    return out


def save_results(path: str, rows: list[dict]) -> None:
    with open(path, "w") as f:
        json.dump(rows, f, indent=1)
