"""Published peaks of the chips the benchmark runs on.

Keyed by `jax.Device.device_kind`.  A chip that is not in the table is
an error, never a default: a share of a peak that nobody published
means nothing.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s in bf16, 393 TOP/s in int8, 16 GB of HBM at 819 GB/s.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    bf16_flops: float   # FLOP/s
    int8_ops: float     # OP/s
    hbm_bytes: float    # capacity, bytes
    hbm_bw: float       # bytes/s


PEAKS: dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(
        bf16_flops=197e12, int8_ops=393e12, hbm_bytes=16e9, hbm_bw=819e9,
    ),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of `device_kind`; an unknown kind raises."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None
