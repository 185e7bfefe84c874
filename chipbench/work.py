"""The operations and bytes that the algorithms require, from shapes.

Each function counts what the algorithm needs for one call at the given
shapes: its operands read once and its results written once, at their
dtypes' sizes.  Relayouts, padding and temporaries that an
implementation adds are not counted, so a share of a peak computed from
these numbers is a share of what the chip could do for this work, and
it reads the same whichever implementation runs.

A roofline share is the least time the chip needs for the call, the
larger of `flops / peak FLOP/s` and `bytes / peak bytes/s`, over the
measured time (`roofline_share`).
"""

from __future__ import annotations

import dataclasses
import math


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)


ZERO = Work(0.0, 0.0)
F32 = 4
S32 = 4
BOOL = 1


def fwht(c: int, n: int) -> Work:
    """Walsh-Hadamard transform of `c` columns of `n` f32 values:
    n log2 n adds or subtracts per column; the columns read and the
    transforms written once."""
    return Work(flops=float(c * n * int(math.log2(n))), bytes=float(2 * c * n * F32))


# One HARP cell update reads the decision aggregate, the conductance,
# the streak counter, the frozen flag, the two pre-drawn write-noise
# fields and the device-to-device efficiency, and writes the
# conductance, streak, frozen flag, pulse count and pulse direction.
_WV_STEP_READ = 6 * F32 + BOOL      # agg, g, c2c, nmap, d2d (f32); streak (s32); frozen
_WV_STEP_WRITE = 4 * F32 + BOOL     # g, n_p, direction (f32); streak (s32); frozen
# Threshold (2 compares), streak (select, add), freeze (2 logic ops),
# rail taper (divide, clip, power, select, 2 multiplies), pulse
# (3 multiplies, add, noise multiply-add), clip (2): about 20 per cell.
_WV_STEP_FLOPS = 20


def wv_step(c: int, n: int, magnitude: bool = False) -> Work:
    """One fused write-and-verify cell update of `c` columns of `n` cells.
    Schemes that size their pulses by the estimated deviation
    (`magnitude`) also read that estimate."""
    cells = c * n
    read = _WV_STEP_READ + (F32 if magnitude else 0)
    return Work(
        flops=float(_WV_STEP_FLOPS * cells),
        bytes=float((read + _WV_STEP_WRITE) * cells),
    )


# The state one write-and-verify iteration must read and write for each
# cell: the conductance (read and written), the target and the
# device-to-device efficiency (read), and the per-cell status, a streak
# counter and a frozen flag (read and written).
WV_ITERATION_BYTES_PER_CELL = 2 * F32 + F32 + F32 + 2 * S32 + 2 * BOOL


def wv_iteration(c: int, n: int) -> Work:
    """One whole write-and-verify iteration over `c` columns of `n` cells
    (verify read, decision and write): bound by the bytes of the state."""
    cells = c * n
    # Two n-point transforms per column (encode the conductances, decode
    # the comparator signs) plus the cell update.
    return Work(
        flops=float(2 * c * n * int(math.log2(n)) + _WV_STEP_FLOPS * cells),
        bytes=float(WV_ITERATION_BYTES_PER_CELL * cells),
    )


def roofline_seconds(work: Work, flops_per_s: float, bytes_per_s: float) -> tuple[float, str]:
    """The least time the chip needs for `work`, and which bound sets it."""
    t_flops = work.flops / flops_per_s
    t_bytes = work.bytes / bytes_per_s
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")


def roofline_share(work: Work, seconds: float, flops_per_s: float,
                   bytes_per_s: float) -> float:
    """Percent of the roofline that `work` done in `seconds` reaches."""
    return 100.0 * roofline_seconds(work, flops_per_s, bytes_per_s)[0] / seconds
