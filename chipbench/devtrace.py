"""Reduction of a profiler trace (`.xplane.pb`) to device busy time,
per-kernel time and idle gaps attributed to the harness's host spans.

The harness wraps its measured window in a `jax.profiler.TraceAnnotation`
named `WINDOW` and its calls into each layer in annotations named
`chipbench.<layer call>`; those land on a host plane of the same trace,
on the same clock as the device's operations.

* Device operations are the events of the "XLA Ops" line of each
  `/device:TPU:<n>` plane, clipped to the window, without the control
  flow operations (`while`, `conditional`) that span their bodies.
* Busy time is the union of their intervals, averaged over the devices
  used; the idle share is 1 - busy / window.
* A kernel's time is the summed duration of the operations that carry
  its name (`kernel_events`).
* Each idle gap is charged to the innermost harness span that covers
  its midpoint on the host ("<none>" if none does).
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Iterable

WINDOW = "chipbench.window"
SPAN_PREFIX = "chipbench."
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
_SHAPE = re.compile(r"\b(f32|bf16|f16|s32|u32|s8|u8|pred|s16|u16)\[([0-9,]*)\]")


_INSTR = re.compile(r"^%?([A-Za-z_][A-Za-z0-9_\-]*?)(?:\.\d+)*(?:\s*=|$)")
# Operations that contain others on the same line: their time is their
# children's, so they count neither as busy time nor among the top ops.
CONTAINERS = frozenset({"while", "conditional", "call"})


@dataclasses.dataclass(frozen=True)
class Op:
    text: str  # the HLO instruction as the trace names it
    start_ns: float
    end_ns: float

    @property
    def name(self) -> str:
        """The instruction's name without its numeric suffix:
        `%fwht.28 = f32[...] custom-call(...)` -> `fwht`."""
        m = _INSTR.match(self.text)
        return m.group(1) if m else self.text.split(" ", 1)[0]

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9

    def shapes(self) -> list[tuple[str, tuple[int, ...]]]:
        """Every `dtype[d0,d1,...]` in the instruction, results first."""
        return [
            (dt, tuple(int(d) for d in dims.split(",") if d))
            for dt, dims in _SHAPE.findall(self.text)
        ]


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                 # mean over devices
    devices: int
    ops: list[Op]                 # device 0's operations inside the window
    idle_by_span: dict[str, float]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel_events(self, kernel: str) -> list[Op]:
        """Operations of the Pallas kernel named `kernel` (a Pallas call's
        instruction takes the kernel's name)."""
        return [op for op in self.ops if op.name == kernel]

    def top_ops(self, n: int = 10) -> list[list]:
        """The `n` operation names that took most device time."""
        tot: dict[str, float] = defaultdict(float)
        for op in self.ops:
            tot[op.name] += op.seconds
        return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def top_idle(self, n: int = 10) -> list[list]:
        items = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])
        return [[k, v] for k, v in items[:n]]


def _union(intervals: Iterable[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def reduce(profile) -> Reduction:
    """Reduce a `jax.profiler.ProfileData` to a `Reduction`."""
    spans: list[tuple[float, float, str]] = []
    device_ops: dict[str, list[Op]] = {}
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = device_ops.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend(
                        op for op in (
                            Op(e.name, e.start_ns, e.end_ns) for e in line.events
                        ) if op.name not in CONTAINERS
                    )
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.start_ns, e.end_ns, e.name))
    windows = [(s, e) for s, e, n in spans if n == WINDOW]
    if not windows:
        raise ValueError(f"trace holds no {WINDOW!r} span")
    if not device_ops:
        raise ValueError("trace holds no TPU device plane")
    w0, w1 = windows[0]
    busy = []
    clipped_by_plane = {}
    for plane, ops in sorted(device_ops.items()):
        clipped = [
            Op(op.text, max(op.start_ns, w0), min(op.end_ns, w1))
            for op in ops if op.end_ns > w0 and op.start_ns < w1
        ]
        clipped_by_plane[plane] = clipped
        union = _union((op.start_ns, op.end_ns) for op in clipped)
        busy.append(sum(e - s for s, e in union) * 1e-9)
    first = sorted(clipped_by_plane)[0]
    ops0 = clipped_by_plane[first]
    inner = [(s, e, n) for s, e, n in spans if n != WINDOW]
    idle: dict[str, float] = defaultdict(float)
    prev = w0
    for s, e in _union((op.start_ns, op.end_ns) for op in ops0) + [(w1, w1)]:
        if s > prev:
            mid = 0.5 * (prev + s)
            covering = [(e2 - s2, n) for s2, e2, n in inner if s2 <= mid < e2]
            idle[min(covering)[1] if covering else "<none>"] += (s - prev) * 1e-9
        prev = max(prev, e)
    return Reduction(
        window_s=(w1 - w0) * 1e-9,
        busy_s=sum(busy) / len(busy),
        devices=len(busy),
        ops=ops0,
        idle_by_span=dict(idle),
    )


def load(path: str) -> Reduction:
    from jax.profiler import ProfileData

    return reduce(ProfileData.from_file(path))
