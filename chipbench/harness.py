"""What the harness shares with drivers and metric readers."""

from __future__ import annotations

import dataclasses
import sys
from typing import Any


class Refused(Exception):
    """The run cannot be made here; nothing is printed on stdout."""


@dataclasses.dataclass(frozen=True)
class Check:
    """One number compared with the reference, beside its limit: the run
    is correct when every value is at most its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Context:
    """What a driver is given."""

    cell: dict
    config: dict
    traffic: dict
    seed: int
    devices: list

    def annotate(self, name: str):
        """A host span on the profiler's clock, named `chipbench.<name>`."""
        import jax

        return jax.profiler.TraceAnnotation(f"chipbench.{name}")

    @staticmethod
    def log(msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Run:
    """What a per-layer metric reader is given."""

    cell: str
    trace: Any            # devtrace.Reduction of the traced window
    peaks: Any            # peaks.ChipPeaks of the device
    records: dict         # the driver's counts of the window


class CompileCounter:
    """Counts the programs compiled, or fetched from the persistent
    cache, inside the block: a warm window has none.  (Tracing alone is
    host work of the program and is not counted.)"""

    EVENTS = ("backend_compile", "cache_retrieval")

    def __enter__(self):
        import jax

        self.events: list[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def _on_event(self, event: str, duration: float, **_) -> None:
        if any(e in event for e in self.EVENTS):
            self.events.append(event)

    def __exit__(self, *exc) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_event)


def enable_kernels(cfg):
    """The one place the benchmark turns the program's Pallas kernels on.
    Where a configuration has no such switch, its kernels are its only
    path and nothing needs turning on."""
    names = {f.name for f in dataclasses.fields(cfg)}
    return cfg.replace(use_pallas=True) if "use_pallas" in names else cfg
