"""Host-side phase tracing: Chrome/Perfetto trace-event spans.

The repo's phases — deploy buckets, prefill admissions, decode steps,
refresh scrubs, calibration, benchmark timing loops — are recorded as
*spans* on one global `Tracer` and exported as Chrome trace-event JSON
(`{"traceEvents": [...]}`), the format Perfetto / `chrome://tracing`
load directly.  Every span is a host-side wall-clock interval; nothing
here touches the device, so tracing can never add a host sync or a
retrace to an instrumented hot path (the zero-extra-sync contract,
DESIGN.md Sec. 14).

Each recorded span is also a `jax.profiler.TraceAnnotation` of the same
name: inside a profiler capture (`jax.profiler.start_trace` /
`stop_trace`) the program's spans land on the host plane of the
`.xplane.pb`, on the same clock as the device's operations, so device
idle time can be charged to what the host was doing.  Outside a capture
an annotation records nothing.

Usage:

    from repro.obs import trace
    with trace.span("serve.decode", cat="serve", step=i) as args:
        ...                      # args is mutable: fill in results
        args["tokens"] = 4

    trace.export("TRACE_run.json")

Span events are "ph": "X" (complete) events with `ts`/`dur` in
microseconds; `instant` emits "ph": "i" markers (compiles, swaps);
ledger charges ride along as "cat": "ledger" instants (`obs.ledger`).
`repro.obs.report` summarizes an exported file per phase name.

Recording honours the global obs enable flag (`obs.disabled()`), read
when a span opens: a span opened while it is off records no event and
opens no annotation.
"""

from __future__ import annotations

import contextlib
import json
import os
import time
from typing import Any, Iterator

import jax

__all__ = [
    "Tracer",
    "tracer",
    "span",
    "instant",
    "export",
    "reset",
    "events",
]

# Global obs enable flag, shared by the tracer and the ledger.  Contract
# counters (obs.metrics registry) are NOT gated on it: they are cheap
# and tests assert on them regardless of instrumentation verbosity.
_ENABLED = True


def _set_enabled(flag: bool) -> bool:
    global _ENABLED
    old = _ENABLED
    _ENABLED = bool(flag)
    return old


def is_enabled() -> bool:
    return _ENABLED


class Tracer:
    """An append-only list of Chrome trace events on one wall clock."""

    def __init__(self, pid: int | None = None):
        self.pid = os.getpid() if pid is None else pid
        self.t0_ns = time.perf_counter_ns()
        self._events: list[dict] = []

    # ------------------------------------------------------------ clock
    def now_us(self) -> float:
        """Microseconds since the tracer's epoch (reset rebases it)."""
        return (time.perf_counter_ns() - self.t0_ns) / 1e3

    # ----------------------------------------------------------- record
    def _append(self, ev: dict) -> None:
        if _ENABLED:
            self._events.append(ev)

    @contextlib.contextmanager
    def span(self, name: str, cat: str = "phase", **args: Any) -> Iterator[dict]:
        """Record one complete ("ph": "X") event around the body.

        Yields the (mutable) args dict so the body can attach results —
        values filled in before exit land in the exported event.  While
        recording, the body also runs inside a `TraceAnnotation` of the
        same name, opened and closed next to the span's own clock reads.
        """
        mutable = dict(args)
        if not _ENABLED:
            yield mutable
            return
        ts = self.now_us()
        try:
            with jax.profiler.TraceAnnotation(name):
                yield mutable
        finally:
            self._events.append(
                {
                    "name": name,
                    "cat": cat,
                    "ph": "X",
                    "ts": ts,
                    "dur": self.now_us() - ts,
                    "pid": self.pid,
                    "tid": 1,
                    "args": mutable,
                }
            )

    def instant(self, name: str, cat: str = "phase", **args: Any) -> None:
        """Record a zero-duration marker event ("ph": "i")."""
        self._append(
            {
                "name": name,
                "cat": cat,
                "ph": "i",
                "s": "t",
                "ts": self.now_us(),
                "pid": self.pid,
                "tid": 1,
                "args": dict(args),
            }
        )

    # ------------------------------------------------------- export/reset
    def events(self) -> list[dict]:
        return list(self._events)

    def export(self, path: str | os.PathLike) -> str:
        """Write the Chrome/Perfetto trace-event JSON; returns the path."""
        doc = {
            "traceEvents": self._events,
            "displayTimeUnit": "ms",
        }
        path = os.fspath(path)
        with open(path, "w") as f:
            json.dump(doc, f, indent=1)
        return path

    def reset(self) -> None:
        """Drop all events and rebase the clock (fresh run in-process)."""
        self._events = []
        self.t0_ns = time.perf_counter_ns()


# The global tracer every subsystem records onto.  One process = one
# timeline; `benchmarks/run.py` resets it between registered benchmarks
# so each exported trace is self-contained.
tracer = Tracer()


def span(name: str, cat: str = "phase", **args: Any):
    return tracer.span(name, cat=cat, **args)


def instant(name: str, cat: str = "phase", **args: Any) -> None:
    tracer.instant(name, cat=cat, **args)


def events() -> list[dict]:
    return tracer.events()


def export(path: str | os.PathLike) -> str:
    return tracer.export(path)


def reset() -> None:
    tracer.reset()
