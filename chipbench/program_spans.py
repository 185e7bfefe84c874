"""The program's own deploy spans, for the per-layer readers that use them.

The program records its phases as spans with `repro.obs.trace`
(`deploy` around each `deploy_arrays` call, `deploy.plan`,
`deploy.dispatch`, `deploy.report`, `deploy.sync` and `deploy.fold`
inside it).  The window's deploys are the last `run.records["deploys"]`
`deploy` spans; a span that opens inside one belongs to it.  Nothing is
read where the program records no such spans: with its telemetry
disabled, or in a program that has none.
"""

from __future__ import annotations


def window_deploys(run) -> list[tuple[dict, list[dict]]] | None:
    """(deploy span, its `deploy.*` spans) for each deploy of the window,
    or None where the program recorded fewer `deploy` spans than the
    window ran deploys."""
    from repro.obs import trace

    n = int(run.records.get("deploys", 0))
    spans = [e for e in trace.events() if e.get("ph") == "X"]
    deploys = [e for e in spans if e["name"] == "deploy"]
    if n == 0 or len(deploys) < n:
        return None
    out = []
    for d in deploys[-n:]:
        t0, t1 = d["ts"], d["ts"] + d["dur"]
        out.append((d, [e for e in spans
                        if e["name"].startswith("deploy.") and t0 <= e["ts"] < t1]))
    return out


def child_share(run, name: str) -> float | None:
    """Percent of the window's deploy time spent in the spans `name`."""
    deploys = window_deploys(run)
    if deploys is None:
        return None
    part = [e["dur"] for _, kids in deploys for e in kids if e["name"] == name]
    if not part:
        return None
    return 100.0 * sum(part) / sum(d["dur"] for d, _ in deploys)
