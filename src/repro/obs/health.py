"""Per-tile health maps + declarative fleet SLO rules.

The health layer answers "which silicon is dying and does the fleet
still meet its objectives" from signals the stack already produces
(DESIGN.md Sec. 16).  Ownership is split exactly like the rest of obs:

* **Device-side reduction** — `tile_reduce` / `tile_deploy_stats` turn
  per-column WV statistics into per-tile sums with jnp segment sums.
  The tile axis is tiny (columns / columns_per_tile), so the per-tile
  arrays ride the host syncs the paths already perform: the deploy's
  single `host_fetch` (`DeployReport.reductions`) and the scrub's drift
  fetch.  A deploy routes column->tile on the device: one jitted
  segment sum per metric over the dense tile range its physical column
  uids span (`uid // columns_per_tile`, offset by the range's first
  tile); after the fetch, `present_tiles` drops the range's empty tiles
  on the host.
* **Host-side registry** — `HealthRegistry` folds the fetched per-tile
  values into named maps (give-up density, retry pulses, drift RMS,
  remapped columns) plus scalar gauges (refresh debt, scrub backlog).
* **Host-side policy** — `SLORule`/`SLOPolicy` evaluate declarative
  ceilings against a machine-readable `fleet_status()` snapshot,
  emitting `cat="slo"` trace instants on breach and bumping
  `slo.breaches.*` registry counters (contract-bearing: benchmarks
  assert on them, so they are not gated on the obs enable flag).

The dashboard (`repro.obs.dashboard`) only ever reads exported files —
it never touches this module's live state.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import ops as jops

__all__ = [
    "tile_reduce",
    "tile_deploy_stats",
    "present_tiles",
    "HealthRegistry",
    "health",
    "SLORule",
    "SLOPolicy",
    "fleet_status",
    "resolve_metric",
]


# ------------------------------------------------------- device-side
def tile_reduce(values, tile_inv, num_tiles: int):
    """Segment-sum per-column `values` into `num_tiles` tile bins.

    `tile_inv` is the column->tile-slot index (host numpy or traced), so
    the only device work is one segment sum — traced-safe and fetchable
    alongside whatever the caller was already fetching.
    """
    return jops.segment_sum(
        jnp.asarray(values, jnp.float32),
        jnp.asarray(tile_inv, jnp.int32),
        num_segments=num_tiles,
    )


# Deploy tile maps: metric name -> the `WVStats` field it sums
# (`err2_sum` sums the field's square).
_DEPLOY_TILE_FIELDS = (
    ("gave_up_cells", "gave_up"),
    ("retry_pulses", "retry_pulses"),
    ("write_pulses", "write_pulses"),
    ("verify_reads", "reads"),
    ("err2_sum", "rms_error_lsb"),
)


@functools.partial(
    jax.jit, static_argnames=("columns_per_tile", "num_tiles")
)
def _dense_tile_sums(uids, tile_lo, fields, extra, *, columns_per_tile,
                     num_tiles):
    """Per-tile sums over the dense tile range [tile_lo, tile_lo +
    num_tiles): `fields` and `extra` map metric -> per-leaf column
    vectors, in the order of `uids`."""
    seg = uids // columns_per_tile - tile_lo
    cols = {m: jnp.concatenate(v) for m, v in {**fields, **extra}.items()}
    cols["err2_sum"] = cols["err2_sum"] ** 2
    tree = {m: tile_reduce(v, seg, num_tiles) for m, v in cols.items()}
    tree["columns"] = jops.segment_sum(
        jnp.ones_like(seg), seg, num_segments=num_tiles
    )
    return tree


def tile_deploy_stats(
    stats_map: Mapping[str, Any],
    uids_map: Mapping[str, np.ndarray],
    columns_per_tile: int,
    extra_columns: Mapping[str, Mapping[str, Any]] | None = None,
) -> dict[str, Any]:
    """Per-tile deployment health reductions (device-side).

    Returns a tree for the caller to append to an existing fetch
    (nothing here synchronizes); `present_tiles` turns its host copy
    into ``(tile_ids, maps)``.  `stats_map` values are `WVStats`-shaped
    (duck-typed: gave_up / retry_pulses / write_pulses / reads /
    rms_error_lsb per column); `uids_map` holds each leaf's physical
    column uids (below 2**31).  `extra_columns` adds caller-supplied
    per-column vectors (metric -> leaf name -> (C,) array) reduced with
    the same tile assignment — e.g. the spare-remap path's per-column
    remapped flags.

    The host reads only the uids' bounds and uploads them once; the
    device sums every metric, and a count of columns, over each tile of
    the dense range [min uid, max uid] // columns_per_tile.  A
    contiguous deploy fills that range; spare placement spreads uids
    over its provision of tiles, which bounds the range to a small
    multiple of the tiles that hold a column.  Tile ids rise with the
    segment index, so each tile sums its columns in the same order as a
    sort of the tile ids would give.
    """
    names = [
        n for n in stats_map if n in uids_map and np.size(uids_map[n])
    ]
    if not names:
        return {}
    leaf_uids = [np.asarray(uids_map[n]) for n in names]
    u_min = min(int(u.min()) for u in leaf_uids)
    u_max = max(int(u.max()) for u in leaf_uids)
    if u_min < 0 or u_max >= 2**31:
        raise ValueError(f"column uids [{u_min}, {u_max}] exceed int32")
    uids = np.concatenate(leaf_uids, dtype=np.int32, casting="same_kind")
    cpt = int(columns_per_tile)
    tile_lo = u_min // cpt
    fields = {
        metric: [getattr(stats_map[n], attr) for n in names]
        for metric, attr in _DEPLOY_TILE_FIELDS
    }
    extra = {
        metric: [leaf_vecs[n] for n in names]
        for metric, leaf_vecs in (extra_columns or {}).items()
    }
    sums = _dense_tile_sums(
        uids, np.int32(tile_lo), fields, extra,
        columns_per_tile=cpt, num_tiles=u_max // cpt - tile_lo + 1,
    )
    return {"tile_lo": tile_lo, "sums": sums}


def present_tiles(tree_h: Mapping[str, Any]) -> tuple[np.ndarray, dict]:
    """``(tile_ids, maps)`` from the HOST copy of `tile_deploy_stats`'
    tree: the range's tiles that hold a column, ascending, and each
    metric's per-tile values at them (`columns` as float64 counts)."""
    if not tree_h:
        return np.zeros((0,), np.int64), {}
    sums = tree_h["sums"]
    pos = np.flatnonzero(np.asarray(sums["columns"]) > 0)
    tiles = {
        metric: np.asarray(v)[pos] for metric, v in sums.items()
        if metric != "columns"
    }
    tiles["columns"] = np.asarray(sums["columns"])[pos].astype(np.float64)
    return int(tree_h["tile_lo"]) + pos, tiles


# -------------------------------------------------------- host-side
class HealthRegistry:
    """Host-side per-tile health maps + scalar gauges.

    `fold_tiles` adds fetched per-tile values into a named map (one
    float per physical tile id); `set_gauge` overwrites a scalar.  All
    inputs are host scalars/arrays — folding a live device array here
    would be a hidden sync, so callers fetch first.
    """

    def __init__(self):
        self._tiles: dict[str, dict[int, float]] = {}
        self._gauges: dict[str, float] = {}

    # ------------------------------------------------------------ tiles
    def fold_tiles(self, metric: str, tile_ids, values,
                   mode: str = "sum") -> None:
        m = self._tiles.setdefault(metric, {})
        for tid, v in zip(np.asarray(tile_ids), np.asarray(values)):
            tid, v = int(tid), float(v)
            if mode == "sum":
                m[tid] = m.get(tid, 0.0) + v
            elif mode == "max":
                m[tid] = max(m.get(tid, float("-inf")), v)
            elif mode == "last":
                m[tid] = v
            else:
                raise ValueError(f"unknown fold mode {mode!r}")

    def tiles(self, metric: str) -> dict[int, float]:
        return dict(self._tiles.get(metric, {}))

    def worst(self, metric: str, k: int = 8) -> list[tuple[int, float]]:
        m = self._tiles.get(metric, {})
        return sorted(m.items(), key=lambda kv: -kv[1])[:k]

    # ----------------------------------------------------------- gauges
    def set_gauge(self, name: str, value: float) -> None:
        self._gauges[name] = float(value)

    def gauge(self, name: str, default: float = 0.0) -> float:
        return self._gauges.get(name, default)

    # -------------------------------------------------------- reporting
    def snapshot(self) -> dict[str, Any]:
        """JSON-safe snapshot: tile maps keyed by stringified tile id."""
        return {
            "tiles": {
                metric: {str(t): v for t, v in sorted(m.items())}
                for metric, m in sorted(self._tiles.items())
            },
            "gauges": dict(sorted(self._gauges.items())),
        }

    def emit(self) -> None:
        """Mirror the health maps into the trace as cat="health"
        instants (per-metric summary + worst tiles), so the dashboard
        can read them from the exported TRACE json."""
        from . import trace

        for metric, m in sorted(self._tiles.items()):
            vals = np.array(list(m.values()), np.float64)
            trace.instant(
                f"health.{metric}", cat="health",
                n_tiles=len(m),
                total=float(vals.sum()) if len(m) else 0.0,
                max=float(vals.max()) if len(m) else 0.0,
                worst={str(t): v for t, v in self.worst(metric)},
            )
        for name, v in sorted(self._gauges.items()):
            trace.instant(f"health.gauge.{name}", cat="health", value=v)

    def reset(self, prefix: str | None = None) -> None:
        if prefix is None:
            self._tiles = {}
            self._gauges = {}
        else:
            for d in (self._tiles, self._gauges):
                for k in [k for k in d if k.startswith(prefix)]:
                    del d[k]


# The global health registry (one process = one fleet view).
health = HealthRegistry()


# ------------------------------------------------------------- SLOs
def resolve_metric(status: Mapping[str, Any], path: str):
    """Resolve a dotted metric path against a nested status dict.

    Key names themselves contain dots ("serve.latency_steps"), so
    resolution tries the longest matching key prefix at every level;
    missing paths resolve to None (a rule on an absent metric does not
    breach — it reports value None).
    """
    if not path:
        return status
    if not isinstance(status, Mapping):
        return None
    if path in status:
        return status[path]
    parts = path.split(".")
    for i in range(len(parts) - 1, 0, -1):
        head = ".".join(parts[:i])
        if head in status:
            return resolve_metric(status[head], ".".join(parts[i:]))
    return None


@dataclasses.dataclass(frozen=True)
class SLORule:
    """One declarative service-level objective: `metric <= ceiling`.

    `metric` is a dotted path into the `fleet_status()` dict, e.g.
    ``digests.serve.latency_steps.p99`` or
    ``counters.deploy.gave_up_cells``.
    """

    name: str
    metric: str
    ceiling: float

    def evaluate(self, status: Mapping[str, Any]) -> dict[str, Any]:
        v = resolve_metric(status, self.metric)
        value = float(v) if isinstance(v, (int, float)) else None
        return {
            "name": self.name,
            "metric": self.metric,
            "ceiling": float(self.ceiling),
            "value": value,
            "breached": value is not None and value > self.ceiling,
        }


@dataclasses.dataclass(frozen=True)
class SLOPolicy:
    """A set of SLO rules evaluated host-side against a status snapshot.

    Evaluation is pure host work on already-fetched floats; breaches
    emit `cat="slo"` trace instants (for the dashboard timeline) and
    bump `slo.breaches.<rule>` registry counters (contract-bearing, so
    benchmarks can hard-assert when a breach must/must not fire).
    """

    rules: tuple[SLORule, ...]

    def evaluate(self, status: Mapping[str, Any],
                 emit: bool = True, **context: Any) -> list[dict[str, Any]]:
        from . import metrics, trace

        results = []
        for rule in self.rules:
            res = rule.evaluate(status)
            res.update(context)
            results.append(res)
            if res["breached"]:
                metrics.registry.inc(f"slo.breaches.{rule.name}")
                if emit:
                    trace.instant(
                        f"slo.breach.{rule.name}", cat="slo",
                        **{k: v for k, v in res.items() if k != "name"},
                    )
        metrics.registry.inc("slo.evaluations")
        return results


def fleet_status(extra: Mapping[str, Any] | None = None) -> dict[str, Any]:
    """Machine-readable fleet snapshot joining every obs namespace.

    The canonical SLO evaluation input: digest percentile summaries,
    per-tile health maps, gauges, and the full counter registry — all
    host floats, JSON-safe, zero device work.
    """
    from . import digest, metrics

    status: dict[str, Any] = {
        "digests": digest.snapshot(),
        "health": health.snapshot(),
        "counters": metrics.snapshot(),
    }
    if extra:
        status.update(extra)
    return status
