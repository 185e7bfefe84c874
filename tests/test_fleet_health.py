"""Fleet health & SLO contracts (DESIGN.md Sec. 16).

Tier-1 versions of what benchmarks/fleet_health.py asserts at scale:

* per-tile health maps reduce device-side over the deploy's dense tile
  range, equal bit for bit to a numpy reduction over the present tiles,
  and ride the deploy's single host sync (no extra fetch for the maps
  or the deploy digests), on the spare-remap path too;
* the lifetime scrub populates drift/give-up health state and the
  refresh-debt gauge on its existing epoch sync;
* declarative SLO rules resolve dotted metric paths (including literal
  dotted key names), treat missing metrics as non-breaching, and fire
  exactly when injected degradation crosses the ceiling — a sick chip's
  stuck-cell population surfaces give-ups only when ITS scrub runs.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import WVConfig, WVMethod, pipeline, remap
from repro.core.programmer import deploy_arrays
from repro.core.wv import WVStats
from repro.core.types import FaultConfig
from repro.lifetime import LifetimeSimulator
from repro.lifetime.refresh import RefreshConfig, RefreshPolicy
from repro.obs import metrics
from repro.obs.health import present_tiles, resolve_metric, tile_deploy_stats


@pytest.fixture(autouse=True)
def _fresh_obs():
    obs.reset_all()
    yield
    obs.reset_all()


def _tiny_params():
    k = jax.random.split(jax.random.PRNGKey(0), 2)
    return {
        "wa": jax.random.normal(k[0], (32, 48)) * 0.02,
        "wb": jax.random.normal(k[1], (48, 32)) * 0.02,
        "norm": jnp.ones((32,)),
    }


_WV = WVConfig(method=WVMethod.HARP, give_up_pulses=80)


# ------------------------------------------------------------- SLO rules
def test_slo_rule_resolution_and_missing_metric():
    status = {
        "digests": {"rep0.latency_steps": {"p99": 40.0, "count": 7.0}},
        "health": {"gauges": {"fleet.give_up_rate": 2e-3}},
        "counters": {"lifetime.gave_up_cells": 12.0},
    }
    # dotted digest name + summary field resolve longest-prefix-first
    assert resolve_metric(status, "digests.rep0.latency_steps.p99") == 40.0
    assert resolve_metric(status, "health.gauges.fleet.give_up_rate") == 2e-3
    assert resolve_metric(status, "digests.rep9.latency_steps.p99") is None

    hit = obs.SLORule("p99", "digests.rep0.latency_steps.p99", 30.0)
    ok = obs.SLORule("p99_ok", "digests.rep0.latency_steps.p99", 50.0)
    missing = obs.SLORule("gone", "digests.rep9.latency_steps.p99", 1.0)
    assert hit.evaluate(status)["breached"] is True
    assert ok.evaluate(status)["breached"] is False
    res = missing.evaluate(status)
    assert res["value"] is None and res["breached"] is False


def test_slo_policy_counters_and_trace_gating():
    status = {"digests": {}, "health": {"gauges": {"g": 3.0}}, "counters": {}}
    policy = obs.SLOPolicy(rules=(obs.SLORule("g_high", "health.gauges.g", 1.0),))
    policy.evaluate(status, window=0)
    with obs.disabled():
        policy.evaluate(status, window=1)
    # counters are contract-bearing: they count even while disabled
    assert metrics.value("slo.breaches.g_high") == 2.0
    assert metrics.value("slo.evaluations") == 2.0
    # trace instants are presentation: only the enabled evaluation emits
    slo_events = [
        e for e in obs.trace.events() if e.get("cat") == "slo"
    ]
    assert len(slo_events) == 1
    assert slo_events[0]["args"]["window"] == 0
    assert slo_events[0]["args"]["value"] == 3.0


def test_fleet_status_joins_namespaces():
    obs.digests.observe("d", 2.0, lo=0.0, hi=4.0, n_buckets=4)
    obs.health_registry.set_gauge("g", 1.0)
    metrics.registry.inc("c", 5.0)
    status = obs.fleet_status(extra={"fleet": {"inject_window": 2}})
    assert status["digests"]["d"]["count"] == 1.0
    assert status["health"]["gauges"]["g"] == 1.0
    assert status["counters"]["c"] == 5.0
    assert status["fleet"]["inject_window"] == 2


# ---------------------------------------------------- deploy health maps
_CPT = 16
_LEAF_COLS = (40, 72, 9)


def _leaf_stats(rng, c):
    def vec(hi):
        return jnp.asarray(rng.integers(0, hi, c).astype(np.float32))

    return WVStats(
        iterations=vec(50), latency_ns=vec(900), energy_pj=vec(900),
        reads=vec(400), write_pulses=vec(300),
        rms_error_lsb=jnp.asarray(rng.random(c, dtype=np.float32) * 2.5),
        frozen_frac=vec(1),
        gave_up=jnp.asarray(rng.integers(0, 3, c), jnp.int32),
        retry_pulses=vec(60),
    )


def _placement_uids(counts):
    fc = FaultConfig(p_stuck_hrs=0.02, columns_per_tile=_CPT, tiles_per_chip=4,
                     sigma_tile_fault_dec=1.0)
    return remap.plan_placement(jax.random.PRNGKey(7), counts, fc)


def _contiguous_uids(counts, base=0):
    edges = base + np.cumsum([0, *counts])
    return [np.arange(a, b, dtype=np.int64) for a, b in zip(edges, edges[1:])]


_TILE_CASES = {
    "contiguous": lambda: (_contiguous_uids(_LEAF_COLS), False),
    "far-from-zero": lambda: (_contiguous_uids(_LEAF_COLS, 2**30 + 5), False),
    "placement": lambda: (_placement_uids(_LEAF_COLS), False),
    "extra-columns": lambda: (_placement_uids(_LEAF_COLS), True),
    "empty": lambda: ([], False),
}


@pytest.mark.parametrize("case", list(_TILE_CASES))
def test_tile_deploy_stats_match_numpy_reference(case):
    """Through the fetch and `present_tiles`, the dense-range device
    reduction gives the tile ids and every per-tile map, bit for bit, of
    a numpy reference that sorts the tile ids and sums each column into
    its tile in column order; the registry folds the same values."""
    rng = np.random.default_rng(11)
    leaf_uids, with_extra = _TILE_CASES[case]()
    names = [f"leaf{i}" for i in range(len(leaf_uids))]
    stats_map = {n: _leaf_stats(rng, len(u)) for n, u in zip(names, leaf_uids)}
    uids_map = dict(zip(names, leaf_uids))
    extra = None
    if with_extra:
        extra = {"remapped_columns": {
            n: jnp.asarray(rng.integers(0, 2, len(u)).astype(np.float32))
            for n, u in zip(names, leaf_uids)
        }}
    tile_ids, maps = present_tiles(
        metrics.fetch(tile_deploy_stats(stats_map, uids_map, _CPT, extra))
    )

    if not names:
        assert tile_ids.shape == (0,) and maps == {}
        return
    uids = np.concatenate([np.asarray(u, np.int64) for u in leaf_uids])
    ref_ids, inv = np.unique(uids // _CPT, return_inverse=True)

    def cat(vecs):
        return np.concatenate([np.asarray(v) for v in vecs]).astype(np.float32)

    cols = {
        "gave_up_cells": cat(s.gave_up for s in stats_map.values()),
        "retry_pulses": cat(s.retry_pulses for s in stats_map.values()),
        "write_pulses": cat(s.write_pulses for s in stats_map.values()),
        "verify_reads": cat(s.reads for s in stats_map.values()),
        "err2_sum": cat(s.rms_error_lsb for s in stats_map.values()) ** 2,
    }
    if extra:
        cols["remapped_columns"] = cat(extra["remapped_columns"][n] for n in names)
    ref = {}
    for metric, vals in cols.items():
        ref[metric] = np.zeros(ref_ids.shape, np.float32)
        np.add.at(ref[metric], inv, vals)
    ref["columns"] = np.bincount(inv, minlength=len(ref_ids)).astype(np.float64)

    assert tile_ids.dtype == np.int64 and np.array_equal(tile_ids, ref_ids)
    assert sorted(maps) == sorted(ref)
    for metric, want in ref.items():
        got = maps[metric]
        assert got.dtype == want.dtype, metric
        np.testing.assert_array_equal(got, want, err_msg=metric)
        obs.health_registry.fold_tiles(f"t.{metric}", tile_ids, got)
        assert obs.health_registry.tiles(f"t.{metric}") == {
            int(t): float(v) for t, v in zip(ref_ids, want)
        }


def test_tile_deploy_stats_refuses_uids_past_int32():
    """The uids go to the device as int32: one past its range is refused,
    not wrapped onto another tile."""
    rng = np.random.default_rng(0)
    uids = {"leaf": np.array([2**31 - 2, 2**31 - 1, 2**31], np.int64)}
    with pytest.raises(ValueError, match="int32"):
        tile_deploy_stats({"leaf": _leaf_stats(rng, 3)}, uids, _CPT)


def _deploy_span():
    return [e for e in obs.trace.events()
            if e["name"] == "deploy" and e["ph"] == "X"][-1]


def test_deploy_health_rides_single_sync():
    """Tile health maps + deploy digests populate on the batched
    deploy's ONE host sync — faulty silicon shows up as per-tile
    give-up mass without any extra fetch.  A contiguous deploy fills
    its dense tile range: the `deploy` span's two tile counters agree."""
    fc = FaultConfig(p_stuck_hrs=0.05, columns_per_tile=16, tiles_per_chip=4)
    pipeline.reset_counters()
    deploy_arrays(jax.random.PRNGKey(3), _tiny_params(), _WV, fault_cfg=fc)
    assert pipeline.host_sync_count() == 1
    tiles = obs.health_registry.tiles("deploy.gave_up_cells")
    assert tiles and sum(tiles.values()) > 0
    assert obs.health_registry.tiles("deploy.write_pulses")
    for name in ("deploy.write_pulses_per_column",
                 "deploy.iterations_per_column"):
        d = obs.digests.get(name)
        assert d is not None and d.count > 0
    args = _deploy_span()["args"]
    n_tiles = len(obs.health_registry.tiles("deploy.columns"))
    assert args["health_tile_range"] == args["health_tiles"] == n_tiles
    assert metrics.value("deploy.health_tiles") == n_tiles
    assert metrics.value("deploy.health_tile_range") == n_tiles


@pytest.mark.parametrize("placement", [False, True], ids=["packed", "placement"])
def test_deploy_remap_health_rides_single_sync(placement):
    """The two-pass spare-remap deploy still makes ONE host sync, with
    the per-tile remapped-column map riding it.  Placement spreads the
    uids over its provision of tiles, so the dense range it reduces
    holds empty tiles that the fold leaves out (the per-tile fault-rate
    spread gives it tiles to prefer)."""
    fc = FaultConfig(p_stuck_hrs=0.05, columns_per_tile=16, tiles_per_chip=4,
                     sigma_tile_fault_dec=1.0)
    rc = remap.RemapConfig(spare_frac=0.25, placement=placement)
    pipeline.reset_counters()
    dep, rep = deploy_arrays(
        jax.random.PRNGKey(3), _tiny_params(), _WV, fault_cfg=fc, remap_cfg=rc,
    )
    assert pipeline.host_sync_count() == 1
    remapped = obs.health_registry.tiles("deploy.remapped_columns")
    assert sum(remapped.values()) == rep.remapped_columns
    uids = np.concatenate([a.uids for a in dep.arrays.values()])
    present = np.unique(uids // 16)
    assert sorted(obs.health_registry.tiles("deploy.columns")) == list(present)
    args = _deploy_span()["args"]
    assert args["health_tiles"] == len(present)
    assert args["health_tile_range"] == present[-1] - present[0] + 1
    if placement:
        assert args["health_tiles"] < args["health_tile_range"]
    else:
        assert args["health_tiles"] == args["health_tile_range"]


# ------------------------------------- injected degradation -> SLO epoch
def test_give_up_slo_fires_only_when_sick_scrub_runs():
    """Two chips, one sick (stuck cells), staggered scrubs: the
    give-up-rate rule stays green while only the healthy chip scrubs
    and breaches exactly when the sick chip's deferred scrub surfaces
    its bad silicon."""
    params = _tiny_params()
    dep_h, _ = deploy_arrays(jax.random.PRNGKey(1), params, _WV)
    fc = FaultConfig(p_stuck_hrs=0.05, columns_per_tile=16, tiles_per_chip=4)
    dep_s, rep_s = deploy_arrays(
        jax.random.PRNGKey(2), params, _WV, fault_cfg=fc
    )
    assert rep_s.total_gave_up_cells > 0  # the bad silicon is real
    n_cells = sum(
        int(np.prod(a.g.shape))
        for d in (dep_h, dep_s)
        for a in d.arrays.values()
    )
    sim_h = LifetimeSimulator(
        jax.random.PRNGKey(4), dep_h,
        refresh_cfg=RefreshConfig(policy=RefreshPolicy.VERIFY_TRIGGERED),
        columns_per_tile=16,
    )
    sim_s = LifetimeSimulator(
        jax.random.PRNGKey(5), dep_s,
        refresh_cfg=RefreshConfig(policy=RefreshPolicy.VERIFY_TRIGGERED),
        columns_per_tile=16,
    )
    policy = obs.SLOPolicy(
        rules=(
            obs.SLORule(
                "give_up_rate", "health.gauges.fleet.give_up_rate", 3e-4
            ),
        )
    )

    def window(sims):
        for sim in sims:
            sim.step_epoch(10.0)
        gave_up = metrics.snapshot().get("lifetime.gave_up_cells", 0.0)
        obs.health_registry.set_gauge("fleet.give_up_rate", gave_up / n_cells)
        (res,) = policy.evaluate(obs.fleet_status())
        return res

    # windows 0-1: only the healthy chip scrubs -> green
    assert window([sim_h])["breached"] is False
    assert window([sim_h])["breached"] is False
    # window 2: the sick chip's deferred scrub runs -> breach
    res = window([sim_h, sim_s])
    assert res["breached"] is True, res
    # the scrub also populated drift health + the refresh-debt gauge
    assert obs.health_registry.tiles("lifetime.drift_rms_lsb")
    gauges = obs.health_registry.snapshot()["gauges"]
    assert "lifetime.refresh_debt_epochs" in gauges
    d = obs.digests.get("lifetime.drift_lsb")
    assert d is not None and d.count > 0
