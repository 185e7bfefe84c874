"""Deployment throughput: bucketed pipeline vs per-leaf baseline.

The paper's headline is programming *throughput* (up to 6.1x latency /
9.5x energy per column); this benchmark tracks whether the model-level
deployment path preserves it.  A synthetic multi-leaf transformer-style
parameter tree is deployed twice through each path:

* baseline    — the pre-pipeline deployment path reproduced verbatim
                (PR 1's `_program_leaf` loop): one EAGER
                `program_columns` call per leaf — the while loop
                re-traces on every call — plus `DeployReport.merge`'s
                7 scalar host pulls per leaf;
* perleaf_jit — `deploy_arrays(batched=False)`: per-leaf dispatches
                through the shared jit cache (one trace per distinct
                leaf shape), still per-leaf host syncs;
* pipeline    — `deploy_arrays(batched=True)`: all packed columns
                concatenated into power-of-two buckets, ONE jitted
                donated dispatch per bucket, device-side stats, exactly
                one host sync.

Emits ``name,us_per_call,derived`` CSV rows plus `BENCH_deploy.json`
with cold/warm columns-per-second, compile counts (must stay <= the
number of buckets) and host-sync counts — the deployment-throughput
trajectory tracked from PR 2 on.  `--quick` shrinks the model for CI
smoke runs.
"""

from __future__ import annotations

import json
import pathlib
import sys

import jax
import jax.numpy as jnp

from repro.core import WVConfig, WVMethod, pipeline, program_columns
from repro.core import device as dev_mod
from repro.core.cost import CircuitCost
from repro.core.programmer import DeployReport, _eligible_leaves, deploy_arrays
from repro.quant import QuantConfig, pack_columns, quantize_weight

from .common import emit, export_trace, stopwatch

_MIN_BUCKET = 256


def _toy_params(n_blocks: int, d_model: int, d_ff: int, seed: int = 0):
    """Multi-leaf transformer-shaped tree: repeated AND distinct shapes."""
    keys = jax.random.split(jax.random.PRNGKey(seed), n_blocks + 1)
    params = {
        "embed": jax.random.normal(keys[-1], (256, d_model)) * 0.02,
        "final_norm": jnp.ones((d_model,)),
    }
    for b in range(n_blocks):
        k = jax.random.split(keys[b], 6)
        params[f"block{b}"] = {
            "wq": jax.random.normal(k[0], (d_model, d_model)) * 0.02,
            "wkv": jax.random.normal(k[1], (d_model, d_model // 2)) * 0.02,
            "wo": jax.random.normal(k[2], (d_model, d_model)) * 0.02,
            "w_up": jax.random.normal(k[3], (d_model, d_ff)) * 0.02,
            "w_down": jax.random.normal(k[4], (d_ff, d_model)) * 0.02,
            "norm": jnp.ones((d_model,)),
        }
    return params


def _deploy_baseline_eager(params, cfg: WVConfig, seed: int = 1) -> DeployReport:
    """PR 1's per-leaf deployment loop, reproduced verbatim.

    Eager `program_columns` per leaf (the `lax.while_loop` re-traces on
    EVERY call — this is the "retraces per leaf" cost the pipeline
    removes), legacy batch-shaped RNG, and `DeployReport.merge` blocking
    on 7 scalar host pulls per leaf.
    """
    q_cfg = QuantConfig(weight_bits=cfg.weight_bits, cell_bits=cfg.device.bc)
    key = jax.random.PRNGKey(seed)
    cost = CircuitCost()
    report = DeployReport()
    records, _ = _eligible_leaves(params, False, None)
    for i, name, leaf, eligible in records:
        if not eligible:
            continue
        k = jax.random.fold_in(key, i)
        w2 = leaf.reshape((-1, leaf.shape[-1]))
        q, _ = quantize_weight(w2, q_cfg)
        cols, _ = pack_columns(q, cfg.n_cells, q_cfg.cell_bits, q_cfg.slices)
        k_d2d, _, _ = jax.random.split(k, 3)
        d2d = dev_mod.sample_d2d(k_d2d, cols.shape, cfg.device)
        _, stats = program_columns(k, cols, cfg, cost=cost, d2d=d2d)
        report.merge(name, stats, cfg)
    return report


def _time_deploy(params, cfg, batched: bool, seed: int = 1):
    """One full deploy; returns (seconds, report, compiles, host_syncs)."""
    c0, s0 = pipeline.compile_count(), pipeline.host_sync_count()
    with stopwatch(
        "deploy_arrays", batched=batched, seed=seed
    ) as w:
        _, report = deploy_arrays(
            jax.random.PRNGKey(seed), params, cfg,
            batched=batched, min_bucket=_MIN_BUCKET,
        )
    return (
        w.seconds,
        report,
        pipeline.compile_count() - c0,
        pipeline.host_sync_count() - s0,
    )


def main(quick: bool = False) -> dict:
    if quick:
        params = _toy_params(n_blocks=2, d_model=64, d_ff=128)
    else:
        params = _toy_params(n_blocks=4, d_model=128, d_ff=256)
    cfg = WVConfig(method=WVMethod.HARP)

    rows = {}
    # Every call of the eager baseline re-traces, so one timed run IS
    # its steady state (cold == warm).
    with stopwatch("deploy_baseline_eager") as w:
        base_report = _deploy_baseline_eager(params, cfg)
    base_s = w.seconds
    n_leaves = len(base_report.leaves)
    rows["baseline"] = dict(
        columns=base_report.num_columns,
        leaves=n_leaves,
        cold_s=base_s,
        warm_s=base_s,
        cold_columns_per_sec=base_report.num_columns / base_s,
        warm_columns_per_sec=base_report.num_columns / base_s,
        compiles=n_leaves,        # eager: the WV loop re-traces per leaf
        warm_compiles=n_leaves,
        host_syncs=7 * n_leaves,  # DeployReport.merge scalar pulls
        mean_iterations=base_report.mean_iterations,
        rms_cell_error_lsb=base_report.rms_cell_error_lsb,
    )
    emit(
        f"deploy.baseline{'.quick' if quick else ''}",
        base_s * 1e6,
        f"cols_per_s={base_report.num_columns / base_s:.0f} "
        f"retraces={n_leaves} host_syncs={7 * n_leaves}",
    )

    for name, batched in (("perleaf_jit", False), ("pipeline", True)):
        cold_s, report, compiles, syncs = _time_deploy(params, cfg, batched)
        warm_s, _, warm_compiles, _ = _time_deploy(params, cfg, batched, seed=2)
        cols = report.num_columns
        # The per-leaf paths pay `DeployReport.merge`'s 7 scalar
        # device->host pulls per leaf; the pipeline path is counted by
        # `host_fetch`.
        host_syncs = syncs if batched else 7 * len(report.leaves)
        rows[name] = dict(
            columns=cols,
            leaves=len(report.leaves),
            cold_s=cold_s,
            warm_s=warm_s,
            cold_columns_per_sec=cols / cold_s,
            warm_columns_per_sec=cols / warm_s,
            compiles=compiles,
            warm_compiles=warm_compiles,
            host_syncs=host_syncs,
            mean_iterations=report.mean_iterations,
            rms_cell_error_lsb=report.rms_cell_error_lsb,
        )
        emit(
            f"deploy.{name}{'.quick' if quick else ''}",
            warm_s * 1e6,
            f"cols_per_s={cols / warm_s:.0f} compiles={compiles} "
            f"host_syncs={host_syncs}",
        )

    n_buckets = len(pipeline.bucket_sizes(
        rows["pipeline"]["columns"], _MIN_BUCKET
    ))
    speedup = (
        rows["pipeline"]["warm_columns_per_sec"]
        / rows["baseline"]["warm_columns_per_sec"]
    )
    cold_speedup = (
        rows["pipeline"]["cold_columns_per_sec"]
        / rows["baseline"]["cold_columns_per_sec"]
    )
    result = dict(
        quick=quick,
        method=cfg.method.value,
        n_buckets=n_buckets,
        min_bucket=_MIN_BUCKET,
        speedup_warm=speedup,
        speedup_cold=cold_speedup,
        **{f"{k}__{kk}": vv for k, v in rows.items() for kk, vv in v.items()},
    )
    emit(
        f"deploy.speedup{'.quick' if quick else ''}",
        0.0,
        f"warm={speedup:.1f}x cold={cold_speedup:.1f}x buckets={n_buckets}",
    )
    # Perf contract (ISSUE 2 acceptance): the bucketed pipeline must
    # beat the per-leaf path >= 3x, compile at most once per bucket,
    # never retrace on a same-shape redeploy, and sync exactly once.
    assert rows["pipeline"]["compiles"] <= n_buckets, result
    assert rows["pipeline"]["warm_compiles"] == 0, result
    assert rows["pipeline"]["host_syncs"] == 1, result
    assert speedup >= 3.0, result

    # Quick (CI smoke) runs must not clobber the committed full-mode
    # perf trajectory.
    name = "BENCH_deploy_quick.json" if quick else "BENCH_deploy.json"
    out = pathlib.Path(__file__).with_name(name)
    out.write_text(json.dumps(result, indent=1))
    export_trace("deploy", quick)
    return result


if __name__ == "__main__":
    print("name,us_per_call,derived")
    main(quick="--quick" in sys.argv)
