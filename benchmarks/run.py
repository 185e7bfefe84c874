# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
#
# The registry is declarative and LAZY: ``--list`` and unknown-name
# errors never import jax (or any benchmark module), so sweep drivers
# and the tier-1 registry smoke test stay fast.  Each registered
# benchmark runs in sequence; a benchmark that raises aborts the run
# LOUDLY — full traceback to stderr and a non-zero exit — so CI and
# sweep drivers can never mistake a half-finished run for a passing one.
#
#   python -m benchmarks.run                      # run everything
#   python -m benchmarks.run --list               # names only, no imports
#   python -m benchmarks.run serving.traffic --quick
from __future__ import annotations

import argparse
import importlib
import sys
import time
import traceback

# (name, module under benchmarks/, attribute, kwargs)
REGISTRY: list[tuple[str, str, str, dict]] = [
    ("fig9.tau_sweep", "fig9_convergence", "main", {"sweep_tau": True}),
    ("fig9.convergence", "fig9_convergence", "convergence_curves", {}),
    ("fig9.n_scaling", "fig9_convergence", "n_scaling", {}),
    ("fig9c.common_mode", "fig9c_common_mode", "main", {}),
    ("fig10.robustness", "fig10_robustness", "main", {}),
    ("fig11.iso_footprint_64", "fig10_robustness", "main_fig11", {}),
    ("fig12.iso_footprint", "fig12_iso_footprint", "main", {}),
    ("fig13.latency_energy_32", "fig13_latency_energy", "main", {"n_cells": 32}),
    ("fig13.latency_energy_64", "fig13_latency_energy", "main", {"n_cells": 64}),
    ("table2.prior_work", "table2_prior_work", "main", {}),
    ("retention.refresh", "retention_refresh", "main", {}),
    ("kernels.bench", "kernels_bench", "main", {}),
    ("deploy.throughput", "deploy_throughput", "main", {}),
    ("cim.inference", "cim_inference", "main", {}),
    ("readout.sweep", "readout_sweep", "main", {}),
    ("serving.traffic", "serving_traffic", "main", {}),
    ("fault.tolerance", "fault_tolerance", "main", {}),
    ("fleet.health", "fleet_health", "main", {}),
]

# Benchmarks whose entry accepts quick=True (CI smoke mode).
QUICK_CAPABLE = {
    "kernels.bench",
    "deploy.throughput",
    "cim.inference",
    "readout.sweep",
    "serving.traffic",
    "fault.tolerance",
    "fleet.health",
}

# --check-baselines: declarative quick-vs-committed comparison table.
#
# Quick and full runs use different model/stream sizes, so raw
# magnitudes are NOT comparable; each check names a key that is either
# a hard contract (mode "eq": must match the committed value exactly),
# scale-invariant within a declared relative tolerance (mode "rel"),
# or a ratio with a floor (mode "min").  Key paths resolve dotted
# segments longest-prefix-first so literal dotted key names (e.g.
# "sigma0.7__logit_rmse") resolve correctly.
#   (key_path, mode, tolerance_or_floor)
BASELINE_CHECKS: dict[str, tuple[str, str, list[tuple[str, str, float]]]] = {
    "deploy.throughput": ("BENCH_deploy.json", "BENCH_deploy_quick.json", [
        ("pipeline__host_syncs", "eq", 0.0),
        ("pipeline__warm_compiles", "eq", 0.0),
        ("speedup_warm", "min", 1.0),
        ("speedup_cold", "min", 1.0),
        ("pipeline__rms_cell_error_lsb", "rel", 0.10),
        ("baseline__rms_cell_error_lsb", "rel", 0.10),
        ("pipeline__mean_iterations", "rel", 0.10),
    ]),
    "cim.inference": ("BENCH_cim.json", "BENCH_cim_quick.json", [
        ("harp.deploy__rms_cell_error_lsb", "rel", 0.15),
        ("cw_sc.deploy__rms_cell_error_lsb", "rel", 0.15),
        ("harp.analog.sigma0__logit_rmse", "rel", 0.50),
        ("harp.analog.sigma0.7__logit_rmse", "rel", 0.50),
        ("serving__planes_per_token", "eq", 0.0),
    ]),
    "readout.sweep": ("BENCH_readout.json", "BENCH_readout_quick.json", [
        ("harp.clean.rms_cell_lsb", "rel", 0.15),
        ("harp.drifted.rms_cell_lsb", "rel", 0.15),
        ("harp.calibrated.rms_cell_lsb", "rel", 0.15),
        ("mra.drifted.rms_cell_lsb", "rel", 0.25),
        ("mra.calibrated.rms_cell_lsb", "rel", 0.25),
    ]),
    "serving.traffic": ("BENCH_serving.json", "BENCH_serving_quick.json", [
        ("digital.counters.host_syncs_per_step", "eq", 0.0),
        ("digital.counters.retraces_after_warmup", "eq", 0.0),
        ("analog.counters.host_syncs_per_step", "eq", 0.0),
        ("analog.counters.retraces_after_warmup", "eq", 0.0),
        ("config.rms_cell_error_lsb", "rel", 0.15),
        # Fused analog decode throughput gate (DESIGN.md Sec. 17): the
        # pre-fusion interpreter loop cost 25-90x more per decode step,
        # so even these generous runner-jitter tolerances fail loudly
        # if per-tile/per-plane Python dispatch ever creeps back.
        ("analog.summary.step_us", "rel", 2.0),
        ("analog.summary.tokens_per_s", "rel", 0.9),
        # SLO sweep (ISSUE-10): chunked prefill + EDF admission must cut
        # p99 TTFT vs whole-prompt FIFO on the mixed deadline stream
        # (>1 = improvement), and the policy variants must serve
        # byte-identical tokens (0.0 = zero mismatched requests).
        ("slo.summary.ttft_p99_improvement", "min", 1.0),
        ("slo.summary.tokens_bit_identical_across_policies", "eq", 0.0),
        # Data-sharded decode must stay bit-identical to the unsharded
        # run (0.0 = zero mismatches) with one host sync per step.
        ("sharded.tokens_bit_identical", "eq", 0.0),
        ("sharded.host_syncs_per_step", "eq", 0.0),
    ]),
    "fault.tolerance": ("BENCH_faults.json", "BENCH_faults_quick.json", [
        ("contracts.host_syncs_per_deploy", "eq", 0.0),
        ("contracts.zero_fault_bit_identical", "eq", 0.0),
        ("config.give_up_pulses", "eq", 0.0),
    ]),
    "fleet.health": ("BENCH_fleet.json", "BENCH_fleet_quick.json", [
        ("contracts.host_syncs_per_step", "eq", 0.0),
        ("contracts.retraces_after_warmup", "eq", 0.0),
        ("contracts.no_breach_before_inject", "eq", 0.0),
        ("contracts.give_up_first_breach_window", "eq", 0.0),
        ("config.inject_window", "eq", 0.0),
    ]),
}


def names() -> list[str]:
    return [name for name, _, _, _ in REGISTRY]


def _resolve_key(doc, path: str):
    """Resolve a dotted key path, longest key prefix first, so literal
    dotted key names inside the json resolve too.  Returns None when
    any segment is missing."""
    if not path:
        return doc
    if not isinstance(doc, dict):
        return None
    parts = path.split(".")
    for i in range(len(parts), 0, -1):
        head = ".".join(parts[:i])
        if head in doc:
            rest = ".".join(parts[i:])
            if not rest:
                return doc[head]
            found = _resolve_key(doc[head], rest)
            if found is not None:
                return found
    return None


def check_baselines(selected_names: list[str] | None = None) -> int:
    """Compare fresh quick metrics against the committed BENCH json.

    For every BASELINE_CHECKS entry whose committed baseline exists:
    run the quick benchmark if its quick json is missing (CI runs the
    quick smokes first, so this is normally a pure file comparison),
    then evaluate each declared check.  Returns the number of failed
    checks; prints one grep-able CSV row per check:
    ``check,<bench>,<key>,<mode>,<quick>,<committed>,<ok|FAIL>``.
    """
    import json
    import os

    here = os.path.dirname(os.path.abspath(__file__))
    failures = 0
    items = [
        (n, BASELINE_CHECKS[n])
        for n in (selected_names or list(BASELINE_CHECKS))
        if n in BASELINE_CHECKS
    ]
    print("check,benchmark,key,mode,quick,committed,status")
    for bench, (full_file, quick_file, checks) in items:
        full_path = os.path.join(here, full_file)
        quick_path = os.path.join(here, quick_file)
        if not os.path.exists(full_path):
            print(f"check,{bench},-,-,-,-,SKIP:no-baseline")
            continue
        if not os.path.exists(quick_path):
            by_name = {e[0]: e for e in REGISTRY}
            _, module, attr, kwargs = by_name[bench]
            from repro import obs  # noqa: PLC0415

            obs.reset_all()
            _resolve(module, attr)(**dict(kwargs, quick=True))
        with open(full_path) as f:
            full = json.load(f)
        with open(quick_path) as f:
            quick = json.load(f)
        for key, mode, arg in checks:
            qv, fv = _resolve_key(quick, key), _resolve_key(full, key)
            ok = qv is not None and fv is not None
            if ok:
                if mode == "eq":
                    ok = qv == fv
                elif mode == "min":
                    ok = float(qv) >= arg
                elif mode == "rel":
                    ok = abs(float(qv) - float(fv)) <= arg * max(
                        abs(float(fv)), 1e-9
                    )
                else:
                    raise ValueError(f"unknown check mode {mode!r}")
            status = "ok" if ok else "FAIL"
            failures += 0 if ok else 1
            print(f"check,{bench},{key},{mode},{qv},{fv},{status}")
    return failures


def _resolve(module: str, attr: str):
    pkg = __package__ or "benchmarks"
    return getattr(importlib.import_module(f"{pkg}.{module}"), attr)


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(prog="benchmarks.run")
    ap.add_argument("benchmarks", nargs="*", metavar="NAME",
                    help="benchmark names to run (default: all)")
    ap.add_argument("--list", action="store_true", help="print names and exit")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke mode (quick-capable benchmarks only)")
    ap.add_argument("--check-baselines", action="store_true",
                    help="compare fresh quick metrics against committed "
                         "BENCH_*.json baselines; non-zero exit on drift")
    args = ap.parse_args(argv)
    if not args.list:  # --list stays jax-free
        from repro.launch.compile_cache import enable_compile_cache  # noqa: PLC0415

        enable_compile_cache()

    if args.check_baselines:
        failures = check_baselines(args.benchmarks or None)
        if failures:
            print(f"baseline-check,{failures},FAILED", file=sys.stderr)
            sys.exit(1)
        print("baseline-check,0,all-within-tolerance")
        return

    if args.list:
        for n in names():
            tag = " [quick]" if n in QUICK_CAPABLE else ""
            print(f"{n}{tag}")
        return

    selected = REGISTRY
    if args.benchmarks:
        by_name = {entry[0]: entry for entry in REGISTRY}
        unknown = [n for n in args.benchmarks if n not in by_name]
        if unknown:
            print(
                f"unknown benchmark(s): {', '.join(unknown)}; "
                f"known: {', '.join(names())}",
                file=sys.stderr,
            )
            sys.exit(2)
        selected = [by_name[n] for n in args.benchmarks]
    if args.quick:
        bad = [n for n, _, _, _ in selected if n not in QUICK_CAPABLE]
        if args.benchmarks and bad:
            print(f"not quick-capable: {', '.join(bad)}", file=sys.stderr)
            sys.exit(2)
        selected = [e for e in selected if e[0] in QUICK_CAPABLE]

    t0 = time.time()
    print("name,us_per_call,derived")
    for name, module, attr, kwargs in selected:
        kw = dict(kwargs, quick=True) if args.quick else kwargs
        try:
            # Lazy import (keeps --list jax-free): fresh telemetry per
            # benchmark, so each exported TRACE_*.json is self-contained.
            from repro import obs  # noqa: PLC0415

            obs.reset_all()
            _resolve(module, attr)(**kw)
        except Exception:
            traceback.print_exc()
            print(
                f"benchmarks.total,{(time.time() - t0) * 1e6:.0f},"
                f"FAILED:{name}",
                file=sys.stderr,
            )
            sys.exit(1)
    print(f"benchmarks.total,{(time.time() - t0) * 1e6:.0f},all-passed")


if __name__ == "__main__":
    main()
