"""Run one cell of the chip benchmark and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up in `BENCHMARK.json` at the root of the checkout.
Everything else is found by name: the configuration at the cell's
`file`, the traffic mix at `chipbench/traffic/<traffic>.json`, the
driver that the mix names at `chipbench/drivers/<driver>.py`, and each
per-layer metric's reader at `chipbench/metrics/<metric>.py`.  Adding a
configuration, a mix or a metric is a new file plus an entry.

A run: set-up (inputs from the seed, the program built and every shape
the window uses warmed up), the measured window, then the check of what
the window produced against a plain reference.  With `--trace 0` the
result carries the cell's end-to-end metrics; with `--trace 1` the
window runs under the profiler and the result carries the per-layer
metrics, the device's busy time and a breakdown.  Without a TPU, or
outside a checkout that holds the program (`src/repro`), it exits
non-zero and prints no result.  The last line of standard output is the
result, as one JSON object.
"""

from __future__ import annotations

import time

PROCESS_T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Any  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if sys.path and os.path.abspath(sys.path[0]) == HERE:
    sys.path[0] = ROOT  # import the package, and shadow no standard module

from chipbench.harness import (  # noqa: E402
    CompileCounter, Context, Refused, Run,
)


def load_module(path: str, name: str):
    if not os.path.isfile(path):
        raise Refused(f"{path} not found")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(path: str) -> dict:
    if not os.path.isfile(path):
        raise Refused(f"{path} not found")
    with open(path) as f:
        return json.load(f)


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def _devices(cell: dict, require_tpu: bool) -> list:
    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise Refused(f"no TPU: JAX runs on {devices[0].platform!r}")
    if len(devices) < cell["chips"]:
        raise Refused(f"the cell needs {cell['chips']} chips; JAX sees {len(devices)}")
    return devices[: cell["chips"]]


def _compile_cache() -> str:
    """JAX's persistent cache in the checkout's fixed `.jax_cache`, as the
    program keeps it, holding every program so that only a checkout's
    first run compiles."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def _trace_reduction(trace_dir: str):
    import glob

    from chipbench import devtrace

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise RuntimeError("the profiler wrote no trace")
    Context.log(f"trace {os.path.getsize(paths[-1])} bytes")
    return devtrace.load(paths[-1])


def load_cell(name: str, seed: int, *, root: str = ROOT, require_tpu: bool = True):
    """The benchmark, the cell's driver module and its `Context`."""
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _json(os.path.join(root, configs[cell["config"]]["file"]))
    traffic = _json(os.path.join(root, "chipbench", "traffic", f"{cell['traffic']}.json"))
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise Refused(f"the program under test is not at {src}/repro")
    if src not in sys.path:
        sys.path.insert(0, src)
    _compile_cache()
    devices = _devices(cell, require_tpu)
    driver_mod = load_module(
        os.path.join(root, "chipbench", "drivers", f"{traffic['driver']}.py"),
        f"chipbench_driver_{traffic['driver']}",
    )
    ctx = Context(cell=cell, config=config, traffic=traffic, seed=seed,
                  devices=devices)
    return bench, driver_mod, ctx


def run(args, *, root: str = ROOT, require_tpu: bool = True) -> dict:
    bench, driver_mod, ctx = load_cell(args.workload, args.seed, root=root,
                                       require_tpu=require_tpu)
    cell, devices = ctx.cell, ctx.devices

    import jax

    from chipbench import peaks as peaks_mod

    peaks = peaks_mod.chip_peaks(devices[0].device_kind)
    driver = driver_mod.Driver(ctx)
    driver.setup()
    setup_s = time.perf_counter() - PROCESS_T0
    ctx.log(f"set-up {setup_s!r} s")

    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if args.trace else None
    if trace_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with CompileCounter() as compiles, ctx.annotate("window"):
            values = driver.window(args.seconds)
    finally:
        if trace_dir:
            jax.profiler.stop_trace()
    if compiles.events:
        ctx.log(f"warning: {len(compiles.events)} compile events in the window: "
                f"{sorted(set(compiles.events))}")
    peak = [d.memory_stats() for d in devices]
    memory_peak = max(int((s or {}).get("peak_bytes_in_use", 0)) for s in peak)

    reduction = None
    if trace_dir:
        t0 = time.perf_counter()
        try:
            reduction = _trace_reduction(trace_dir)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        ctx.log(f"trace reduction took {time.perf_counter() - t0!r} s")
    driver.release()
    t0 = time.perf_counter()
    checks = driver.check()
    ctx.log(f"check took {time.perf_counter() - t0!r} s")

    metrics: dict[str, dict] = {}
    if not args.trace:
        values = dict(values, setup_s=setup_s)
        for m in bench["end_to_end"]:
            if _applies(m, cell["name"]):
                metrics[m["name"]] = {"value": float(values[m["name"]]), "unit": m["unit"]}
    else:
        rec = Run(cell=cell["name"], trace=reduction, peaks=peaks,
                  records=driver.records)
        reported = {m["name"] for m in bench["end_to_end"] if _applies(m, cell["name"])}
        for m in bench["per_layer"]:
            if not _applies(m, cell["name"]) or m["moves"] not in reported:
                continue
            reader = load_module(
                os.path.join(root, "chipbench", "metrics", f"{m['name']}.py"),
                "chipbench_metric_" + m["name"].replace(".", "_").replace("-", "_"),
            )
            value = reader.read(rec)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    device = {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(jax.devices()), "memory_peak_bytes": memory_peak,
    }
    result: dict[str, Any] = {
        "correct": bool(checks) and all(c.ok for c in checks),
        "attempted": int(driver.attempted), "failed": int(driver.failed),
        "metrics": metrics, "device": device,
        "window_compiles": len(compiles.events),
    }
    if reduction is not None:
        device["busy_s"] = reduction.busy_s
        device["window_s"] = reduction.window_s
        result["breakdown"] = {
            "device_ops": reduction.top_ops(10), "idle_gaps": reduction.top_idle(10),
        }
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit} for c in checks}
    for c in checks:
        ctx.log(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
                f"{'ok' if c.ok else 'FAILED'}")
    return result


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, root: str = ROOT, require_tpu: bool = True) -> int:
    args = parse_args(argv)
    try:
        result = run(args, root=root, require_tpu=require_tpu)
    except Refused as e:
        print(f"chipbench: {e}; nothing was run", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
