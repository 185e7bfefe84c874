import os

if "--dryrun" in __import__("sys").argv:
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=512 "
        + os.environ.get("XLA_FLAGS", "")
    )

"""Distributed RRAM programming driver — the paper's technique at scale.

Columns are embarrassingly parallel: the launcher shards the packed
column axis over the ENTIRE mesh (("data","model") — 256 chips/pod) so
programming a 235B-parameter model's 2.1e9 columns runs with zero
cross-chip traffic inside the verify loop.

Modes:
  * real (default): program a smoke-config model end-to-end on CPU.
  * --dryrun: lower + compile `program_columns` for a production-scale
    column batch on the 16x16 mesh and emit the roofline row — this is
    the paper-representative cell of EXPERIMENTS.md Sec. Perf.
"""

import argparse
import json

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import WVConfig, WVMethod, program_columns


def run_dryrun(method: str, n_columns: int, use_pallas: bool, out_dir: str):
    from repro.launch import roofline as rf
    from repro.launch.mesh import make_production_mesh

    mesh = make_production_mesh()
    cfg = WVConfig(method=WVMethod(method), use_pallas=use_pallas)
    spec = NamedSharding(mesh, P(("data", "model"), None))
    t_sds = jax.ShapeDtypeStruct((n_columns, cfg.n_cells), jnp.float32)
    k_sds = jax.ShapeDtypeStruct((2,), jnp.uint32)

    fn = jax.jit(
        lambda k, t: program_columns(k, t, cfg),
        in_shardings=(NamedSharding(mesh, P()), spec),
        out_shardings=(spec, None),
    )
    with jax.set_mesh(mesh):
        compiled = fn.lower(k_sds, t_sds).compile()
    cost = rf.summarize_cost_analysis(compiled.cost_analysis())
    mem = rf.summarize_memory_analysis(compiled.memory_analysis())
    coll = rf.collective_bytes_from_hlo(compiled.as_text())
    chips = mesh.devices.size
    cells = n_columns * cfg.n_cells
    terms = rf.RooflineTerms(
        arch=f"program-wv-{method}" + ("-pallas" if use_pallas else ""),
        shape=f"cols{n_columns}",
        mesh="pod16x16",
        chips=chips,
        hlo_flops=cost.get("flops", 0.0) * chips,
        hlo_bytes=cost.get("bytes accessed", 0.0) * chips,
        collective_bytes=coll["total_bytes"],
        model_flops=2.0 * cells * 50,  # ~50 sweeps x O(cells) work floor
        device_kind=rf.DRYRUN_DEVICE_KIND,
        collective_detail=coll,
        memory_analysis=mem,
    ).finalize()
    row = terms.to_json()
    row["status"] = "ok"
    os.makedirs(os.path.join(out_dir, "pod16x16"), exist_ok=True)
    path = os.path.join(
        out_dir, "pod16x16", f"{terms.arch}__{terms.shape}.json"
    )
    with open(path, "w") as f:
        json.dump(row, f, indent=1)
    print(
        f"[program-wv {method}{'+pallas' if use_pallas else ''}] cols={n_columns} "
        f"flops/job={terms.hlo_flops:.3e} bytes/job={terms.hlo_bytes:.3e} "
        f"coll={coll['total_bytes'] / 2**20:.1f}MiB bottleneck={terms.bottleneck}"
    )
    print("  memory_analysis:", mem)


def run_real(method: str, arch: str, baseline: bool = False):
    """Program a smoke-config model end-to-end.

    Default: the bucketed whole-model pipeline (one jitted dispatch per
    column bucket, device-side stats, column axis sharded over all local
    devices when there are several).  `--baseline` forces the per-leaf
    path for comparison.
    """
    import time

    from repro.configs import get_smoke_config
    from repro.core import pipeline
    from repro.core.programmer import deploy_params
    from repro.launch.mesh import make_mesh
    from repro.models import init_params

    cfg = get_smoke_config(arch)
    params = init_params(jax.random.PRNGKey(0), cfg)
    mesh = None
    if not baseline and jax.device_count() > 1:
        mesh = make_mesh((jax.device_count(),), ("cols",))
    pipeline.reset_counters()
    t0 = time.perf_counter()
    prog, report = deploy_params(
        jax.random.PRNGKey(1), params, WVConfig(method=WVMethod(method)),
        batched=not baseline, mesh=mesh,
    )
    dt = time.perf_counter() - t0
    path = "per-leaf baseline" if baseline else (
        f"bucketed pipeline ({pipeline.compile_count()} compiles, "
        f"{pipeline.host_sync_count()} host sync)"
    )
    print(
        f"programmed {arch} (smoke) with {method} [{path}]: "
        f"{report.num_cells:,} cells, "
        f"{report.num_columns:,} columns, rms={report.rms_cell_error_lsb:.3f} LSB, "
        f"mean iters={report.mean_iterations:.1f}, "
        f"energy={report.total_energy_pj / 1e6:.2f} uJ, "
        f"{report.num_columns / dt:,.0f} columns/s"
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--method", default="harp",
                    choices=[m.value for m in WVMethod])
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--dryrun", action="store_true")
    ap.add_argument("--pallas", action="store_true")
    ap.add_argument("--baseline", action="store_true",
                    help="per-leaf deployment path (vs bucketed pipeline)")
    ap.add_argument("--columns", type=int, default=1 << 22)
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()
    if args.dryrun:
        run_dryrun(args.method, args.columns, args.pallas, args.out)
    else:
        run_real(args.method, args.arch, baseline=args.baseline)


if __name__ == "__main__":
    main()
