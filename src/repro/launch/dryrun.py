import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production mesh, prove it shards/fits, and extract roofline terms.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun \
        [--arch olmoe-1b-7b ...] [--shape train_4k ...] \
        [--multi-pod | --both] [--out results/dryrun]

Per cell this script:
  1. builds the step function (train_step / prefill_step / decode_step),
  2. jits it with the DESIGN.md Sec.-4 shardings,
  3. .lower(**input ShapeDtypeStructs)  — no arrays are allocated,
  4. .compile()                          — sharding errors surface here,
  5. prints compiled.memory_analysis() (proves per-device fit) and
     cost_analysis(), parses collective bytes from the per-device HLO,
  6. appends the roofline row to <out>/<mesh>/<arch>__<shape>.json.
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, get_config, input_specs, runnable_cells
from repro.launch import roofline as rf
from repro.launch.mesh import make_production_mesh
from repro.launch.shardings import (
    batch_sharding,
    cache_sharding,
    state_sharding,
)
from repro.models import ModelConfig, init_params
from repro.optim import AdamWConfig
from repro.serving import make_decode_step, make_prefill_step
from repro.training import init_train_state, make_train_step


# Per-arch gradient-accumulation factors for train_4k: big-activation
# stacks split the 256-sequence global batch into microbatches so the
# per-device working set fits HBM (EXPERIMENTS.md Sec. Perf, H8).
GRAD_ACCUM = {
    "qwen3-moe-235b-a22b": 8,
    "llama-3.2-vision-11b": 8,
    "hymba-1.5b": 4,
    "musicgen-medium": 2,
}


def build_lowerable(arch: str, shape: str, mesh, grad_accum: int | None = None):
    """Returns (jitted_fn, example_args) ready for .lower(*args)."""
    cfg = get_config(arch)
    spec = SHAPES[shape]
    specs = input_specs(cfg, spec)
    key = jax.random.PRNGKey(0)

    if spec.kind == "train":
        if grad_accum is None:
            grad_accum = GRAD_ACCUM.get(arch, 1)
        opt_cfg = AdamWConfig(state_dtype=cfg.opt_state_dtype)
        step = make_train_step(cfg, opt_cfg, mesh, grad_accum=grad_accum)
        state_sds = jax.eval_shape(
            lambda: init_train_state(key, cfg, opt_cfg)
        )
        st_sh = state_sharding(mesh, state_sds, cfg)
        b_sh = batch_sharding(mesh, specs["batch"], spec.global_batch)
        fn = jax.jit(
            step,
            in_shardings=(st_sh, b_sh),
            out_shardings=(st_sh, None),
            donate_argnums=(0,),
        )
        return fn, (state_sds, specs["batch"]), cfg, spec

    params_sds = jax.eval_shape(lambda: init_params(key, cfg))
    p_sh = state_sharding(mesh, params_sds, cfg)
    if spec.kind == "prefill":
        step = make_prefill_step(cfg, mesh, max_len=spec.seq_len)
        b_sh = batch_sharding(mesh, specs["batch"], spec.global_batch)
        cache_sds = jax.eval_shape(lambda p, b: step(p, b)[1], params_sds, specs["batch"])
        c_sh = cache_sharding(mesh, cache_sds, cfg, spec.global_batch)
        fn = jax.jit(step, in_shardings=(p_sh, b_sh), out_shardings=(None, c_sh))
        return fn, (params_sds, specs["batch"]), cfg, spec

    # decode
    step = make_decode_step(cfg, mesh)
    b_sh = batch_sharding(mesh, specs["batch"], spec.global_batch)
    c_sh = cache_sharding(mesh, specs["cache"], cfg, spec.global_batch)
    fn = jax.jit(
        lambda p, c, b: step(p, c, b),
        in_shardings=(p_sh, c_sh, b_sh),
        out_shardings=(None, None, c_sh),
        donate_argnums=(1,),
    )
    return fn, (params_sds, specs["cache"], specs["batch"]), cfg, spec


def run_cell(arch: str, shape: str, mesh, mesh_name: str, out_dir: str):
    t0 = time.time()
    fn, args, cfg, spec = build_lowerable(arch, shape, mesh)
    with jax.set_mesh(mesh):
        lowered = fn.lower(*args)
        compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = rf.summarize_memory_analysis(compiled.memory_analysis())
    cost = rf.summarize_cost_analysis(compiled.cost_analysis())
    hlo = compiled.as_text()
    coll = rf.collective_bytes_from_hlo(hlo)

    chips = mesh.devices.size
    tokens = spec.global_batch * (spec.seq_len if spec.kind != "decode" else 1)
    # cost_analysis flops are per-device for SPMD modules: scale to job.
    flops_job = cost.get("flops", 0.0) * chips
    bytes_job = cost.get("bytes accessed", 0.0) * chips
    terms = rf.RooflineTerms(
        arch=arch,
        shape=shape,
        mesh=mesh_name,
        chips=chips,
        hlo_flops=flops_job,
        hlo_bytes=bytes_job,
        collective_bytes=coll["total_bytes"],
        model_flops=rf.model_flops(cfg, spec, tokens),
        device_kind=rf.DRYRUN_DEVICE_KIND,
        collective_detail=coll,
        memory_analysis=mem,
    ).finalize()

    row = terms.to_json()
    row["compile_seconds"] = t_compile
    row["status"] = "ok"
    os.makedirs(os.path.join(out_dir, mesh_name), exist_ok=True)
    path = os.path.join(out_dir, mesh_name, f"{arch}__{shape}.json")
    with open(path, "w") as f:
        json.dump(row, f, indent=1)

    print(
        f"[{mesh_name}] {arch} x {shape}: compiled in {t_compile:.0f}s | "
        f"mem/device argbytes={mem.get('argument_size_in_bytes', 0)/2**30:.2f}GiB "
        f"temp={mem.get('temp_size_in_bytes', 0)/2**30:.2f}GiB | "
        f"flops/job={flops_job:.3e} | coll={coll['total_bytes']/2**20:.1f}MiB "
        f"| bottleneck={terms.bottleneck}",
        flush=True,
    )
    print("  memory_analysis:", mem, flush=True)
    print("  cost_analysis:", {k: v for k, v in cost.items() if v}, flush=True)
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", nargs="*", default=None)
    ap.add_argument("--shape", nargs="*", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    meshes = []
    if args.both or not args.multi_pod:
        meshes.append(("pod16x16", make_production_mesh(multi_pod=False)))
    if args.both or args.multi_pod:
        meshes.append(("multipod2x16x16", make_production_mesh(multi_pod=True)))

    cells = runnable_cells()
    if args.arch:
        cells = [(a, s) for a, s in cells if a in args.arch]
    if args.shape:
        cells = [(a, s) for a, s in cells if s in args.shape]

    failures = []
    for mesh_name, mesh in meshes:
        for arch, shape in cells:
            if args.skip_existing and os.path.exists(
                os.path.join(args.out, mesh_name, f"{arch}__{shape}.json")
            ):
                continue
            try:
                run_cell(arch, shape, mesh, mesh_name, args.out)
            except Exception as e:  # noqa: BLE001 - report and continue
                failures.append((mesh_name, arch, shape, repr(e)))
                print(f"[{mesh_name}] {arch} x {shape}: FAILED {e!r}", flush=True)
                traceback.print_exc()
    print(f"\ndone: {len(cells) * len(meshes) - len(failures)} ok, "
          f"{len(failures)} failed")
    for f in failures:
        print("  FAIL:", f)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
