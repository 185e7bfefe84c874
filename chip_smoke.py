"""Drive HARP programming and analog serving once on a TPU.

    python chip_smoke.py             # one chip, Qwen3-0.6B widths
    python chip_smoke.py --chips 4   # the multi-chip paths, four chips

One chip: build Qwen3-0.6B at its published widths (depth cut, random
weights from `--seed`), program it onto simulated RRAM with HARP
(`deploy_arrays`, Pallas kernels on), serve it straight off the
programmed conductances (`CIMExecutor` -> `ServeEngine` ->
`ContinuousScheduler`), then check on the same chip that each Pallas
kernel agrees with its jnp reference and that the ideal analog forward
matches the materialized digital weights.

Four chips: column-sharded programming (`deploy_arrays(mesh=)`) beside
the one-device deploy, and batch-sharded decode
(`ContinuousScheduler(batch_mesh=)`) beside the one-device decode.

Times are taken on the host clock around a host sync and labelled with
the device they ran on.  Any failed check exits non-zero; the last line
of standard output is the JSON result.  Without a TPU, or outside a
checkout of the repo, the script exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import re
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(REPO, "src")
if __name__ == "__main__" and not os.path.isdir(os.path.join(SRC, "repro")):
    sys.exit("chip_smoke.py: src/repro not found; run it from a checkout")
sys.path.insert(0, SRC)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.cim import (  # noqa: E402
    CIMConfig, CIMExecutor, CIMWeight, analog_eligible, batch_mesh, token_stream_ids,
)
from repro.configs import get_config  # noqa: E402
from repro.core import WVConfig, WVMethod, pipeline, program_columns  # noqa: E402
from repro.core.programmer import deploy_arrays  # noqa: E402
from repro.kernels.acim_vmm import ops as vmm_ops  # noqa: E402
from repro.kernels.fwht import ops as fwht_ops, ref as fwht_ref  # noqa: E402
from repro.kernels.wv_step import ops as wv_ops, ref as wv_ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_debug_mesh, make_mesh  # noqa: E402
from repro.launch.shardings import decode_batch_sharding  # noqa: E402
from repro.models import decode_step, init_cache, init_params  # noqa: E402
from repro.models.transformer import forward  # noqa: E402
from repro.serving import ContinuousScheduler, Request, ServeEngine  # noqa: E402
from repro.serving.scheduler import STEP_COMPILER_OPTIONS  # noqa: E402

ARCH = "qwen3-0.6b"
# Depth cut of the 28 layers, by chip count: 4 fit one chip's 16 GB with
# room for deploy's temporaries; the four-chip agreement runs take 1,
# since four chips cost four times as much.
LAYERS = {1: 4, 4: 1}
N_REQUESTS = 8
N_SLOTS = 8
MAX_NEW = 16
LAYER_KEYS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
KERNEL_NAME = re.compile(r'kernel_name\s*=\s*"(\w+)"')


def log(msg: str) -> None:
    print(msg, flush=True)


def require(ok: bool, what: str) -> None:
    """A failed check ends the run (never an assert: -O would drop it)."""
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def agreement(a, b) -> dict:
    """Bit-identity and max abs difference of two arrays, on device."""
    a, b = jnp.asarray(a), jax.device_put(b, jnp.asarray(a).sharding)
    same = bool(jnp.array_equal(a, b))
    diff = float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
    return {"bit_identical": same, "max_abs_diff": diff}


def peak_bytes(device) -> int | None:
    stats = device.memory_stats()
    return None if stats is None else int(stats.get("peak_bytes_in_use", 0))


class CompileCounter:
    """Counts JAX compile-path events (trace, lower, compile, cache
    lookup) recorded inside the block."""

    def __enter__(self):
        self.events: list[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def _on_event(self, event: str, duration: float, **_) -> None:
        if "compil" in event:
            self.events.append(event)

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_event)


# ------------------------------------------------------------------ phases
def smoke_configs():
    """The programming and serving configurations the smoke runs."""
    wv = WVConfig(method=WVMethod.HARP, n_cells=32, use_pallas=True)
    cim = CIMConfig(use_pallas=True, sigma_read_lsb=0.2)
    return wv, cim


def program(params, wv: WVConfig, seed: int, mesh=None):
    """`deploy_arrays` of the projection leaves; returns (deployed,
    report, seconds to deployed on the host clock)."""
    pipeline.reset_counters()
    t0 = time.perf_counter()
    deployed, report = deploy_arrays(
        jax.random.PRNGKey(seed + 1), params, wv, predicate=analog_eligible,
        mesh=mesh,
    )
    jax.block_until_ready([a.g for a in deployed.arrays.values()])
    seconds = time.perf_counter() - t0
    require(pipeline.host_sync_count() == 1, "one host sync per deploy")
    return deployed, report, seconds


def tpu_kernels(lowered) -> list[str]:
    """Names of the TPU kernels (`tpu_custom_call`) in a lowered program."""
    text = lowered.as_text()
    return sorted(set(KERNEL_NAME.findall(text))) if "tpu_custom_call" in text else []


def warm_bucket_seconds(deployed, wv: WVConfig,
                        seed: int) -> tuple[int, float, list[str]]:
    """Host-clock time of one warm dispatch of the deploy's largest
    bucket (its program is already compiled), and the TPU kernels in
    that program."""
    size = pipeline.bucket_sizes(deployed.num_columns)[0]
    targets, d2d, n = [], [], 0
    for a in deployed.arrays.values():  # just enough leaves to fill it
        targets.append(a.targets[: size - n])
        d2d.append(a.d2d[: size - n])
        n += targets[-1].shape[0]
        if n == size:
            break
    pad = ((0, size - n), (0, 0))
    fn = pipeline.get_program_fn(wv, deployed.cost)
    args = (
        jax.random.PRNGKey(seed + 1),
        jnp.pad(jnp.concatenate(targets), pad),
        jnp.pad(jnp.concatenate(d2d), pad, constant_values=1.0),
        jnp.arange(size, dtype=jnp.int32),
    )
    jax.block_until_ready(args)
    kernels = tpu_kernels(fn.lower(*args))
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    return size, time.perf_counter() - t0, kernels


def build_executor(deployed, cim: CIMConfig, seed: int, n_layers: int, mesh=None):
    """`CIMExecutor` with every projection of every layer on tiles."""
    ex = CIMExecutor(deployed, cim, jax.random.PRNGKey(seed + 2), mesh=mesh)
    require(ex.summary()["digital_fallback_leaves"] == 0, "no digital fallback")
    layers = ex.params()["layers"]
    for k in LAYER_KEYS:
        w = layers[k]
        require(
            isinstance(w, CIMWeight) and w.stacked_layers == n_layers,
            f"{k} sits on tiles in all {n_layers} layers",
        )
    return ex


def make_requests(seed: int, n: int, prompt_range: tuple[int, int],
                  max_new: int, vocab: int) -> list[Request]:
    """`n` requests arriving together, prompt lengths spread over the range."""
    g = np.random.default_rng(seed)
    lens = np.linspace(prompt_range[0], prompt_range[1], n).round().astype(int)
    return [
        Request(rid=i, prompt=g.integers(0, vocab, size=int(p)).astype(np.int32),
                max_new=max_new)
        for i, p in enumerate(lens)
    ]


def serve(cfg, executor, requests: list[Request], *, n_slots: int, seed: int,
          batch_mesh=None) -> dict:
    """Warm up a `ContinuousScheduler`, serve `requests`, check the
    serving contracts; returns tokens, host-clock timings and the TPU
    kernels in the scheduler's compiled decode step."""
    lens = [len(r.prompt) for r in requests]
    sched = ContinuousScheduler(
        ServeEngine(cfg, executor=executor), n_slots=n_slots,
        max_len=max(lens) + max(r.max_new for r in requests),
        key=jax.random.PRNGKey(seed + 3), batch_mesh=batch_mesh,
    )
    t0 = time.perf_counter()
    sched.warmup(prompt_range=(min(lens), max(lens)))
    warmup_s = time.perf_counter() - t0
    traces = dict(sched.trace_counts)
    with CompileCounter() as compiles:
        t0 = time.perf_counter()
        records = sched.run(requests)
        run_s = time.perf_counter() - t0
    require(not compiles.events, f"no compile after warmup: {compiles.events}")
    require(sched.trace_counts == traces, "no retrace after warmup")
    require(sched.host_syncs == sched.decode_steps, "one host sync per step")
    require(
        [len(r.tokens) for r in records] == [r.max_new for r in requests],
        "every request served in full",
    )
    return {
        "tokens": {r.rid: list(map(int, r.tokens)) for r in records},
        "tokens_served": sched.tokens_generated,
        "decode_steps": sched.decode_steps,
        "host_syncs": sched.host_syncs,
        "warmup_s": warmup_s,
        "run_s": run_s,
        "ttft_s": sorted(r.first_token_wall - t0 for r in records),
        "decode_step_us": sched.decode_wall_s / sched.decode_steps * 1e6,
        "decode_kernels": tpu_kernels(sched.lower_decode()),
    }


def kernel_agreement(wv: WVConfig, executor, *, n_cols: int, rows: int,
                     seed: int) -> dict[str, dict]:
    """Each Pallas kernel against its jnp reference at the smoke's shapes."""
    ks = jax.random.split(jax.random.PRNGKey(seed + 4), 12)
    shape = (n_cols, wv.n_cells)
    out = {}
    x = jax.random.normal(ks[0], shape)
    out["fwht"] = agreement(jax.jit(fwht_ops.fwht)(x), jax.jit(fwht_ref.fwht)(x))

    dev = wv.device
    args = (
        jax.random.normal(ks[1], shape) * 8,
        jnp.abs(jax.random.normal(ks[2], shape)),
        jax.random.uniform(ks[3], shape, maxval=dev.g_max_lsb),
        jax.random.randint(ks[4], shape, 0, 3),
        jax.random.bernoulli(ks[5], 0.3, shape),
        1 + 0.15 * jax.random.normal(ks[6], shape),
        0.05 * jax.random.normal(ks[7], shape),
        1 + 0.1 * jax.random.normal(ks[8], shape),
    )
    for can_freeze in (False, True):
        p = wv_ref.WVCellParams(
            threshold=wv.tau_w, k_streak=wv.k_streak, can_freeze=can_freeze,
            ternary=True, fine_step=dev.fine_step_lsb,
            max_pulses=float(wv.max_pulses_per_iter), g_max=dev.g_max_lsb,
            nonlinearity=dev.nonlinearity, reset_asymmetry=dev.reset_asymmetry,
            nmap_sqrt_pulses=dev.map_noise_mode == "pulse",
        )
        got = jax.jit(lambda *a: wv_ops.wv_cell_update(*a, p))(*args)
        want = jax.jit(lambda *a: wv_ref.wv_cell_update(*a, p))(*args)
        per = [agreement(a, b) for a, b in zip(got, want)]
        out[f"wv_step[can_freeze={can_freeze}]"] = {
            "bit_identical": all(d["bit_identical"] for d in per),
            "max_abs_diff": max(d["max_abs_diff"] for d in per),
        }

    layers = executor.params()["layers"]
    cim = executor.cfg
    for i, k in enumerate(LAYER_KEYS):
        w = jax.tree.map(lambda a: a[0], layers[k])  # layer 0
        t, s, r, m = w.g_pos.shape
        xp = jax.random.bernoulli(ks[9], 0.5, (rows, t * r)).astype(jnp.float32)
        nz = cim.sigma_read_lsb * jax.random.normal(
            jax.random.fold_in(ks[10], i), (t, s, rows, m)
        )
        full_scale = cim.full_scale_frac * 2.0 * r * float(w.levels - 1)

        def both(adc_bits):
            kw = dict(bc=w.bc, adc_bits=adc_bits, noise=nz, full_scale=full_scale)
            return [
                jax.jit(functools.partial(
                    vmm_ops.acim_vmm_tiled, use_pallas=p, **kw
                ))(xp, w.g_pos, w.g_neg)
                for p in (True, False)
            ]

        got, want = both(cim.adc_bits)
        d = agreement(got, want)
        if not d["bit_identical"] and cim.adc_bits is not None:
            d.update(adc_code_flips(got, want, both(None),
                                    full_scale / float(1 << cim.adc_bits)))
        out[f"acim_vmm_tiled[{k}]"] = d
        # What each of four devices computes under batch-sharded decode:
        # a quarter of the rows, against the whole batch at once.
        shard = jax.jit(functools.partial(
            vmm_ops.acim_vmm_tiled, bc=w.bc, adc_bits=cim.adc_bits,
            full_scale=full_scale, use_pallas=True,
        ))
        q = rows // 4
        split = jnp.concatenate([
            shard(xp[j : j + q], w.g_pos, w.g_neg, noise=nz[:, :, j : j + q])
            for j in range(0, rows, q)
        ])
        out[f"acim_vmm_tiled[{k}] in 4 row shards vs whole"] = agreement(split, got)
    return out


def adc_code_flips(got, want, pre_adc, step: float) -> dict:
    """Anatomy of an ADC-quantized disagreement: Mosaic and XLA add a
    tile's 128 f32 products in different orders, so the analog partial
    sums differ in the last bits, and a partial sum that lies that close
    to a code boundary converts to the neighbouring code.  Checks that
    the unquantized sums agree to f32 rounding, that every difference is
    a whole number of ADC steps, and that such flips are rare."""
    pa, pb = pre_adc
    pre = agreement(pa, pb)["max_abs_diff"]
    scale = float(jnp.max(jnp.abs(pb)))
    diff = jnp.abs(got - want)
    flipped = int(jnp.sum(diff > 0))
    whole = bool(jnp.all(jnp.abs(diff / step - jnp.round(diff / step)) < 1e-2))
    out = {
        "pre_adc_max_abs_diff": pre, "pre_adc_max_abs": scale,
        "elements_differing": flipped, "elements": int(diff.size),
        "whole_adc_steps": whole,
    }
    require(pre <= 2.0**-20 * scale, f"unquantized sums agree to f32 rounding: {out}")
    require(whole, f"every difference is whole ADC steps: {out}")
    require(flipped <= 1e-3 * diff.size, f"ADC code flips are rare: {out}")
    return out


def programming_agreement(deployed, wv: WVConfig, *, n_cols: int,
                          seed: int) -> dict[str, dict]:
    """A slice of one leaf's columns programmed again with and without
    the Pallas kernels, and against what the deploy programmed."""
    name = sorted(deployed.arrays)[0]
    st = deployed.arrays[name]
    prog = jax.jit(program_columns, static_argnames=("cfg", "cost"))
    kw = dict(
        d2d=st.d2d[:n_cols], col_ids=jnp.asarray(st.uids[:n_cols], jnp.int32),
        cost=deployed.cost,
    )
    key = jax.random.PRNGKey(seed + 1)
    g_pallas, _ = prog(key, st.targets[:n_cols], cfg=wv, **kw)
    g_ref, _ = prog(key, st.targets[:n_cols], cfg=wv.replace(use_pallas=False), **kw)
    n = int(g_pallas.shape[0])
    return {
        f"program_columns[{name}, {n} cols] pallas vs jnp": agreement(g_pallas, g_ref),
        "same columns: pallas vs the deploy's buckets": agreement(g_pallas, st.g[:n_cols]),
    }


def ideal_agreement(cfg, deployed, *, n_tokens: int, seed: int) -> dict:
    """The equivalence contract: with ideal converters and no read noise
    the analog forward equals the forward over materialized weights.
    Run in f32 at HIGHEST matmul precision, so neither side rounds the
    programmed conductances to bf16."""
    cfg32 = cfg.replace(dtype=jnp.float32)
    ideal = CIMConfig(dac_bits=None, adc_bits=None, sigma_read_lsb=0.0,
                      use_pallas=True)
    ex = CIMExecutor(deployed, ideal, jax.random.PRNGKey(seed + 5))

    def to_f32(tree):
        return jax.tree.map(
            lambda a: a if isinstance(a, CIMWeight) else a.astype(jnp.float32),
            tree, is_leaf=lambda a: isinstance(a, CIMWeight),
        )

    toks = jax.random.randint(
        jax.random.PRNGKey(seed + 6), (1, n_tokens), 0, cfg.vocab_size
    )
    fwd = jax.jit(lambda p, t: forward(p, {"tokens": t}, cfg32)[0])
    with jax.default_matmul_precision("highest"):
        analog = fwd(to_f32(ex.params()), toks)
        digital = fwd(to_f32(deployed.materialize(jnp.float32)), toks)
    out = agreement(analog, digital)
    out["max_abs_logit"] = float(jnp.max(jnp.abs(digital)))
    out["argmax_agree"] = float(jnp.mean(
        (jnp.argmax(analog, -1) == jnp.argmax(digital, -1)).astype(jnp.float32)
    ))
    return out


def step_logits_agreement(cfg, one, sharded, mesh, *, n_slots: int,
                          seed: int) -> dict:
    """One decode step from an empty cache on one device (executor
    `one`) and batch-sharded over `mesh` (executor `sharded`): the
    logits the two schedulers sample their tokens from."""
    cache = init_cache(cfg, n_slots, 64)
    toks = jax.random.randint(jax.random.PRNGKey(seed + 7), (n_slots, 1), 0,
                              cfg.vocab_size)
    rids = jnp.arange(n_slots, dtype=jnp.int32)

    def step(params, cache, toks, rids, mesh=None):
        with token_stream_ids(rids), batch_mesh(mesh):
            return decode_step(params, cache, {"tokens": toks}, cfg)[0]

    opts = STEP_COMPILER_OPTIONS  # as the schedulers compile their steps
    want = jax.jit(step, compiler_options=opts)(one.params(), cache, toks, rids)
    on_data = NamedSharding(mesh, P("data"))
    got = jax.jit(functools.partial(step, mesh=mesh), compiler_options=opts)(
        sharded.params(), jax.device_put(cache, decode_batch_sharding(mesh, cache)),
        jax.device_put(toks, on_data), jax.device_put(rids, on_data),
    )
    out = agreement(got, want)
    out["argmax_agree"] = float(jnp.mean(
        (jnp.argmax(got, -1) == jnp.argmax(jax.device_put(want, got.sharding), -1))
        .astype(jnp.float32)
    ))
    return out


# ------------------------------------------------------------- the runs
def run_one_chip(cfg, seed: int) -> None:
    dev = jax.devices()[0]
    wv, cim = smoke_configs()
    params = init_params(jax.random.PRNGKey(seed), cfg)
    deployed, report, deploy_s = program(params, wv, seed)
    del params
    log(f"deploy: {report.num_columns:,} columns, {report.num_cells:,} cells, "
        f"rms_cell_error_lsb={report.rms_cell_error_lsb!r}, "
        f"mean_iterations={report.mean_iterations!r}")
    log(f"deploy: time-to-deployed {deploy_s!r} s on {dev.platform} "
        "(host clock, first deploy, includes compiling the bucket program)")
    size, bucket_s, bucket_kernels = warm_bucket_seconds(deployed, wv, seed)
    log(f"deploy: one warm {size}-column bucket {bucket_s!r} s on "
        f"{dev.platform} ({size / bucket_s!r} columns/s, host clock)")
    log(f"memory: peak_bytes_in_use after deploy {peak_bytes(dev)!r}")

    ex = build_executor(deployed, cim, seed, cfg.n_layers)
    log(f"executor: {len(LAYER_KEYS)} projections x {cfg.n_layers} layers on "
        f"tiles, digital_fallback_leaves=0, planes/token={ex.planes}")
    reqs = make_requests(seed, N_REQUESTS, (32, 128), MAX_NEW, cfg.vocab_size)
    res = serve(cfg, ex, reqs, n_slots=N_SLOTS, seed=seed)
    log(f"serve: {res['tokens_served']} tokens for {len(reqs)} requests in "
        f"{res['decode_steps']} decode steps, host_syncs == decode_steps "
        f"({res['host_syncs']}), 0 compiles and 0 retraces after warmup")
    log(f"serve: warmup {res['warmup_s']!r} s, run {res['run_s']!r} s, "
        f"decode step {res['decode_step_us']!r} us on {dev.platform} (host clock)")
    log(f"serve: TTFT s on {dev.platform} (host clock, all requests "
        f"arriving at once): {res['ttft_s']!r}")

    log(f"kernels in the timed programs: deploy bucket {bucket_kernels}, "
        f"decode step {res['decode_kernels']}")
    require(
        {"wv_step", "fwht"} <= set(bucket_kernels),
        "wv_step and fwht compiled as tpu_custom_call in the deploy bucket",
    )
    require(
        "acim_vmm_tiled" in res["decode_kernels"],
        "acim_vmm_tiled compiled as tpu_custom_call in the decode step",
    )
    checks = kernel_agreement(wv, ex, n_cols=size, rows=ex.planes * N_SLOTS,
                              seed=seed)
    checks.update(programming_agreement(deployed, wv, n_cols=1 << 14, seed=seed))
    for name, d in checks.items():
        log(f"agreement {name}: {d}")
    ideal = ideal_agreement(cfg, deployed, n_tokens=32, seed=seed)
    log(f"agreement ideal analog vs materialize() logits (f32): {ideal}")
    require(
        ideal["max_abs_diff"] <= 1e-4 * max(ideal["max_abs_logit"], 1.0),
        "ideal analog logits match materialize() to 1e-4 of the logit scale",
    )
    log(f"memory: peak_bytes_in_use at end {peak_bytes(dev)!r}")


def run_four_chips(cfg, seed: int) -> None:
    require(len(jax.devices()) == 4, "four devices visible")
    wv, cim = smoke_configs()
    params = init_params(jax.random.PRNGKey(seed), cfg)
    one, _, one_s = program(params, wv, seed)
    four, _, four_s = program(params, wv, seed, mesh=make_mesh((4,), ("cols",)))
    del params
    per = {n: agreement(one.arrays[n].g, four.arrays[n].g) for n in one.arrays}
    log(f"4-chip deploy: one device {one_s!r} s, 4-way column-sharded "
        f"{four_s!r} s (host clock, first deploys, include compiles)")
    log(f"4-chip deploy agreement (conductances, per leaf): {per}")
    require(all(d["bit_identical"] for d in per.values()),
            "column-sharded deploy bit-identical to one device")
    del four

    # Prompts of one prefill bucket: each scheduler compiles one prefill.
    reqs = make_requests(seed, N_REQUESTS, (17, 32), MAX_NEW, cfg.vocab_size)
    mesh = make_debug_mesh(4, 1)
    ex_one = build_executor(one, cim, seed, cfg.n_layers)
    ex_shard = build_executor(one, cim, seed, cfg.n_layers, mesh=mesh)
    base = serve(cfg, ex_one, reqs, n_slots=N_SLOTS, seed=seed)
    shard = serve(cfg, ex_shard, reqs, n_slots=N_SLOTS, seed=seed, batch_mesh=mesh)
    mismatched = [rid for rid in base["tokens"]
                  if base["tokens"][rid] != shard["tokens"][rid]]
    log(f"4-chip decode: one device {base['decode_step_us']!r} us/step, "
        f"batch-sharded over 4 {shard['decode_step_us']!r} us/step (host clock)")
    log(f"4-chip decode agreement (tokens): {len(mismatched)} of "
        f"{len(reqs)} requests differ {mismatched}")
    if mismatched:  # where they part: one step's logits, same inputs
        log("4-chip decode agreement (one step's logits): " + str(
            step_logits_agreement(cfg, ex_one, ex_shard, mesh, n_slots=N_SLOTS,
                                  seed=seed)))
    require(not mismatched, "batch-sharded decode tokens equal one device's")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cache = enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke.py: no TPU (JAX platform {dev.platform!r}); "
              "nothing was run", file=sys.stderr)
        return 1
    full = get_config(ARCH)
    cfg = full.replace(n_layers=LAYERS[args.chips])
    log(f"device: {dev.platform} {dev.device_kind!r} x{len(jax.devices())}; "
        f"compile cache {cache}")
    log(f"model: {ARCH} d_model={cfg.d_model} heads={cfg.n_heads}/"
        f"{cfg.n_kv_heads}x{cfg.head_dim} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab_size} {jnp.dtype(cfg.dtype).name} "
        f"qk_norm={cfg.qk_norm} tied={cfg.tie_embeddings}; depth cut "
        f"{full.n_layers} -> {cfg.n_layers} layers; random weights, seed {args.seed}; "
        f"{N_REQUESTS} requests, {N_SLOTS} slots, max_new {MAX_NEW}")
    t0 = time.perf_counter()
    if args.chips == 4:
        run_four_chips(cfg, args.seed)
    else:
        run_one_chip(cfg, args.seed)
    log(f"total {time.perf_counter() - t0!r} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
