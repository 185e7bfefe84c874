"""Batched serving demo: prefill + decode with the ServeEngine.

    PYTHONPATH=src python examples/serve_lm.py --arch qwen3-0.6b

Uses the smoke-size config of the chosen architecture (CPU-friendly),
runs batched greedy generation, and reports tokens/s.  Two RRAM modes:

  --rram    program the weights with HARP, read them back, serve the
            materialized digital weights (the paper's iso-footprint
            deployment, programming error frozen into dense matmuls);
  --analog  program with HARP and serve straight off the live
            `DeployedModel` arrays — no materialize(): every matmul is
            computed *in* the programmed conductance tiles through the
            bit-serial DAC -> analog VMM -> per-slice ADC path, with
            per-read noise, and the cost model's inference phase prices
            every token (repro.cim, DESIGN.md Sec. 11).

`--continuous` swaps the fixed-batch generate loop for the
continuous-batching scheduler (DESIGN.md Sec. 13): a Poisson stream of
variable-length requests is admitted into a fixed decode batch with
zero retraces after warmup, and per-request latency is reported.
"""

import argparse
import time

import jax

from repro.configs import get_smoke_config
from repro.core import WVConfig, WVMethod
from repro.core.programmer import deploy_arrays, deploy_params
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params
from repro.serving import ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--rram", action="store_true")
    ap.add_argument("--analog", action="store_true",
                    help="serve off the live arrays (compute-in-memory)")
    ap.add_argument("--dac-bits", type=int, default=6)
    ap.add_argument("--adc-bits", type=int, default=10)
    ap.add_argument("--read-noise", type=float, default=0.2,
                    help="per-read TIA/ADC noise std, cell-LSB")
    ap.add_argument("--use-pallas", action="store_true")
    ap.add_argument("--continuous", action="store_true",
                    help="serve a Poisson request stream via the scheduler")
    ap.add_argument("--n-slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--load", type=float, default=0.3,
                    help="offered load, requests per decode step")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config(args.arch)
    if cfg.block == "rwkv6" or cfg.frontend == "embed_stub":
        raise SystemExit("pick a token-input arch for this demo (dense/moe/hybrid)")
    params = init_params(jax.random.PRNGKey(0), cfg)

    executor = None
    if args.analog:
        from repro.cim import CIMConfig, CIMExecutor

        print("programming weights onto RRAM with HARP ...")
        deployed, report = deploy_arrays(
            jax.random.PRNGKey(1), params, WVConfig(method=WVMethod.HARP)
        )
        print(f"  programmed {report.num_cells:,} cells, "
              f"rms={report.rms_cell_error_lsb:.3f} LSB")
        executor = CIMExecutor(
            deployed,
            CIMConfig(
                dac_bits=args.dac_bits, adc_bits=args.adc_bits,
                sigma_read_lsb=args.read_noise, use_pallas=args.use_pallas,
            ),
            jax.random.PRNGKey(7),
        )
        s = executor.summary()
        print(f"  analog serving: {s['analog_leaves']} leaves on tiles, "
              f"{s['digital_fallback_leaves']} digital fallback, "
              f"{s['planes_per_token']} read planes/token")
        params = None
    elif args.rram:
        print("programming weights onto RRAM with HARP ...")
        params, report = deploy_params(
            jax.random.PRNGKey(1), params, WVConfig(method=WVMethod.HARP)
        )
        print(f"  programmed {report.num_cells:,} cells, "
              f"rms={report.rms_cell_error_lsb:.3f} LSB")

    engine = ServeEngine(cfg, params, executor=executor)

    if args.continuous:
        from repro.serving import ContinuousScheduler, poisson_requests

        max_len = args.prompt_len + args.max_new + 8
        sched = ContinuousScheduler(
            engine, n_slots=args.n_slots, max_len=max_len,
            key=jax.random.PRNGKey(11),
        )
        lo, hi = max(args.prompt_len // 2, 2), args.prompt_len
        print(f"warming prefill buckets for prompts in [{lo}, {hi}] ...")
        sched.warmup(prompt_range=(lo, hi))
        reqs = poisson_requests(
            3, args.requests, rate=args.load, vocab=cfg.vocab_size,
            prompt_lens=(lo, hi), max_new=(args.max_new // 2, args.max_new),
        )
        recs = sched.run(reqs)
        s = sched.latency_stats()
        print(f"served {len(recs)} requests in {sched.decode_steps} decode "
              f"steps ({s['tokens_per_s']:.1f} tok/s, "
              f"{s['tokens_per_step']:.2f} tok/step)")
        print(f"latency p50={s['p50_latency_steps']:.1f} "
              f"p99={s['p99_latency_steps']:.1f} steps; "
              f"ttft p50={s['p50_ttft_steps']:.1f} steps")
        print(f"retraces after warmup: admit={sched.trace_counts['admit']} "
              f"decode={sched.trace_counts['decode']} (counts incl. warmup)")
        if executor is not None:
            lat_ns, e_pj = executor.token_cost()
            print(f"analog cost model: {lat_ns / 1e3:.2f} us/token, "
                  f"{e_pj / 1e3:.1f} nJ/token")
        return

    prompts = jax.random.randint(
        jax.random.PRNGKey(2), (args.batch, args.prompt_len), 0, cfg.vocab_size
    )
    t0 = time.time()
    out = engine.generate(prompts, max_new=args.max_new)
    dt = time.time() - t0
    total = args.batch * args.max_new
    print(f"arch={args.arch} (smoke config) batch={args.batch}")
    print(f"generated {out.shape} in {dt:.2f}s ({total / dt:.1f} tok/s incl. compile)")
    if executor is not None:
        lat_ns, e_pj = executor.token_cost()
        s = executor.summary()
        print(
            f"analog cost model: {lat_ns / 1e3:.2f} us/token array latency, "
            f"{e_pj / 1e3:.1f} nJ/token "
            f"({s['total_energy_pj'] / 1e6:.2f} uJ for {s['tokens_served']} tokens)"
        )
    print("first sequence:", out[0][:16].tolist(), "...")


if __name__ == "__main__":
    main()
