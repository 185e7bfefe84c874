"""End-to-end training driver: fault-tolerant LM training on synthetic data.

Default is a CPU-friendly reduced config; `--arch smollm-360m --full`
selects the real config (sized for the production mesh).  A ~100M-param
run a few hundred steps long:

    PYTHONPATH=src python examples/train_lm.py --preset 100m --steps 300

Features exercised: deterministic sharded data pipeline, AdamW + cosine
schedule, async checkpointing with keep-k rotation, fault injection +
restore (--inject-failure), straggler monitor, resume (--resume).
"""

import argparse
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.configs import get_config, get_smoke_config
from repro.data import SyntheticLM
from repro.distributed import FaultInjector, FaultTolerantRunner, StragglerMonitor
from repro.launch.compile_cache import enable_compile_cache
from repro.models import ModelConfig
from repro.optim import AdamWConfig
from repro.training import init_train_state, make_train_step

PRESETS = {
    # ~1M params: smoke-speed
    "tiny": dict(n_layers=2, d_model=128, n_heads=4, n_kv_heads=2, head_dim=32,
                 d_ff=256, vocab_size=512, seq=128, batch=8),
    # ~100M params: the "train a ~100M model for a few hundred steps" driver
    "100m": dict(n_layers=12, d_model=768, n_heads=12, n_kv_heads=4, head_dim=64,
                 d_ff=2048, vocab_size=32768, seq=512, batch=8),
}


def build_cfg(args) -> tuple[ModelConfig, int, int]:
    if args.arch:
        cfg = get_config(args.arch) if args.full else get_smoke_config(args.arch)
        return cfg, args.seq or 256, args.batch or 8
    p = PRESETS[args.preset]
    cfg = ModelConfig(
        name=f"lm-{args.preset}", n_layers=p["n_layers"], d_model=p["d_model"],
        n_heads=p["n_heads"], n_kv_heads=p["n_kv_heads"], head_dim=p["head_dim"],
        d_ff=p["d_ff"], vocab_size=p["vocab_size"], dtype=jnp.float32,
        attn_chunk_q=128, attn_chunk_kv=128, remat=False,
    )
    return cfg, args.seq or p["seq"], args.batch or p["batch"]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="tiny", choices=sorted(PRESETS))
    ap.add_argument("--arch", default=None, help="use a registry architecture")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--ckpt-dir", default="/tmp/harp_jax_train")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--inject-failure", type=int, nargs="*", default=())
    args = ap.parse_args()
    enable_compile_cache()

    cfg, seq, batch = build_cfg(args)
    data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=seq, global_batch=batch)
    opt_cfg = AdamWConfig(lr_peak=1e-3)
    state = init_train_state(jax.random.PRNGKey(0), cfg, opt_cfg)
    n_params = sum(x.size for x in jax.tree.leaves(state.params))
    print(f"model={cfg.name} params={n_params:,} seq={seq} batch={batch}")

    raw_step = jax.jit(make_train_step(cfg, opt_cfg, total_steps=args.steps))
    monitor = StragglerMonitor()
    t_last = [time.perf_counter()]

    def step_fn(state, batch):
        state, metrics = raw_step(state, batch)
        loss = float(metrics["loss"])
        now = time.perf_counter()
        monitor.observe(int(state.opt.step), now - t_last[0])
        t_last[0] = now
        return state, {"loss": loss}

    manager = CheckpointManager(args.ckpt_dir, keep=3)
    start = 0
    if args.resume:
        try:
            start, state = manager.restore_latest(template=state)
            print(f"resumed from step {start}")
        except FileNotFoundError:
            print("no checkpoint found; starting fresh")

    runner = FaultTolerantRunner(
        step_fn,
        lambda s: data.global_batch_at(s)._asdict(),
        manager,
        checkpoint_every=args.ckpt_every,
        injector=FaultInjector(fail_at_steps=tuple(args.inject_failure)),
    )
    t0 = time.time()
    state, logs = runner.run(state, start, args.steps)
    dt = time.time() - t0
    first, last = logs[0]["loss"], logs[-1]["loss"]
    print(
        f"steps={len(logs)} loss {first:.4f} -> {last:.4f} "
        f"({dt:.1f}s, {dt / max(len(logs), 1) * 1e3:.0f} ms/step, "
        f"restarts={runner.restarts}, straggler_flags={len(monitor.flagged_steps)})"
    )
    assert last < first, "training did not reduce the loss"


if __name__ == "__main__":
    main()
