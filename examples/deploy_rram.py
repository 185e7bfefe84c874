"""Deploy a trained LM onto simulated RRAM with HARP write-and-verify.

The paper's pipeline end-to-end: train a small LM -> quantize (B=6,
Bc=3) -> bit-slice onto signed column pairs -> program with CW-SC /
MRA / HD-PV / HARP under severe read noise -> serve with the programmed
(noisy) weights and compare eval loss.  This is Fig. 10's experiment on
the framework's own workload.

    PYTHONPATH=src python examples/deploy_rram.py --steps 150 --noise 0.7
"""

import argparse

import jax
import jax.numpy as jnp

from repro.core import NoiseConfig, WVConfig, WVMethod
from repro.core.programmer import deploy_params
from repro.data import SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.models import ModelConfig
from repro.models.transformer import loss_fn
from repro.optim import AdamWConfig
from repro.training import init_train_state, make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=150)
    ap.add_argument("--noise", type=float, default=0.7, help="read noise, LSB")
    ap.add_argument("--n-cells", type=int, default=32)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = ModelConfig(
        name="deploy-demo", n_layers=2, d_model=96, n_heads=4, n_kv_heads=2,
        head_dim=24, d_ff=192, vocab_size=64, dtype=jnp.float32,
        attn_chunk_q=32, attn_chunk_kv=32, remat=False,
    )
    data = SyntheticLM(vocab_size=64, seq_len=64, global_batch=16, seed=1)
    opt_cfg = AdamWConfig(lr_peak=1e-2)
    state = init_train_state(jax.random.PRNGKey(0), cfg, opt_cfg)
    step = jax.jit(make_train_step(cfg, opt_cfg, total_steps=args.steps))
    for i in range(args.steps):
        state, m = step(state, data.global_batch_at(i)._asdict())
    eval_batch = data.global_batch_at(99_999)._asdict()
    eval_fn = jax.jit(lambda p, b: loss_fn(p, b, cfg)[0])
    clean = float(eval_fn(state.params, eval_batch))
    print(f"trained {args.steps} steps; clean eval loss = {clean:.4f}\n")

    print(f"{'method':8s} {'eval loss':>10s} {'dloss':>8s} {'rms[LSB]':>9s} "
          f"{'iters':>6s} {'E[uJ]':>8s}")
    for method in WVMethod:
        wv = WVConfig(
            method=method, n_cells=args.n_cells,
            noise=NoiseConfig(sigma_read_lsb=args.noise),
        )
        prog, report = deploy_params(jax.random.PRNGKey(7), state.params, wv)
        loss = float(eval_fn(prog, eval_batch))
        print(
            f"{method.value:8s} {loss:10.4f} {loss - clean:+8.4f} "
            f"{report.rms_cell_error_lsb:9.3f} {report.mean_iterations:6.1f} "
            f"{report.total_energy_pj / 1e6:8.2f}"
        )
    print("\nUnder severe read noise the Hadamard-domain methods (hd_pv,")
    print("harp) should preserve eval loss where cw_sc degrades.")


if __name__ == "__main__":
    main()
