"""Quickstart: program an RRAM array with all four WV methods.

Runs in ~1 minute on CPU:

    PYTHONPATH=src python examples/quickstart.py

Programs 256 columns of 32 cells (the paper's default array) from HRS to
random 3-bit targets under severe read noise (0.7 LSB) and prints the
Fig.-9-style comparison: mapping error, iterations, latency, energy.
"""

import jax
import jax.numpy as jnp

from repro.core import WVConfig, WVMethod, program_columns
from repro.launch.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    tkey, pkey = jax.random.split(jax.random.PRNGKey(0))
    targets = jax.random.randint(tkey, (256, 32), 0, 8).astype(jnp.float32)

    print(f"{'method':8s} {'rms[LSB]':>9s} {'iters':>6s} {'lat[us]':>8s} {'E[nJ]':>7s}")
    for method in WVMethod:
        cfg = WVConfig(method=method)
        g, stats = jax.jit(lambda k, t, c=cfg: program_columns(k, t, c))(pkey, targets)
        print(
            f"{method.value:8s} "
            f"{float(jnp.mean(stats.rms_error_lsb)):9.3f} "
            f"{float(jnp.mean(stats.iterations)):6.1f} "
            f"{float(jnp.mean(stats.latency_ns)) / 1e3:8.1f} "
            f"{float(jnp.mean(stats.energy_pj)) / 1e3:7.2f}"
        )
    print("\nHadamard-domain verification (hd_pv/harp) should show the")
    print("lowest error/iterations (hd_pv) and the lowest energy (harp).")


if __name__ == "__main__":
    main()
