"""Pallas TPU kernel: fused WV cell update (verify tail -> write).

The fine-WV loop applies, per cell: threshold -> streak -> freeze ->
pulse-size -> device-step -> clip.  Unfused, XLA materializes ~6
intermediate (C, N) arrays in HBM per iteration; programming a 1B-param
model touches ~0.5e9 cells x 50 iterations, so the loop is pure
memory-bandwidth.  This kernel performs the whole chain in one VMEM pass
(everything after the verify aggregate, which comes from the FWHT
kernel), making the per-iteration traffic exactly: 8 input planes read +
5 output planes written.

Layout: cells are processed as 2D blocks (block_r, n) — the column axis
N (32/64/128) is the lane dimension, the column-batch axis is tiled over
the grid.  The column-active reduction (`all(frozen)` along N) happens
in-register per block.

All stochastic fields (c2c jitter, mapping noise, d2d) are pre-sampled
outside — keeping the kernel deterministic and the RNG in one place.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import WVCellParams

DEFAULT_BLOCK_R = 256


def _wv_kernel(
    agg_ref, mag_ref, g_ref, streak_ref, frozen_ref, c2c_ref, nmap_ref,
    d2d_ref, g_out, streak_out, frozen_out, np_out, dir_out, *, p: WVCellParams
):
    # Masks are f32 0/1 planes, never i1 vectors: Mosaic cannot relayout
    # a bool vector through the row reduction and broadcast below.
    agg = agg_ref[...]
    g = g_ref[...]
    frozen = frozen_ref[...].astype(jnp.float32)

    decision = jnp.where(
        agg > p.threshold, 1.0, jnp.where(agg < -p.threshold, -1.0, 0.0)
    )
    streak_new = jnp.where(decision == 0.0, streak_ref[...] + 1, 0)
    frozen_new = frozen
    if p.can_freeze:  # static warmup gate
        frozen_new = jnp.maximum(
            frozen, jnp.where(streak_new >= p.k_streak, 1.0, 0.0)
        )
    col_active = 1.0 - jnp.min(frozen, axis=-1, keepdims=True)

    if p.ternary:
        n_p = jnp.ones_like(g)
    else:
        n_p = jnp.clip(jnp.round(mag_ref[...] / p.fine_step), 1.0, p.max_pulses)
    act = (1.0 - frozen) * jnp.abs(decision) * col_active > 0.0
    n_p = jnp.where(act, n_p, 0.0)
    direction = jnp.where(act, -decision, 0.0)

    frac = jnp.clip(g / p.g_max, 0.0, 1.0)
    set_eff = (1.0 - frac) ** p.nonlinearity
    reset_eff = frac ** p.nonlinearity * p.reset_asymmetry
    eff = jnp.where(direction > 0, set_eff, reset_eff)
    delta = direction * p.fine_step * eff * d2d_ref[...] * n_p * c2c_ref[...]
    nmap = nmap_ref[...]
    if p.nmap_sqrt_pulses:
        nmap = nmap * jnp.sqrt(jnp.maximum(n_p, 1.0))
    g_new = jnp.clip(
        g + delta + jnp.where(n_p > 0, nmap, 0.0), 0.0, p.g_max
    )
    g_out[...] = jnp.where(n_p > 0, g_new, g)
    streak_out[...] = streak_new
    frozen_out[...] = frozen_new.astype(jnp.int32)
    np_out[...] = n_p
    dir_out[...] = direction


@functools.partial(
    jax.jit, static_argnames=("p", "block_r", "interpret")
)
def wv_cell_update_pallas(
    agg, dev_mag, g, streak, frozen, c2c, nmap, d2d,
    p: WVCellParams, *, block_r: int = DEFAULT_BLOCK_R, interpret: bool = True,
):
    c, n = g.shape
    block_r = min(block_r, c)
    pad = (-c) % block_r

    def pad2(x):
        return jnp.pad(x, ((0, pad), (0, 0))) if pad else x

    args = [agg, dev_mag, g, streak, frozen.astype(jnp.int32), c2c, nmap, d2d]
    args = [pad2(x) for x in args]
    rows = args[0].shape[0]
    grid = (rows // block_r,)
    spec = pl.BlockSpec((block_r, n), lambda i: (i, 0))

    outs = pl.pallas_call(
        functools.partial(_wv_kernel, p=p),
        grid=grid,
        in_specs=[spec] * 8,
        out_specs=[spec] * 5,
        out_shape=[
            jax.ShapeDtypeStruct((rows, n), jnp.float32),
            jax.ShapeDtypeStruct((rows, n), jnp.int32),
            jax.ShapeDtypeStruct((rows, n), jnp.int32),
            jax.ShapeDtypeStruct((rows, n), jnp.float32),
            jax.ShapeDtypeStruct((rows, n), jnp.float32),
        ],
        interpret=interpret,
        name="wv_step",
    )(*args)
    g_new, streak_new, frozen_new, n_p, direction = [o[:c] for o in outs]
    return g_new, streak_new, frozen_new != 0, n_p, direction
