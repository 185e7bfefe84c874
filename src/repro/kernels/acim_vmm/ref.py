"""Pure-jnp oracle for the bit-sliced ACiM VMM kernel.

Simulates the CBA macro's inference datapath (paper Fig. 2 / 6(b)): a
weight matrix stored as k = B/Bc conductance slices on signed column
pairs, with per-column ADC quantization of every slice's partial sums
and digital shift-and-add recombination:

    y = sum_l 2^(Bc*(l-1)) * ADC( x @ (G+_l - G-_l) + n_l )

The ADC clamps each slice's analog partial sums to its full-scale range
(n-bit over [-FS/2, FS/2]) — literally the same converter model the
verify path uses: `adc_quantize` is `repro.readout.converter.
sar_quantize` in centered mode (the Pallas kernel inlines the identical
expression in VMEM and is bit-identity-tested against this reference).
`noise` (S, B, M) models per-read TIA/ADC thermal noise entering the
analog partial sum before conversion; `adc_bits=None` is an ideal
converter (identity), the limit in which the analog forward equals the
digital matmul exactly.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.readout.converter import sar_quantize


def adc_quantize(y: jax.Array, bits: int, full_scale: float) -> jax.Array:
    """n-bit uniform quantization over [-FS/2, FS/2] (dequantized)."""
    return sar_quantize(y, bits, full_scale, centered=True)


def acim_vmm(
    x: jax.Array,            # (B, K) activations
    g_pos: jax.Array,        # (S, K, M) positive-column conductance levels
    g_neg: jax.Array,        # (S, K, M) negative-column conductance levels
    bc: int,                 # bits per cell
    adc_bits: int | None,
    full_scale: float,
    noise: jax.Array | None = None,  # (S, B, M) pre-ADC read noise
) -> jax.Array:
    """Bit-sliced signed VMM with per-slice ADC quantization: (B, M)."""
    s = g_pos.shape[0]
    acc = jnp.zeros((x.shape[0], g_pos.shape[2]), jnp.float32)
    for l in range(s):
        part = jnp.matmul(
            x.astype(jnp.float32),
            (g_pos[l] - g_neg[l]).astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,  # f32 conductances, no bf16 pass
        )
        if noise is not None:
            part = part + noise[l].astype(jnp.float32)
        if adc_bits is not None:
            part = adc_quantize(part, adc_bits, full_scale)
        acc = acc + part * float(1 << (bc * l))
    return acc


def acim_vmm_tiled(
    x: jax.Array,            # (B, T*R) row drives, tiles contiguous on K
    g_pos: jax.Array,        # (T, S, R, M) per-tile positive planes
    g_neg: jax.Array,        # (T, S, R, M) per-tile negative planes
    bc: int,
    adc_bits: int | None,
    full_scale: float,
    noise: jax.Array | None = None,  # (T, S, B, M) per-tile pre-ADC noise
) -> jax.Array:
    """Whole-leaf tiled VMM: every macro tile's readout + tile summation.

    One `lax.scan` over the tile axis, each step the single-tile
    `acim_vmm` followed by ``acc + tile_result`` — the EXACT float
    association of the per-tile Python loop this replaced (the outer
    accumulator adds each tile's fully recombined slice sum), so the
    fused forward is bit-identical to the pre-fusion path.
    """
    n_tiles, s, r, m = g_pos.shape
    b = x.shape[0]
    xt = jnp.moveaxis(x.reshape(b, n_tiles, r), 1, 0)  # (T, B, R)
    acc0 = jnp.zeros((b, m), jnp.float32)
    if noise is None:
        def body(acc, op):
            xi, gp, gn = op
            return acc + acim_vmm(xi, gp, gn, bc, adc_bits, full_scale), None
        acc, _ = jax.lax.scan(body, acc0, (xt, g_pos, g_neg))
    else:
        def body(acc, op):
            xi, gp, gn, nz = op
            return (
                acc + acim_vmm(xi, gp, gn, bc, adc_bits, full_scale, nz),
                None,
            )
        acc, _ = jax.lax.scan(body, acc0, (xt, g_pos, g_neg, noise))
    return acc
