"""Public wrapper for the bit-sliced ACiM VMM kernel."""

from __future__ import annotations

import jax

from . import ref
from .acim_vmm import acim_vmm_pallas, acim_vmm_tiled_pallas


def acim_vmm(
    x, g_pos, g_neg, *, bc: int, adc_bits: int | None, full_scale: float,
    noise=None, use_pallas: bool = True,
):
    """Bit-sliced signed ACiM VMM with per-slice ADC quantization.

    `noise` (S, B, M) is added to each slice's analog partial sums
    before conversion; `adc_bits=None` bypasses the ADC (ideal
    converter).  The Pallas and reference paths are bit-identical in
    interpret mode; compiled on a TPU, see `acim_vmm_tiled`.
    """
    if not use_pallas:
        return ref.acim_vmm(x, g_pos, g_neg, bc, adc_bits, full_scale, noise)
    on_tpu = jax.default_backend() == "tpu"
    return acim_vmm_pallas(
        x, g_pos, g_neg, noise, bc=bc, adc_bits=adc_bits, full_scale=full_scale,
        interpret=not on_tpu,
    )


def acim_vmm_tiled(
    x, g_pos, g_neg, *, bc: int, adc_bits: int | None, full_scale: float,
    noise=None, use_pallas: bool = True,
):
    """Whole-leaf fused ACiM VMM: every macro tile in one dispatch.

    x (B, T*R) drives per-tile planes g_pos/g_neg (T, S, R, M) with
    per-tile pre-ADC `noise` (T, S, B, M); the result (B, M) is the sum
    over tiles of each tile's ADC-quantized slice recombination.  Both
    the Pallas mega-kernel and the scanned reference preserve the
    pre-fusion per-tile loop's float association, and in interpret mode
    they are bit-identical.  Compiled on a TPU, Mosaic and XLA add each
    tile's products in different orders: pre-ADC sums differ in the
    last bits, and rarely one converts to the neighbouring ADC code.
    """
    if not use_pallas:
        return ref.acim_vmm_tiled(
            x, g_pos, g_neg, bc, adc_bits, full_scale, noise
        )
    on_tpu = jax.default_backend() == "tpu"
    return acim_vmm_tiled_pallas(
        x, g_pos, g_neg, noise, bc=bc, adc_bits=adc_bits,
        full_scale=full_scale, interpret=not on_tpu,
    )
