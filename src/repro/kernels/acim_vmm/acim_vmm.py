"""Pallas TPU kernel: bit-sliced ACiM VMM with fused ADC epilogue.

Hardware co-design: the paper's CBA macro computes y = sum_l 2^(Bc l) *
ADC(x @ G_l) with analog column sums and per-slice ADCs.  On TPU the
natural mapping is: each conductance slice is a dense operand plane, the
column dimension maps to MXU lanes (128-wide, matching the paper's
128-column macro scaling), and the ADC transfer function (clamp +
uniform quantization) is fused into the matmul epilogue in VMEM — so the
quantized-slice recombination never round-trips to HBM.

Grid: (M/block_m, B/block_b); the slice loop (k = B/Bc, typically 2) is
unrolled inside the kernel, accumulating the shifted slices in VMEM.
The contraction dim K is kept whole per block (RRAM macro columns are
short: K = N <= 128 rows).

Inference extensions (the analog serving path, DESIGN.md Sec. 11):

* an optional per-read noise operand (S, B, M) — sampled outside under
  the fold_in RNG policy — is added to every slice's analog partial sum
  *before* the ADC epilogue, exactly where TIA/ADC thermal noise enters
  the macro;
* ``adc_bits=None`` models an ideal (infinite-resolution) converter:
  the epilogue reduces to the identity, which is what makes the analog
  forward provably collapse to the digitally materialized matmul.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Conductances are f32 levels carrying programming error; the MXU's
# default f32 precision would round them to bf16 and change every
# analog partial sum the ADC epilogue quantizes.  The reference
# (`ref.py`) pins the same precision.
_PRECISION = jax.lax.Precision.HIGHEST


def _acim_kernel(*refs, bc, adc_bits, full_scale, with_noise):
    if with_noise:
        x_ref, gp_ref, gn_ref, nz_ref, o_ref = refs
    else:
        x_ref, gp_ref, gn_ref, o_ref = refs
        nz_ref = None
    x = x_ref[...]
    s = gp_ref.shape[0]
    acc = jnp.zeros((x.shape[0], gp_ref.shape[2]), jnp.float32)
    if adc_bits is not None:
        w = full_scale / float(1 << adc_bits)
        lo = -full_scale / 2.0
    for l in range(s):  # static unroll over bit slices
        part = jnp.dot(
            x, gp_ref[l] - gn_ref[l],
            precision=_PRECISION, preferred_element_type=jnp.float32,
        )
        if nz_ref is not None:
            part = part + nz_ref[l]
        if adc_bits is None:
            acc = acc + part * float(1 << (bc * l))
            continue
        # fused ADC epilogue: clamp to full scale, quantize to code grid
        code = jnp.clip(
            jnp.round((jnp.clip(part, lo, -lo) - lo) / w), 0.0, float((1 << adc_bits) - 1)
        )
        acc = acc + (lo + code * w) * float(1 << (bc * l))
    o_ref[...] = acc


def _acim_tiled_kernel(*refs, bc, adc_bits, full_scale, with_noise):
    """One (B block, M block, tile) grid step of the whole-leaf kernel.

    The tile axis is the innermost, sequential grid axis: each step
    recombines ONE tile's shifted slices into `tacc`, then adds it to
    the output block, which stays resident in VMEM across the tile axis.
    Tiles therefore add in order onto a zero start — the same float
    association as the scanned reference (`ref.acim_vmm_tiled`) — while
    VMEM holds one tile's planes at a time, whatever the leaf's depth.
    """
    if with_noise:
        x_ref, gp_ref, gn_ref, nz_ref, o_ref = refs
    else:
        x_ref, gp_ref, gn_ref, o_ref = refs
        nz_ref = None

    @pl.when(pl.program_id(2) == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]
    tacc = jnp.zeros(o_ref.shape, jnp.float32)
    if adc_bits is not None:
        w = full_scale / float(1 << adc_bits)
        lo = -full_scale / 2.0
    for l in range(gp_ref.shape[1]):  # static unroll over bit slices
        part = jnp.dot(
            x, gp_ref[0, l] - gn_ref[0, l],
            precision=_PRECISION, preferred_element_type=jnp.float32,
        )
        if nz_ref is not None:
            part = part + nz_ref[0, l]
        if adc_bits is None:
            tacc = tacc + part * float(1 << (bc * l))
            continue
        code = jnp.clip(
            jnp.round((jnp.clip(part, lo, -lo) - lo) / w),
            0.0,
            float((1 << adc_bits) - 1),
        )
        tacc = tacc + (lo + code * w) * float(1 << (bc * l))
    o_ref[...] = o_ref[...] + tacc


@functools.partial(
    jax.jit,
    static_argnames=("bc", "adc_bits", "full_scale", "block_b", "block_m", "interpret"),
)
def acim_vmm_tiled_pallas(
    x: jax.Array,            # (B, T*R)
    g_pos: jax.Array,        # (T, S, R, M)
    g_neg: jax.Array,        # (T, S, R, M)
    noise: jax.Array | None = None,  # (T, S, B, M)
    *,
    bc: int,
    adc_bits: int | None,
    full_scale: float,
    block_b: int = 128,
    block_m: int = 128,
    interpret: bool = True,
) -> jax.Array:
    """One `pallas_call` for a whole weight leaf: grid over (B, M)
    blocks and, innermost, the macro tiles.  Each step reads tile t's
    (block_b, R) slice of x and its (S, R, block_m) planes, so VMEM use
    does not grow with the number of tiles."""
    b, k = x.shape
    n_tiles, s, r, m = g_pos.shape
    assert k == n_tiles * r and g_neg.shape == g_pos.shape
    if noise is not None:
        assert noise.shape == (n_tiles, s, b, m), (
            noise.shape, (n_tiles, s, b, m),
        )
    block_b = min(block_b, b)
    block_m = min(block_m, m)
    pad_b, pad_m = (-b) % block_b, (-m) % block_m
    if pad_b:
        x = jnp.pad(x, ((0, pad_b), (0, 0)))
        if noise is not None:
            noise = jnp.pad(noise, ((0, 0), (0, 0), (0, pad_b), (0, 0)))
    if pad_m:
        g_pos = jnp.pad(g_pos, ((0, 0), (0, 0), (0, 0), (0, pad_m)))
        g_neg = jnp.pad(g_neg, ((0, 0), (0, 0), (0, 0), (0, pad_m)))
        if noise is not None:
            noise = jnp.pad(noise, ((0, 0), (0, 0), (0, 0), (0, pad_m)))
    bb, mm = x.shape[0], g_pos.shape[3]

    in_specs = [
        pl.BlockSpec((block_b, r), lambda i, j, t: (i, t)),
        pl.BlockSpec((1, s, r, block_m), lambda i, j, t: (t, 0, 0, j)),
        pl.BlockSpec((1, s, r, block_m), lambda i, j, t: (t, 0, 0, j)),
    ]
    operands = [
        x.astype(jnp.float32),
        g_pos.astype(jnp.float32),
        g_neg.astype(jnp.float32),
    ]
    if noise is not None:
        in_specs.append(
            pl.BlockSpec((1, s, block_b, block_m), lambda i, j, t: (t, 0, i, j))
        )
        operands.append(noise.astype(jnp.float32))

    out = pl.pallas_call(
        functools.partial(
            _acim_tiled_kernel, bc=bc, adc_bits=adc_bits,
            full_scale=full_scale, with_noise=noise is not None,
        ),
        grid=(bb // block_b, mm // block_m, n_tiles),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_b, block_m), lambda i, j, t: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bb, mm), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name="acim_vmm_tiled",
    )(*operands)
    return out[:b, :m]


@functools.partial(
    jax.jit,
    static_argnames=("bc", "adc_bits", "full_scale", "block_b", "block_m", "interpret"),
)
def acim_vmm_pallas(
    x: jax.Array,
    g_pos: jax.Array,
    g_neg: jax.Array,
    noise: jax.Array | None = None,
    *,
    bc: int,
    adc_bits: int | None,
    full_scale: float,
    block_b: int = 128,
    block_m: int = 128,
    interpret: bool = True,
) -> jax.Array:
    b, k = x.shape
    s, k2, m = g_pos.shape
    assert k == k2 and g_neg.shape == g_pos.shape
    if noise is not None:
        assert noise.shape == (s, b, m), (noise.shape, (s, b, m))
    block_b = min(block_b, b)
    block_m = min(block_m, m)
    pad_b, pad_m = (-b) % block_b, (-m) % block_m
    if pad_b:
        x = jnp.pad(x, ((0, pad_b), (0, 0)))
        if noise is not None:
            noise = jnp.pad(noise, ((0, 0), (0, pad_b), (0, 0)))
    if pad_m:
        g_pos = jnp.pad(g_pos, ((0, 0), (0, 0), (0, pad_m)))
        g_neg = jnp.pad(g_neg, ((0, 0), (0, 0), (0, pad_m)))
        if noise is not None:
            noise = jnp.pad(noise, ((0, 0), (0, 0), (0, pad_m)))
    bb, mm = x.shape[0], g_pos.shape[2]

    in_specs = [
        pl.BlockSpec((block_b, k), lambda i, j: (i, 0)),
        pl.BlockSpec((s, k, block_m), lambda i, j: (0, 0, j)),
        pl.BlockSpec((s, k, block_m), lambda i, j: (0, 0, j)),
    ]
    operands = [
        x.astype(jnp.float32),
        g_pos.astype(jnp.float32),
        g_neg.astype(jnp.float32),
    ]
    if noise is not None:
        in_specs.append(pl.BlockSpec((s, block_b, block_m), lambda i, j: (0, i, j)))
        operands.append(noise.astype(jnp.float32))

    out = pl.pallas_call(
        functools.partial(
            _acim_kernel, bc=bc, adc_bits=adc_bits, full_scale=full_scale,
            with_noise=noise is not None,
        ),
        grid=(bb // block_b, mm // block_m),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_b, block_m), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bb, mm), jnp.float32),
        interpret=interpret,
        name="acim_vmm",
    )(*operands)
    return out[:b, :m]
