"""Pallas TPU kernel: batched Walsh-Hadamard transform over RRAM columns.

The kernel runs the same O(N log N) butterfly as the jnp oracle
(`core.hadamard.fwht`), stage by stage in VMEM, so its output is
bit-identical to the oracle: every output element is the same single
f32 add or subtract of the same two operands.  A dense `x @ H` on the
MXU would reassociate the N-term sums (and, at default precision, round
the conductances to bf16), which moves HARP's compare decisions.

A column of N <= 128 cells (the paper uses N = 32 / 64) lies along the
lane axis of one VREG row.  Stage s pairs lane i with lane i ^ 2^s; the
partner value comes from a lane rotation (`pltpu.roll`) by +2^s or
-2^s, and the rotated lane index says which of the two rotations
delivered it, so the kernel does not depend on the rotation's sign
convention.

Grid: one program per batch block of `block_c` columns.
BlockSpecs: x block (block_c, N) in VMEM, out block (block_c, N).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_C = 512


def _fwht_kernel(x_ref, o_ref, *, n):
    x = x_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    for s in range(n.bit_length() - 1):
        h = 1 << s
        fwd = pltpu.roll(x, h, 1)
        bwd = pltpu.roll(x, n - h, 1)
        partner = jnp.where(pltpu.roll(lane, h, 1) == (lane ^ h), fwd, bwd)
        # Oracle order: low lane a, high lane b -> (a + b, a - b).
        x = jnp.where((lane & h) == 0, x + partner, partner - x)
    o_ref[...] = x


@functools.partial(jax.jit, static_argnames=("block_c", "interpret"))
def fwht_pallas(
    x: jax.Array, *, block_c: int = DEFAULT_BLOCK_C, interpret: bool = True
) -> jax.Array:
    """Batched FWHT: (C, N) -> (C, N) f32, N a power of two <= 128.

    `interpret=True` runs the kernel body on CPU for validation; on a real
    TPU backend pass interpret=False.
    """
    c, n = x.shape
    if n & (n - 1) or n > 128:
        raise ValueError(f"kernel supports power-of-two N <= 128, got {n}")
    block_c = min(block_c, c)
    # Pad the column batch to a multiple of the block size.
    pad = (-c) % block_c
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    spec = pl.BlockSpec((block_c, n), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_fwht_kernel, n=n),
        grid=(x.shape[0] // block_c,),
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((x.shape[0], n), jnp.float32),
        interpret=interpret,
        name="fwht",
    )(x.astype(jnp.float32))
    return out[:c]
