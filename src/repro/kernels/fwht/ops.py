"""Jit'd public wrapper for the FWHT kernel with backend dispatch."""

from __future__ import annotations

import jax

from . import ref
from .fwht import fwht_pallas


def fwht(x: jax.Array) -> jax.Array:
    """Batched Walsh-Hadamard transform along the last axis.

    Any leading batch dims are flattened to the kernel's (C, N) layout.
    On TPU backends the Pallas kernel runs compiled; elsewhere it runs in
    interpret mode (same kernel body, Python evaluation) unless the shape
    is unsupported, in which case the pure-jnp oracle is used.
    """
    n = x.shape[-1]
    lead = x.shape[:-1]
    if n & (n - 1) or n > 128:
        return ref.fwht(x.reshape((-1, n))).reshape(lead + (n,))
    on_tpu = jax.default_backend() == "tpu"
    y = fwht_pallas(x.reshape((-1, n)), interpret=not on_tpu)
    return y.reshape(lead + (n,))
