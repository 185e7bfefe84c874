"""Inputs the benchmark makes from `--seed`: keys and model weights.

The weights are the benchmark's, not the program's: both the program
under test and the plain references are handed the same arrays, made
here on the device in one jitted call.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def key_from_seed(seed: int, stream: int = 0) -> jax.Array:
    """A PRNG key for a seed of any size up to 64 bits, and a stream."""
    if not 0 <= seed < 1 << 64:
        raise ValueError(f"seed must be a whole number in [0, 2**64): {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, stream)


def projection_shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    """The stacked (layers, in, out) projection leaves of a dense
    decoder, named as the program's parameter tree names them."""
    n_l, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    ff = cfg["intermediate_size"]
    return {
        "wq": (n_l, d, q), "wk": (n_l, d, kv), "wv": (n_l, d, kv),
        "wo": (n_l, q, d), "w_gate": (n_l, d, ff), "w_up": (n_l, d, ff),
        "w_down": (n_l, ff, d),
    }


@functools.partial(jax.jit, static_argnames=("shapes", "std", "dtype"))
def _draw(key, shapes, std, dtype):
    return {
        name: (std * jax.random.normal(jax.random.fold_in(key, i), shape,
                                       jnp.float32)).astype(dtype)
        for i, (name, shape) in enumerate(shapes)
    }


def projection_weights(cfg: dict, seed: int) -> dict:
    """`{"layers": {name: (L, in, out) array}}`, random from the seed."""
    shapes = tuple(sorted(projection_shapes(cfg).items()))
    dtype = DTYPES[cfg["torch_dtype"]]
    std = float(cfg["initializer_range"])
    return {"layers": _draw(key_from_seed(seed, 1), shapes, std, dtype)}
