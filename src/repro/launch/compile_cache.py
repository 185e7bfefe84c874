"""JAX's persistent compilation cache for the repo's entry points.

`chip_smoke.py`, `benchmarks/run.py` and the examples call
`enable_compile_cache()` once, before their first compile, so processes
that compile the same programs share them.  The tests do not: they run
on the CPU and compile small shapes.
"""

from __future__ import annotations

import os

# The path is part of each entry's key, so it is fixed: a directory
# that moves between runs never hits.
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    )))),
    ".jax_cache",
)
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Point JAX's compilation cache at its directory; returns the path.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache lives in `.jax_cache` at
    the root of the checkout.
    """
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
