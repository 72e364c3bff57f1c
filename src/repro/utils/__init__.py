"""Shared utilities: pytree accounting, rng, timing."""
from __future__ import annotations

import os
import time
from pathlib import Path
from typing import Any, Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any

# src/repro/utils/__init__.py → the checkout root
_CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other directory is set.  Otherwise the cache lives at the fixed path
    ``<checkout>/.jax_cache``: the directory is part of what a later run
    must find again, so it is never built from a temporary name, a pid or
    the time.  Entry points call this; importing a module never does.
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(_CHECKOUT / ".jax_cache"))


def param_count(params: PyTree) -> int:
    """Total number of scalar parameters in a pytree."""
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))


def param_bytes(params: PyTree) -> int:
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree_util.tree_leaves(params))


def tree_cast(params: PyTree, dtype) -> PyTree:
    """Cast every floating leaf to ``dtype`` (ints left untouched)."""
    def _cast(x):
        if jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(dtype)
        return x
    return jax.tree_util.tree_map(_cast, params)


def rng_seq(seed: int | jax.Array) -> Iterator[jax.Array]:
    """Infinite stream of fresh PRNG keys."""
    key = jax.random.PRNGKey(seed) if isinstance(seed, int) else seed
    while True:
        key, sub = jax.random.split(key)
        yield sub


def check_finite(tree: PyTree) -> jax.Array:
    """Scalar bool: every floating leaf is finite."""
    leaves = [jnp.all(jnp.isfinite(x)) for x in jax.tree_util.tree_leaves(tree)
              if jnp.issubdtype(x.dtype, jnp.floating)]
    if not leaves:
        return jnp.asarray(True)
    return jnp.stack(leaves).all()


def timeit(fn: Callable[[], Any], iters: int = 10, warmup: int = 2) -> float:
    """Median wall-clock seconds per call; blocks on JAX outputs."""
    for _ in range(warmup):
        jax.block_until_ready(fn())
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def human_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024.0:
            return f"{n:.2f}{unit}"
        n /= 1024.0
    return f"{n:.2f}PiB"
