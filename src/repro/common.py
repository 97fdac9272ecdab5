"""Small shared utilities: rng threading, pytree helpers, timing, shape math."""
from __future__ import annotations

import os
import pathlib
import time
from typing import Any, Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array
PyTree = Any

# src/repro/common.py -> the checkout root
_CHECKOUT = pathlib.Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; every entry point calls
    this at start-up, before its first compile. Returns the directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is where the cache lives (JAX
    reads it itself) and no other directory is set here. Otherwise the
    cache goes to ``<checkout>/.jax_cache``: a fixed path, because the
    path is part of what a later run must match to hit. Every compile is
    kept, however short: a serving run is many small programs."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(_CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


class KeySeq:
    """Stateful PRNG key splitter for init code (training uses explicit keys)."""

    def __init__(self, seed_or_key):
        if isinstance(seed_or_key, int):
            self._key = jax.random.PRNGKey(seed_or_key)
        else:
            self._key = seed_or_key

    def __next__(self) -> Array:
        self._key, sub = jax.random.split(self._key)
        return sub

    def take(self, n: int) -> list[Array]:
        return [next(self) for _ in range(n)]


def tree_size(tree: PyTree) -> int:
    """Total number of scalar elements in a pytree of arrays."""
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))


def tree_bytes(tree: PyTree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(tree))


def tree_cast(tree: PyTree, dtype) -> PyTree:
    return jax.tree_util.tree_map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x, tree
    )


def tree_zeros_like(tree: PyTree) -> PyTree:
    return jax.tree_util.tree_map(jnp.zeros_like, tree)


def tree_allclose(a: PyTree, b: PyTree, rtol=1e-5, atol=1e-6) -> bool:
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    if len(la) != len(lb):
        return False
    return all(np.allclose(x, y, rtol=rtol, atol=atol) for x, y in zip(la, lb))


def glorot(key: Array, shape: tuple[int, ...], dtype=jnp.float32) -> Array:
    fan_in, fan_out = shape[0], shape[-1]
    lim = float(np.sqrt(6.0 / (fan_in + fan_out)))
    return jax.random.uniform(key, shape, dtype, -lim, lim)


def normal_init(key: Array, shape: tuple[int, ...], stddev: float = 0.02,
                dtype=jnp.float32) -> Array:
    return jax.random.normal(key, shape, dtype) * stddev


def timeit(fn: Callable[[], Any], *, warmup: int = 2, iters: int = 10) -> dict:
    """Wall-clock a thunk returning jax arrays; blocks on results.

    Returns mean/std/p50/p99 in microseconds over `iters` runs.
    """
    for _ in range(warmup):
        jax.block_until_ready(fn())
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append((time.perf_counter() - t0) * 1e6)
    ts = np.asarray(times)
    return {
        "mean_us": float(ts.mean()),
        "std_us": float(ts.std()),
        "p50_us": float(np.percentile(ts, 50)),
        "p99_us": float(np.percentile(ts, 99)),
        "iters": iters,
    }


def round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (1 for n <= 1)."""
    p = 1
    while p < n:
        p *= 2
    return p


def prev_pow2(n: int) -> int:
    """Largest power of two <= n (requires n >= 1)."""
    return 1 << (n.bit_length() - 1)


def cdiv(a: int, b: int) -> int:
    return (a + b - 1) // b


def chunked(seq, n: int) -> Iterator:
    for i in range(0, len(seq), n):
        yield seq[i : i + n]
