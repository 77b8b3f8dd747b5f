"""Shared utilities: pytree manipulation, PRNG plumbing, shape helpers.

Everything here is dependency-free (jax + numpy only) and used across the
framework.  No flax/optax in this environment, so the conventions are:

* a "module" is an ``init(rng, ...) -> params`` / ``apply(params, ...)`` pair
  of pure functions over plain-dict pytrees;
* optimizer state, FL server state, RL state are all NamedTuples of arrays so
  they jit/shard cleanly.
"""
from __future__ import annotations

import dataclasses
import math
import os
import pathlib
from typing import Any, Callable, Iterable, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any
PRNGKey = jax.Array


def enable_compile_cache() -> None:
    """Keep JAX's persistent compilation cache, for entry-point scripts.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and nothing
    else is set.  Otherwise the cache goes to the fixed ``<checkout>/.jax_cache``,
    never a path built from a temporary name, a pid or the time, which a
    later run would never find again.  Tests leave the cache off.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    checkout = pathlib.Path(__file__).resolve().parents[2]
    jax.config.update("jax_compilation_cache_dir", str(checkout / ".jax_cache"))


# ---------------------------------------------------------------------------
# PRNG helpers
# ---------------------------------------------------------------------------


def rng_seq(key: PRNGKey, n: int) -> list[PRNGKey]:
    """Split ``key`` into ``n`` independent keys (list, host-side friendly)."""
    return list(jax.random.split(key, n))


def fold_in_str(key: PRNGKey, name: str) -> PRNGKey:
    """Deterministically derive a key from a string tag (stable across runs)."""
    h = np.uint32(2166136261)
    for ch in name.encode():
        h = np.uint32((int(h) ^ ch) * 16777619 & 0xFFFFFFFF)
    return jax.random.fold_in(key, int(h))


# ---------------------------------------------------------------------------
# Tree helpers
# ---------------------------------------------------------------------------


def tree_zeros_like(tree: PyTree, dtype=None) -> PyTree:
    return jax.tree.map(lambda x: jnp.zeros_like(x, dtype=dtype or x.dtype), tree)


def tree_add(a: PyTree, b: PyTree) -> PyTree:
    return jax.tree.map(jnp.add, a, b)


def tree_sub(a: PyTree, b: PyTree) -> PyTree:
    return jax.tree.map(jnp.subtract, a, b)


def tree_scale(tree: PyTree, s) -> PyTree:
    return jax.tree.map(lambda x: x * s, tree)


def tree_axpy(a, x: PyTree, y: PyTree) -> PyTree:
    """a*x + y elementwise over matching pytrees."""
    return jax.tree.map(lambda xi, yi: a * xi + yi, x, y)


def tree_dot(a: PyTree, b: PyTree) -> jax.Array:
    leaves = jax.tree.map(lambda x, y: jnp.sum(x.astype(jnp.float32) * y.astype(jnp.float32)), a, b)
    return jax.tree.reduce(jnp.add, leaves, jnp.float32(0.0))


def global_norm(tree: PyTree) -> jax.Array:
    sq = jax.tree.map(lambda x: jnp.sum(jnp.square(x.astype(jnp.float32))), tree)
    return jnp.sqrt(jax.tree.reduce(jnp.add, sq, jnp.float32(0.0)))


def clip_by_global_norm(tree: PyTree, max_norm: float) -> tuple[PyTree, jax.Array]:
    """L2-clip a pytree; returns (clipped, pre-clip norm)."""
    norm = global_norm(tree)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
    return jax.tree.map(lambda x: (x * scale).astype(x.dtype), tree), norm


def tree_size(tree: PyTree) -> int:
    return int(sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree)))


def tree_bytes(tree: PyTree) -> int:
    return int(sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(tree)))


def tree_cast(tree: PyTree, dtype) -> PyTree:
    return jax.tree.map(lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)


# -- flatten a pytree of arrays into one 1-D vector and back (privacy codecs
#    and the secure-aggregation path operate on flat vectors) ----------------


@dataclasses.dataclass(frozen=True)
class TreeDef:
    treedef: Any
    shapes: tuple[tuple[int, ...], ...]
    dtypes: tuple[Any, ...]
    sizes: tuple[int, ...]

    @property
    def total(self) -> int:
        return int(sum(self.sizes))


def tree_ravel(tree: PyTree) -> tuple[jax.Array, TreeDef]:
    leaves, treedef = jax.tree.flatten(tree)
    shapes = tuple(tuple(x.shape) for x in leaves)
    dtypes = tuple(x.dtype for x in leaves)
    sizes = tuple(int(np.prod(s)) if s else 1 for s in shapes)
    flat = jnp.concatenate([jnp.ravel(x).astype(jnp.float32) for x in leaves]) if leaves else jnp.zeros((0,), jnp.float32)
    return flat, TreeDef(treedef, shapes, dtypes, sizes)


def tree_unravel(td: TreeDef, flat: jax.Array) -> PyTree:
    leaves = []
    off = 0
    for shape, dtype, size in zip(td.shapes, td.dtypes, td.sizes):
        leaves.append(flat[off : off + size].reshape(shape).astype(dtype))
        off += size
    return jax.tree.unflatten(td.treedef, leaves)


# ---------------------------------------------------------------------------
# Math / shape helpers
# ---------------------------------------------------------------------------


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return cdiv(a, b) * b


def pad_to(x: jax.Array, size: int, axis: int = 0, value=0) -> jax.Array:
    pad = size - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def human_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB", "PiB"):
        if abs(n) < 1024.0 or unit == "PiB":
            return f"{n:.2f} {unit}"
        n /= 1024.0
    return f"{n:.2f} PiB"


def human_count(n: float) -> str:
    for unit in ("", "K", "M", "B", "T"):
        if abs(n) < 1000.0 or unit == "T":
            return f"{n:.2f}{unit}"
        n /= 1000.0
    return f"{n:.2f}T"
