"""ParamSpace: the canonical pytree <-> flat-row mapping for the FL runtime.

Every aggregation-side subsystem (cohort trainers, the buffered async
runtime, the privacy stack, the Pallas kernels, the server update) operates
on ONE representation of a model delta: a float32 **row** of length ``dim``
whose layout is the ravel order of ``params0``'s leaves.  A cohort of k
clients is a ``(k, dim)`` **rows** matrix.  This module is the only place in
``repro.fl`` / ``repro.privacy`` where pytrees are flattened or rows are
folded back into pytrees — the single conversion site.

Flattening in one place keeps one ravel order and one set of dtype rules.
A ``ParamSpace`` is built once from ``params0`` and owns:

  * the treedef + per-leaf shapes/dtypes/sizes/offsets (ravel order),
  * ``dim`` (P, the flat parameter count) and ``padded_dim`` (P rounded up
    to the Pallas kernels' lane-block alignment, so the fused aggregation
    kernels see whole VMEM tiles and their internal pad branch is a no-op),
  * the conversions: ``ravel``/``unravel`` for one tree, ``stack``/
    ``unstack`` for k-stacked trees, ``pad_row``/``pad_rows`` for kernel
    dispatch, and ``add_to_tree`` for applying a row delta to a model.

All conversions are pure jnp ops (reshape/concat/slice/astype), so they are
free inside jit — the cohort trainer returns rows straight off the device
and the rows stay device-resident through privacy, kernels and the server
reduction; pytrees only reappear at the model-update boundary.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.utils import PyTree, pad_to, round_up

# Default row alignment: the fused aggregation kernels' block_p default
# (2048 lanes = 8 sublanes x 256 float32 lanes per VMEM tile).
BLOCK_ALIGN = 2048


@dataclasses.dataclass(frozen=True)
class ParamSpace:
    """Canonical flat-parameter coordinate system of one model pytree."""

    treedef: Any
    shapes: tuple[tuple[int, ...], ...]
    dtypes: tuple[Any, ...]
    sizes: tuple[int, ...]
    offsets: tuple[int, ...]
    dim: int         # P: total parameter count (sum of leaf sizes)
    padded_dim: int  # P rounded up to ``align`` for kernel block dispatch
    align: int

    # ------------------------------------------------------------------
    @classmethod
    def build(cls, params0: PyTree, align: int = BLOCK_ALIGN) -> "ParamSpace":
        """Construct the space from a template pytree (shapes/dtypes only)."""
        leaves, treedef = jax.tree.flatten(params0)
        shapes = tuple(tuple(x.shape) for x in leaves)
        dtypes = tuple(jnp.dtype(x.dtype) for x in leaves)
        sizes = tuple(int(np.prod(s)) if s else 1 for s in shapes)
        offsets = tuple(int(o) for o in np.concatenate([[0], np.cumsum(sizes)[:-1]])) if leaves else ()
        dim = int(sum(sizes))
        return cls(
            treedef=treedef, shapes=shapes, dtypes=dtypes, sizes=sizes,
            offsets=offsets, dim=dim, padded_dim=round_up(max(dim, 1), align),
            align=align,
        )

    @property
    def nbytes(self) -> int:
        """Wire size of one row (float32)."""
        return self.dim * 4

    def matches(self, tree: PyTree) -> bool:
        """Cheap structural check: does ``tree`` live in this space?"""
        leaves, treedef = jax.tree.flatten(tree)
        return treedef == self.treedef and tuple(tuple(x.shape) for x in leaves) == self.shapes

    # -- single tree <-> (dim,) row ------------------------------------
    def ravel(self, tree: PyTree) -> jax.Array:
        """Pytree -> (dim,) float32 row (leaf ravel order)."""
        leaves = jax.tree.leaves(tree)
        if not leaves:
            return jnp.zeros((0,), jnp.float32)
        return jnp.concatenate([jnp.ravel(x).astype(jnp.float32) for x in leaves])

    def unravel(self, row: jax.Array) -> PyTree:
        """(dim,) or (padded_dim,) row -> pytree (leaf dtypes restored)."""
        leaves = [
            jax.lax.slice_in_dim(row, off, off + size).reshape(shape).astype(dtype)
            for off, size, shape, dtype in zip(self.offsets, self.sizes, self.shapes, self.dtypes)
        ]
        return jax.tree.unflatten(self.treedef, leaves)

    # -- k-stacked tree <-> (k, dim) rows ------------------------------
    def stack(self, stacked: PyTree) -> jax.Array:
        """k-stacked pytree (every leaf (k, *shape)) -> (k, dim) float32 rows."""
        leaves = jax.tree.leaves(stacked)
        k = leaves[0].shape[0]
        return jnp.concatenate(
            [d.reshape(k, -1).astype(jnp.float32) for d in leaves], axis=1
        )

    def unstack(self, rows: jax.Array) -> PyTree:
        """(k, dim) rows -> k-stacked pytree (leaf dtypes restored)."""
        k = rows.shape[0]
        leaves = [
            rows[:, off : off + size].reshape((k,) + shape).astype(dtype)
            for off, size, shape, dtype in zip(self.offsets, self.sizes, self.shapes, self.dtypes)
        ]
        return jax.tree.unflatten(self.treedef, leaves)

    # -- kernel-facing helpers -----------------------------------------
    def pad_row(self, row: jax.Array) -> jax.Array:
        """(dim,) -> (padded_dim,) zero-padded row (whole kernel blocks)."""
        return pad_to(row, self.padded_dim, axis=-1)

    def pad_rows(self, rows: jax.Array) -> jax.Array:
        """(k, dim) -> (k, padded_dim) zero-padded rows."""
        return pad_to(rows, self.padded_dim, axis=-1)

    def zeros_row(self) -> jax.Array:
        """The additive identity of the space (edge accumulators start here)."""
        return jnp.zeros((self.dim,), jnp.float32)

    # -- model-update boundary -----------------------------------------
    def add_to_tree(self, tree: PyTree, row: jax.Array) -> PyTree:
        """Apply a row delta to a model pytree: tree + unravel(row)."""
        return jax.tree.map(jnp.add, tree, self.unravel(row))
