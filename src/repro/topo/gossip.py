"""Row-native gossip mixing: the compute side of decentralized aggregation.

One mixing step replaces every node's model row with the W-weighted average
of its neighborhood:

    X ← W X,        X: (k, P) ParamSpace rows,  W: (k, k) mixing matrix

This is the fused Pallas ``gossip_mix`` kernel — neighbor gather +
weighted combine over (k, block_p) row tiles in a single VMEM pass
(``repro.kernels.gossip_mix``); off the TPU ``kernels.ops`` runs its XLA
reference instead, as for ``RuntimeContext.weighted_sum``.

Also here: the optional carbon-aware neighbor reweighting (low-intensity
peers weighted up, ``carbon_reweight``) and the consensus-distance
diagnostic the ``MixEvent`` telemetry reports.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.fl.paramspace import ParamSpace
from repro.kernels import ops as kernel_ops

__all__ = ["carbon_reweight", "consensus_distance", "mix_rows"]


def mix_rows(pspace: ParamSpace, rows: jax.Array, mixing: jax.Array) -> jax.Array:
    """One gossip pass X ← W X over (k, P) ParamSpace rows, pre-padded to
    whole VMEM blocks for the kernel.  The kernel and its reference are
    checked against each other (allclose) in ``tests/test_topo.py``.
    """
    W = jnp.asarray(mixing, jnp.float32)
    return kernel_ops.gossip_mix(pspace.pad_rows(rows), W)[:, : pspace.dim]


def carbon_reweight(mixing: np.ndarray, intensities: np.ndarray, beta: float) -> np.ndarray:
    """Tilt neighbor weights toward low-carbon peers (paper §III-D spirit).

    Each off-diagonal column j is scaled by ``exp(-beta · z_j)`` where z_j
    is peer j's grid intensity standardized over the cohort, normalized so
    the largest factor is 1 (weights only shrink); the diagonal absorbs the
    slack.  The result stays row-stochastic and nonnegative — every step is
    still a convex combination — but symmetry is deliberately given up:
    consensus drifts toward models trained where the grid is green, the
    decentralized analogue of carbon-aware selection.  ``beta = 0`` returns
    the matrix unchanged (the FedAvg-equivalence anchor regime).
    """
    W = np.asarray(mixing, np.float64)
    if beta == 0.0 or W.shape[0] <= 1:
        return W.astype(np.float32)
    inten = np.asarray(intensities, np.float64)
    z = (inten - inten.mean()) / (inten.std() + 1e-9)
    factor = np.exp(-beta * z)
    factor = factor / factor.max()  # <= 1: off-diag mass only ever shrinks
    off = W * factor[None, :]
    np.fill_diagonal(off, 0.0)
    off[np.arange(len(off)), np.arange(len(off))] = 1.0 - off.sum(axis=1)
    return off.astype(np.float32)


def consensus_distance(rows: jax.Array) -> float:
    """Mean L2 distance of node models to their average — the disagreement
    the mixing passes contract (0 = exact consensus)."""
    rows = jnp.asarray(rows, jnp.float32)
    center = jnp.mean(rows, axis=0, keepdims=True)
    return float(jnp.mean(jnp.linalg.norm(rows - center, axis=1)))
