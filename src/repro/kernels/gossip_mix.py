"""Pallas TPU kernel: fused gossip mixing over flat parameter rows.

The hot loop of the decentralized ``"gossip"`` strategy: one mixing step
replaces every node's model row with the mixing-matrix-weighted combination
of its neighborhood,

    out = W @ X,    W: (k, k) row-stochastic,  X: (k, P) ParamSpace rows.

An XLA matmul would be correct but tiles both operands for the MXU's
(128, 128) systolic shape; with k ≤ ~32 cohort rows and P in the millions
the op is utterly memory-bound (arithmetic intensity ≈ k/4 FLOP/byte at
useful k), so the win is the access pattern: grid over parameter blocks,
each step one (k, block_p) X tile read + one written, with the whole (k, k)
mixing matrix riding along in VMEM and broadcast into every grid step — the
neighbor gather and the weighted combine happen in a single VMEM pass per
tile, and X is read exactly once per mixing step.

The mixing matrix's zero pattern IS the communication graph: a row of W
touching only its graph neighbors means each output row is the neighbor
gather the topology prescribes (``repro.topo.graph``), with no gather
indices materialized.

Multiple mixing steps are applied by re-invoking the kernel — the strategy
reports per-step telemetry (consensus contraction, bytes moved), so the
steps intentionally stay separate dispatches rather than a precomputed W^m.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _gossip_kernel(w_ref, x_ref, o_ref):
    w = w_ref[...]  # (k, k) float32 mixing matrix, same block every step
    x = x_ref[...]  # (k, block_p) float32 row tile
    # HIGHEST: a full float32 contraction on the MXU, not one bf16 pass
    o_ref[...] = jnp.dot(w, x, precision=jax.lax.Precision.HIGHEST,
                         preferred_element_type=jnp.float32)


def gossip_mix(rows, mixing, *, block_p: int = 2048, interpret: bool = True):
    """rows: (k, P) float32, mixing: (k, k) float32 -> (k, P) W @ rows."""
    k, P = rows.shape
    W = mixing.astype(jnp.float32)
    n_pb = pl.cdiv(P, block_p)
    pad = n_pb * block_p - P
    if pad:
        rows = jnp.pad(rows, ((0, 0), (0, pad)))
    out = pl.pallas_call(
        _gossip_kernel,
        grid=(n_pb,),
        in_specs=[
            pl.BlockSpec((k, k), lambda i: (0, 0)),
            pl.BlockSpec((k, block_p), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((k, block_p), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((k, n_pb * block_p), jnp.float32),
        interpret=interpret,
    )(W, rows.astype(jnp.float32))
    return out[:, :P]
