"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth).

Contractions ask for ``Precision.HIGHEST``: on a TPU the default float32
matmul is one bf16 pass, which would make the oracle less exact than the
kernels it checks.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def flash_attention_ref(q, k, v, *, causal: bool = True, window: Optional[int] = None,
                        logit_cap: float = 0.0):
    """Reference attention. q: (B, T, H, hd); k, v: (B, S, K, hd); GQA groups.

    Identical contract to kernels.ops.flash_attention; fp32 softmax.
    """
    B, T, H, hd = q.shape
    S, K = k.shape[1], k.shape[2]
    G = H // K
    qg = q.reshape(B, T, K, G, hd)
    s = jnp.einsum("btkgh,bskh->bkgts", qg.astype(jnp.float32), k.astype(jnp.float32)) * hd**-0.5
    if logit_cap > 0.0:
        s = logit_cap * jnp.tanh(s / logit_cap)
    tpos = jnp.arange(T)[:, None]
    spos = jnp.arange(S)[None, :]
    mask = jnp.ones((T, S), bool)
    if causal:
        mask &= spos <= tpos
    if window is not None:
        mask &= spos > tpos - window
    s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgts,bskh->btkgh", p, v.astype(jnp.float32))
    return o.reshape(B, T, H, hd).astype(q.dtype)


def staleness_aggregate_ref(deltas, weights):
    """Reference staleness-weighted buffer aggregation.

    deltas: (k, P) float32, weights: (k,) float32.  Returns float32 (P,):
        Σ_i w_i · delta_i
    """
    return jnp.einsum(
        "kp,k->p", deltas.astype(jnp.float32), weights.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )


def gossip_mix_ref(rows, mixing):
    """Reference gossip mixing step.

    rows: (k, P) float32 node-model rows, mixing: (k, k) float32
    row-stochastic matrix.  Returns float32 (k, P):  W @ X
    """
    return jnp.dot(
        mixing.astype(jnp.float32), rows.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )


def clip_quant_mask_ref(rows, masks, clip: float, bits: int, dim=None):
    """Reference fused delta-to-wire compression (one XLA expression).

    rows: (k, P) float32 block-padded delta rows, masks: (k, P) uint32
    one-time pads.  Returns uint32 (k, P) ciphertext:

        encode( clip_L2(row, c) ) + pad   (mod 2^32)

    ``dim`` bounds the norm reduction to the valid (unpadded) columns.  The
    expressions (and reduction lengths) are the staged ClipStage ->
    QuantizeStage -> MaskStage composition's own; being one program rather
    than three, it matches them and the Pallas kernel to within one
    quantization step.
    """
    rows = rows.astype(jnp.float32)
    dim = rows.shape[1] if dim is None else int(dim)
    norms = jnp.sqrt(
        jnp.sum(jnp.square(rows[:, :dim]), axis=-1, keepdims=True)
    )
    scale = jnp.minimum(1.0, clip / jnp.maximum(norms, 1e-12))
    qscale = ((1 << (bits - 1)) - 1) / clip
    v = jnp.clip(rows * scale, -clip, clip) * qscale
    q = jnp.round(v).astype(jnp.int32).astype(jnp.uint32)
    return q + masks


def masked_aggregate_ref(masked, masks, clip: float, bits: int):
    """Reference fused unmask+dequantize.

    masked, masks: (n_clients, P) uint32.  Returns float32 (P,):
        decode( Σ masked - Σ masks  (mod 2^32) )
    """
    total = jnp.sum(masked, axis=0, dtype=jnp.uint32) - jnp.sum(masks, axis=0, dtype=jnp.uint32)
    scale = ((1 << (bits - 1)) - 1) / clip
    return total.astype(jnp.int32).astype(jnp.float32) / scale
