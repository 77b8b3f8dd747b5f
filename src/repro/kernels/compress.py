"""Pallas TPU kernel: fused delta-to-wire compression (clip + quantize + mask).

The client-side hot loop of MetaFed's communication pillar: between local
training and the wire, a delta row is L2-clipped (the DP sensitivity bound),
fixed-point-encoded into the uint32 ring, and one-time-padded.  Run as
separate ``PrivacyPipeline`` stages, each step re-reads the whole (k, P)
cohort from HBM and writes it back — seven full traversals of the delta
block before the reducer ever sees a ciphertext.  Here it takes two passes:

    scale_i = min(1, c / max(||row_i||, eps))                       (XLA)
    out     = ( round( clamp(row * scale, ±c) · s ) + pad ) mod 2^32 (Pallas)

The per-row norm is one XLA reduction over the rows; the Pallas kernel is
tiled over parameter blocks, each grid step one (k, block_p) tile of rows,
pads and ciphertext with the (k, 1) scale column riding along.  VMEM use is
``3 · k · block_p · 4`` bytes per buffer whatever P is, so a full-width
ResNet-Tiny row (P ≈ 4.7M) compiles for a 16 MiB scoped-VMEM core.  HBM
traffic: rows read twice (norm, encode), pads read once, ciphertext written
once — four traversals (``repro.roofline.compress_traffic``).

The ring addition runs in int32 (bitcast in and out): two's-complement
addition wraps exactly like uint32 addition, both being mod 2^32.

Tolerance contract: the norm reduction is a different XLA program from the
staged ``ClipStage`` one and Mosaic's encode is a different program from
XLA's, so the clip factor may differ in its last ulp and a value on a
rounding boundary may land one quantization step away.  Ciphertexts
therefore decode within one step of the staged composition's
(``tests/test_kernels.py``, ``tests/test_property.py``).
``clip_quant_mask_ref`` in ``kernels/ref.py`` is the same math as one XLA
expression; it is the CPU-dispatch path and the oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _encode_kernel(scale_ref, rows_ref, masks_ref, o_ref, *, clip: float, bits: int):
    qscale = ((1 << (bits - 1)) - 1) / clip
    v = jnp.clip(rows_ref[...] * scale_ref[...], -clip, clip) * qscale
    q = jnp.round(v).astype(jnp.int32)
    pad = jax.lax.bitcast_convert_type(masks_ref[...], jnp.int32)
    o_ref[...] = jax.lax.bitcast_convert_type(q + pad, jnp.uint32)  # wraps = mod 2^32


def clip_quant_mask(rows, masks, clip: float, bits: int, *, dim: int | None = None,
                    block_p: int = 2048, interpret: bool = True):
    """rows (k, P) float32, masks (k, P) uint32 -> (k, P) uint32 ciphertext.

    ``dim``: valid parameter count (columns past it are block padding and do
    not enter the norm); defaults to P.  Rows should be pre-padded to whole
    ``block_p`` blocks (``ParamSpace.pad_rows``) by the caller.
    """
    k, P = rows.shape
    if masks.shape != (k, P):
        raise ValueError(f"masks shape {masks.shape} != rows shape {(k, P)}")
    dim = P if dim is None else int(dim)
    if not (0 < dim <= P):
        raise ValueError(f"dim={dim} outside (0, {P}]")
    rows = rows.astype(jnp.float32)
    norms = jnp.sqrt(jnp.sum(jnp.square(rows[:, :dim]), axis=-1, keepdims=True))
    scale = jnp.minimum(1.0, clip / jnp.maximum(norms, 1e-12))  # (k, 1)
    n_pb = pl.cdiv(P, block_p)
    pad = n_pb * block_p - P
    if pad:
        # zero columns encode to 0 and carry zero pads: inert, sliced off below
        rows = jnp.pad(rows, ((0, 0), (0, pad)))
        masks = jnp.pad(masks, ((0, 0), (0, pad)))
    out = pl.pallas_call(
        functools.partial(_encode_kernel, clip=clip, bits=bits),
        grid=(n_pb,),
        in_specs=[
            pl.BlockSpec((k, 1), lambda i: (0, 0)),
            pl.BlockSpec((k, block_p), lambda i: (0, i)),
            pl.BlockSpec((k, block_p), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((k, block_p), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((k, n_pb * block_p), jnp.uint32),
        interpret=interpret,
    )(scale, rows, masks)
    return out[:, :P]
