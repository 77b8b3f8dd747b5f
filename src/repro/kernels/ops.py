"""Jitted public wrappers around the Pallas kernels.

Shape plumbing: (B, T, H, hd) model-layout attention -> (B*H, T, hd) kernel
layout, GQA head mapping, head-dim padding to the 128-lane MXU width, and
sequence padding to block multiples.

Every wrapper picks its backend by one rule (:func:`_dispatch`):
``interpret=None`` runs the Mosaic kernel on a TPU and the XLA reference of
``kernels/ref.py`` anywhere else (the Pallas interpreter is far slower than
XLA off the TPU); ``interpret=True`` forces the Pallas interpreter and
``False`` forces Mosaic.  The reference and the kernel are different
programs, so they agree to within rounding (one quantization step for the
ring encoder), not bitwise.

Every kernel dispatch runs under a ``jax.named_scope`` (``repro.kernels/*``).
On the TPU the profiler's operation events carry no scope; what the scope
does give is the Mosaic custom call's name, which XLA takes from it
(``%masked_agg.1 = ... custom-call``), so a kernel's device time is found
in a ``jax.profiler`` trace by that operation name.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import compress as cp
from repro.kernels import flash_attention as fa
from repro.kernels import gossip_mix as gm
from repro.kernels import masked_agg as ma
from repro.kernels import ref as ref_mod
from repro.kernels import staleness_agg as sa
from repro.utils import round_up


def default_interpret() -> bool:
    """Pallas interpret mode unless we are actually on a TPU backend."""
    return jax.default_backend() != "tpu"


def _dispatch(kernel, reference, interpret: Optional[bool]):
    """The backend rule of every wrapper: ``reference()`` where
    ``interpret`` is None off the TPU, else ``kernel(interpret=...)`` with
    None resolved to Mosaic."""
    if interpret is None:
        if default_interpret():
            return reference()
        interpret = False
    return kernel(interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "logit_cap", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q, k, v, *, causal: bool = True, window: Optional[int] = None,
    logit_cap: float = 0.0, block_q: int = 128, block_k: int = 128,
    interpret: Optional[bool] = None,
):
    """Flash attention with GQA. q: (B, T, H, hd); k, v: (B, S, K, hd)."""
    return _dispatch(
        functools.partial(_flash_attention_kernel, q, k, v, causal=causal, window=window,
                          logit_cap=logit_cap, block_q=block_q, block_k=block_k),
        functools.partial(ref_mod.flash_attention_ref, q, k, v, causal=causal,
                          window=window, logit_cap=logit_cap),
        interpret,
    )


def _flash_attention_kernel(q, k, v, *, causal, window, logit_cap, block_q, block_k,
                            interpret):
    B, T, H, hd = q.shape
    S, K = k.shape[1], k.shape[2]
    assert H % K == 0
    group = H // K

    hd_p = round_up(hd, 128)
    T_p = round_up(T, block_q)
    S_p = round_up(S, block_k)

    def prep(x, L, Lp, heads):
        x = jnp.pad(x, ((0, 0), (0, Lp - L), (0, 0), (0, hd_p - hd)))
        return x.transpose(0, 2, 1, 3).reshape(B * heads, Lp, hd_p)

    qk_scale_fix = (hd_p / hd) ** 0.5  # kernel scales by hd_p^-0.5 after padding
    qbh = prep(q, T, T_p, H) * qk_scale_fix
    kbh = prep(k, S, S_p, K)
    vbh = prep(v, S, S_p, K)

    with jax.named_scope("repro.kernels/flash_attention"):
        out = fa.flash_attention_bh(
            qbh, kbh, vbh, causal=causal, window=window, logit_cap=logit_cap,
            block_q=block_q, block_k=block_k, group=group, seq_k=S, interpret=interpret,
        )
    out = out.reshape(B, H, T_p, hd_p).transpose(0, 2, 1, 3)
    return out[:, :T, :, :hd].astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("clip", "bits", "block_p", "interpret"))
def masked_aggregate(masked, masks, clip: float, bits: int, *, block_p: int = 2048,
                     interpret: Optional[bool] = None):
    """Fused unmask+dequantize ring aggregation (see masked_agg.py).

    masked, masks: (k, P) uint32 ParamSpace rows -> (P,) float32 ring sum.
    The FL engines hand in rows pre-padded to whole ``block_p`` blocks
    (``ParamSpace.pad_rows``), so the kernel's defensive pad is a no-op on
    the hot path; arbitrary P still works for direct callers.
    """
    with jax.named_scope("repro.kernels/masked_agg"):
        return _dispatch(
            functools.partial(ma.masked_aggregate, masked, masks, clip, bits, block_p=block_p),
            functools.partial(ref_mod.masked_aggregate_ref, masked, masks, clip, bits),
            interpret,
        )


@functools.partial(jax.jit, static_argnames=("clip", "bits", "dim", "block_p", "interpret"))
def clip_quant_mask(rows, masks, clip: float, bits: int, *, dim: Optional[int] = None,
                    block_p: int = 2048, interpret: Optional[bool] = None):
    """Fused delta-to-wire compression: a norm pass, then clip + quantize +
    mask in one P-tiled kernel pass (see compress.py).  rows (k, P) float32,
    masks (k, P) uint32 -> (k, P) uint32 ciphertext; ``dim`` bounds the L2
    norm to the unpadded columns.  The kernel's and the reference's
    ciphertexts agree to within one quantization step (tests/test_kernels.py
    pins this).
    """
    with jax.named_scope("repro.kernels/clip_quant_mask"):
        return _dispatch(
            functools.partial(cp.clip_quant_mask, rows, masks, clip, bits, dim=dim,
                              block_p=block_p),
            functools.partial(ref_mod.clip_quant_mask_ref, rows, masks, clip, bits, dim=dim),
            interpret,
        )


@functools.partial(jax.jit, static_argnames=("block_p", "interpret"))
def staleness_aggregate(deltas, weights, *, block_p: int = 2048,
                        interpret: Optional[bool] = None):
    """Fused staleness-weighted buffer aggregation (see staleness_agg.py).

    deltas: (k, P) float32 ParamSpace rows, weights: (k,) -> (P,)
    Σ_i w_i·delta_i.  Like :func:`masked_aggregate`, the engines pre-pad
    rows to whole blocks so no reshaping or padding happens here.
    """
    with jax.named_scope("repro.kernels/staleness_agg"):
        return _dispatch(
            functools.partial(sa.staleness_aggregate, deltas, weights, block_p=block_p),
            functools.partial(ref_mod.staleness_aggregate_ref, deltas, weights),
            interpret,
        )


@functools.partial(jax.jit, static_argnames=("block_p", "interpret"))
def gossip_mix(rows, mixing, *, block_p: int = 2048,
               interpret: Optional[bool] = None):
    """Fused gossip mixing step (see gossip_mix.py).

    rows: (k, P) float32 ParamSpace rows, mixing: (k, k) float32 ->
    (k, P) W @ rows.  The gossip strategy pre-pads rows to whole blocks
    (``ParamSpace.pad_rows``) so the kernel's defensive pad is a no-op on
    the hot path; arbitrary P still works for direct callers.
    """
    with jax.named_scope("repro.kernels/gossip_mix"):
        return _dispatch(
            functools.partial(gm.gossip_mix, rows, mixing, block_p=block_p),
            functools.partial(ref_mod.gossip_mix_ref, rows, mixing),
            interpret,
        )
