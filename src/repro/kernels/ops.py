"""Jitted public wrappers around the Pallas kernels.

Shape plumbing: (B, T, H, hd) model-layout attention -> (B*H, T, hd) kernel
layout, GQA head mapping, head-dim padding to the 128-lane MXU width, and
sequence padding to block multiples.  ``interpret`` defaults to None, which
resolves per-backend: interpreter on CPU (this container), Mosaic lowering
on TPU.  Pass an explicit bool to override.

Every kernel dispatch runs under a ``jax.named_scope`` (``repro.kernels/*``)
so the ops are attributable in ``jax.profiler`` traces — the device-side
counterpart of the host-side ``repro.obs`` span tracer.
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


def _resolve(interpret: Optional[bool]) -> bool:
    return default_interpret() if interpret is None else interpret


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
            block_q=block_q, block_k=block_k, group=group, seq_k=S,
            interpret=_resolve(interpret),
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
        return ma.masked_aggregate(
            masked, masks, clip, bits, block_p=block_p, interpret=_resolve(interpret)
        )


@functools.partial(jax.jit, static_argnames=("clip", "bits", "dim", "block_p", "interpret"))
def clip_quant_mask(rows, masks, clip: float, bits: int, *, dim: Optional[int] = None,
                    block_p: int = 2048, interpret: Optional[bool] = None):
    """Fused delta-to-wire compression: a norm pass, then clip + quantize +
    mask in one P-tiled kernel pass (see compress.py).  rows (k, P) float32,
    masks (k, P) uint32 -> (k, P) uint32 ciphertext; ``dim`` bounds the L2
    norm to the unpadded columns.

    Dispatch mirrors ``RuntimeContext.weighted_sum``: on TPU the Pallas
    kernel runs (Mosaic lowering); on CPU the interpreter would be strictly
    slower than XLA, so ``interpret=None`` routes to the *same fused math*
    as one XLA expression (``ref.clip_quant_mask_ref``).  The paths are
    different programs, so their ciphertexts agree to within one
    quantization step, not bitwise (tests/test_kernels.py pins this).
    Pass ``interpret=True`` to force the Pallas interpreter.
    """
    with jax.named_scope("repro.kernels/clip_quant_mask"):
        if interpret is None and default_interpret():
            return ref_mod.clip_quant_mask_ref(rows, masks, clip, bits, dim=dim)
        return cp.clip_quant_mask(
            rows, masks, clip, bits, dim=dim, block_p=block_p,
            interpret=_resolve(interpret),
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
        return sa.staleness_aggregate(
            deltas, weights, block_p=block_p, interpret=_resolve(interpret)
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
        return gm.gossip_mix(rows, mixing, block_p=block_p, interpret=_resolve(interpret))
