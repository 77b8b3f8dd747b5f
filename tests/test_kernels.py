"""Pallas kernel validation (interpret mode) against the pure-jnp oracles.

Per the brief: sweep shapes/dtypes and assert_allclose against ref.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import compress as compress_mod
from repro.kernels import ops, ref
from repro.privacy import dp as dp_mod
from repro.privacy import quantize, secure_agg


def _qkv(key, B, T, S, H, K, hd, dtype):
    ks = jax.random.split(key, 3)
    return (
        jax.random.normal(ks[0], (B, T, H, hd), dtype),
        jax.random.normal(ks[1], (B, S, K, hd), dtype),
        jax.random.normal(ks[2], (B, S, K, hd), dtype),
    )


CASES = [
    # (B, T, S, H, K, hd, causal, window, cap)
    (2, 128, 128, 4, 2, 64, True, None, 0.0),
    (1, 100, 100, 4, 4, 32, True, None, 0.0),     # non-block-multiple T
    (2, 256, 256, 4, 2, 64, True, 64, 0.0),       # sliding window
    (2, 128, 128, 8, 2, 64, True, 256, 0.0),      # window > T
    (1, 128, 128, 4, 1, 64, False, None, 0.0),    # bidirectional, MQA
    (2, 128, 128, 4, 2, 64, True, None, 30.0),    # grok logit cap
    (1, 64, 64, 2, 2, 80, True, None, 0.0),       # hd=80 (hubert) pads to 128
    (1, 72, 72, 3, 1, 48, True, 17, 8.0),         # awkward everything
]


@pytest.mark.parametrize("case", CASES)
def test_flash_attention_fp32(case):
    B, T, S, H, K, hd, causal, window, cap = case
    q, k, v = _qkv(jax.random.PRNGKey(0), B, T, S, H, K, hd, jnp.float32)
    out = ops.flash_attention(q, k, v, causal=causal, window=window, logit_cap=cap,
                              block_q=64, block_k=64)
    expect = ref.flash_attention_ref(q, k, v, causal=causal, window=window, logit_cap=cap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("dtype,tol", [(jnp.bfloat16, 3e-2), (jnp.float32, 3e-5)])
def test_flash_attention_dtypes(dtype, tol):
    q, k, v = _qkv(jax.random.PRNGKey(1), 2, 128, 128, 4, 2, 64, dtype)
    out = ops.flash_attention(q, k, v, causal=True)
    expect = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32), atol=tol, rtol=tol
    )


@pytest.mark.parametrize("blocks", [(32, 32), (64, 128), (128, 64)])
def test_flash_attention_block_shapes(blocks):
    bq, bk = blocks
    q, k, v = _qkv(jax.random.PRNGKey(2), 1, 160, 160, 4, 4, 64, jnp.float32)
    out = ops.flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk)
    expect = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("n,P,bits", [(4, 1000, 16), (8, 5000, 20), (16, 2048, 16), (3, 7777, 24)])
def test_masked_agg_kernel(n, P, bits):
    rng = np.random.default_rng(n)
    ups = rng.normal(0, 0.05, (n, P)).astype(np.float32)
    qs = jnp.stack([quantize.encode(jnp.asarray(u), 1.0, bits) for u in ups])
    keys = list(jax.random.split(jax.random.PRNGKey(7), n))
    masked = jnp.stack([secure_agg.mask_update(q, k) for q, k in zip(qs, keys)])
    masks = jnp.stack([secure_agg.mask_stream(k, P) for k in keys])
    out = ops.masked_aggregate(masked, masks, 1.0, bits)
    expect = ref.masked_aggregate_ref(masked, masks, 1.0, bits)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=1e-6)
    # and the decoded result matches the true float sum within quant error
    bound = quantize.quant_error_bound(1.0, bits) * n + 1e-6
    np.testing.assert_allclose(np.asarray(out), ups.sum(0), atol=bound)


def _staged_compress(rows, masks, clip, bits, dim):
    """The exact ClipStage -> QuantizeStage -> MaskStage ops over pre-padded
    rows: the fused kernel's ground truth (dim = unpadded columns)."""
    clipped, _ = dp_mod.clip_rows(rows[:, :dim], clip)
    padded = jnp.pad(clipped, ((0, 0), (0, rows.shape[1] - dim)))
    return quantize.encode(padded, clip, bits) + masks


def assert_within_one_step(cipher, expect):
    """Ciphertexts under the same pads decode within one quantization step:
    their ring difference, read as a signed integer, is -1, 0 or 1."""
    diff = (np.asarray(cipher, np.uint32) - np.asarray(expect, np.uint32)).view(np.int32)
    assert np.abs(diff).max() <= 1, f"ring values differ by up to {np.abs(diff).max()} steps"


# (k, dim, P, clip, bits) — P is the block-padded width, dim the true one
COMPRESS_CASES = [
    (3, 1000, 1024, 1.0, 16),
    (8, 5000, 6144, 0.5, 20),     # padded-dim case: norm must stop at dim
    (16, 2048, 2048, 10.0, 24),   # aligned: dim == P
    (5, 7777, 8192, 2.0, 18),
    (1, 123, 256, 0.25, 12),      # single row, tiny dim
]


@pytest.mark.parametrize("k,dim,P,clip,bits", COMPRESS_CASES)
def test_clip_quant_mask_bitwise_vs_staged(k, dim, P, clip, bits):
    """Pallas interpret mode, the fused XLA ref and the public dispatcher all
    reproduce the staged stage composition to within one quantization step.
    Not bitwise: the norm reduction and the encode are different programs
    from the staged ones, so the clip factor may move by an ulp and a value
    on a rounding boundary may round the other way."""
    rng = np.random.default_rng(k * 31 + bits)
    rows = np.zeros((k, P), np.float32)
    rows[:, :dim] = rng.normal(0, clip, (k, dim)).astype(np.float32)
    rows = jnp.asarray(rows)
    masks = secure_agg.mask_rows(jax.random.PRNGKey(3), k, P)
    expect = np.asarray(_staged_compress(rows, masks, clip, bits, dim))

    pallas = compress_mod.clip_quant_mask(rows, masks, clip, bits, dim=dim,
                                          interpret=True)
    assert pallas.shape == (k, P) and pallas.dtype == jnp.uint32
    assert_within_one_step(pallas, expect)
    assert_within_one_step(ref.clip_quant_mask_ref(rows, masks, clip, bits, dim=dim), expect)
    # the public dispatcher (CPU -> fused XLA, TPU -> Mosaic) agrees too
    assert_within_one_step(ops.clip_quant_mask(rows, masks, clip, bits, dim=dim), expect)


def test_clip_quant_mask_roundtrips_through_masked_agg():
    """compress -> masked_aggregate recovers the clipped float sum within
    the ring's quantization error (the full wire round trip)."""
    k, dim, P, clip, bits = 6, 3000, 4096, 1.0, 20
    rng = np.random.default_rng(0)
    rows = np.zeros((k, P), np.float32)
    rows[:, :dim] = rng.normal(0, 0.05, (k, dim)).astype(np.float32)
    rows = jnp.asarray(rows)
    masks = secure_agg.mask_rows(jax.random.PRNGKey(5), k, P)
    cipher = ops.clip_quant_mask(rows, masks, clip, bits, dim=dim)
    dec = np.asarray(ops.masked_aggregate(cipher, masks, clip, bits))
    clipped, _ = dp_mod.clip_rows(rows[:, :dim], clip)
    bound = quantize.quant_error_bound(clip, bits) * k + 1e-6
    np.testing.assert_allclose(dec[:dim], np.asarray(clipped).sum(0), atol=bound)


def test_clip_quant_mask_validates_shapes():
    rows = jnp.zeros((2, 64), jnp.float32)
    with pytest.raises(ValueError, match="masks shape"):
        compress_mod.clip_quant_mask(rows, jnp.zeros((3, 64), jnp.uint32), 1.0, 16)
    with pytest.raises(ValueError, match="dim"):
        compress_mod.clip_quant_mask(rows, jnp.zeros((2, 64), jnp.uint32), 1.0, 16, dim=65)


def test_compress_traffic_roofline_model():
    """The bandwidth argument for the fused kernel: 7 vs 4 HBM traversals
    (the norm pass reads the rows once more), and the wire pricing matches
    ``upload_bytes_per_client`` semantics."""
    from repro.roofline.analysis import compress_traffic

    t = compress_traffic(k=16, P=262144, bits=18)
    assert t["staged_hbm_bytes"] == 7 * 16 * 262144 * 4.0
    assert t["fused_hbm_bytes"] == 4 * 16 * 262144 * 4.0
    assert t["predicted_speedup"] == pytest.approx(7 / 4)
    assert t["fused_s"] < t["staged_s"]
    # dense ring: bit-packed values only, no index stream
    assert t["wire_bytes_per_client"] == 262144 * 18 / 8.0
    sp = compress_traffic(k=16, P=262144, bits=18, density=0.05)
    kept = round(0.05 * 262144)
    assert sp["wire_bytes_per_client"] == kept * 18 / 8.0 + kept * 4.0
    assert sp["wire_vs_float32"] < 0.1
    with pytest.raises(ValueError, match="density"):
        compress_traffic(4, 1024, density=0.0)
    with pytest.raises(ValueError, match="k, P"):
        compress_traffic(0, 1024)
