"""Composable row-native privacy pipeline (paper §III-C).

The legacy engines hard-coded one aggregation chain in
``Simulation._aggregate`` (clip → quantize → mask → kernel-sum → noise) with
the composition decided by two config flags.  Here the chain is a
:class:`PrivacyPipeline` of explicit stages over ``ParamSpace`` rows:

    TopKStage      error-feedback top-k sparsification             [rows]
    ClipStage      per-client L2 clip (DP sensitivity bound)       [rows]
    ScaleStage     pre-scale rows by k·(n_i/Σn) (weighted masking) [rows]
    QuantizeStage  fixed-point encode into the uint32 ring         [rows]
    MaskStage      per-client one-time pads (dealer model)         [rows]
    NoiseStage     server-side Gaussian mechanism on the sum       [sum]

    FusedCompressStage = ClipStage→QuantizeStage→MaskStage collapsed into
    the ``clip_quant_mask`` kernel: a norm pass plus one P-tiled encode
    pass, ciphertexts within one quantization step of the staged ones.  It
    records the *same three* ``StageRecord``s (clip/quantize/mask), so the
    accountant and every records consumer cannot tell the paths apart.
    ``fuse_pipeline`` rewrites any matching composition;  ``build_pipeline``
    applies it by default (``PrivacyConfig.fuse=False`` opts out).

The executor applies row-scope stages in order, reduces (the fused
``masked_agg`` Pallas kernel when the rows were masked, a plain ring sum
when only quantized, the weighted-sum kernel otherwise), applies sum-scope
stages, and rescales to the mean.  Every stage appends a
:class:`StageRecord` to the call's :class:`AggregationContext`, so the
accountant (``privacy.accountant.SubsampledAccountant``) and the engines see
exactly what ran — the per-region DP accounting is driven entirely by the
``NoiseStage`` records.

``build_pipeline`` maps a :class:`~repro.api.config.PrivacyConfig` onto the
three canonical compositions (plain / secure-agg / DP), reproducing the
legacy chains (bit-for-bit unfused; fused, the DP ciphertexts within one
quantization step); hand-compose stages for anything else, e.g.
central DP without masking::

    PrivacyPipeline((ClipStage(1.0), NoiseStage(dp_cfg)), weighting="uniform")
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.fl.paramspace import ParamSpace
from repro.kernels import ops as kernel_ops
from repro.privacy import dp as dp_mod
from repro.privacy import quantize, secure_agg
from repro.privacy.dp import DPConfig


@dataclasses.dataclass(frozen=True)
class StageRecord:
    """What one stage did in one aggregate call (static metadata only)."""

    stage: str
    info: dict


class AggregationContext:
    """Per-call scratch shared along the pipeline.

    Carries the experiment's ``ParamSpace``, the cohort size/weights, the
    independent PRNG streams for masks and noise, and the engine's
    kernel-aware weighted-sum reduction.  Stages communicate through it:
    ``QuantizeStage`` sets ``ring``, ``MaskStage`` deposits the pad block
    the reducer needs for unmasking, and every stage appends its record.
    """

    def __init__(
        self,
        pspace: ParamSpace,
        k: int,
        weights,
        key_mask,
        key_noise,
        weighted_sum: Callable,
        clients=None,
        residuals: Optional[jax.Array] = None,
    ):
        self.pspace = pspace
        self.k = int(k)
        self.weights = np.asarray(weights, np.float64)
        self.key_mask = key_mask
        self.key_noise = key_noise
        self.weighted_sum = weighted_sum
        # cohort identity + the EF residual bank: TopKStage reads the rows
        # for ``clients`` out of ``residuals`` ((n_clients, dim), strategy
        # state) and writes the updated bank back here; the RuntimeContext
        # commits it after the aggregate call.
        self.clients = None if clients is None else np.asarray(clients, np.int32)
        self.residuals = residuals
        self.ring: Optional[tuple[float, int]] = None  # (clip, bits) once quantized
        self.masks: Optional[jax.Array] = None
        self.records: list[StageRecord] = []
        # normalized once: the round loop reads this per stage AND per
        # reduction, and re-normalizing on every property access was a
        # measurable constant in the hot loop
        self._norm_weights = jnp.asarray(
            self.weights / np.sum(self.weights), jnp.float32
        )

    @property
    def norm_weights(self) -> jax.Array:
        """(k,) float32 data-size weights normalized to sum 1 (Eq. 6)."""
        return self._norm_weights

    def record(self, stage: str, **info) -> None:
        self.records.append(StageRecord(stage, info))


# ---------------------------------------------------------------------------
# Stages
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TopKStage:
    """Error-feedback top-k sparsification (EF-SGD / memory-feedback line).

    Each client keeps only the ``density·dim`` largest-magnitude coordinates
    of (delta + residual) and banks the rest as its residual for the next
    participation, so nothing is ever dropped — only delayed.  Exact
    invariant (what the Hypothesis property pins):

        sparse + residual_new = delta + residual_old       (per row)

    The residual bank lives as ParamSpace rows in ``RuntimeContext`` state
    ((n_clients, dim) float32), so it checkpoints and resumes bitwise with
    the rest of the federation state.  Without a wired bank (hand-composed
    pipelines outside a strategy) the stage degrades to plain one-shot
    top-k (zero residual in, feedback discarded).

    Placed *before* ClipStage: the clip then bounds the sensitivity of what
    actually leaves the client (the sparse row), keeping DP accounting
    untouched, and leaves the clip→quantize→mask suffix contiguous for
    ``fuse_pipeline``.  The record carries (density, k_kept, index_bits) —
    what wire-byte accounting needs to price the index+value encoding.
    """

    density: float
    name = "topk"
    scope = "rows"

    def __post_init__(self):
        if not (0.0 < self.density <= 1.0):
            raise ValueError(f"topk density must be in (0, 1], got {self.density}")

    def apply(self, rows: jax.Array, ctx: AggregationContext) -> jax.Array:
        dim = rows.shape[1]
        k_keep = max(1, int(round(self.density * dim)))
        if ctx.residuals is not None:
            if ctx.clients is None:
                raise ValueError(
                    "TopKStage has a residual bank but no cohort client ids; "
                    "pass clients= to RuntimeContext.aggregate"
                )
            corrected = rows + ctx.residuals[ctx.clients]
        else:
            corrected = rows
        # exact-k selection: scatter the top-k *indices* (distinct per row)
        # rather than thresholding on the k-th value, so ties never widen
        # the payload past what the wire record claims
        _, idx = jax.lax.top_k(jnp.abs(corrected), k_keep)
        keep = (
            jnp.zeros(corrected.shape, bool)
            .at[jnp.arange(corrected.shape[0])[:, None], idx]
            .set(True)
        )
        sparse = jnp.where(keep, corrected, 0.0)
        if ctx.residuals is not None:
            # duplicate cohort entries for one client (possible in async
            # flushes) follow scatter semantics: one entry's feedback wins
            ctx.residuals = ctx.residuals.at[ctx.clients].set(corrected - sparse)
        ctx.record(self.name, density=self.density, k_kept=k_keep, index_bits=32)
        return sparse


@dataclasses.dataclass(frozen=True)
class ClipStage:
    """Per-client L2 clip of the delta rows — the DP sensitivity bound."""

    clip: float
    name = "clip"
    scope = "rows"

    def apply(self, rows: jax.Array, ctx: AggregationContext) -> jax.Array:
        clipped, _ = dp_mod.clip_rows(rows, self.clip)
        ctx.record(self.name, clip=self.clip)
        return clipped


@dataclasses.dataclass(frozen=True)
class ScaleStage:
    """Pre-scale rows by k·(n_i/Σn): data-size weighting pushed client-side
    so the masked ring sum / k is the weighted mean (secure-agg path)."""

    name = "scale"
    scope = "rows"

    def apply(self, rows: jax.Array, ctx: AggregationContext) -> jax.Array:
        w = ctx.norm_weights
        ctx.record(self.name, mode="data_size")
        return rows * (w * ctx.k)[:, None]


@dataclasses.dataclass(frozen=True)
class QuantizeStage:
    """Fixed-point encode into the uint32 ring (pads rows to whole kernel
    blocks first, exactly as the fused kernels expect)."""

    clip: float
    bits: int
    name = "quantize"
    scope = "rows"

    def apply(self, rows: jax.Array, ctx: AggregationContext) -> jax.Array:
        quantize.check_headroom(self.bits, ctx.k)
        rows = ctx.pspace.pad_rows(rows)
        ctx.ring = (self.clip, self.bits)
        ctx.record(self.name, clip=self.clip, bits=self.bits)
        return quantize.encode(rows, self.clip, self.bits)


@dataclasses.dataclass(frozen=True)
class MaskStage:
    """Add per-client one-time pads (dealer model); the reducer unmasks via
    the fused ``masked_agg`` kernel, which only ever sees ciphertexts."""

    name = "mask"
    scope = "rows"

    def apply(self, rows: jax.Array, ctx: AggregationContext) -> jax.Array:
        if ctx.ring is None:
            raise ValueError("MaskStage requires a QuantizeStage before it "
                             "(one-time pads live in the uint32 ring)")
        ctx.masks = secure_agg.mask_rows(ctx.key_mask, ctx.k, rows.shape[1])
        ctx.record(self.name, ring_bits=quantize.RING_BITS)
        return rows + ctx.masks  # uint32 wraps = mod 2^32


@dataclasses.dataclass(frozen=True)
class FusedCompressStage:
    """ClipStage → QuantizeStage → MaskStage as ONE pass over the rows.

    Dispatches the fused ``clip_quant_mask`` kernel (``kernels/compress.py``):
    per-row L2 norm + clip factor, then fixed-point ring encode + one-time
    pad in one P-tiled pass — four HBM traversals of the cohort block where
    the staged composition makes seven.  Its ciphertexts decode within one
    quantization step of the staged stages' (pinned by
    tests/test_property.py), and it records the *same three*
    ``StageRecord``s in the same order, so DP accounting and wire-byte
    pricing are unchanged by the fusion.
    """

    clip: float
    bits: int
    name = "fused_compress"
    names = ("clip", "quantize", "mask")  # what this stage stands in for
    scope = "rows"

    def apply(self, rows: jax.Array, ctx: AggregationContext) -> jax.Array:
        quantize.check_headroom(self.bits, ctx.k)
        ctx.record("clip", clip=self.clip)
        rows = ctx.pspace.pad_rows(rows)
        ctx.ring = (self.clip, self.bits)
        ctx.record("quantize", clip=self.clip, bits=self.bits)
        ctx.masks = secure_agg.mask_rows(ctx.key_mask, ctx.k, rows.shape[1])
        ctx.record("mask", ring_bits=quantize.RING_BITS)
        return kernel_ops.clip_quant_mask(
            rows, ctx.masks, self.clip, self.bits, dim=ctx.pspace.dim
        )


@dataclasses.dataclass(frozen=True)
class NoiseStage:
    """Server-side Gaussian mechanism on the summed clipped rows.

    Its record carries (sigma, clip, delta) — the exact metadata the
    subsampled-RDP accountant composes per region.
    """

    dp: DPConfig
    name = "noise"
    scope = "sum"

    def apply(self, summed: jax.Array, ctx: AggregationContext) -> jax.Array:
        ctx.record(self.name, sigma=self.dp.sigma, clip=self.dp.clip,
                   delta=self.dp.delta, mechanism="gaussian")
        return dp_mod.add_noise(ctx.key_noise, summed, self.dp)


# ---------------------------------------------------------------------------
# Pipeline
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PrivacyPipeline:
    """An ordered stage composition plus the aggregation weighting.

    ``weighting``: how un-quantized rows are reduced —
      * ``"data"``     Σ (n_i/Σn)·row_i (the plain Eq. 6 weighted mean);
      * ``"uniform"``  Σ row_i, then /k after the sum-scope stages (the DP
        mean: the clip bounds per-client sensitivity of the *sum*).
    Ring reductions (after ``QuantizeStage``) always sum and divide by k;
    data-size weighting there is ``ScaleStage``'s job.
    """

    stages: tuple = ()
    weighting: str = "data"  # data | uniform

    def __post_init__(self):
        if self.weighting not in ("data", "uniform"):
            raise ValueError(f"unknown weighting {self.weighting!r}")
        # declared order IS execution order: row-scope stages run before the
        # reduction, sum-scope after, so a sum stage ahead of a row stage
        # would execute in a different order than describe() reports
        scopes = [s.scope for s in self.stages]
        if "sum" in scopes and "rows" in scopes[scopes.index("sum"):]:
            raise ValueError(
                "row-scope stages must precede sum-scope stages "
                f"(got {[s.name for s in self.stages]})"
            )

    def describe(self) -> list[str]:
        """Logical stage names: fused stages expand to what they stand in
        for (``FusedCompressStage`` -> clip, quantize, mask), so a fused
        pipeline describes — like it records — exactly as the staged one."""
        return [n for s in self.stages for n in getattr(s, "names", (s.name,))]

    def aggregate(self, rows: jax.Array, ctx: AggregationContext) -> jax.Array:
        """(k, P) delta rows -> (P,) MEAN row, recording every stage."""
        row_stages = [s for s in self.stages if s.scope == "rows"]
        sum_stages = [s for s in self.stages if s.scope == "sum"]
        for stage in row_stages:
            rows = stage.apply(rows, ctx)

        if ctx.ring is not None:
            clip, bits = ctx.ring
            if ctx.masks is not None:
                # fused unmask + dequantize + sum in one VMEM pass
                dec = kernel_ops.masked_aggregate(rows, ctx.masks, clip, bits)
            else:  # quantized but unmasked: plain ring sum + decode
                dec = quantize.decode_sum(
                    jnp.sum(rows, axis=0, dtype=jnp.uint32), clip, bits, ctx.k
                )
            summed = dec[: ctx.pspace.dim]
            mean_scale = 1.0 / ctx.k
        elif self.weighting == "uniform":
            summed = ctx.weighted_sum(rows, jnp.ones((ctx.k,), jnp.float32))
            mean_scale = 1.0 / ctx.k
        else:
            summed = ctx.weighted_sum(rows, ctx.norm_weights)
            mean_scale = 1.0

        for stage in sum_stages:
            summed = stage.apply(summed, ctx)
        return summed if mean_scale == 1.0 else summed * mean_scale


def fuse_pipeline(pipeline: PrivacyPipeline) -> PrivacyPipeline:
    """Collapse every contiguous ClipStage → QuantizeStage → MaskStage run
    (with a shared clip value) into a :class:`FusedCompressStage`.

    Compositions that don't match — scale-based secure-agg, a stage wedged
    between clip and quantize, clip values that disagree — are left on the
    staged path untouched.  The rewrite changes neither ``describe()`` nor
    the emitted ``StageRecord``s; only the number of HBM passes.
    """
    stages = list(pipeline.stages)
    fused: list = []
    i = 0
    while i < len(stages):
        s = stages[i]
        if (
            isinstance(s, ClipStage)
            and i + 2 < len(stages)
            and isinstance(stages[i + 1], QuantizeStage)
            and isinstance(stages[i + 2], MaskStage)
            and stages[i + 1].clip == s.clip
        ):
            fused.append(FusedCompressStage(s.clip, stages[i + 1].bits))
            i += 3
        else:
            fused.append(s)
            i += 1
    if fused == stages:
        return pipeline
    return dataclasses.replace(pipeline, stages=tuple(fused))


def upload_bytes_per_client(records, dim: int) -> float:
    """Wire bytes of ONE client's upload, priced from the stage records.

    The records say exactly what left the client: a ``topk`` record shrinks
    the payload to ``k_kept`` (index, value) pairs; a ``quantize`` record
    prices each value at its ring width (bit-packed) instead of float32.
    No records -> a plain float32 row of ``dim`` values.
    """
    n_values = dim
    value_bits = 32.0  # float32 unless a quantize record says otherwise
    index_bytes = 0.0
    for r in records:
        if r.stage == "topk":
            n_values = int(r.info["k_kept"])
            index_bytes = n_values * r.info["index_bits"] / 8.0
        elif r.stage == "quantize":
            value_bits = float(r.info["bits"])
    return n_values * value_bits / 8.0 + index_bytes


def cohort_wire_bytes(records, cohort: int, model_bytes: float, dim: int) -> float:
    """Total wire traffic of one aggregate call: per client, one full-model
    download (float32) plus the record-priced upload.  With no compression
    records this is exactly the legacy ``2 · cohort · model_bytes``."""
    return cohort * (model_bytes + upload_bytes_per_client(records, dim))


def build_pipeline(privacy) -> PrivacyPipeline:
    """Map a ``PrivacyConfig`` onto the canonical stage compositions.

    Reproduces the legacy ``Simulation._aggregate`` chains exactly:

        dp set       : [topk →] clip → quantize → mask → [kernel sum] → noise, /k
        secure_agg   : [topk →] scale → quantize → mask → [kernel sum], /k
        neither      : [topk →] [weighted-sum kernel]  (plain Eq. 6)

    ``privacy.topk_density > 0`` prepends the EF sparsifier;
    ``privacy.fuse`` (default) then collapses any clip→quantize→mask suffix
    into the one-pass fused kernel — same records, same bits on the wire.
    """
    topk = (TopKStage(privacy.topk_density),) if privacy.topk_density else ()
    if privacy.dp is not None:
        dp = privacy.dp
        pipe = PrivacyPipeline(
            stages=topk + (ClipStage(dp.clip), QuantizeStage(dp.clip, dp.bits),
                           MaskStage(), NoiseStage(dp)),
            weighting="uniform",
        )
        return fuse_pipeline(pipe) if privacy.fuse else pipe
    if privacy.secure_agg:
        return PrivacyPipeline(
            stages=topk + (ScaleStage(),
                           QuantizeStage(privacy.sa_clip, privacy.sa_bits),
                           MaskStage()),
            weighting="uniform",
        )
    if topk:
        return PrivacyPipeline(stages=topk, weighting="data")
    return PrivacyPipeline()
