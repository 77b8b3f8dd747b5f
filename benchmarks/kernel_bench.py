"""Kernel micro-benchmarks: Pallas (interpret) vs pure-jnp reference.

Wall-times on this CPU container measure the *interpreter*, not TPU perf —
the derived column therefore reports the roofline-relevant quantities
(working-set bytes per VMEM block, arithmetic intensity) rather than a
speedup claim.  Correctness (allclose vs oracle) is asserted on every case.

Besides the human-readable ``name,us_per_call,derived`` CSV, every run
appends machine-readable records and ``main()`` writes them to
``BENCH_kernels.json`` (op, shape, backend, ms, GB/s) so the perf
trajectory stays diffable across PRs; CI uploads the file as an artifact.

The aggregation benches exercise the kernels on the flat-row
representation the FL runtime actually dispatches: ``(k, P)`` float32 /
uint32 rows built through ``repro.fl.paramspace.ParamSpace`` (stack +
block padding), not ad-hoc arrays.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import check_regression as common_check_regression
from benchmarks.common import csv_line
from repro.fl.paramspace import ParamSpace
from repro.kernels import compress as compress_mod
from repro.kernels import ops, ref
from repro.privacy import dp as dp_mod
from repro.privacy import quantize, secure_agg
from repro.topo import graph as topo_graph

RECORDS: list[dict] = []


def _kernel_interpret() -> bool:
    """The ``interpret`` flag that times the Pallas kernel itself: off the
    TPU the wrappers would otherwise run the XLA reference."""
    return ops.default_interpret()


def _backend(kernel: bool) -> str:
    base = jax.default_backend()
    if kernel:
        mode = "pallas-interpret" if _kernel_interpret() else "pallas-mosaic"
        return f"{base}:{mode}"
    return f"{base}:xla-ref"


def _record(op: str, shape, us: float, bytes_moved: float, kernel: bool,
            backend: str | None = None) -> None:
    RECORDS.append({
        "op": op,
        "shape": list(shape),
        "backend": backend if backend is not None else _backend(kernel),
        "ms": us / 1e3,
        "gb_per_s": bytes_moved / (us * 1e-6) / 1e9 if us > 0 else 0.0,
    })


def _time(fn, *args, reps=3):
    fn(*args)  # compile
    t0 = time.time()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.time() - t0) / reps * 1e6


def _row_space(P: int, seed: int) -> ParamSpace:
    """A ParamSpace whose flat dim is exactly P (a tree of 1-D chunks) —
    the benches go through stack()/pad_rows() like the FL engines do."""
    sizes, left, i = [], P, 0
    rng = np.random.default_rng(seed)
    while left > 0:
        s = min(left, int(rng.integers(1000, 50_000)))
        sizes.append(s)
        left -= s
        i += 1
    tree = {f"leaf{j}": jnp.zeros((s,), jnp.float32) for j, s in enumerate(sizes)}
    return ParamSpace.build(tree)


def _stacked_rows(pspace: ParamSpace, k: int, seed: int) -> jax.Array:
    rng = np.random.default_rng(seed)
    stacked = {
        f"leaf{j}": jnp.asarray(rng.normal(0, 0.05, (k, s)).astype(np.float32))
        for j, s in enumerate(pspace.sizes)
    }
    return pspace.stack(stacked)


def bench_flash(B=1, T=512, H=4, K=2, hd=64, block=128):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, T, H, hd))
    k = jax.random.normal(ks[1], (B, T, K, hd))
    v = jax.random.normal(ks[2], (B, T, K, hd))
    interp = _kernel_interpret()
    out = ops.flash_attention(q, k, v, causal=True, block_q=block, block_k=block,
                              interpret=interp)
    expect = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=5e-5, rtol=5e-5)
    us_k = _time(lambda: ops.flash_attention(q, k, v, causal=True, block_q=block, block_k=block,
                                             interpret=interp))
    us_r = _time(lambda: ref.flash_attention_ref(q, k, v, causal=True))
    vmem_kib = (block * 128 * 4 * 2 + 2 * block * 128 * 4 + block * (128 + 2) * 4) / 1024
    flops = 4 * B * H * T * T * hd / 2  # causal
    bytes_moved = 2 * B * T * (H + 2 * K) * hd * 4
    ai = flops / bytes_moved
    _record("flash_attention", (B, T, H, hd), us_k, bytes_moved, kernel=True)
    _record("flash_attention", (B, T, H, hd), us_r, bytes_moved, kernel=False)
    rows = [
        csv_line(f"flash_attn_pallas_T{T}", us_k, f"vmem_block_kib={vmem_kib:.0f};arith_intensity={ai:.0f}"),
        csv_line(f"flash_attn_xla_ref_T{T}", us_r, "materializes_TxT=1"),
    ]
    return rows


def bench_masked_agg(n=16, P=262144, bits=16):
    """Secure-agg hot path on ParamSpace rows: unmask + dequantize fused."""
    pspace = _row_space(P, seed=n)
    ups = _stacked_rows(pspace, n, seed=0)
    qs = quantize.encode(pspace.pad_rows(ups), 1.0, bits)
    masks = secure_agg.mask_rows(jax.random.PRNGKey(7), n, pspace.padded_dim)
    masked = qs + masks
    interp = _kernel_interpret()
    out = ops.masked_aggregate(masked, masks, 1.0, bits, interpret=interp)
    expect = ref.masked_aggregate_ref(masked, masks, 1.0, bits)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=1e-6)
    us_k = _time(lambda: ops.masked_aggregate(masked, masks, 1.0, bits, interpret=interp))
    us_r = _time(lambda: ref.masked_aggregate_ref(masked, masks, 1.0, bits))
    Pp = pspace.padded_dim
    bytes_moved = 2 * n * Pp * 4 + Pp * 4
    _record("masked_agg", (n, Pp), us_k, bytes_moved, kernel=True)
    _record("masked_agg", (n, Pp), us_r, bytes_moved, kernel=False)
    return [
        csv_line(f"masked_agg_pallas_n{n}_P{Pp}", us_k, f"bytes={bytes_moved};fused_unmask_dequant=1"),
        csv_line(f"masked_agg_xla_ref_n{n}_P{Pp}", us_r, "separate_pass=1"),
    ]


def bench_staleness_agg(k=16, P=262144):
    """Async-runtime hot path: Σ_i w_i·row_i over the K-deep rows buffer."""
    pspace = _row_space(P, seed=k)
    deltas = pspace.pad_rows(_stacked_rows(pspace, k, seed=1))
    rng = np.random.default_rng(1)
    taus = rng.integers(0, 8, k)
    weights = jnp.asarray((1.0 / np.sqrt(1.0 + taus)).astype(np.float32))
    interp = _kernel_interpret()
    out = ops.staleness_aggregate(deltas, weights, interpret=interp)
    expect = ref.staleness_aggregate_ref(deltas, weights)
    err = float(np.max(np.abs(np.asarray(out) - np.asarray(expect))))
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=1e-5)
    us_k = _time(lambda: ops.staleness_aggregate(deltas, weights, interpret=interp))
    us_r = _time(lambda: ref.staleness_aggregate_ref(deltas, weights))
    Pp = pspace.padded_dim
    bytes_moved = k * Pp * 4 + Pp * 4
    _record("staleness_agg", (k, Pp), us_k, bytes_moved, kernel=True)
    _record("staleness_agg", (k, Pp), us_r, bytes_moved, kernel=False)
    return [
        csv_line(
            f"staleness_agg_pallas_k{k}_P{Pp}", us_k,
            f"bytes={bytes_moved};parity_max_abs_err={err:.2e};"
            f"ref_over_kernel_speedup={us_r / us_k:.2f}x",
        ),
        csv_line(f"staleness_agg_xla_ref_k{k}_P{Pp}", us_r, "einsum_reference=1"),
    ]


def bench_gossip_mix(k=16, P=262144, graph="torus"):
    """Decentralized-strategy hot path: one X <- W X mixing pass over the
    cohort's (k, P) node-model rows (Metropolis weights on ``graph``)."""
    pspace = _row_space(P, seed=k)
    rows_x = pspace.pad_rows(_stacked_rows(pspace, k, seed=2))
    W = jnp.asarray(topo_graph.plan(graph, k, seed=0).mixing)
    interp = _kernel_interpret()
    out = ops.gossip_mix(rows_x, W, interpret=interp)
    expect = ref.gossip_mix_ref(rows_x, W)
    # different accumulation order from the matmul oracle: a few float32 ulps
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=1e-6, atol=1e-6)
    us_k = _time(lambda: ops.gossip_mix(rows_x, W, interpret=interp))
    us_r = _time(lambda: ref.gossip_mix_ref(rows_x, W))
    Pp = pspace.padded_dim
    bytes_moved = 2 * k * Pp * 4 + k * k * 4  # X read + written, W rides in VMEM
    _record("gossip_mix", (k, Pp), us_k, bytes_moved, kernel=True)
    _record("gossip_mix", (k, Pp), us_r, bytes_moved, kernel=False)
    gap = topo_graph.spectral_gap(np.asarray(W))
    return [
        csv_line(
            f"gossip_mix_pallas_{graph}_k{k}_P{Pp}", us_k,
            f"bytes={bytes_moved};spectral_gap={gap:.3f};allclose_vs_ref=1",
        ),
        csv_line(f"gossip_mix_xla_ref_{graph}_k{k}_P{Pp}", us_r, "matmul_reference=1"),
    ]


def bench_compress(k=16, P=262144, bits=18, clip=1.0):
    """Delta-to-wire hot path: fused clip+quantize+mask vs the staged stage
    sequence (three separate dispatches with materialized intermediates —
    exactly what ClipStage -> QuantizeStage -> MaskStage do per aggregate).

    Both rows carry the SAME ``bytes_moved`` — the fused path's useful
    traffic (rows read + pads read + ciphertext write) — so ``gb_per_s`` is
    *delivered* bandwidth and its ordering equals the wall-time ordering:
    the fused entry beats the staged one iff it is actually faster.  The
    staged path additionally materializes ~3 more row-block traversals
    (see ``repro.roofline.analysis.compress_traffic``).  Outputs are
    asserted to decode within one quantization step of each other before
    timing (the two norm reductions are different programs).
    """
    pspace = _row_space(P, seed=k)
    rows_f = _stacked_rows(pspace, k, seed=3)
    Pp = pspace.padded_dim
    masks = secure_agg.mask_rows(jax.random.PRNGKey(11), k, Pp)

    def staged(rows, masks):
        # the three stage dispatches, one jit boundary each, as the pipeline runs them
        clipped, _ = dp_mod.clip_rows(rows, clip)
        q = quantize.encode(pspace.pad_rows(clipped), clip, bits)
        return q + masks

    fused = ops.clip_quant_mask(rows_f, masks, clip, bits, dim=pspace.dim)
    expect = staged(rows_f, masks)
    steps = (np.asarray(fused) - np.asarray(expect)).view(np.int32)
    assert np.abs(steps).max() <= 1, "fused and staged ciphertexts differ by > 1 step"
    us_f = _time(lambda: ops.clip_quant_mask(rows_f, masks, clip, bits, dim=pspace.dim))
    us_s = _time(lambda: staged(rows_f, masks))
    base = jax.default_backend()
    bytes_moved = 3 * k * Pp * 4  # rows in + pads in + ciphertext out
    _record("compress", (k, Pp), us_f, bytes_moved, kernel=True, backend=f"{base}:fused")
    _record("compress", (k, Pp), us_s, bytes_moved, kernel=False, backend=f"{base}:staged")
    out = [
        csv_line(
            f"compress_fused_k{k}_P{Pp}", us_f,
            f"bytes={bytes_moved};bits={bits};within_one_step_of_staged=1;"
            f"staged_over_fused_speedup={us_s / us_f:.2f}x",
        ),
        csv_line(f"compress_staged_k{k}_P{Pp}", us_s, "three_dispatches=1"),
    ]
    if ops.default_interpret() and k <= 8 and Pp <= 65536:
        # the Pallas interpreter is ~100x XLA on CPU: time it at the small
        # shape only, for parity visibility (not recorded — TPU runs record
        # the Mosaic kernel through the fused entry above)
        us_i = _time(
            lambda: compress_mod.clip_quant_mask(
                pspace.pad_rows(rows_f), masks, clip, bits,
                dim=pspace.dim, interpret=True,
            ),
            reps=1,
        )
        out.append(csv_line(f"compress_pallas_interp_k{k}_P{Pp}", us_i,
                            "interpreter_parity_only=1"))
    return out


# ---------------------------------------------------------------------------
def check_regression(baseline: list[dict], max_drop: float = 0.30) -> list[str]:
    """Compare RECORDS against a committed baseline (the parsed JSON list):
    any (op, shape, backend) whose GB/s dropped more than ``max_drop`` — or
    disappeared from the bench — fails.  New ops absent from the baseline
    pass (the refreshed JSON picks them up).  Delegates to the shared gate
    in ``benchmarks.common`` (``engine_bench`` runs the same one over
    events/sec)."""
    return common_check_regression(
        RECORDS, baseline, metric="gb_per_s", max_drop=max_drop
    )


def main(out_json: str | None = "BENCH_kernels.json"):
    RECORDS.clear()
    rows = []
    rows += bench_flash(T=256)
    rows += bench_flash(T=512)
    rows += bench_masked_agg(n=8, P=65536)
    rows += bench_masked_agg(n=16, P=262144)
    rows += bench_staleness_agg(k=8, P=65536)
    rows += bench_staleness_agg(k=16, P=262144)
    rows += bench_gossip_mix(k=8, P=65536, graph="ring")
    rows += bench_gossip_mix(k=16, P=262144, graph="torus")
    rows += bench_compress(k=8, P=65536)
    rows += bench_compress(k=16, P=262144)
    for r in rows:
        print(r)
    if out_json:
        with open(out_json, "w") as f:
            json.dump(RECORDS, f, indent=1)
        print(f"wrote {len(RECORDS)} records -> {out_json}")
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default="BENCH_kernels.json",
                    help="machine-readable output path ('' disables)")
    ap.add_argument("--check", default=None, metavar="BASELINE",
                    help="regression mode: fail (exit 1) if any op's GB/s "
                         "drops >30%% vs this committed baseline JSON")
    args = ap.parse_args()
    baseline = None
    if args.check:
        # read BEFORE main(), which may rewrite the same path via --json
        with open(args.check) as f:
            baseline = json.load(f)
    main(out_json=args.json or None)
    if baseline is not None:
        failures = check_regression(baseline)
        if failures:
            print(f"PERF REGRESSION vs {args.check}:")
            for f in failures:
                print(f"  {f}")
            raise SystemExit(1)
        print(f"perf check vs {args.check}: OK ({len(RECORDS)} records)")
