"""Chip smoke: the paper's federation at full ResNet-Tiny width on a TPU.

    python chip_smoke.py              # one chip: phases A, B and C
    python chip_smoke.py --chips 4    # four chips: the sharded cohort path only

Drives ``repro.api.Federation`` as a user does, at the paper's protocol
(CIFAR-10-shaped data, Dirichlet(0.5) label skew over 50 clients, 10 per
round, batch 32) with the paper's client model at full width
(``configs/resnet_tiny.CONFIG``, P = 4,696,394).  Local rounds are cut to
2 steps (the paper trains 5 epochs) because local steps unroll into the
compiled cohort trainer.  Data and weights are random, made from ``--seed``.

  A  the main path: ``sync`` rounds, ``rl_green`` selection, secure
     aggregation (``masked_agg`` kernel), 3 rounds.  One round's decoded
     mean must lie within k quantization steps of the float mean of the
     same rows.
  B  the DP path: clip + quantize + mask (``compress`` kernel), then
     ``masked_agg`` and Gaussian noise, 2 rounds; epsilon must be spent.
  C  the other main-path kernels, ``staleness_agg`` and ``gossip_mix``, at
     (10, padded P) against their oracles in ``kernels/ref.py``.

The compiled HLO of every kernel call must hold ``tpu_custom_call``: the
kernel ran as Mosaic, not in interpret mode and not as the einsum the
runtime uses off the TPU.  ``--chips 4`` runs only
``TrainingConfig(sharded=True)`` over a 4-device ``data`` mesh and compares
its first round's cohort rows with the unsharded trainer on one device.

Without a TPU the script exits non-zero before any phase.  The times it
prints are smoke timings of one run, not a benchmark.  The last line of
stdout is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import api  # noqa: E402
from repro.configs.resnet_tiny import CONFIG  # noqa: E402
from repro.data.partition import dirichlet_partition  # noqa: E402
from repro.data.pipeline import build_clients  # noqa: E402
from repro.data.synthetic import CIFAR_LIKE, make_image_dataset  # noqa: E402
from repro.fl import client as client_mod  # noqa: E402
from repro.fl.paramspace import ParamSpace  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.models.resnet import init_resnet, resnet_loss  # noqa: E402
from repro.privacy import quantize  # noqa: E402
from repro.privacy.dp import DPConfig, calibrated  # noqa: E402
from repro.topo import graph as graph_mod  # noqa: E402
from repro.utils import enable_compile_cache  # noqa: E402

# the paper's protocol (benchmarks/common.py), local steps cut as above
N_CLIENTS, PER_ROUND, BATCH, LOCAL_STEPS = 50, 10, 32, 2
# float32 sums of 10 terms taken in different orders by kernel and oracle
KERNEL_RTOL = KERNEL_ATOL = 1e-5
# sharded vs unsharded rows: the same per-client math in programs of
# different cohort shapes (3 clients per device against 10 on one).  On the
# TPU a float32 convolution is by default one bf16 pass (relative rounding
# 2^-9), and programs that tile it differently can round an input a last
# float32 ulp apart to the other bf16 neighbour; two local steps carry such
# flips into the deltas.  So the bound is a few bf16 roundings of the
# largest entry of the rows (on the CPU the two agree bitwise).
SHARDED_REL_TOL = 1e-2
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileClock:
    """Sums the XLA backend-compile seconds that JAX reports."""

    def __init__(self):
        self.total = 0.0
        self._mark = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.total += duration

    def lap(self) -> float:
        """Compile seconds since the previous lap."""
        lap, self._mark = self.total - self._mark, self.total
        return lap


def require_mosaic(name: str, kernel, *args, **kw) -> None:
    """The compiled program of ``kernel(*args)`` holds a Mosaic kernel."""
    text = kernel.lower(*args, **kw).compile().as_text()
    check("tpu_custom_call" in text, f"{name}: no tpu_custom_call in its compiled HLO")
    print(f"  {name}: tpu_custom_call in compiled HLO")


def build_task(seed: int, rcfg=CONFIG, n_train=None, n_test=None) -> api.FederatedTask:
    data = make_image_dataset(CIFAR_LIKE, seed=seed, n_train=n_train, n_test=n_test)
    parts = dirichlet_partition(data["train"]["label"], N_CLIENTS, alpha=0.5, seed=seed)
    return api.FederatedTask(
        loss_fn=lambda p, b: resnet_loss(p, rcfg, b),
        eval_fn=lambda p, b: resnet_loss(p, rcfg, b)[1],
        params0=init_resnet(jax.random.PRNGKey(seed), rcfg),
        clients=build_clients(data["train"], parts, seed=seed),
        test_data=data["test"],
    )


def experiment(seed: int, rounds: int, *, dp=None, sharded=False) -> api.ExperimentConfig:
    return api.ExperimentConfig(
        training=api.TrainingConfig(
            algorithm="fedavg", n_clients=N_CLIENTS, clients_per_round=PER_ROUND,
            rounds=rounds, local_steps=LOCAL_STEPS, batch_size=BATCH,
            sharded=sharded, seed=seed,
        ),
        privacy=api.PrivacyConfig(secure_agg=True, dp=dp),
        orchestrator=api.OrchestratorConfig(selection="rl_green"),
    )


def run_federation(name: str, cfg, task, clock: CompileClock):
    """Build and run one Federation; returns (history, first aggregate
    call's inputs and outputs, the federation).  Prints smoke timings."""
    clock.lap()
    t0 = time.perf_counter()
    fed = api.Federation(cfg, task)
    build_s = time.perf_counter() - t0
    first: dict = {}
    aggregate = fed.ctx.aggregate

    def aggregate_keeping_first(rows, weights, key, clients=None):
        mean_row, records = aggregate(rows, weights, key, clients=clients)
        if not first:
            first.update(rows=rows, weights=weights, clients=clients,
                         mean_row=mean_row, records=records)
        return mean_row, records

    fed.ctx.aggregate = aggregate_keeping_first
    stamps = [time.perf_counter()]
    hist = fed.run(progress=lambda _row: stamps.append(time.perf_counter()))
    del fed.ctx.aggregate  # the wrapper closes a reference cycle through ctx
    walls = [round(b - a, 3) for a, b in zip(stamps, stamps[1:])]
    print(f"phase {name} smoke timing (one run, not a benchmark): "
          f"Federation build {build_s:.1f} s, XLA compile {clock.lap():.1f} s, "
          f"wall s per round {walls} (round 1 includes the first eval and compiles)")
    rounds = cfg.training.rounds
    check(len(hist["loss"]) == rounds, f"phase {name}: {len(hist['loss'])} of {rounds} rounds ran")
    check(all(math.isfinite(v) for v in hist["loss"]), f"phase {name}: loss {hist['loss']}")
    check(all(0.0 <= a <= 1.0 for a in hist["acc"]), f"phase {name}: accuracy {hist['acc']}")
    print(f"  losses {hist['loss']}, eval accuracy {hist['acc']}")
    return hist, first, fed


def phase_secure_agg(task, seed: int, clock: CompileClock):
    """A: the paper's main path with secure aggregation on."""
    cfg = experiment(seed, rounds=3)
    _, first, fed = run_federation("A (sync, rl_green, secure aggregation)", cfg, task, clock)
    stages = [r.stage for r in first["records"]]
    check(stages == ["scale", "quantize", "mask"], f"phase A ran stages {stages}")
    w = np.asarray(first["weights"], np.float64)
    k = len(w)
    float_mean = jnp.einsum("kp,k->p", first["rows"], jnp.asarray(w / w.sum(), jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
    err = float(jnp.max(jnp.abs(first["mean_row"] - float_mean)))
    bound = k * quantize.quant_error_bound(cfg.privacy.sa_clip, cfg.privacy.sa_bits)
    peak = float(jnp.max(jnp.abs(float_mean)))
    print(f"  secure-agg decode: max |decoded - float mean| = {err:.3e}, "
          f"bound k*quant_error_bound = {bound:.3e}, max |float mean| = {peak:.3e}")
    check(err <= bound and peak > 0.0, "phase A: decoded secure-aggregation mean outside its bound")
    ring = jax.ShapeDtypeStruct((k, fed.ctx.pspace.padded_dim), jnp.uint32)
    require_mosaic("masked_agg", ops.masked_aggregate, ring, ring,
                   cfg.privacy.sa_clip, cfg.privacy.sa_bits)


def phase_dp(task, seed: int, clock: CompileClock):
    """B: client-level DP through the fused compress stage and masked_agg."""
    dp = calibrated(DPConfig(clip=1.0, target_eps=1.2, delta=1e-5,
                             sample_rate=PER_ROUND / N_CLIENTS, rounds=100))
    cfg = experiment(seed, rounds=2, dp=dp)
    hist, first, fed = run_federation("B (DP: compress + masked_agg + noise)", cfg, task, clock)
    stages = [r.stage for r in first["records"]]
    check(stages == ["clip", "quantize", "mask", "noise"], f"phase B ran stages {stages}")
    eps = hist["eps_spent"]
    print(f"  sigma {dp.sigma:.4f}, epsilon spent per round {eps}")
    check(all(math.isfinite(e) and e > 0.0 for e in eps), f"phase B: epsilon {eps}")
    shape = (PER_ROUND, fed.ctx.pspace.padded_dim)
    ring = jax.ShapeDtypeStruct(shape, jnp.uint32)
    require_mosaic("compress", ops.clip_quant_mask, jax.ShapeDtypeStruct(shape, jnp.float32),
                   ring, dp.clip, dp.bits, dim=fed.ctx.pspace.dim)
    require_mosaic("masked_agg", ops.masked_aggregate, ring, ring, dp.clip, dp.bits)


def phase_kernels(padded_dim: int, seed: int, clock: CompileClock):
    """C: staleness_agg and gossip_mix at (k, padded P) against ref.py."""
    k_rows, k_w = jax.random.split(jax.random.PRNGKey(seed + 2))
    rows = jax.random.normal(k_rows, (PER_ROUND, padded_dim), jnp.float32)
    weights = jax.random.uniform(k_w, (PER_ROUND,), jnp.float32, 0.1, 1.0)
    mixing = jnp.asarray(graph_mod.plan("ring", PER_ROUND).mixing)
    cases = (
        ("staleness_agg", ops.staleness_aggregate, ref.staleness_aggregate_ref, (rows, weights)),
        ("gossip_mix", ops.gossip_mix, ref.gossip_mix_ref, (rows, mixing)),
    )
    clock.lap()
    for name, kernel, oracle, args in cases:
        require_mosaic(name, kernel, *args)
        out = jax.block_until_ready(kernel(*args))  # compiles
        t0 = time.perf_counter()
        out = jax.block_until_ready(kernel(*args))
        wall = time.perf_counter() - t0
        expect = oracle(*args)
        excess = float(jnp.max(jnp.abs(out - expect) - (KERNEL_ATOL + KERNEL_RTOL * jnp.abs(expect))))
        err = float(jnp.max(jnp.abs(out - expect)))
        print(f"  {name} {tuple(rows.shape)}: max |kernel - oracle| = {err:.3e} "
              f"(allclose rtol={KERNEL_RTOL}, atol={KERNEL_ATOL}); "
              f"smoke timing: one call {wall * 1e3:.2f} ms")
        check(out.shape == expect.shape and excess <= 0.0, f"phase C: {name} differs from its oracle")
    print(f"phase C smoke timing (one run, not a benchmark): XLA compile {clock.lap():.1f} s")


def phase_sharded(task, seed: int, clock: CompileClock):
    """--chips 4: the cohort split over a 4-device ``data`` mesh, checked
    against the unsharded cohort trainer on one device for round 1."""
    cfg = experiment(seed, rounds=2, sharded=True)
    _, first, fed = run_federation("S (sync, sharded cohort over 4 chips)", cfg, task, clock)
    mesh = fed.ctx.cohort_trainer.mesh
    check(mesh.shape["data"] == 4 and len(set(mesh.devices.flat)) == 4,
          f"cohort mesh {dict(mesh.shape)} does not hold 4 distinct devices")
    rows = first["rows"]
    dev0 = jax.devices()[0]
    check(rows.sharding.device_set == {dev0},
          f"sharded trainer handed rows to {rows.sharding.device_set}, not to one device")
    inputs = fed.ctx._cohort_inputs(first["clients"], 0)  # round 1's
    single = client_mod.make_cohort_trainer(task.loss_fn, fed.ctx.local_opt, fed.ctx.pspace)
    expect = single(*jax.device_put((task.params0, *inputs), dev0)).rows
    err = float(jnp.max(jnp.abs(rows - expect)))
    scale = float(jnp.max(jnp.abs(expect)))
    print(f"  sharded vs unsharded rows {tuple(expect.shape)}: max |diff| = {err:.3e}, "
          f"max |row| = {scale:.3e}, allclose rtol=0, atol={SHARDED_REL_TOL} * max |row|")
    # control: the unsharded trainer alone, on a cohort of one device's
    # shape (the first 3 clients), against its own 10-client rows
    per_device = -(-len(first["clients"]) // mesh.shape["data"])
    head = jax.tree.map(lambda x: x[:per_device], inputs)
    control = single(*jax.device_put((task.params0, *head), dev0)).rows
    drift = float(jnp.max(jnp.abs(control - expect[:per_device])))
    print(f"  control: unsharded trainer, {per_device} vs {len(first['clients'])} clients "
          f"per program, max |diff| = {drift:.3e}")
    check(rows.shape == expect.shape and 0.0 < scale and err <= SHARDED_REL_TOL * scale,
          "sharded cohort rows differ from the unsharded trainer's")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform {device.platform!r}); nothing ran")
    count = len(jax.devices())
    if count < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, JAX sees {count}")
    enable_compile_cache()
    clock = CompileClock()
    task = build_task(args.seed)
    pspace = ParamSpace.build(task.params0)
    print(f"device {device.device_kind} x{count}; ResNet-Tiny widths {tuple(CONFIG.widths)}, "
          f"P = {pspace.dim:,} (padded {pspace.padded_dim:,})")
    if args.chips == 4:
        phase_sharded(task, args.seed, clock)
    else:
        phase_secure_agg(task, args.seed, clock)
        phase_dp(task, args.seed, clock)
        phase_kernels(pspace.padded_dim, args.seed, clock)
    for d in jax.devices()[: args.chips]:
        print(f"peak_bytes_in_use {d.id}: {d.memory_stats()['peak_bytes_in_use']:,}")
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind, "count": count}}))


if __name__ == "__main__":
    main()
