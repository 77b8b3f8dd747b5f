"""Shared experiment fabric for the paper-table benchmarks.

The paper's protocol is 50 clients / 10 per round / 100 rounds / ResNet-Tiny
on a P100 cluster.  This container is a single CPU core, so the benchmarks
run a REDUCED protocol (same structure, smaller numbers) and validate the
paper's *claims* — the ordering and the emission ratios across variants —
rather than absolute values.  Scale factors are recorded in every output.

Variant map (paper §IV-A):
    metafed_full   = MetaFed (RL + Green + RT)   selection=rl_green
    metafed_rl     = MetaFed (RL + RT)           selection=rl
    metafed_green  = MetaFed (Green + RT)        selection=green
    fedavg/fedprox/fedadam                       selection=random
"""
from __future__ import annotations

import dataclasses
import json
import os
import time

import jax
import numpy as np

from repro import api
from repro.data.partition import dirichlet_partition
from repro.data.pipeline import build_clients
from repro.data.synthetic import get_dataset_spec, make_image_dataset
from repro.models.resnet import ResNetConfig, init_resnet, resnet_loss

# reduced protocol (paper values in comments)
N_CLIENTS = 12        # 50
PER_ROUND = 4         # 10  (participation stays ~20-30%)
ROUNDS = 16           # 100 (RL convergence needs the long horizon; at 16
                      #      rounds the claims are checked with wider bands)
LOCAL_STEPS = 6       # 5 epochs x ~37 batches
BATCH = 32            # 32 (paper)
N_TRAIN = 6000
N_TEST = 1000

VARIANTS = {
    "metafed_full": dict(algorithm="fedavg", selection="rl_green"),
    "metafed_rl": dict(algorithm="fedavg", selection="rl"),
    "metafed_green": dict(algorithm="fedavg", selection="green"),
    "fedavg": dict(algorithm="fedavg", selection="random"),
    "fedprox": dict(algorithm="fedprox", selection="random"),
    "fedadam": dict(algorithm="fedadam", selection="random", server_lr=0.02),
}

PAPER_LABELS = {
    "metafed_full": "MetaFed (RL + Green + RT)",
    "metafed_rl": "MetaFed (RL + RT)",
    "metafed_green": "MetaFed (Green + RT)",
    "fedavg": "FedAvg (RT)",
    "fedprox": "FedProx (RT)",
    "fedadam": "FedAdam (RT)",
}


def build_experiment(dataset: str, seed: int = 0, rounds: int = ROUNDS,
                     n_clients: int = N_CLIENTS, fast: bool = False):
    spec = get_dataset_spec(dataset)  # "mnist(_synthetic)" | "cifar(_synthetic)"
    n_train = N_TRAIN // (3 if fast else 1)
    data = make_image_dataset(spec, seed=seed, n_train=n_train, n_test=N_TEST)
    parts = dirichlet_partition(data["train"]["label"], n_clients, alpha=0.5, seed=seed)
    clients = build_clients(data["train"], parts)
    rcfg = ResNetConfig(
        name=f"rt-{dataset}", widths=(16, 32), depths=(1, 1),
        in_channels=spec.shape[2], num_classes=spec.n_classes,
    )
    params = init_resnet(jax.random.PRNGKey(seed), rcfg)
    loss_fn = lambda p, b: resnet_loss(p, rcfg, b)
    eval_fn = lambda p, b: resnet_loss(p, rcfg, b)[1]
    return data, clients, params, loss_fn, eval_fn, rounds


def run_variant(name: str, dataset: str, seed: int = 0, rounds: int = ROUNDS,
                fast: bool = False, secure_agg: bool = True) -> dict:
    data, clients, params, loss_fn, eval_fn, rounds = build_experiment(
        dataset, seed, rounds, fast=fast
    )
    kw = dict(VARIANTS[name])
    algorithm = kw.pop("algorithm")
    cfg = api.ExperimentConfig(
        training=api.TrainingConfig(
            algorithm=algorithm, server_lr=kw.pop("server_lr", 1.0),
            n_clients=N_CLIENTS, clients_per_round=PER_ROUND,
            rounds=rounds // (2 if fast else 1), local_steps=LOCAL_STEPS,
            batch_size=BATCH, client_lr=0.08, eval_every=max(2, rounds // 6),
            seed=seed,
        ),
        privacy=api.PrivacyConfig(secure_agg=secure_agg and algorithm != "fednova"),
        orchestrator=api.OrchestratorConfig(selection=kw.pop("selection")),
    )
    if kw:  # don't silently drop keys the config has no field for
        raise TypeError(f"unmapped variant keys for {name!r}: {sorted(kw)}")
    task = api.FederatedTask(loss_fn, eval_fn, params, clients, data["test"])
    fed = api.build(cfg.to_dict(), task)  # round-trips the JSON-grid path
    t0 = time.time()
    hist = fed.run()
    hist["wall_s"] = time.time() - t0
    hist["variant"] = name
    hist["dataset"] = dataset
    return hist


def summarize(hist: dict) -> dict:
    return {
        "variant": hist["variant"],
        "label": PAPER_LABELS[hist["variant"]],
        "accuracy_pct": 100.0 * hist["final_acc"],
        "co2_g_per_round": hist["mean_co2_g"],
        "time_s_per_round": hist["mean_duration_s"],
        "cum_co2_g": hist["cum_co2_total_g"],
    }


def save_results(results: list[dict], path: str) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(results, f, indent=1)


def csv_line(name: str, us_per_call: float, derived: str) -> str:
    """Scaffold contract: ``name,us_per_call,derived`` CSV."""
    return f"{name},{us_per_call:.1f},{derived}"


def check_regression(current: list[dict], baseline: list[dict], *,
                     metric: str = "gb_per_s", max_drop: float = 0.30) -> list[str]:
    """Shared perf gate over bench record lists keyed ``(op, shape, backend)``.

    Any key whose ``metric`` dropped more than ``max_drop`` vs the committed
    baseline — or that disappeared from the bench — fails.  New ops absent
    from the baseline pass (the refreshed JSON picks them up).  Used by both
    ``kernel_bench`` (metric=gb_per_s, BENCH_kernels.json) and
    ``engine_bench`` (metric=events_per_s, BENCH_engine.json).
    """
    cur = {(r["op"], tuple(r["shape"]), r["backend"]): r[metric] for r in current}
    failures = []
    for b in baseline:
        key = (b["op"], tuple(b["shape"]), b["backend"])
        got = cur.get(key)
        if got is None:
            failures.append(f"{key}: present in baseline but not benched")
            continue
        floor = b[metric] * (1.0 - max_drop)
        if got < floor:
            failures.append(
                f"{key}: {metric} {got:.3f} < floor {floor:.3f} "
                f"(baseline {b[metric]:.3f}, max drop {max_drop:.0%})"
            )
    return failures
