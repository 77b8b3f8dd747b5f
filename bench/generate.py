"""Inputs of a cell, made from ``--seed``: the seed streams, the partition
and the batch schedule.

The data itself comes from the configuration's generator,
``bench/datasets/<generator>.py``.  The partition is a copy of
``repro.data.partition.dirichlet_partition``, drawn over the integer key the
configuration names in ``partition_by`` (a label, a domain), and the batch
schedule a copy of ``repro.data.pipeline.ClientDataset.stacked_steps``, so
that the plain reference can follow the program's rounds without importing
it.

Every seed gives the same sizes: the same number of samples, clients,
clients per round, local steps and batch.  Only values and the order of
the work differ between seeds.
"""
from __future__ import annotations

import numpy as np

# derived streams of one --seed; each use takes its own
STREAMS = ("data", "partition", "clients", "weights", "federation")


def derive_seeds(seed: int) -> dict[str, int]:
    """Independent 31-bit seeds for each input stream of one run."""
    words = np.random.SeedSequence(int(seed)).generate_state(len(STREAMS))
    return {name: int(w) & 0x7FFFFFFF for name, w in zip(STREAMS, words)}


def dirichlet_partition(labels: np.ndarray, n_clients: int, alpha: float, seed: int,
                        min_per_client: int = 8) -> list[np.ndarray]:
    """Dirichlet(alpha) label skew over ``n_clients`` (Hsu et al. 2019)."""
    rng = np.random.default_rng(seed)
    n_classes = int(labels.max()) + 1
    for _attempt in range(100):
        idx_by_client: list[list[int]] = [[] for _ in range(n_clients)]
        for c in range(n_classes):
            idx_c = np.flatnonzero(labels == c)
            rng.shuffle(idx_c)
            p = rng.dirichlet(np.full(n_clients, alpha))
            cuts = (np.cumsum(p) * len(idx_c)).astype(int)[:-1]
            for client, chunk in enumerate(np.split(idx_c, cuts)):
                idx_by_client[client].extend(chunk.tolist())
        if min(len(ix) for ix in idx_by_client) >= min_per_client:
            return [np.array(sorted(ix), dtype=np.int64) for ix in idx_by_client]
    raise RuntimeError("dirichlet_partition: could not satisfy min_per_client")


def _epoch_batches(indices: np.ndarray, client_seed: int, batch: int, epoch: int):
    rng = np.random.default_rng((client_seed * 1_000_003 + epoch) & 0x7FFFFFFF)
    order = rng.permutation(indices)
    n = len(order) - (len(order) % batch)
    if n == 0:  # tiny client: sample with replacement to fill one batch
        order = rng.choice(indices, batch, replace=True)
        n = batch
    return [order[i:i + batch] for i in range(0, n, batch)]


def local_batches(indices: np.ndarray, client_seed: int, batch: int, n_steps: int,
                  round_idx: int) -> np.ndarray:
    """(n_steps, batch) sample indices one client trains on in one round:
    shuffled epochs of its shard, cycled until ``n_steps`` batches."""
    out: list[np.ndarray] = []
    epoch = 0
    while len(out) < n_steps:
        out.extend(_epoch_batches(indices, client_seed, batch, round_idx * 131 + epoch))
        epoch += 1
    return np.stack(out[:n_steps])
