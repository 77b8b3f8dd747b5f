"""Inputs of a cell, made from ``--seed``: data, partition, batch schedule.

The image data is a copy of the construction in ``repro.data.synthetic``
(a smooth random prototype per class, a per-sample scale nuisance, unit
pixel noise), drawn on the device in one jitted call and copied to the
host, where the program's client datasets index it.  The partition is a
copy of ``repro.data.partition.dirichlet_partition`` and the batch schedule
a copy of ``repro.data.pipeline.ClientDataset.stacked_steps``, so that the
plain reference can follow the program's rounds without importing it.

Every seed gives the same sizes: the same number of samples, clients,
clients per round, local steps and batch.  Only values and the order of
the work differ between seeds.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# derived streams of one --seed; each use takes its own
STREAMS = ("data", "partition", "clients", "weights", "federation")


def derive_seeds(seed: int) -> dict[str, int]:
    """Independent 31-bit seeds for each input stream of one run."""
    words = np.random.SeedSequence(int(seed)).generate_state(len(STREAMS))
    return {name: int(w) & 0x7FFFFFFF for name, w in zip(STREAMS, words)}


def _prototypes(rng: np.random.Generator, shape, n_classes: int) -> np.ndarray:
    """Low-frequency class prototypes (random Fourier features)."""
    H, W, C = shape
    yy, xx = np.mgrid[0:H, 0:W].astype(np.float32)
    protos = np.zeros((n_classes, H, W, C), np.float32)
    for c in range(n_classes):
        img = np.zeros((H, W, C), np.float32)
        for _ in range(6):
            fx, fy = rng.uniform(0.05, 0.35, 2)
            ph = rng.uniform(0, 2 * np.pi, 2)
            amp = rng.normal(0, 1.0)
            wave = np.sin(2 * np.pi * (fx * xx + fy * yy) + ph[0]) * np.cos(ph[1])
            img += amp * wave[..., None] * rng.normal(0, 1.0, (1, 1, C)).astype(np.float32)
        protos[c] = img / (np.std(img) + 1e-6)
    return protos


@functools.partial(jax.jit, static_argnames=("n_train", "n_test"))
def _draw(key, protos, snr, *, n_train: int, n_test: int):
    out = []
    for split_key, n in zip(jax.random.split(key), (n_train, n_test)):
        k_lab, k_noise, k_shift = jax.random.split(split_key, 3)
        labels = jax.random.randint(k_lab, (n,), 0, protos.shape[0], jnp.int32)
        noise = jax.random.normal(k_noise, (n, *protos.shape[1:]), jnp.float32)
        shift = 0.35 * jax.random.normal(k_shift, (n, 1, 1, 1), jnp.float32)
        out.append((snr * protos[labels] * (1.0 + shift) + noise, labels))
    return out


def make_dataset(spec: dict, seed: int) -> dict[str, dict[str, np.ndarray]]:
    """Train and test splits: images (N, H, W, C) float32, labels int32."""
    shape = tuple(spec["shape"])
    rng = np.random.default_rng(seed)
    protos = _prototypes(rng, shape, spec["n_classes"])
    (tr_x, tr_y), (te_x, te_y) = jax.device_get(_draw(
        jax.random.PRNGKey(seed), jnp.asarray(protos), jnp.float32(spec["snr"]),
        n_train=spec["n_train"], n_test=spec["n_test"]))
    return {"train": {"image": tr_x, "label": tr_y}, "test": {"image": te_x, "label": te_y}}


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
