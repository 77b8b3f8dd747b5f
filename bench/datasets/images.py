"""Image data of a cell, made from the seed: ``{"image": (N, H, W, C)
float32, "label": (N,) int32}`` for the train and test splits.

A copy of the construction in ``repro.data.synthetic`` (a smooth random
prototype per class, a per-sample scale nuisance, unit pixel noise), drawn
on the device in one jitted call and copied to the host, where the
program's client datasets index it.  The spec's keys: ``shape`` (H, W, C),
``n_classes``, ``n_train``, ``n_test``, ``snr``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


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


def make(spec: dict, seed: int) -> dict[str, dict[str, np.ndarray]]:
    """Train and test splits: images (N, H, W, C) float32, labels int32."""
    shape = tuple(spec["shape"])
    rng = np.random.default_rng(seed)
    protos = _prototypes(rng, shape, spec["n_classes"])
    (tr_x, tr_y), (te_x, te_y) = jax.device_get(_draw(
        jax.random.PRNGKey(seed), jnp.asarray(protos), jnp.float32(spec["snr"]),
        n_train=spec["n_train"], n_test=spec["n_test"]))
    return {"train": {"image": tr_x, "label": tr_y}, "test": {"image": te_x, "label": te_y}}
