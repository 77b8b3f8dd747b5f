"""Plain reference of a tiny causal language model, for the CPU test that
a token configuration runs through the harness by new files alone (the
test copies it into a benchmark tree as ``bench/configs/<reference>.py``).

One pre-norm block: RMS norm, single-head causal self-attention, RMS norm,
ReLU MLP, each with a residual; a final RMS norm and an untied output
head.  No positional embedding: the causal mask alone orders the tokens.
Every product runs at ``Precision.HIGHEST`` when ``dtype`` is float32;
``dtype=bfloat16`` computes everything in bfloat16, the control.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def param_shapes(model: dict) -> dict[str, tuple[int, ...]]:
    v, d, f = model["vocab"], model["d_model"], model["d_ff"]
    return {"embed": (v, d), "wq": (d, d), "wk": (d, d), "wv": (d, d), "wo": (d, d),
            "w_in": (d, f), "w_out": (f, d), "head": (d, v)}


def init_params(model: dict, seed: int) -> dict:
    key = jax.random.PRNGKey(seed)
    return {name: jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32) / shape[0] ** 0.5
            for i, (name, shape) in enumerate(sorted(param_shapes(model).items()))}


def param_count(model: dict) -> int:
    total = 0
    for shape in param_shapes(model).values():
        total += shape[0] * shape[1]
    return total


def train_flops_per_sample(model: dict, dataset: dict) -> int:
    """Matrix-product FLOPs of one sequence, forward and backward (three
    times the forward): the projections, the MLP, the head, and the
    attention scores and values over all of the sequence's positions."""
    t, v, d, f = dataset["seq_len"], model["vocab"], model["d_model"], model["d_ff"]
    return 3 * (2 * t * (4 * d * d + 2 * d * f + d * v) + 4 * t * t * d)


def _rms(x):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + 1e-6)


def logits(params: dict, tokens, dtype=jnp.float32):
    prec = jax.lax.Precision.HIGHEST if dtype == jnp.float32 else jax.lax.Precision.DEFAULT
    p = {k: v.astype(dtype) for k, v in params.items()}

    def mm(a, b):
        return jnp.matmul(a, b, precision=prec)

    x = p["embed"][tokens]
    h = _rms(x)
    q, k, v = mm(h, p["wq"]), mm(h, p["wk"]), mm(h, p["wv"])
    scores = mm(q, jnp.swapaxes(k, -1, -2)) / jnp.sqrt(jnp.asarray(q.shape[-1], dtype))
    t = tokens.shape[-1]
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    x = x + mm(mm(jax.nn.softmax(scores, axis=-1), v), p["wo"])
    x = x + mm(jax.nn.relu(mm(_rms(x), p["w_in"])), p["w_out"])
    return mm(_rms(x), p["head"])


def loss(params: dict, model: dict, batch: dict, dtype=jnp.float32):
    """Mean next-token cross-entropy of one batch of ``"tokens"``."""
    tokens = batch["tokens"]
    logp = jax.nn.log_softmax(logits(params, tokens[:, :-1], dtype), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))
