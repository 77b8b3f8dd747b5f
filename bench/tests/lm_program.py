"""The program side of the tiny causal language model in
``lm_reference.py``: a configuration class and a loss by name, in the form
the harness builds a program task from (``program`` in a configuration).
Written apart from the reference, with einsums and a log-sum-exp."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class LMConfig:
    vocab: int
    d_model: int
    d_ff: int


def _norm(x):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6)


def lm_loss(p, cfg: LMConfig, batch):
    inputs, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]
    x = jnp.take(p["embed"], inputs, axis=0)
    h = _norm(x)
    q, k, v = (jnp.einsum("btd,de->bte", h, p[w]) for w in ("wq", "wk", "wv"))
    s = jnp.einsum("bqd,bkd->bqk", q, k) * cfg.d_model ** -0.5
    t = inputs.shape[1]
    s = jnp.where(jnp.arange(t)[:, None] >= jnp.arange(t)[None, :], s, -jnp.inf)
    a = jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, axis=-1), v)
    x = x + jnp.einsum("btd,de->bte", a, p["wo"])
    x = x + jnp.einsum("btf,fd->btd", jax.nn.relu(jnp.einsum("btd,df->btf", _norm(x), p["w_in"])),
                       p["w_out"])
    out = jnp.einsum("btd,dv->btv", _norm(x), p["head"])
    gold = jnp.take_along_axis(out, targets[..., None], axis=-1)[..., 0]
    loss = jnp.mean(jax.nn.logsumexp(out, axis=-1) - gold)
    acc = jnp.mean((jnp.argmax(out, -1) == targets).astype(jnp.float32))
    return loss, {"loss": loss, "acc": acc}
