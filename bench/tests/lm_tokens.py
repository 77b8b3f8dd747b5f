"""Token data of the tiny causal language model's CPU test, made from the
seed: ``{"tokens": (N, seq_len + 1) int32, "domain": (N,) int32}`` for the
train and test splits.  The test copies it into a benchmark tree as
``bench/datasets/tokens.py``, a generator found by name like any other.  A
sequence is ``seq_len + 1`` ids because the model trains on the next-token
shift of ``tokens``.

Packed pre-training text from several domains:

- each domain has its own Zipf(``zipf``) distribution over a seeded
  permutation of the document ids 1 .. vocab - 1 (its own frequent words);
- documents have log-normal lengths (``exp`` of a normal with mean
  ``doc_len_mu`` and deviation ``doc_len_sigma``, at least one token) and
  are packed back to back, each closed by the end-of-document id ``EOD``;
  a sequence starts at a random point of its first document, so every
  sequence carries document boundaries, as real packed training data does;
- one sequence draws from one domain, which it names in ``"domain"``.

The clients' skew is a Dirichlet partition over ``"domain"`` (the
configuration's ``partition_by``), as OpenFedLLM (arXiv:2402.06954)
partitions instruction data across clients.

The spec's keys: ``vocab``, ``seq_len``, ``n_train``, ``n_test``,
``n_domains``, ``zipf``, ``doc_len_mu``, ``doc_len_sigma``.  Every seed
gives the same sizes.  All of it is drawn on the device in one jitted call
and copied to the host, where the program's client datasets index it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

EOD = 0


@functools.partial(jax.jit, static_argnames=("vocab", "seq_len", "n_train", "n_test", "n_domains"))
def _draw(key, zipf, mu, sigma, *, vocab: int, seq_len: int, n_train: int, n_test: int,
          n_domains: int):
    k_perm, *k_splits = jax.random.split(key, 3)
    # each domain's ids by rank: row d, column r is the id of rank r + 1
    perms = 1 + jax.vmap(lambda k: jax.random.permutation(k, vocab - 1))(
        jax.random.split(k_perm, n_domains))
    log_w = -zipf * jnp.log(jnp.arange(1, vocab, dtype=jnp.float32))
    cdf = jnp.cumsum(jax.nn.softmax(log_w))
    length = seq_len + 1
    out = []
    for split_key, n in zip(k_splits, (n_train, n_test)):
        k_dom, k_tok, k_len, k_off = jax.random.split(split_key, 4)
        domain = jax.random.randint(k_dom, (n,), 0, n_domains, jnp.int32)
        u = jax.random.uniform(k_tok, (n, length), jnp.float32) * cdf[-1]
        rank = jnp.minimum(jnp.searchsorted(cdf, u, side="right"), vocab - 2)
        ids = perms[domain[:, None], rank]
        # a sequence holds at most `length` documents; longer ones are cut
        doc_len = jnp.exp(mu + sigma * jax.random.normal(k_len, (n, length), jnp.float32))
        doc_len = jnp.clip(jnp.round(doc_len), 1, length).astype(jnp.int32)
        ends = jnp.cumsum(doc_len + 1, axis=1) - 1  # each document's EOD in the packed stream
        start = jnp.floor(jax.random.uniform(k_off, (n,)) * (doc_len[:, 0] + 1)).astype(jnp.int32)
        eod = jnp.zeros((n, length), bool).at[
            jnp.arange(n)[:, None], ends - start[:, None]].set(True, mode="drop")
        out.append((jnp.where(eod, EOD, ids).astype(jnp.int32), domain))
    return out


def make(spec: dict, seed: int) -> dict[str, dict[str, np.ndarray]]:
    """Train and test splits: tokens (N, seq_len + 1) int32, domain (N,) int32."""
    (tr_t, tr_d), (te_t, te_d) = jax.device_get(_draw(
        jax.random.PRNGKey(seed), jnp.float32(spec["zipf"]), jnp.float32(spec["doc_len_mu"]),
        jnp.float32(spec["doc_len_sigma"]), vocab=spec["vocab"], seq_len=spec["seq_len"],
        n_train=spec["n_train"], n_test=spec["n_test"], n_domains=spec["n_domains"]))
    return {"train": {"tokens": tr_t, "domain": tr_d}, "test": {"tokens": te_t, "domain": te_d}}
